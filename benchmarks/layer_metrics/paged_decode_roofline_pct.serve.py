"""The least time the chip could take to read the K and V rows the paged
decode kernel ran over, over the time the kernel took. Bytes bound it (a
decode step is one query row a slot), and they are counted from tensor
sizes: for every iteration that ran the kernel, the context rows of its
slots (the span's `kv_rows`, which only the engine knows) x layers x 2
(K and V) x n_embd x the bytes of an element as attention reads it (the
span's `kv_itemsize`), over the chip's HBM bandwidth. Queries, outputs
and page tables are left out, and a row counts once although the kernel
fetches whole blocks: the share is a floor."""

from benchmarks import program_spans


def read(run):
    steps = program_spans.decode_kernel_steps(run)
    seconds = program_spans.decode_kernel_seconds(run)
    if not steps or not seconds or any(
            "kv_rows" not in s[3] for s in steps):
        return None
    moved = sum(s[3]["kv_rows"] * s[3]["kv_itemsize"] for s in steps) * (
        run.config["n_layer"] * 2 * run.config["n_embd"])
    return 100.0 * moved / run.peaks["hbm_bytes_per_s"] / seconds
