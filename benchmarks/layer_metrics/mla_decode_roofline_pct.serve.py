"""The least time the chip could take to read the latent rows a step's
slots hold, over the time the paged latent kernel (`paged_latent_decode`)
took, in the steps that only decode. Bytes bound it: a step's `kv_rows`
(the sum of its slots' contexts, which only the engine knows) x the bytes
of a token's PUBLISHED latent row over the layers held
(ms4_events.latent_bytes_a_row: kv_lora_rank + qk_rope_head_dim numbers,
640 B a layer in bf16, not the 768 B the pool stores), over the chip's HBM
bandwidth. A row is read at least once whatever a later PR stores of it;
queries, outputs and page tables are left out: the share is a floor."""

from benchmarks import ms4_events


def read(run):
    return ms4_events.kernel_roofline_pct(run)
