"""Compile a cell's step at its real size for a described v5e, without the
chip:

    python benchmarks/rehearse.py --workload <cell>

The model is built on the CPU host through FFModel.compile (and serve()),
as the cell's job builds it; its step is then lowered with the arguments
described on the devices of a `v5e:2x2` topology, `executor.mesh` swapped
for a mesh of them and `jax.default_backend` answering "tpu" so that the
kernels leave interpret mode (the recipe of
tests/test_chip_compile.py::test_train_step_lowers_for_four_chips). Prints
`memory_analysis()` and the kernels and collectives each executable holds.
Nothing runs: a compile that passes is not a chip run. The CPU host's
"chip" has no 16 GB cap, so `-ll:fsize 16384` stands in for the chip's
where the program sizes its plan by memory.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import re
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if os.path.abspath(sys.path[0]) == HERE:
    sys.path[0] = REPO

CHIP_MEMORY_FLAGS = ["-ll:fsize", "16384"]
COLLECTIVE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)(?:-start)?\(")


def report(tag: str, lowered, t0: float) -> None:
    from flexflow_tpu.kernels.dispatch import pallas_kernels

    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    gib = 2.0**30
    print(f"[{tag}] compiled in {time.perf_counter() - t0:.1f} s: arguments "
          f"{mem.argument_size_in_bytes / gib:.2f} GiB (aliased "
          f"{mem.alias_size_in_bytes / gib:.2f}), outputs "
          f"{mem.output_size_in_bytes / gib:.2f} GiB, temporaries "
          f"{mem.temp_size_in_bytes / gib:.2f} GiB, a chip")
    print(f"[{tag}] kernels {dict(pallas_kernels(text))}")
    print(f"[{tag}] collectives "
          f"{dict(collections.Counter(COLLECTIVE.findall(text)))}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--global-batch", type=int, default=None,
                    help="try another batch than the traffic file's "
                         "(training cells)")
    opts, more_flags = ap.parse_known_args()   # the rest: program flags
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        workload = next(w for w in json.load(f)["workloads"]
                        if w["name"] == opts.workload)
    chips = workload["chips"]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={chips}")

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from benchmarks import harness

    jax.config.update("jax_enable_compilation_cache", False)
    cell = harness.load_json("workloads", workload["name"] + ".json")
    config = harness.load_json("configs", workload["config"] + ".json")
    traffic = harness.load_json("traffic", workload["traffic"] + ".json")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    flags = [*cell["flags"], *CHIP_MEMORY_FLAGS, *more_flags]

    def on_described(model):
        """(mesh of described chips, tree -> described arguments)"""
        mesh = Mesh(np.array(topo.devices[:model.mesh.devices.size]).reshape(
            model.mesh.devices.shape), model.mesh.axis_names)

        def described(x):
            spec = (x.sharding.spec if isinstance(x.sharding, NamedSharding)
                    else PartitionSpec())
            return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                        sharding=NamedSharding(mesh, spec))

        return mesh, lambda tree: jax.tree.map(described, tree)

    t0 = time.perf_counter()
    if cell["job"] == "train":
        seq = traffic["sequence_length"]
        batch = opts.global_batch or traffic["global_batch"]
        ff = harness.build_lm(
            harness.lm_config(config, seq, cell["attention_impl"]), flags,
            batch, cell["optimizer"])
        upd = ff._update_sharding
        print(f"[train] FFModel.compile on the CPU host "
              f"{time.perf_counter() - t0:.1f} s; mesh "
              f"{dict(ff.mesh.shape)}; weight update "
              f"{'stage ' + str(upd.get('stage')) if upd.get('enabled') else 'replicated'}"
              f" ({upd.get('reason', '')})")
        toks = np.zeros((batch, seq), np.int32)
        data = ff._make_batch({"tokens": toks, "positions": toks},
                              np.zeros((batch, seq, 1), np.int32))
        rng = jax.device_put(jax.random.key(0),
                             NamedSharding(ff.mesh, PartitionSpec()))
        mesh, describe = on_described(ff)
        args = describe((ff._params, ff._state, ff._opt_slots, ff._step,
                         ff._counters, rng, data))
        ff.executor.mesh = mesh
        jax.default_backend = lambda: "tpu"
        t0 = time.perf_counter()
        report(f"train {batch} x {seq}",
               jax.jit(ff.executor._train_step_body,
                       donate_argnums=(0, 1, 2, 3, 4)).lower(*args), t0)
    elif cell["job"] == "serve":
        ff = harness.build_lm(
            harness.lm_config(config, config["n_positions"],
                              cell["attention_impl"]), flags,
            cell["train_batch"], cell["optimizer"])
        engine = ff.serve(**cell["serve"])
        dec, slots = engine.decode_model, engine.spec.slots
        print(f"[serve] FFModel.compile and serve() on the CPU host "
              f"{time.perf_counter() - t0:.1f} s; pool "
              f"{engine.block_manager.num_blocks} blocks")
        mesh, describe = on_described(dec)
        # staged on the host's devices while the executor still has them
        steps = {q: describe((
            dec._params, dec._state,
            engine._stage_inputs(np.zeros((slots, q), np.int32),
                                 np.zeros((slots, q), np.int32)),
            jnp.zeros((slots,), jnp.int32), jax.random.key(0),
            jnp.zeros((slots,), jnp.float32)))
            for q in (1, engine.spec.prefill_chunk)}
        dec.executor.mesh = mesh
        jax.default_backend = lambda: "tpu"
        for q, args in steps.items():
            t0 = time.perf_counter()
            report(f"serve step q={q}",
                   jax.jit(engine._step_fn.__wrapped__,
                           donate_argnums=(1,)).lower(*args), t0)
    else:
        sys.exit(f"rehearse: no recipe for job kind {cell['job']!r}")


if __name__ == "__main__":
    main()
