"""The gated short convolution alone, on the chip, at `lfm2-train-8k`'s
shape: two sequences of 8,192 tokens, 2,048 channels, 3 taps, bf16.

    chiprun -- python scripts/sconv_bench.py [--rows 2] [--tokens 8192]
        [--channels 2048] [--taps 3] [--layers 4]
        [--blocks 512x512x32,256x512x32]

Prints, kernel form (kernels/short_conv.py) beside jnp form
(`ShortConvFrontEnd.conv` over ops/recurrent.causal_conv, what every call
ran before PR 63 and what the CPU runs):

- the whole layer (in-projection, middle, out-projection), forward and
  backward with every gradient, ms a layer: `--layers` layers round a
  residual sum inside one program, as a step runs them;
- the middle alone (`bcx`, taps -> y), forward, and forward with backward,
  ms a call, and the GB/s of what the kernel form has to move (B, C, x read
  and y written forward; B, C, x, dy read, d_bcx and y written backward)
  over each form's time;
- the largest difference of the kernel form's y and gradients from the jnp
  form's, over the largest value.

`--blocks` runs the kernel form again under other (token block x channel
block x sub-block) sizes: the sweep sets the module's constants (the
kernel has no knob for them) and traces anew. A number from here is a
layer's, not a step's: a step compiled at the chip's memory limit makes
the jnp form's forward twice (PERF.md section 6, PR 63). Needs a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import warnings

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=2)
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--channels", type=int, default=2048)
    ap.add_argument("--taps", type=int, default=3)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--blocks", default="")
    opts = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.fftype import OperatorType as OT
    from flexflow_tpu.kernels import short_conv as kernel
    from flexflow_tpu.kernels.dispatch import KernelFallbackWarning
    from flexflow_tpu.ops import short_conv as op
    from flexflow_tpu.ops.base import OpContext, get_op_def

    if jax.devices()[0].platform != "tpu":
        sys.exit("sconv_bench: needs a TPU")
    warnings.simplefilter("ignore", KernelFallbackWarning)
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "peaks.json")) as f:
        hbm = json.load(f)[jax.devices()[0].device_kind]["hbm_bytes_per_s"]
    R, T, E, K = opts.rows, opts.tokens, opts.channels, opts.taps
    bf = jnp.bfloat16
    rng = np.random.default_rng(0)
    front = op.ShortConvFrontEnd(embed_dim=E, conv_kernel=K)
    params = op.ShortConvParams(front)
    forward = get_op_def(OT.OP_SHORT_CONV).forward
    weights = [{"w_in": jnp.asarray(rng.normal(0, E ** -0.5, (E, 3, E)), bf),
                "conv": jnp.asarray(rng.uniform(-K ** -0.5, K ** -0.5,
                                                (K, E)), bf),
                "w_out": jnp.asarray(rng.normal(0, E ** -0.5, (E, E)), bf)}
               for _ in range(opts.layers)]
    x = jnp.asarray(rng.normal(0, 1, (R, T, E)), bf)
    bcxs = [jnp.asarray(rng.normal(0, 1, (3, R, T, E)), bf)
            for _ in range(opts.layers)]
    bcx = bcxs[0]
    dy = jnp.asarray(rng.normal(0, 1, (R, T, E)), bf)
    taps = weights[0]["conv"]
    one = R * T * E * 2                    # a bf16 array of (rows, tokens, channels)
    moved = {"forward": 4 * one, "forward and backward": 12 * one}

    def timed(fn, *args):
        fn = jax.jit(fn)
        jax.block_until_ready(fn(*args))                # compiles
        t0 = time.perf_counter()
        for _ in range(opts.calls):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / opts.calls

    def stack(weights, x):
        for w in weights:
            (y,), _ = forward(params, [x], w, {}, OpContext(training=True))
            x = x + y
        return jnp.sum(x.astype(jnp.float32))

    def middles(form):
        """`layers` middles in one program, each on operands of its own (a
        call dispatched alone is the host's 0.3 ms, not the kernel's, and
        XLA folds calls on the same operands into one): forward, and
        forward with backward."""
        def fwd(bcxs, taps):
            return [form(b, taps, None)[0] for b in bcxs]

        def both(bcxs, taps, dy):
            return [form(b, taps, dy) for b in bcxs]

        return fwd, both

    def jnp_form(bcx, taps, dy):
        """(y, d_bcx, d_taps): autodiff through `causal_conv`."""
        y, vjp = jax.vjp(lambda b, t: front.conv({"conv": t}, b), bcx, taps)
        return (y,) if dy is None else (y, *vjp(dy))

    def kernel_form(bcx, taps, dy):
        """The two kernels as a step calls them; the backward's own y (for
        dW_out) is the third array it writes."""
        y = kernel.forward(bcx, taps)
        if dy is None:
            return (y,)
        d_bcx, d_taps, _ = kernel.backward(bcx, taps, dy)
        return y, d_bcx, d_taps[0].astype(taps.dtype)

    def error(a, b):
        a, b = (np.asarray(v, np.float32) for v in (a, b))
        return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))

    want = jax.jit(jnp_form)(bcx, taps, dy)

    def run(name, form, whole):
        took = timed(jax.grad(stack, argnums=(0, 1)), weights, x)
        print(f"[sconv] {name}: the layer, forward and backward, "
              f"{took / opts.layers * 1e3:.3f} ms a layer")
        fwd, both = middles(form)
        for what, t in (("forward", timed(fwd, bcxs, taps)),
                        ("forward and backward", timed(both, bcxs, taps, dy))):
            t /= opts.layers
            print(f"[sconv] {name}: the middle, {what}, {t * 1e3:.3f} ms a "
                  f"call, {moved[what] / t / 1e9:.0f} GB/s of the "
                  f"{moved[what] / 1e6:.0f} MB one pass each way moves "
                  f"({100 * moved[what] / hbm / t:.1f} % of the chip's HBM "
                  f"rate)")
        if whole:
            got = jax.jit(form)(bcx, taps, dy)
            print(f"[sconv] {name}: from the jnp form, over the largest "
                  f"value: " + ", ".join(
                      f"{k} {error(g, w):.2e}" for k, g, w in
                      zip(("y", "d_bcx", "d_taps"), got, want)))

    plan = op._kernel_plan
    op._kernel_plan = lambda *a: (None, "sconv_bench times the jnp form")
    run("jnp form", jnp_form, False)
    op._kernel_plan = plan
    run("kernel form", kernel_form, True)
    for size in filter(None, opts.blocks.split(",")):
        tb, cb, sub = map(int, size.split("x"))
        kernel._TOKEN_BLOCK, kernel._CHANNEL_BLOCK, kernel._SUB = tb, cb, sub
        try:
            run(f"kernel form, blocks of {tb} tokens x {cb} channels in "
                f"sub-blocks of {sub}", kernel_form, True)
        except Exception as e:  # noqa: BLE001 - what the chip refuses
            print(f"[sconv] blocks {size}: refused: "
                  f"{str(e).splitlines()[0][:300]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
