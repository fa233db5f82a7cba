"""Micro-benchmark attention fwd+bwd at the bench shape on the real chip.

Compares flash-kernel variants (and the XLA path) so layout changes can be
measured in ~seconds instead of re-running the full bench. Iterations are
chained through a lax.scan inside one jit so per-dispatch overhead
amortizes away and nothing is dead-code-eliminated.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

INNER = 50


def timed_scan(step, init, n=INNER, reps=5):
    @jax.jit
    def run(x):
        return jax.lax.scan(lambda c, _: (step(c), None), x, None, length=n)[0]

    out = run(init)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = run(init)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best / n * 1e3  # ms per iteration


def main():
    b, h, s, d = 8, 16, 512, 64
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(b, h, s, d), jnp.bfloat16)
    k = jnp.asarray(rs.randn(b, h, s, d), jnp.bfloat16)
    v = jnp.asarray(rs.randn(b, h, s, d), jnp.bfloat16)
    g = jnp.asarray(rs.randn(b, h, s, d), jnp.bfloat16)

    from flexflow_tpu.kernels.flash_attention import flash_attention
    from flexflow_tpu.ops.attention import sdpa_xla

    def f_flash(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def f_xla(q, k, v):
        return sdpa_xla(q, k, v, causal=True, scale=1.0 / d ** 0.5)

    def fwd_step(f):
        def step(carry):
            q, k, v = carry
            out = f(q, k, v)
            return (out, k, v)  # chain: next q is this out
        return step

    def fb_step(f):
        def step(carry):
            q, k, v = carry
            out, vjp = jax.vjp(f, q, k, v)
            dq, dk, dv = vjp((out * 0 + g).astype(out.dtype))
            return (out + 0.01 * dq.astype(out.dtype), k, v)
        return step

    for name, f in [("flash", f_flash), ("xla", f_xla)]:
        t_f = timed_scan(fwd_step(f), (q, k, v))
        t_fb = timed_scan(fb_step(f), (q, k, v))
        print(f"{name:6s} fwd {t_f:7.3f} ms   f+b {t_fb:7.3f} ms")


if __name__ == "__main__":
    main()
