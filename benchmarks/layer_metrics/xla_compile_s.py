"""Seconds the backend spent compiling or reading the compile cache during
set-up (jax.monitoring, /jax/core/compile/backend_compile_duration)."""


def read(run):
    return run.ctx.xla_compile_setup_s
