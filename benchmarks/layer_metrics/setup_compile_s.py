"""Seconds the backend spent compiling (or loading from the persistent
cache) during set-up. Source: program counter (jax monitoring)."""


def read(ctx):
    return float(ctx.setup_counters.get("compile_s", 0.0))
