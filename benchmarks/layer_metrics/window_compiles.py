"""Backend compiles (jax monitoring's backend_compile_duration events)
inside the measured window: anything but 0 means a shape or a literal
was not warmed in set-up. Source: program counter."""


def read(ctx):
    return float(ctx.window_counters.get("compiles", 0))
