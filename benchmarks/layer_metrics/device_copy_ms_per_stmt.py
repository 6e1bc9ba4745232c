"""The part of ``device.wait`` after the device was done: its phase
``copy`` (``jax.device_get`` of arrays that are ready: the device-to-host
copy and the conversion to numpy), per statement. The rest of
``device_wait_ms_per_stmt`` is the phase ``ready``: the host waiting for
the device's programs. A part of that metric, not beside it
(``program_parts.py``). Mean over the statements of the window. Nothing to
read from a program without phases. Source: program span."""

from benchmarks import program_parts


def read(ctx):
    return program_parts.phases_mean_ms(ctx, "device.wait", ("copy",))
