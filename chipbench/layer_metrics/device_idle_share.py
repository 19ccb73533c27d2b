"""``device_idle_share``: per cent of the traced window in which no operation
ran, on the chip that idled most.  Layer: device."""

from chipbench.harness import trace as tr


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    return 100.0 * tr.idle_share(ctx.trace)
