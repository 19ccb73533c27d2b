"""``collective_exposed_share``: per cent of the collective time during which
no other operation runs on the same chip, all chips summed.  Layer: comm."""

from chipbench.harness import trace as tr


def read(ctx):
    if ctx.trace is None:
        return None
    lo, hi = tr.window(ctx.trace)
    times = [tr.collective_time(d, lo, hi) for d in ctx.trace.devices]
    whole = sum(t[0] for t in times)
    return 100.0 * sum(t[1] for t in times) / whole if whole else None
