"""Share of the traced window in which no operation ran on a chip:
1 - (union of the device's operation intervals) / window, averaged over
the cell's chips."""
from bench import trace as tr


def read(ctx):
    t = ctx.get("trace")
    if t is None or not t.devices:
        return None
    lo, hi = ctx["trace_window"]
    busy = sum(tr.busy_ns(ops, lo, hi) for ops in t.devices) / len(t.devices)
    return 1.0 - busy / (hi - lo)
