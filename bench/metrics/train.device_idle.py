"""Device: share of the traced window (first traced step's dispatch to the
last operation) in which no operation ran, averaged over the chips."""
from bench import trace_reduce


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.devices:
        return None
    lo, hi = ctx["lo"], ctx["hi"]
    busy = [trace_reduce.busy_ns(evs, lo, hi) for evs in tr.devices.values()]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
