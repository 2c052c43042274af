"""Communicator: device time of the collective operations per traced
step, averaged over the chips (the union of their intervals)."""
from bench import trace_reduce


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.devices:
        return None
    t = [trace_reduce.collective_ns(evs, ctx["lo"], ctx["hi"])
         for evs in tr.devices.values()]
    return sum(t) / len(t) / 1e6 / ctx["steps"] if any(t) else None
