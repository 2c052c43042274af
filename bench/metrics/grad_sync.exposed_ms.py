"""Communicator: the part of the collective time with no other operation
under it, per traced step, averaged over the chips."""
from bench import trace_reduce


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.devices:
        return None
    if not any(trace_reduce.collective_ns(evs) for evs in tr.devices.values()):
        return None
    t = [trace_reduce.exposed_ns(evs, ctx["lo"], ctx["hi"])
         for evs in tr.devices.values()]
    return sum(t) / len(t) / 1e6 / ctx["steps"]
