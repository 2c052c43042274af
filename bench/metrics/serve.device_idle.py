"""Device: idle share of the traced time in which at least one request
was queued or active (from its due time to its last token, or the drain
deadline), in %. Request times are put on the trace's clock through the
benchmark's ``bench.window_start`` span."""
from bench import trace_reduce


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.devices:
        return None
    lo, hi = ctx["lo"], ctx["hi"]
    shift = lo + 1e9 * (ctx["window_start"] - ctx["t_win"])

    def ns(t):
        return int(shift + 1e9 * t)

    demand = trace_reduce.clip(trace_reduce.union(
        (ns(r["due"]), ns(r["tokens"][-1] if r["finished"] else ctx["deadline"]))
        for r in ctx["records"]), lo, hi)
    total = trace_reduce.length(demand)
    if not total:
        return None
    idle = []
    for evs in tr.devices.values():
        busy = trace_reduce.union((e.start, e.end) for e in evs
                                  if e.line == trace_reduce.OPS_LINE)
        idle.append(trace_reduce.length(trace_reduce.subtract(demand, busy)))
    return 100.0 * sum(idle) / len(idle) / total
