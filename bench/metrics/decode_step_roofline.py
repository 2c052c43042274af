"""Model step, decode: the least time of the traced decode steps (per step
the larger of its FLOPs over peak and of the HBM bytes of the weights plus
each active request's state over peak bandwidth) over the device time
spent inside them, in %. A step is the benchmark's
``bench.decode_step.<active>`` span; its device time is the busy time of
the chip inside the span, so host time between its operations does not
count."""
from bench import trace_reduce
from bench.flops import ssm_decode_step

SPAN = "bench.decode_step."


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.devices:
        return None
    spans = [h for h in tr.host if h.name.startswith(SPAN)]
    cfg, peak = ctx["cell"].config, ctx["peak"]
    least = 0.0
    for h in spans:
        f, b = ssm_decode_step(cfg, int(h.name[len(SPAN):]))
        least += max(f / peak["bf16_flops"], b / peak["hbm_bytes_per_s"])
    busy = [trace_reduce.span_busy_ns(evs, spans)
            for evs in tr.devices.values()]
    t = sum(busy) / len(busy) / 1e9
    return 100.0 * least / t if t else None
