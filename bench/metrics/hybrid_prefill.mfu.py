"""Model step, prefill of the Zamba2 hybrid: the FLOPs of the traced
admissions' prefills (``bench/flops_zamba2.py``: the Mamba-2 layers with
the SSD scan of each B/C group, each use's shared block with its causal
attention, the last position's logits) over the device time spent
inside them times the chip's peak, in %. An admission is the
benchmark's ``bench.admit.<prompt length>`` span; its device time is the
busy time of the chip inside the span."""
from bench import trace_reduce
from bench.flops_zamba2 import prefill

SPAN = "bench.admit."


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.devices:
        return None
    spans = [h for h in tr.host if h.name.startswith(SPAN)]
    if not spans:
        return None
    cfg = ctx["cell"].config
    q = cfg["ssd_chunk"]
    flops = 0.0
    for h in spans:
        n = int(h.name[len(SPAN):])
        flops += prefill(cfg, n, min(q, n))
    busy = [trace_reduce.span_busy_ns(evs, spans)
            for evs in tr.devices.values()]
    t = sum(busy) / len(busy) / 1e9
    return 100.0 * flops / (t * ctx["peak"]["bf16_flops"]) if t else None
