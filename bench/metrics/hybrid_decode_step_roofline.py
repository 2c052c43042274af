"""Model step, decode of the Zamba2 hybrid: the least time of the traced
decode steps over the device time spent inside them, in %. Per step
(the benchmark's ``bench.decode_step.<active>`` span) the least time is
the larger of its FLOPs over peak and of its HBM bytes over peak
bandwidth (``bench/flops_zamba2.py``): the bfloat16 weights once, each
active request's state read and written, its live KV read at each use
and one token of KV written. A request's KV length at a step comes from
the run's request records (its prompt and the tokens it had by the
step's start), put on the trace's clock through the benchmark's
``bench.window_start`` span; the view's empty rows are never counted."""
from bench import trace_reduce
from bench.flops_zamba2 import decode_step

SPAN = "bench.decode_step."


def lengths_at(records, t):
    """KV lengths, at record time ``t``, of the requests being decoded:
    admitted, with a first token, and with a token still to come."""
    out = []
    for r in records:
        toks = r["tokens"]
        if r["admit"] is None or not toks or toks[0] > t or toks[-1] <= t:
            continue
        out.append(r["prompt_len"] + sum(1 for x in toks if x <= t) - 1)
    return out


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.devices:
        return None
    spans = [h for h in tr.host if h.name.startswith(SPAN)]
    if not spans:
        return None
    cfg, peak = ctx["cell"].config, ctx["peak"]
    # trace ns -> record seconds (serve.device_idle's shift, inverted)
    shift = ctx["lo"] + 1e9 * (ctx["window_start"] - ctx["t_win"])
    least = 0.0
    for h in spans:
        f, b = decode_step(cfg, lengths_at(ctx["records"],
                                           (h.start - shift) / 1e9))
        least += max(f / peak["bf16_flops"], b / peak["hbm_bytes_per_s"])
    busy = [trace_reduce.span_busy_ns(evs, spans)
            for evs in tr.devices.values()]
    t = sum(busy) / len(busy) / 1e9
    return 100.0 * least / t if t else None
