"""Model step: the FLOPs the traced steps need (forward and backward,
nothing recomputed) over the traced window times the chips' peak, in %."""


def read(ctx):
    if ctx["trace"] is None:
        return None
    window = (ctx["hi"] - ctx["lo"]) / 1e9
    return 100.0 * ctx["steps"] * ctx["step_flops"] / (
        window * ctx["chips"] * ctx["peak"]["bf16_flops"])
