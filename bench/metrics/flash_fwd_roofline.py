"""Kernels: the flash-attention forward's share of its roofline over the
traced window, in %. Its events are the Pallas custom calls with three
operands (q, k, v as heads x sequence x head width) and one output of q's
shape; operations and bytes come from those shapes (bench/flops.py)."""
from bench import flops, trace_reduce


def _is_flash(op):
    if len(op.operands) != 3 or len(op.out) != 1:
        return False
    (_, q), (_, k), (_, v) = op.operands
    return len(q) == 3 and op.out[0][1] == q and k == v and k[2] == q[2]


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    f = b = t = 0
    for evs in tr.devices.values():
        for op, dur in trace_reduce.kernels(evs):
            if not _is_flash(op):
                continue
            (dt, q), (_, k), _ = op.operands
            ff, bb = flops.flash_fwd(q[0], q[1], q[2], k[0],
                                     trace_reduce.DTYPE_BYTES[dt])
            f, b, t = f + ff, b + bb, t + dur
    return flops.share(f, b, t / 1e9, ctx["peak"]) if t else None
