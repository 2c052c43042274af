"""Kernels: the chunked SSD forward's share of its roofline over the
traced window, in %. Its events are the Pallas custom calls with six
operands (A, x as batch x heads x chunks x chunk x head width, dt as a row
and a column, B transposed, C) and three outputs; operations and bytes
come from those shapes (bench/flops.py)."""
from bench import flops, trace_reduce


def _is_ssd(op):
    return (len(op.operands) == 6 and len(op.out) == 3
            and len(op.operands[1][1]) == 5)


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    f = b = t = 0
    for evs in tr.devices.values():
        for op, dur in trace_reduce.kernels(evs):
            if not _is_ssd(op):
                continue
            xdt, (B, H, nc, Q, P) = op.operands[1]
            bdt, (_, _, N, _) = op.operands[4]
            ff, bb = flops.ssd_fwd(B, H, nc * Q, Q, N, P,
                                   trace_reduce.DTYPE_BYTES[xdt],
                                   trace_reduce.DTYPE_BYTES[bdt])
            f, b, t = f + ff, b + bb, t + dur
    return flops.share(f, b, t / 1e9, ctx["peak"]) if t else None
