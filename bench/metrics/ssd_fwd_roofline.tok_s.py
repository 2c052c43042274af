"""Kernels, in a cell judged on tokens per second (above the knee):
``ssd_fwd_roofline``, the chunked SSD forward's share of its roofline
over the traced window, in %."""
from bench.common import reader


def read(ctx):
    return reader("ssd_fwd_roofline")(ctx)
