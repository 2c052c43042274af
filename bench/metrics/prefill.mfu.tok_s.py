"""Model step, in a cell judged on tokens per second (above the knee):
``prefill.mfu``, the FLOPs of the traced prefills over the device time
spent inside them times the chip's peak, in %."""
from bench.common import reader


def read(ctx):
    return reader("prefill.mfu")(ctx)
