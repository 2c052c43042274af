"""Model step, in a cell judged on tokens per second (above the knee):
``decode_step_roofline``, the least time of the traced decode steps over
the device time spent inside them, in %."""
from bench.common import reader


def read(ctx):
    return reader("decode_step_roofline")(ctx)
