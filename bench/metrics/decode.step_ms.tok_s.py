"""Serving engine, in a cell judged on tokens per second (above the
knee): ``decode.step_ms``, the wall time of the engine's decode steps
over the number of steps in the window."""
from bench.common import reader


def read(ctx):
    return reader("decode.step_ms")(ctx)
