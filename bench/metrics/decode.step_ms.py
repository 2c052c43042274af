"""Serving engine: wall time of the engine's decode steps (dispatch,
device and the token read-back) over the number of decode steps in the
window (host clock around each step, summed)."""


def read(ctx):
    steps = ctx["steps"]
    return 1e3 * sum(w for w, _ in steps) / len(steps) if steps else None
