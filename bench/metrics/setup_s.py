"""Set-up: from the harness's start to the window's (imports, weights,
batches or engine, compilation or the compile cache, warm-up and, in
training, the check steps the reference follows)."""


def read(ctx):
    return ctx["setup_s"]
