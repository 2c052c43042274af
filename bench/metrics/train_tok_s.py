"""Tokens trained in the window over the window's time (global tokens:
every chip's share), from the host clock around all the window's steps
and one block at the end."""


def read(ctx):
    return ctx["tokens"] / ctx["window_s"]
