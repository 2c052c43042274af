"""Output tokens of the finished requests over the time from the first
due time to the last finish."""


def read(ctx):
    done = [r for r in ctx["records"] if r["finished"]]
    if not done:
        return None
    span = max(r["tokens"][-1] for r in done) - min(r["due"] for r in ctx["records"])
    return sum(len(r["generated"]) for r in done) / span
