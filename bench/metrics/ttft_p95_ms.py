"""95th percentile of first-token time minus due time over every request
due in the window; a request with no first token by the drain deadline
counts at the deadline."""
import numpy as np


def read(ctx):
    recs, end = ctx["records"], ctx["deadline"]
    ttft = [1e3 * ((r["tokens"][0] if r["tokens"] else end) - r["due"])
            for r in recs]
    return float(np.percentile(ttft, 95)) if ttft else None
