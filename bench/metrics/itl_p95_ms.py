"""95th percentile of all gaps between consecutive output tokens of all
requests."""
import numpy as np


def read(ctx):
    gaps = [1e3 * (b - a) for r in ctx["records"]
            for a, b in zip(r["tokens"], r["tokens"][1:])]
    return float(np.percentile(gaps, 95)) if gaps else None
