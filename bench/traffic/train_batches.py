"""Training batches from the parameters of a job file.

``batches`` makes ``distinct_batches`` different global batches of
next-token data (uniform random ids: labels are the tokens shifted by
one) on the device, in one jitted call, laid out with the step's batch
sharding. The window cycles through them; the first steps, which the
reference follows, each get a batch of their own.
"""
from __future__ import annotations


def batches(mix: dict, key, vocab: int, global_batch: int, sharding=None):
    import jax
    n, S = mix["distinct_batches"], mix["seq"]

    def make(k):
        toks = jax.random.randint(k, (n, global_batch, S + 1), 0, vocab,
                                  dtype="int32")
        return [{"tokens": toks[i, :, :-1], "labels": toks[i, :, 1:]}
                for i in range(n)]

    out = None if sharding is None else [sharding] * n
    return jax.jit(make, out_shardings=out)(key)
