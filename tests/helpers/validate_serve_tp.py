"""Validate the serving engine's tensor-parallel decode path on 2
simulated devices.

Default: the continuous-batching engine driving its logits collective
through the committed decision artifact must generate tokens
BIT-IDENTICAL to the per-request dense (single-program) oracle, for both
TP collectives — and the decode-plan requests must resolve through the
KB-scale (small-message) end of the tuned grid, with an algorithm choice
that differs from the MB training regime.

``--static``: each TP collective under several static algorithms; every
decode step's logits must be BIT-IDENTICAL to the engine's without a
mesh, and the tokens to the oracle's.

Prints OK/FAIL lines and ``FAILS: n``; exit 1 on any FAIL.
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=2")
import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))
from repro import compat
from repro.comms import CollectiveRequest, Communicator
from repro.configs import get_config
from repro.core.collectives.dispatch import CollectiveSpec
from repro.models.registry import build_model
from repro.serve import ServeEngine, Scheduler, synthetic_trace

ART = os.path.join(os.path.dirname(__file__), "..", "..", "examples",
                   "artifacts", "tuned_decision.json")
BLOCK, MAX_ACTIVE = 4, 2

cfg = get_config("smollm-135m").reduced()
api = build_model(cfg, attn_impl="xla")
params = api.init(jax.random.PRNGKey(0))
mesh = compat.make_mesh((2,), ("model",))
comm = Communicator.create(artifact=ART)


def trace():
    return synthetic_trace(4, rate_rps=500.0, vocab=cfg.vocab_size,
                           prompt_lens=(4, 6), max_new=6, seed=0)


VIEW = -(-max(r.prompt_len + r.max_new for r in trace()) // BLOCK) * BLOCK


def oracle(req, api=api, params=params):
    tokens = jnp.asarray(np.asarray(req.prompt, np.int32))[None]
    logits, cache = api.prefill(params, tokens, VIEW)
    tok = int(jnp.argmax(logits[0, -1]))
    out = [tok]
    for _ in range(req.max_new - 1):
        logits, cache = api.decode_step(params, cache,
                                        jnp.asarray([[tok]], jnp.int32))
        tok = int(jnp.argmax(logits[0]))
        out.append(tok)
    return out


def serve(**engine_kw):
    """Serve the trace on the simulated clock; the tokens per request and
    the logits of every decode step."""
    engine = ServeEngine(api, params, max_active=MAX_ACTIVE, view_len=VIEW,
                         block_size=BLOCK, **engine_kw)
    (rung, step), = engine._steps.items()
    logits = []

    def recording(*args):
        out = step(*args)
        logits.append(np.asarray(out[0]))
        return out

    engine._steps[rung] = recording
    sched = Scheduler(trace(), max_active=MAX_ACTIVE,
                      token_budget=MAX_ACTIVE * VIEW)
    engine.run(sched, cost_model=lambda kind, n: 1e-3)
    return engine, {r.rid: list(r.generated) for r in sched.finished}, \
        np.stack(logits)


want = {r.rid: oracle(r) for r in trace()}

fails = []
if "--static" in sys.argv[1:]:
    _, _, ref = serve()
    for collective, algo in [("all_gather", "xla"), ("all_gather", "ring"),
                             ("all_gather", "bruck"),
                             ("all_reduce", "xla"), ("all_reduce", "ring"),
                             ("all_reduce", "recursive_doubling"),
                             ("all_reduce", "rabenseifner")]:
        _, got, logits = serve(
            mesh=mesh, comm=Communicator.create(
                mesh, static=CollectiveSpec(algo, 1)),
            collective=collective)
        identical = bool(got == want and logits.shape == ref.shape
                         and (logits == ref).all())
        print(("OK  " if identical else "FAIL"),
              f"serve_tp/{collective}/{algo} bit-identical={identical}")
        if not identical:
            fails.append((collective, algo))
    print(f"FAILS: {len(fails)}")
    sys.exit(1 if fails else 0)

for collective in ("all_gather", "all_reduce"):
    engine, got, _ = serve(mesh=mesh, comm=comm, collective=collective)
    identical = got == want
    print(("OK  " if identical else "FAIL"),
          f"serve_tp/{collective} bit-identical={identical}")
    if not identical:
        fails.append(collective)

# the SSM family's layered step under the shard_map, at every rung: 24
# requests at once over 32 slots fill past slot 16, so the 16- and
# 32-slot programs both run
ssm_api = build_model(get_config("mamba2-130m").reduced())
ssm_params = ssm_api.init(jax.random.PRNGKey(0))
ssm_trace = synthetic_trace(24, rate_rps=1e6, vocab=ssm_api.cfg.vocab_size,
                            prompt_lens=(4, 6), max_new=6, seed=1)
ssm_want = {r.rid: oracle(r, ssm_api, ssm_params) for r in ssm_trace}
for collective in ("all_gather", "all_reduce"):
    ssm_engine = ServeEngine(ssm_api, ssm_params, max_active=32,
                             view_len=VIEW, block_size=BLOCK, mesh=mesh,
                             comm=comm, collective=collective)
    sched = Scheduler(synthetic_trace(
        24, rate_rps=1e6, vocab=ssm_api.cfg.vocab_size, prompt_lens=(4, 6),
        max_new=6, seed=1), max_active=32, token_budget=32 * VIEW)
    res = ssm_engine.run(sched, cost_model=lambda kind, n: 1e-3)
    got = {r.rid: list(r.generated) for r in sched.finished}
    rungs = {label for name, label, _ in res.counters.items()
             if name == "decode_steps"}
    identical = got == ssm_want and rungs == {"16", "32"}
    print(("OK  " if identical else "FAIL"),
          f"serve_tp/ssm/{collective} rungs={sorted(rungs)} "
          f"bit-identical={got == ssm_want}")
    if not identical:
        fails.append(f"ssm/{collective}")

# the executed decode plan resolves in the small-message regime and picks
# a different algorithm than the MB-scale training regime
reqs = engine.decode_requests()
print(comm.explain(reqs).render())
small = all(r.nbytes < (1 << 20) for r in reqs)
print(("OK  " if small else "FAIL"), "serve_tp/requests_kb_scale")
if not small:
    fails.append("kb_scale")
dec = next(r for r in reqs if r.op == "all_reduce")
train = CollectiveRequest("all_reduce", 4 << 20, axis="model",
                          axis_size=2, dtype="float32")
dec_alg = comm.spec(dec).algorithm
train_alg = comm.spec(train).algorithm
differs = dec_alg != train_alg
print(("OK  " if differs else "FAIL"),
      f"serve_tp/regime_flip decode={dec_alg} train={train_alg}")
if not differs:
    fails.append("regime_flip")

print(f"FAILS: {len(fails)}")
sys.exit(1 if fails else 0)
