"""Production serving tier: continuous batching over a paged KV cache.

Layering (bottom to top):

  * ``paged_kv``   — block pool + free list + per-request block tables;
                     the only code that touches pool storage layout.
  * ``scheduler``  — pure-python continuous-batching policy: arrival
                     queue, token-budget admission, SLO-aware
                     prefill/decode interleave, mid-flight join/retire.
  * ``tp``         — the tensor-parallel logits assembly through the
                     tuned ``Communicator``, and the requests its plan
                     renders.
  * ``engine``     — JAX execution: per-family prefill + vmapped decode
                     over fixed request slots, paged KV reads, the
                     ``tp`` assembly under a mesh, and per-request
                     latency records for ``decode_summary``.

``launch/serve.py`` is a thin CLI over this package. The engine's
correctness oracle is the model's own dense ``decode_step``, one request
at a time (``tests/test_serving.py``).
"""
from repro.serve.paged_kv import BlockPool, PagedKV
from repro.serve.scheduler import Request, Scheduler, load_trace, \
    synthetic_trace
from repro.serve.engine import ServeEngine, ServeResult

__all__ = [
    "BlockPool",
    "PagedKV",
    "Request",
    "Scheduler",
    "load_trace",
    "synthetic_trace",
    "ServeEngine",
    "ServeResult",
]
