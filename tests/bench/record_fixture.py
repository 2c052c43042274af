"""Record the small trace the reduction tests read.

    python3 tests/bench/record_fixture.py tests/bench/fixtures/trace_small.json

Run on the chip (on four chips the trace has collectives). A jitted step
runs the flash-attention kernel on each chip's shard, a matrix product,
and a gradient-sized all-reduce over the chips, under the profiler with
``bench.*`` host spans; the events of the trace are written as JSON lists
``[line, name, start_ns, end_ns]`` per device plane, and the host spans.
"""
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(out):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from bench import trace_reduce
    from repro import compat
    from repro.kernels.attention import flash_attention

    n = len(jax.devices())
    mesh = compat.make_mesh((n,), ("data",))

    def body(q, k, v, w, g):
        o = flash_attention(q, k, v, causal=True)
        y = jnp.einsum("sd,de->se", o.reshape(-1, 64)[:512], w)
        return o, y, jax.lax.psum(g, "data")

    f = jax.jit(compat.shard_map(
        body, mesh=mesh, in_specs=(P("data"),) * 3 + (P(), P("data")),
        out_specs=(P("data"), P("data"), P("data")), check_vma=False))
    key = jax.random.key(0)
    sh = NamedSharding(mesh, P("data"))
    q = jax.device_put(jax.random.normal(key, (n, 512, 4, 64), jnp.bfloat16), sh)
    kv = jax.device_put(jax.random.normal(key, (n, 512, 1, 64), jnp.bfloat16), sh)
    w = jnp.ones((64, 64), jnp.bfloat16)
    g = jax.device_put(jnp.ones((n, 1 << 20), jnp.float32), sh)
    jax.block_until_ready(f(q, kv, kv, w, g))
    tdir = tempfile.mkdtemp()
    jax.profiler.start_trace(tdir)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.step"):
            r = f(q, kv, kv, w, g)
    with jax.profiler.TraceAnnotation("bench.block"):
        jax.block_until_ready(r)
    jax.profiler.stop_trace()
    tr = trace_reduce.load(tdir)
    t0 = min(e.start for e in tr.host)
    rel = lambda e: [e.line, e.name, e.start - t0, e.end - t0]  # noqa: E731
    data = {"devices": {d: [rel(e) for e in evs] for d, evs in
                        sorted(tr.devices.items())},
            "host": [rel(e) for e in tr.host],
            "device_kind": jax.devices()[0].device_kind}
    with open(out, "w") as fh:
        json.dump(data, fh, indent=0)
    print(out, os.path.getsize(out), "bytes",
          {d: len(v) for d, v in data["devices"].items()})


if __name__ == "__main__":
    main(sys.argv[1])
