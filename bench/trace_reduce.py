"""From a profiler trace to the numbers the metrics read.

``load`` reads the ``.xplane.pb`` a ``jax.profiler`` trace writes, with
``jax.profiler.ProfileData``, into plain lists: per device the operation
events of its ``XLA Ops`` and ``Async XLA Ops`` lines, and the host's
``bench.*`` annotations. A TPU names an operation event by its HLO text
(``%fusion.3 = bf16[4,2048]{...} fusion(...)``), so ``parse_op`` recovers
the opcode and the shapes of the output and of each operand from it.

Everything below ``load`` works on those lists (``Event`` tuples), which is
what the tests feed it from a small recorded fixture:

* ``busy_ns``: the union of the intervals in which an operation runs;
* ``collective_ns`` and ``exposed_ns``: time in collective operations, and
  the part of it with no other operation under it (control flow, whose
  events enclose the operations of its body, covers nothing);
* ``span_busy_ns``: device busy time inside the benchmark's host spans;
* ``kernels``: the events of custom calls (Pallas kernels) with shapes.
"""
from __future__ import annotations

import functools
import glob
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "collective-broadcast",
               "ragged-all-to-all")
CONTROL_FLOW = ("while", "call", "conditional")
_SHAPE = re.compile(r"\b(pred|s8|s16|s32|s64|u8|u16|u32|u64|f16|bf16|f32|f64"
                    r"|f8e4m3fn|f8e5m2)\[([0-9,]*)\]")
DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
               "s16": 2, "u16": 2, "f16": 2, "bf16": 2, "s32": 4, "u32": 4,
               "f32": 4, "s64": 8, "u64": 8, "f64": 8}


class Event(NamedTuple):
    line: str        # "XLA Ops", "Async XLA Ops" or a host annotation line
    name: str
    start: int       # ns
    end: int         # ns


class Trace(NamedTuple):
    devices: Dict[str, List[Event]]   # device plane name -> op events
    host: List[Event]                 # "bench.*" annotations


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            evs = []
            for line in plane.lines:
                if line.name in (OPS_LINE, ASYNC_LINE):
                    evs.extend(Event(line.name, e.name, int(e.start_ns),
                                     int(e.end_ns)) for e in line.events)
            devices[plane.name] = sorted(evs, key=lambda e: e.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(line.name, e.name, int(e.start_ns),
                                  int(e.end_ns)) for e in line.events
                            if e.name.startswith("bench."))
    return Trace(devices, sorted(host, key=lambda e: e.start))


# --------------------------------------------------------------------------
# HLO text of an operation event
# --------------------------------------------------------------------------
class Op(NamedTuple):
    name: str                            # "%fusion.3"
    opcode: str                          # "fusion", "custom-call", ...
    out: List[Tuple[str, Tuple[int, ...]]]
    operands: List[Tuple[str, Tuple[int, ...]]]
    text: str


def _shapes(s: str):
    return [(m.group(1), tuple(int(x) for x in m.group(2).split(",") if x))
            for m in _SHAPE.finditer(s)]


def _balanced(s: str, i: int) -> int:
    """Index just past the parenthesis group that opens at ``s[i]``."""
    depth = 0
    for j in range(i, len(s)):
        if s[j] == "(":
            depth += 1
        elif s[j] == ")":
            depth -= 1
            if depth == 0:
                return j + 1
    return len(s)


@functools.lru_cache(maxsize=1 << 16)
def parse_op(text: str) -> Op:
    """Split ``%name = TYPE opcode(operands), attributes``. Names that are
    not HLO text come back with an empty opcode."""
    m = re.match(r"\s*(%[\w.\-]+)\s*=\s*", text)
    if not m:
        return Op(text, "", [], [], text)
    i = m.end()
    if text[i:i + 1] == "(":
        j = _balanced(text, i)
    else:
        j = text.find(" ", i)
        j = len(text) if j < 0 else j
    out = _shapes(text[i:j])
    k = text.find("(", j)
    opcode = text[j:k].strip() if k >= 0 else text[j:].strip()
    end = _balanced(text, k) if k >= 0 else len(text)
    operands = []
    if k >= 0:
        depth, start = 0, k + 1
        for p in range(k + 1, end - 1):
            c = text[p]
            if c in "([{":
                depth += 1
            elif c in ")]}":
                depth -= 1
            elif c == "," and depth == 0:
                operands.extend(_shapes(text[start:p])[:1])
                start = p + 1
        operands.extend(_shapes(text[start:end - 1])[:1])
    return Op(m.group(1), opcode, out, operands, text)


def is_collective(op: Op) -> bool:
    base = op.name.lstrip("%").split(".")[0]
    return any(op.opcode.startswith(c) or base.startswith(c)
               for c in COLLECTIVES)


def is_control_flow(op: Op) -> bool:
    return op.opcode in CONTROL_FLOW


def is_kernel(op: Op) -> bool:
    return op.opcode == "custom-call" and "tpu_custom_call" in op.text


# --------------------------------------------------------------------------
# interval arithmetic
# --------------------------------------------------------------------------
def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo: int, hi: int):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b):
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def busy_ns(events: List[Event], lo: Optional[int] = None,
            hi: Optional[int] = None) -> int:
    """Time in which at least one operation of ``XLA Ops`` ran."""
    iv = union((e.start, e.end) for e in events if e.line == OPS_LINE)
    if lo is not None:
        iv = clip(iv, lo, hi)
    return length(iv)


def collective_intervals(events: List[Event]):
    return union((e.start, e.end) for e in events
                 if is_collective(parse_op(e.name)))


def collective_ns(events: List[Event], lo=None, hi=None) -> int:
    iv = collective_intervals(events)
    if lo is not None:
        iv = clip(iv, lo, hi)
    return length(iv)


def exposed_ns(events: List[Event], lo=None, hi=None) -> int:
    """Collective time with no other operation of ``XLA Ops`` under it. A
    loop or call that encloses the collective is not work under it."""
    coll = collective_intervals(events)
    compute = union((e.start, e.end) for e in events if e.line == OPS_LINE
                    and not is_collective(op := parse_op(e.name))
                    and not is_control_flow(op))
    iv = subtract(coll, compute)
    if lo is not None:
        iv = clip(iv, lo, hi)
    return length(iv)


def span_busy_ns(events: List[Event], spans: Iterable[Event]) -> int:
    """Device busy time inside the host spans ``spans`` (which do not
    overlap): the device work of the calls they wrap."""
    iv = union((e.start, e.end) for e in events if e.line == OPS_LINE)
    return sum(length(clip(iv, s.start, s.end)) for s in spans)


def kernels(events: List[Event]) -> List[Tuple[Op, int]]:
    """Custom-call (Pallas kernel) events: (parsed op, duration ns)."""
    out = []
    for e in events:
        if e.line != OPS_LINE or "custom-call" not in e.name:
            continue
        op = parse_op(e.name)
        if is_kernel(op):
            out.append((op, e.end - e.start))
    return out


def reduce_window(tr: Trace, lo: int, ctx: dict, device: dict):
    """Put the traced window [lo, last device operation] into ``ctx`` for
    the metric readers, the device's busy and window seconds into
    ``device``, and return the run's ``breakdown`` (device 0's operations
    that took most time and its longest idle gaps). A trace with no device
    plane (a run off the chip) leaves the trace out."""
    if not tr.devices:
        return None
    hi = max(e.end for evs in tr.devices.values() for e in evs)
    hi = max([hi] + [h.end for h in tr.host if h.start >= lo])
    ctx.update(trace=tr, lo=lo, hi=hi)
    busy = [busy_ns(evs, lo, hi) for evs in tr.devices.values()]
    device.update(busy_s=sum(busy) / len(busy) / 1e9,
                  window_s=(hi - lo) / 1e9)
    first = tr.devices[sorted(tr.devices)[0]]
    return {"device_ops": top_ops(first),
            "idle_gaps": idle_gaps(first, tr.host, lo, hi)}


def top_ops(events: List[Event], n: int = 10):
    """The ``n`` operations (by opcode and output shape) that took most
    device time, in seconds; loops and calls, which enclose operations
    counted on their own, are left out."""
    tot: Dict[str, int] = {}
    for e in events:
        if e.line != OPS_LINE:
            continue
        op = parse_op(e.name)
        if is_control_flow(op):
            continue
        key = f"{op.opcode or op.name} {op.out[0][0]}{list(op.out[0][1])}" \
            if op.out else (op.opcode or e.name[:60])
        if is_kernel(op):
            key = "tpu_custom_call " + key
        tot[key] = tot.get(key, 0) + (e.end - e.start)
    return [[k, v / 1e9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(events: List[Event], host: List[Event], lo: int, hi: int,
              n: int = 10):
    """The ``n`` longest device-idle gaps in [lo, hi], each named by the
    innermost ``bench.*`` host span that covers its middle."""
    iv = clip(union((e.start, e.end) for e in events if e.line == OPS_LINE),
              lo, hi)
    gaps = subtract([(lo, hi)], iv)
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) // 2
        cover = [h for h in host if h.start <= mid <= h.end]
        name = min(cover, key=lambda h: h.end - h.start).name \
            if cover else "host: outside any benchmark span"
        out.append([name, (e - s) / 1e9])
    return out
