"""From a profiler trace to intervals: device operations, the host's own
spans, and the gaps in which the device sat idle.

The JAX profiler writes an XSpace (``*.xplane.pb``). Its device planes
(``/device:TPU:<n>``) carry the executed operations on the line
``XLA Ops``; the host plane (``/host:CPU``) carries one line per thread
with the ``jax.profiler.TraceAnnotation`` spans the harness opens around
each call into the program. Both are on one clock in nanoseconds.

Everything here is plain arithmetic on ``(name, start_ns, end_ns)``
tuples, so that the per-layer readers can be checked on a small recorded
trace without a chip.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, NamedTuple, Tuple

Interval = Tuple[str, int, int]           # (name, start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
# The harness's own host spans (bench/harness.py), in the order of a step.
HOST_SPANS = ("batch_draw", "dispatch", "metric_pull", "heldout_eval")
STEP_SPAN = "hf_step"


class Trace(NamedTuple):
    device_ops: Dict[int, List[Interval]]   # device id -> its operations
    host_spans: List[Interval]              # the harness's spans, all threads


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    """Read an XSpace file into a :class:`Trace`."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    device_ops: Dict[int, List[Interval]] = {}
    host: List[Interval] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                device_ops.setdefault(int(m.group(1)), []).extend(
                    (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                    for e in line.events)
            elif not m and plane.name.startswith("/host"):
                host.extend(
                    (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                    for e in line.events
                    if e.name in HOST_SPANS or e.name == STEP_SPAN)
    for ops in device_ops.values():
        ops.sort(key=lambda iv: iv[1])
    host.sort(key=lambda iv: iv[1])
    return Trace(device_ops, host)


def union(intervals) -> List[Tuple[int, int]]:
    """Merge (start, end) pairs into disjoint, sorted pairs."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted((iv[-2], iv[-1]) for iv in intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(pairs, lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in pairs if e > lo and s < hi]


def covered(pairs) -> int:
    return sum(e - s for s, e in pairs)


def window(trace: Trace) -> Tuple[int, int]:
    """The traced window: from the first traced step's start to the last
    one's end, by the harness's ``hf_step`` spans."""
    steps = [iv for iv in trace.host_spans if iv[0] == STEP_SPAN]
    if not steps:
        raise ValueError("the trace holds no hf_step span")
    return steps[0][1], max(iv[2] for iv in steps)


def busy_ns(trace: Trace, device: int, lo: int, hi: int) -> int:
    """Nanoseconds of [lo, hi) in which some operation ran on ``device``."""
    return covered(clip(union(trace.device_ops.get(device, [])), lo, hi))


def gaps(trace: Trace, device: int, lo: int, hi: int):
    """The idle stretches of ``device`` inside [lo, hi), each named after
    the host span it began in (the innermost harness span, else "host")."""
    busy = clip(union(trace.device_ops.get(device, [])), lo, hi)
    idle, t = [], lo
    for s, e in busy:
        if s > t:
            idle.append((t, s))
        t = max(t, e)
    if t < hi:
        idle.append((t, hi))
    spans = [iv for iv in trace.host_spans if iv[0] != STEP_SPAN]
    named = []
    for s, e in idle:
        owner = "host"
        for name, hs, he in spans:
            if hs <= s < he:
                owner = name
        named.append((owner, s, e))
    return named


def op_label(text: str) -> str:
    """A device operation's short name. The trace names each operation by
    its HLO text, ``%fusion.12 = f32[8,4]{1,0} fusion(...), ...``; the label
    keeps the instruction's name, its opcode and its result's shape:
    ``%fusion.12 fusion f32[8,4]`` (``tuple`` for a tuple result)."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text[:80]
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        shape, rest = "tuple", rest[i + 1:].lstrip()
    else:
        shape, _, rest = rest.partition(" ")
        shape = shape.split("{")[0]
    return f"{name} {rest.split('(')[0]} {shape}"


def self_ns(ops: List[Interval], lo: int, hi: int) -> Dict[str, int]:
    """Nanoseconds inside [lo, hi) of each operation name, less the time of
    the operations nested in it: a loop's body runs inside the loop's own
    operation, and would otherwise count twice."""
    ivs = sorted(((max(s, lo), min(e, hi), n) for n, s, e in ops
                  if e > lo and s < hi), key=lambda iv: (iv[0], -iv[1]))
    tot: Dict[str, int] = {}
    stack: List[Tuple[int, int, str]] = []
    for s, e, n in ivs:
        while stack and stack[-1][1] <= s:
            stack.pop()
        tot[n] = tot.get(n, 0) + (e - s)
        if stack and e <= stack[-1][1]:
            parent = stack[-1][2]
            tot[parent] -= e - s
        stack.append((s, e, n))
    return tot


def top_ops(trace: Trace, device: int, lo: int, hi: int, n: int = 10):
    """[(op label, seconds)] of the ``n`` operations that took most device
    time of their own inside [lo, hi), summed over their calls."""
    tot: Dict[str, int] = {}
    for name, ns in self_ns(trace.device_ops.get(device, []), lo, hi).items():
        label = op_label(name)
        tot[label] = tot.get(label, 0) + ns
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns * 1e-9] for name, ns in ranked]


def top_gaps(trace: Trace, device: int, lo: int, hi: int, n: int = 10):
    """[(host span, seconds)] of the ``n`` longest idle gaps."""
    g = sorted(gaps(trace, device, lo, hi), key=lambda iv: iv[1] - iv[2])[:n]
    return [[name, (e - s) * 1e-9] for name, s, e in g]
