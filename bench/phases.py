"""Device time of the HF step by phase, from a profiler trace.

The program runs every region of its step inside a phase scope
(``repro.obs.telemetry.phase``, a ``jax.named_scope``), and XLA keeps the
scope in each instruction's ``op_name``:
``jit(step)/krylov_solve/while/body/curvature_product/.../dot_general``.
The TPU profiler writes that name beside every executed operation, as the
``tf_op`` stat of the operation's metadata in the XSpace.
``jax.profiler.ProfileData`` does not expose it, so this module reads the
few fields it needs from the XSpace itself, with a descriptor built here.

Each instant of device time inside the traced window goes to the innermost
operation running then, the one that started last: a loop's body runs
inside the loop's own operation. An operation's phases are the phase
scopes in its ``op_name``, outermost first. A phase's time counts the
phases nested in it (``curvature_product`` in ``krylov_solve``); its own
time counts only the operations whose innermost phase it is. The own times
of the phases and of the unscoped remainder sum to the step's busy time.
"""
from __future__ import annotations

import functools
import glob
import heapq
import os
import re
import sys
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

from bench import trace_reduce as tr

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEP_MODULE = "jit_step"          # the harness's jax.jit of the optimizer's step
MODULES_LINE = "XLA Modules"
MODULE_NAME = re.compile(r"^(.*)\((\d+)\)$")
IDENT = re.compile(r"[A-Za-z_]\w*")


class Op(NamedTuple):
    start: int                    # ns, the clock of trace_reduce
    end: int
    module: Optional[str]         # e.g. "jit_step"
    op_name: Optional[str]        # the instruction's op_name, where it has one
    name: str                     # the operation's HLO text


# ----------------------------------------------------------------- XSpace --
@functools.lru_cache(maxsize=None)
def _xspace_class():
    """The XSpace message, cut to the fields read here (xplane.proto's
    numbers; every other field is skipped as unknown)."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fdp = descriptor_pb2.FileDescriptorProto(name="bench_xplane.proto",
                                             package="bench_xplane")

    def message(name, fields):
        m = fdp.message_type.add(name=name)
        for fname, number, ftype, label, type_name in fields:
            f = m.field.add(name=fname, number=number, type=ftype, label=label)
            if type_name:
                f.type_name = ".bench_xplane." + type_name

    one, rep = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    message("XStat", [("metadata_id", 1, F.TYPE_INT64, one, None),
                      ("double_value", 2, F.TYPE_DOUBLE, one, None),
                      ("uint64_value", 3, F.TYPE_UINT64, one, None),
                      ("int64_value", 4, F.TYPE_INT64, one, None),
                      ("str_value", 5, F.TYPE_STRING, one, None),
                      ("ref_value", 7, F.TYPE_UINT64, one, None)])
    message("XEvent", [("metadata_id", 1, F.TYPE_INT64, one, None),
                       ("offset_ps", 2, F.TYPE_INT64, one, None),
                       ("duration_ps", 3, F.TYPE_INT64, one, None)])
    message("XLine", [("name", 2, F.TYPE_STRING, one, None),
                      ("timestamp_ns", 3, F.TYPE_INT64, one, None),
                      ("events", 4, F.TYPE_MESSAGE, rep, "XEvent")])
    message("XEventMetadata", [("id", 1, F.TYPE_INT64, one, None),
                               ("name", 2, F.TYPE_STRING, one, None),
                               ("stats", 5, F.TYPE_MESSAGE, rep, "XStat")])
    message("XStatMetadata", [("id", 1, F.TYPE_INT64, one, None),
                              ("name", 2, F.TYPE_STRING, one, None)])
    # The two maps, as the repeated key/value entries they are on the wire.
    message("EventMetadataEntry", [("key", 1, F.TYPE_INT64, one, None),
                                   ("value", 2, F.TYPE_MESSAGE, one,
                                    "XEventMetadata")])
    message("StatMetadataEntry", [("key", 1, F.TYPE_INT64, one, None),
                                  ("value", 2, F.TYPE_MESSAGE, one,
                                   "XStatMetadata")])
    message("XPlane", [("name", 2, F.TYPE_STRING, one, None),
                       ("lines", 3, F.TYPE_MESSAGE, rep, "XLine"),
                       ("event_metadata", 4, F.TYPE_MESSAGE, rep,
                        "EventMetadataEntry"),
                       ("stat_metadata", 5, F.TYPE_MESSAGE, rep,
                        "StatMetadataEntry")])
    message("XSpace", [("planes", 1, F.TYPE_MESSAGE, rep, "XPlane")])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def _stat_value(stat, stat_names):
    if stat.HasField("str_value"):
        return stat.str_value
    if stat.HasField("ref_value"):
        return stat_names.get(stat.ref_value)
    for field in ("uint64_value", "int64_value"):
        if stat.HasField(field):
            return str(getattr(stat, field))
    return None


def read_ops(data: bytes) -> Dict[int, List[Op]]:
    """Every operation on each TPU device's ``XLA Ops`` line, with its
    module and ``op_name``, in the order of ``trace_reduce.load``."""
    space = _xspace_class()()
    space.ParseFromString(data)
    out: Dict[int, List[Op]] = {}
    for plane in space.planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {}
        for e in plane.event_metadata:
            stats = {stat_names.get(s.metadata_id): _stat_value(s, stat_names)
                     for s in e.value.stats}
            meta[e.key] = (e.value.name, stats.get("tf_op"),
                           stats.get("program_id"))
        modules = {}
        for line in plane.lines:
            if line.name == MODULES_LINE:
                for ev in line.events:
                    mm = MODULE_NAME.match(meta.get(ev.metadata_id, ("",))[0])
                    if mm:
                        modules[mm.group(2)] = mm.group(1)
        ops = out.setdefault(int(m.group(1)), [])
        for line in plane.lines:
            if line.name != tr.OPS_LINE:
                continue
            for ev in line.events:
                name, tf_op, program = meta.get(ev.metadata_id,
                                                ("", None, None))
                start = line.timestamp_ns + ev.offset_ps // 1000
                ops.append(Op(start, start + ev.duration_ps // 1000,
                              modules.get(program), tf_op or None, name))
    for ops in out.values():
        ops.sort(key=lambda o: o.start)       # as trace_reduce.load sorts
    return out


def find_xplane(trace: tr.Trace, device: int, root: Optional[str] = None) -> str:
    """The ``.xplane.pb`` under the harness's trace directory that
    ``trace`` (``trace_reduce.load``'s reading) was read from: the newest
    whose operations on ``device`` are the same."""
    root = root or ROOT
    want = trace.device_ops.get(device, [])
    paths = glob.glob(os.path.join(root, ".bench_out", "trace", "**",
                                   "*.xplane.pb"), recursive=True)
    for path in sorted(paths, key=os.path.getmtime, reverse=True):
        with open(path, "rb") as f:
            ops = read_ops(f.read()).get(device, [])
        if ops and [(o.name, o.start, o.end) for o in ops] == want:
            return path
    raise FileNotFoundError(f"no trace under {root}/.bench_out/trace holds "
                            f"the traced window's {len(want)} operations")


# ------------------------------------------------------------- attribution --
def phase_path(op_name: Optional[str], phases: Sequence[str]) -> Tuple[str, ...]:
    """The phase scopes in ``op_name``, outermost first. Scopes may sit
    inside transformation names: ``jvp(krylov_solve)``."""
    if not op_name:
        return ()
    return tuple(t for t in IDENT.findall(op_name) if t in phases)


def own_ns(intervals, lo: int, hi: int) -> Dict[object, int]:
    """{key: ns} over ``(start, end, key)`` intervals clipped to [lo, hi):
    each instant covered by some interval goes to the one of them that
    started last (of two that start together, the one that ends first)."""
    ivs = sorted((max(s, lo), min(e, hi), i, k)
                 for i, (s, e, k) in enumerate(intervals) if e > lo and s < hi)
    points = sorted({p for s, e, _, _ in ivs for p in (s, e)})
    out: Dict[object, int] = {}
    live: list = []                       # heap of (-start, end, index)
    j = 0
    for a, b in zip(points, points[1:]):
        while j < len(ivs) and ivs[j][0] <= a:
            heapq.heappush(live, (-ivs[j][0], ivs[j][1], j))
            j += 1
        while live and live[0][1] <= a:
            heapq.heappop(live)
        if live:
            key = ivs[live[0][2]][3]
            out[key] = out.get(key, 0) + (b - a)
    return out


def paths(ops: Sequence[Op], phases: Sequence[str]) -> List[Tuple[str, ...]]:
    """Each op's phases. The trace gives XLA's loop ops, and the copies XLA
    adds, no ``op_name``; such an op takes the phases common to the ops
    running inside it (a loop's body), else those of the op it runs inside
    (a copy in a loop's body)."""
    own = [phase_path(o.op_name, phases) for o in ops]
    order = sorted(range(len(ops)), key=lambda i: (ops[i].start, -ops[i].end))
    parent: Dict[int, int] = {}
    stack: List[int] = []
    for i in order:
        while stack and ops[stack[-1]].end < ops[i].end:
            stack.pop()
        if stack:
            parent[i] = stack[-1]
        stack.append(i)
    inner: Dict[int, List[Tuple[str, ...]]] = {}
    for i in reversed(order):               # every op after the ops inside it
        if not own[i] and inner.get(i):
            common = os.path.commonprefix(inner[i])
            own[i] = tuple(common)
        if own[i] and i in parent:
            inner.setdefault(parent[i], []).append(own[i])
    for i in order:                         # every op after the op it is in
        if not own[i] and i in parent:
            own[i] = own[parent[i]]
    return own


class Phases(NamedTuple):
    busy_ns: int                  # the step module's busy time in the window
    own: Dict[Optional[str], int]        # innermost phase (None: unscoped) -> ns
    inclusive: Dict[str, int]     # phase -> ns, nested phases included


def split(ops: Sequence[Op], lo: int, hi: int, phases: Sequence[str],
          module: str = STEP_MODULE) -> Phases:
    """The device time in [lo, hi) of ``module``'s operations, by phase."""
    ops = [o for o in ops if o.module == module]
    ivs = [(o.start, o.end, path)
           for o, path in zip(ops, paths(ops, phases))]
    by_path = own_ns(ivs, lo, hi)
    own: Dict[Optional[str], int] = {}
    inclusive: Dict[str, int] = {}
    for path, ns in by_path.items():
        inner = path[-1] if path else None
        own[inner] = own.get(inner, 0) + ns
        for p in set(path):
            inclusive[p] = inclusive.get(p, 0) + ns
    return Phases(sum(by_path.values()), own, inclusive)


# ------------------------------------------------------------------ reader --
def log(msg):
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def program_phases() -> Optional[Tuple[str, ...]]:
    """The program's phase vocabulary, or None for a program without one."""
    from repro.obs import telemetry

    return getattr(telemetry, "PHASES", None)


def per_step(ctx) -> Optional[Dict[str, float]]:
    """{phase: ms per traced step, nested phases included} of the traced
    window, averaged over the cell's devices, and logged with each phase's
    own time and the unscoped remainder. None for a program that names no
    phases, and for a trace without TPU operations (a run on the CPU);
    raises where the program names them and the step's operations carry
    none, e.g. an executable loaded from a cache entry compiled without
    them. Computed once per run (kept in ``ctx``)."""
    if "phase_ms_per_step" in ctx:
        return ctx["phase_ms_per_step"]
    phases = program_phases()
    result = None
    if phases is not None and any(ctx["trace"].device_ops.get(d)
                                  for d in ctx["devices"]):
        trace, lo, hi = ctx["trace"], ctx["lo"], ctx["hi"]
        path = find_xplane(trace, ctx["devices"][0])
        with open(path, "rb") as f:
            ops = read_ops(f.read())
        splits = [split(ops.get(d, []), lo, hi, phases) for d in ctx["devices"]]
        if not any(s.inclusive for s in splits):
            raise RuntimeError(f"no operation of {STEP_MODULE} in {path} "
                               f"carries a phase of {phases}")
        k = len(splits) * len(ctx["traced_steps"]) * 1e6   # ns -> ms/step

        def mean(field):
            out = {}
            for s in splits:
                for key, ns in getattr(s, field).items():
                    out[key] = out.get(key, 0.0) + ns / k
            return out

        inclusive, own = mean("inclusive"), mean("own")
        result = {p: inclusive.get(p, 0.0) for p in phases}
        for p in phases:
            log(f"phase {p} {result[p]:.3f} ms/step, own {own.get(p, 0.0):.3f}")
        log(f"phase unscoped own {own.get(None, 0.0):.3f} ms/step, of "
            f"{sum(s.busy_ns for s in splits) / k:.3f} ms/step busy")
    ctx["phase_ms_per_step"] = result
    return result
