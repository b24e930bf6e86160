"""Per-process structured telemetry sink + trace-time instrumentation hooks.

Three parts:

  * **Phase vocabulary** — :data:`PHASES` names the regions of an outer HF
    step, and :func:`phase` enters ``jax.named_scope(name)`` for one of
    them, always. A named scope is trace-time metadata: every operation
    traced inside it carries the name in its XLA ``op_name``
    (``jit(step)/krylov_solve/while/body/curvature_product/...``), which
    the profiler's device trace reports per executed operation, while the
    compiled instructions stay the same. This is how device time is split
    by phase on the chip.

  * **Host side** — :class:`Telemetry` appends JSON events to
    ``events-p{N}.jsonl`` (one object per line) and offers a wall-clock
    ``span`` context manager plus instant/counter emitters for host code
    (train loop, serve scheduler). Each span also opens a
    ``jax.profiler.TraceAnnotation`` of the same name, so a profiler trace
    shows it on the device trace's clock.

  * **In-jit side** — module-level trace-time state, following the
    ``core.collectives.count_executed`` pattern: while a sink is installed
    via :func:`install`, tracing the optimizer step bakes in host-callback
    timestamps — phase end-markers (named after the phases), collective
    begin/end pairs (see ``core.collectives.preduce``)
    and Krylov solve summaries. With no sink installed **nothing is traced
    in**: every hook checks ``_active`` at trace time and returns before
    touching jax, so the disabled jaxpr carries no callback (zero-cost-off;
    asserted in tests/test_telemetry.py). The markers are host callbacks:
    the right source on the CPU, not on the chip, where a sink changes the
    program and the device trace is read instead.

Timing semantics on XLA:CPU: a callback's ``time.time()`` is when the
executor ran it, no earlier than the value it depends on became ready;
two callbacks on one value reach the host in no fixed order. A
collective's begin callback depends only on the reduce *input* and its
end callback on the reduce *output*. Under ``HFConfig.overlap`` the hidden
grad-reduce span therefore brackets the curvature primal build. The
blocking schedule builds the primal from the parameters its
``grad_reduce`` marker hands on (:func:`gated_marker`, core/hf.py), so the
build starts after the reduce; ``obs/trace.py`` closes that phase at the
later of its marker and the collective's end, which wait on one value.

Every callback operand is multiplied by ``0 * sum(dep)`` so it stays
data-dependent (can't be constant-folded or hoisted past the value it
brackets) while adding no numerics.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from collections import deque
from typing import Any, Optional

__all__ = [
    "PHASES", "phase", "Telemetry", "install", "active",
    "collective_label", "current_collective_label", "step_scope",
    "current_step", "marker", "gated_marker", "solve_event", "reject_event",
    "register_crash_flush",
]

# The regions of one outer HF step (core/hf.py), in step order, and the
# scope nested in ``krylov_solve`` around each curvature-operator
# application. The end-markers take their names from here; no marker
# closes ``curvature_product`` or ``direction``.
PHASES = (
    "grad_build", "grad_reduce", "curvature_primal", "krylov_solve",
    "curvature_product", "direction", "line_search", "update_damping",
)


@contextlib.contextmanager
def phase(name: str):
    """Scope the operations traced inside as phase ``name`` of the step.

    Always on: ``jax.named_scope`` only names the operations (their
    ``op_name``), adding none."""
    if name not in PHASES:
        raise ValueError(f"unknown phase {name!r}; one of {PHASES}")
    import jax
    with jax.named_scope(name):
        yield


class Telemetry:
    """Append-only JSONL event sink for one process.

    Thread-safe: jax debug callbacks may land on a runtime thread while the
    host loop emits spans. Events are flushed line-by-line so a crashed or
    killed process still leaves a parseable file.
    """

    def __init__(self, out_dir: str, process_index: int = 0,
                 meta: Optional[dict] = None):
        os.makedirs(out_dir, exist_ok=True)
        self.out_dir = out_dir
        self.process_index = process_index
        self.path = os.path.join(out_dir, f"events-p{process_index}.jsonl")
        self._lock = threading.Lock()
        self._f = open(self.path, "a", buffering=1)
        # Pending collective begins, FIFO per (tag, label). On CPU same-tag
        # reduces are serialized by data dependence, so FIFO pairing is
        # faithful; a leftover begin (e.g. process killed mid-step) is
        # dropped at close().
        self._pending: dict = {}
        self.emit({"ev": "meta", "process": process_index,
                   "ts": time.time(), **(meta or {})})

    # -- raw emission ----------------------------------------------------
    def emit(self, event: dict) -> None:
        line = json.dumps(event, separators=(",", ":"), default=float)
        with self._lock:
            self._f.write(line + "\n")

    # -- host-side API ---------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str, **fields):
        """A host span: a ``span`` event on the wall clock, and a profiler
        ``TraceAnnotation`` of the same name on the trace's clock."""
        from jax.profiler import TraceAnnotation

        t0 = time.time()
        try:
            with TraceAnnotation(name):
                yield
        finally:
            t1 = time.time()
            self.emit({"ev": "span", "name": name, "t0": t0, "t1": t1,
                       **fields})

    def instant(self, name: str, **fields) -> None:
        self.emit({"ev": "instant", "name": name, "ts": time.time(),
                   **fields})

    def counter(self, name: str, value, ts: Optional[float] = None) -> None:
        self.emit({"ev": "counter", "name": name, "value": float(value),
                   "ts": time.time() if ts is None else ts})

    def log(self, msg: str) -> None:
        self.emit({"ev": "log", "msg": str(msg), "ts": time.time()})

    # -- in-jit callback receivers --------------------------------------
    def phase_event(self, name: str, step: int) -> None:
        self.emit({"ev": "phase", "name": name, "step": int(step),
                   "ts": time.time()})

    def collective_begin(self, tag: str, label: str) -> None:
        key = (tag, label)
        with self._lock:
            self._pending.setdefault(key, deque()).append(time.time())

    def collective_end(self, tag: str, label: str, step: int = -1) -> None:
        t1 = time.time()
        key = (tag, label)
        with self._lock:
            q = self._pending.get(key)
            t0 = q.popleft() if q else t1
        self.emit({"ev": "coll", "tag": tag, "label": label,
                   "step": int(step), "t0": t0, "t1": t1})

    def solve_event(self, step: int, **fields) -> None:
        self.emit({"ev": "solve", "step": int(step), "ts": time.time(),
                   **fields})

    # -- lifecycle -------------------------------------------------------
    def close(self) -> None:
        with self._lock:
            if not self._f.closed:
                self._f.flush()
                self._f.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# -- trace-time state (checked when the step function is TRACED) ---------
_active: Optional[Telemetry] = None
_labels: list = []        # collective_label stack (trace-time)
_steps: list = []         # step_scope stack of traced step arrays


def active() -> Optional[Telemetry]:
    """The installed sink, or None. Checked at trace time by every hook."""
    return _active


@contextlib.contextmanager
def install(sink: Telemetry):
    """Trace optimizer steps inside this context to bake telemetry
    callbacks into the jitted program. The callbacks close over ``sink``
    and keep writing to it on every execution of the compiled step, even
    after the context exits (same lifetime rule as ``count_executed``)."""
    global _active
    prev = _active
    _active = sink
    try:
        yield sink
    finally:
        _active = prev


@contextlib.contextmanager
def collective_label(label: str):
    """Relabel telemetry events for preduce calls traced inside this
    context (e.g. the gradient all-reduce, whose count tag stays
    ``grad_hvp`` so PR 7 executed-count audits are untouched)."""
    _labels.append(label)
    try:
        yield
    finally:
        _labels.pop()


def current_collective_label() -> Optional[str]:
    return _labels[-1] if _labels else None


@contextlib.contextmanager
def step_scope(step):
    """Provide the traced outer-step index to markers emitted from code
    (e.g. the curvature engine) that has no access to ``HFState``."""
    _steps.append(step)
    try:
        yield
    finally:
        _steps.pop()


def current_step():
    """The traced step of the innermost :func:`step_scope`, else -1."""
    import jax.numpy as jnp
    return _steps[-1] if _steps else jnp.int32(-1)


def _dep_scalar(deps):
    """A zero f32 scalar data-dependent on every leaf of ``deps`` — the
    callback operand that pins a marker to its phase's outputs."""
    import jax
    import jax.numpy as jnp
    total = jnp.zeros((), jnp.float32)
    for d in deps:
        for leaf in jax.tree_util.tree_leaves(d):
            total = total + jnp.sum(leaf).astype(jnp.float32)
    return jnp.zeros((), jnp.float32) * total


def marker(name: str, *deps, step=None) -> None:
    """Emit a phase end-marker callback, data-dependent on ``deps``.

    No-op (nothing traced) when no sink is installed. The marker closes
    the phase named ``name``; trace.py reconstructs phase spans as the
    interval between consecutive markers of one (process, step).
    """
    sink = _active
    if sink is None:
        return
    import jax
    if step is None:
        step = current_step()

    def _cb(s, _unused, _sink=sink, _name=name):
        _sink.phase_event(_name, int(s))

    jax.debug.callback(_cb, step, _dep_scalar(deps))


def gated_marker(name: str, *deps, step=None):
    """:func:`marker`, returning ``deps`` released only after the marker
    ran: each floating leaf plus the zero an ``io_callback`` returns, so
    work that reads the returned values starts after the marker reached
    the host. A data dependence, since XLA:CPU drops optimization
    barriers before it schedules; exact but for the sign of a zero.

    No-op (nothing traced, ``deps`` returned as they are) when no sink is
    installed."""
    sink = _active
    if sink is None:
        return deps
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import io_callback
    if step is None:
        step = current_step()

    def _cb(s, _unused, _sink=sink, _name=name):
        _sink.phase_event(_name, int(s))
        return np.zeros((), np.float32)

    # The callback's operands carry no tangent, so differentiating through
    # the returned values never differentiates the callback.
    done = io_callback(_cb, jax.ShapeDtypeStruct((), jnp.float32),
                       *jax.lax.stop_gradient((step, _dep_scalar(deps))))
    return jax.tree_util.tree_map(
        lambda v: v + done.astype(v.dtype)
        if jnp.issubdtype(v.dtype, jnp.floating) else v, deps)


def solve_event(step, *, iters, residual, syncs, residual_history,
                nc_found, breakdown) -> None:
    """Emit the per-step Krylov solve summary (iteration count, final
    residual, per-iteration residual curve). No-op when no sink."""
    sink = _active
    if sink is None:
        return
    import jax
    import numpy as np

    def _cb(s, it, res, sy, hist, nc, brk, _sink=sink):
        h = np.asarray(hist, dtype=np.float64)
        h = h[np.isfinite(h)]
        _sink.solve_event(
            int(s), iters=int(it), residual=float(res), syncs=int(sy),
            residual_history=[round(float(v), 8) for v in h],
            nc_found=bool(nc), breakdown=bool(brk))

    jax.debug.callback(_cb, step, iters, residual, syncs,
                       residual_history, nc_found, breakdown)


def reject_event(step, rejected, lam, f_new) -> None:
    """Divergence-sentinel hook: traced into every step, but the host-side
    callback emits a ``fault`` event only when the step was actually
    rejected (non-finite or non-descending update, see core/hf.py).
    No-op (nothing traced) when no sink is installed."""
    sink = _active
    if sink is None:
        return
    import jax

    def _cb(s, rej, l, f, _sink=sink):
        if bool(rej):
            _sink.emit({"ev": "fault", "kind": "step_reject",
                        "step": int(s), "lam": float(l),
                        "loss_new": float(f), "ts": time.time()})

    jax.debug.callback(_cb, step, rejected, lam, f_new)


def register_crash_flush(sink: Telemetry):
    """Close ``sink`` on abnormal exit so a SIGTERM'd / interrupted worker
    still leaves a flushed, parseable event file.

    Installs an ``atexit`` hook plus SIGTERM/SIGINT handlers that flush the
    sink, emit a final ``fault`` event recording the signal, then re-raise
    the default disposition (so the supervisor still sees a signal death).
    Handlers chain to any previously-installed callable handler. Safe to
    call from non-main threads: signal installation failures are ignored
    (the atexit hook alone still covers normal interpreter shutdown).
    """
    import atexit
    import signal

    atexit.register(sink.close)

    def _make(signum, prev):
        def _handler(num, frame):
            try:
                sink.emit({"ev": "fault", "kind": "signal",
                           "signal": int(num), "ts": time.time()})
                sink.close()
            except Exception:
                pass
            if callable(prev):
                prev(num, frame)
            else:
                signal.signal(num, signal.SIG_DFL)
                os.kill(os.getpid(), num)
        return _handler

    for signum in (signal.SIGTERM, signal.SIGINT):
        try:
            prev = signal.getsignal(signum)
            signal.signal(signum, _make(signum, prev))
        except ValueError:
            # signal only works in the main thread; atexit still covers us.
            pass
