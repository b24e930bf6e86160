"""Collective accounting: validate reported sync counts against reality.

``KrylovResult.syncs`` (and ``metrics["blocking_syncs"]``) are *claims* —
integers the solvers compute about their own communication schedule. This
module provides two independent ways to check the claims against what the
compiled program actually does, used by tests/test_collective_audit.py and
``benchmarks/fig5_scaling.py --executed``:

1. **Static jaxpr audit** — :func:`jaxpr_collective_counts` walks a traced
   jaxpr and counts collective primitives (``psum`` — what ``lax.pmean``
   lowers to — plus friends), split into top-level occurrences vs
   occurrences inside ``while_loop`` bodies. For the HF step the invariant
   is: executed collectives = top-level count + Σ (body count × trips),
   where the trip counts are exactly what ``KrylovResult.syncs`` /
   ``n_evals`` report. This catches collectives that silently appear or
   vanish at trace time (e.g. an extra GSPMD-inserted reduce).

2. **Executed-collective counter** — :func:`count_executed` + the
   :func:`preduce` wrapper. ``core.distributed`` routes every explicit
   reduction through ``preduce(tree, axes, tag)``; inside a
   ``count_executed()`` region each traced ``preduce`` site also embeds a
   ``jax.debug.callback`` that fires once per *execution* (per local
   device), including executions inside ``while_loop`` trips — so the
   counter observes the runtime collective count that the static audit can
   only bound. Tracing must happen inside the region (callbacks are baked
   in at trace time): jit a fresh step function under the context manager.

Why an own-layer wrapper instead of monkeypatching ``jax.lax.psum``:
``lax.pmean`` calls ``psum`` through jax-internal bindings that a module
level monkeypatch does not intercept, and primitive ``bind`` hooks see
retraces/transforms, not executions. Tagging at the call site is the only
layer where "one logical reduction" is well-defined.
"""
from __future__ import annotations

import collections
import contextlib
import os
import sys
import threading
import time
from typing import Any, Callable, Iterator, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.extend import core as jex_core

from ..obs import telemetry as _telemetry

# Process exit code used when the watchdog kills a worker stuck in a
# collective. Chosen distinct from Python's 0/1/2 and from signal codes
# (128+N) so the supervisor (launch/multiproc.py, which re-exports this)
# can tell "watchdog fired" apart from an ordinary crash in its logs.
EXIT_WATCHDOG = 87

# Primitive names that move data across mesh axes. A pmean binds one
# ``psum`` per reduced leaf (``psum_invariant`` inside a shard_map with
# ``check_vma=True``).
COLLECTIVE_PRIMS = frozenset({
    "psum", "psum_invariant", "pmin", "pmax", "ppermute", "all_gather",
    "all_to_all", "reduce_scatter",
})


class CollectiveCounts:
    """Mutable tally of executed tagged collectives (host-side)."""

    def __init__(self) -> None:
        self.counts: collections.Counter = collections.Counter()

    def add(self, tag: str) -> None:
        self.counts[tag] += 1

    def total(self) -> int:
        return sum(self.counts.values())

    def per_device(self, n_local_devices: int) -> dict:
        """Callbacks fire once per local device shard; normalize them out."""
        out = {}
        for tag, n in self.counts.items():
            assert n % n_local_devices == 0, (tag, n, n_local_devices)
            out[tag] = n // n_local_devices
        return out


_active: CollectiveCounts | None = None


@contextlib.contextmanager
def count_executed() -> Iterator[CollectiveCounts]:
    """Instrument ``preduce`` sites traced within this region.

    The counter observes executions of the instrumented program — keep
    using the jitted function after the region closes and it will keep
    counting into the same object (the callback closes over it).
    """
    global _active
    prev, _active = _active, CollectiveCounts()
    try:
        yield _active
    finally:
        _active = prev


class Watchdog:
    """Turn an indefinitely-blocking collective into a detectable death.

    A gloo all-reduce whose peer died blocks *forever* inside a C++ call:
    no Python exception can be raised there and a signal handler will not
    run until the call returns (which it never does). The only reliable
    escape is a side thread that notices the collective has been
    outstanding too long and hard-exits the process — the supervisor
    (``launch.multiproc.spawn_supervised``) then sees ``EXIT_WATCHDOG``
    and restarts the job from the last valid checkpoint.

    Arm/disarm callbacks are baked into :func:`preduce` sites traced while
    :func:`collective_watchdog` is installed: arm fires at reduce-input-
    ready (the earliest the collective can issue), disarm at reduce-output
    (completion) — the same data-dependence trick as the telemetry spans,
    so the armed window brackets exactly the blocking region. Per-tag FIFO
    pairing mirrors ``Telemetry._pending``.

    ``on_timeout`` (tests) replaces the default hard-exit with a callable
    ``(tag, waited_s) -> None``.
    """

    def __init__(self, timeout_s: float,
                 on_timeout: Optional[Callable[[str, float], None]] = None,
                 poll_s: Optional[float] = None):
        self.timeout_s = float(timeout_s)
        self.on_timeout = on_timeout
        self._poll_s = poll_s if poll_s is not None else max(
            0.05, self.timeout_s / 4.0)
        self._lock = threading.Lock()
        self._outstanding: dict = {}   # tag -> deque of arm timestamps
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.fired = False
        self.fired_tag: Optional[str] = None

    def arm(self, tag: str) -> None:
        with self._lock:
            self._outstanding.setdefault(
                tag, collections.deque()).append(time.time())

    def disarm(self, tag: str) -> None:
        with self._lock:
            q = self._outstanding.get(tag)
            if q:
                q.popleft()

    def _oldest_overdue(self, now: float):
        with self._lock:
            for tag, q in self._outstanding.items():
                if q and now - q[0] > self.timeout_s:
                    return tag, now - q[0]
        return None

    def start(self) -> "Watchdog":
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="collective-watchdog")
        self._thread.start()
        return self

    def _run(self) -> None:
        while not self._stop.wait(self._poll_s):
            hit = self._oldest_overdue(time.time())
            if hit is None:
                continue
            tag, waited = hit
            self.fired, self.fired_tag = True, tag
            if self.on_timeout is not None:
                self.on_timeout(tag, waited)
                return
            sys.stderr.write(
                f"[watchdog] collective {tag!r} blocked {waited:.1f}s "
                f"(> {self.timeout_s:.1f}s); peer presumed dead — "
                f"exiting {EXIT_WATCHDOG}\n")
            sys.stderr.flush()
            # os._exit, not sys.exit: the main thread is wedged in gloo
            # C++ and will never unwind a SystemExit.
            os._exit(EXIT_WATCHDOG)

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=1.0)


_watchdog: Optional[Watchdog] = None


@contextlib.contextmanager
def collective_watchdog(timeout_s: float,
                        on_timeout: Optional[Callable] = None,
                        poll_s: Optional[float] = None):
    """Trace-time install: ``preduce`` sites traced inside this context
    bake in watchdog arm/disarm callbacks (same lifetime rule as
    ``count_executed`` — the compiled program keeps feeding the returned
    :class:`Watchdog` after the context exits). The monitor thread starts
    immediately; call ``.stop()`` to retire it (tests), or leave it for
    the life of the process (training)."""
    global _watchdog
    wd = Watchdog(timeout_s, on_timeout, poll_s).start()
    prev, _watchdog = _watchdog, wd
    try:
        yield wd
    finally:
        _watchdog = prev


def preduce(tree: Any, axes: Sequence[str] | str, tag: str = "reduce"):
    """``lax.pmean`` over a pytree, tagged for executed-count auditing.

    One ``preduce`` call = one logical collective (jax binds one psum per
    leaf, which XLA's all-reduce combiner may merge). When tracing happens inside
    :func:`count_executed`, a debug callback rides along and fires once
    per execution per local device — inside ``while_loop`` bodies too,
    which is the whole point: loop-borne collectives are counted at their
    true multiplicity, not once.
    """
    if _active is not None:
        counter = _active
        leaf = jax.tree_util.tree_leaves(tree)[0]
        # The zero-valued scalar operand keeps the callback data-dependent
        # on the reduced value, so it cannot be hoisted out of a loop body.
        jax.debug.callback(
            lambda _: counter.add(tag),
            jnp.zeros((), jnp.float32) * jnp.sum(leaf).astype(jnp.float32),
        )
    sink = _telemetry.active()
    wd = _watchdog
    if sink is None and wd is None:
        return jax.lax.pmean(tree, axes)
    # Telemetry span / watchdog window per executed reduction: the begin
    # callback depends only on the reduce INPUT (XLA:CPU runs it at
    # input-ready — the earliest the collective could issue), the end
    # callback on the reduce OUTPUT (completion). Under HFConfig.overlap
    # the hidden grad-reduce span therefore visibly brackets the curvature
    # primal build; the blocking schedule closes it first. The watchdog
    # arms over exactly the same window, so a peer death mid-reduce leaves
    # it armed past its timeout. Count tag is unchanged — the label (e.g.
    # "grad_reduce" from telemetry.collective_label) only distinguishes
    # events, so PR 7 executed-count audits stay valid.
    label = _telemetry.current_collective_label() or tag

    def _begin(_, _s=sink, _w=wd, _t=tag, _l=label):
        if _w is not None:
            _w.arm(_t)
        if _s is not None:
            _s.collective_begin(_t, _l)

    def _end(step, _, _s=sink, _w=wd, _t=tag, _l=label):
        if _w is not None:
            _w.disarm(_t)
        if _s is not None:
            _s.collective_end(_t, _l, int(step))

    leaf_in = jax.tree_util.tree_leaves(tree)[0]
    jax.debug.callback(
        _begin, jnp.zeros((), jnp.float32) * jnp.sum(leaf_in).astype(jnp.float32))
    out = jax.lax.pmean(tree, axes)
    leaf_out = jax.tree_util.tree_leaves(out)[0]
    jax.debug.callback(
        _end, _telemetry.current_step(),
        jnp.zeros((), jnp.float32) * jnp.sum(leaf_out).astype(jnp.float32))
    return out


def _sub_jaxprs(eqn) -> Iterator:
    for val in eqn.params.values():
        vals = val if isinstance(val, (list, tuple)) else (val,)
        for v in vals:
            if isinstance(v, jex_core.ClosedJaxpr):
                yield v.jaxpr
            elif isinstance(v, jex_core.Jaxpr):
                yield v


def jaxpr_collective_counts(jaxpr) -> dict:
    """Count collective primitive equations in a (closed) jaxpr.

    Returns ``{"top": Counter, "while_body": Counter}`` mapping primitive
    name → static occurrence count. "top" is everything executed exactly
    once per step (including inside cond branches, scans with known length
    1, pjit bodies); "while_body" is everything inside a ``while`` body or
    cond jaxpr, which executes once per trip — multiply by the trip count
    (= the solver's reported syncs) to predict executed collectives.
    """
    if isinstance(jaxpr, jex_core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    out = {"top": collections.Counter(), "while_body": collections.Counter()}

    def walk(jx, in_while: bool) -> None:
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if name in COLLECTIVE_PRIMS:
                out["while_body" if in_while else "top"][name] += 1
            child_in_while = in_while or name == "while"
            for sub in _sub_jaxprs(eqn):
                walk(sub, child_in_while)

    walk(jaxpr, False)
    return out


def total_static_collectives(jaxpr) -> dict:
    """Convenience: summed psum-family counts per region."""
    c = jaxpr_collective_counts(jaxpr)
    return {k: sum(v.values()) for k, v in c.items()}
