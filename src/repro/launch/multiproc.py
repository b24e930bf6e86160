"""Multi-process launcher: the shard_map HF step on N real processes.

The shard_map schedule in ``core.distributed`` is process-count agnostic —
the same program runs on 8 fake CPU devices in one process (tests) or on a
TPU pod. What was missing is the harness that actually *spawns* processes
and wires ``jax.distributed`` between them, so the collectives cross a real
process boundary and the sync counts are measured, not simulated:

  PYTHONPATH=src python -m repro.launch.train --arch mlp-30-10 --smoke \\
      --num-processes 2 --sstep 2 --overlap

The parent re-executes its own command line N times with
``REPRO_MULTIPROC_*`` set; each child calls :func:`initialize_from_env`
BEFORE any jax device use, which points ``jax.distributed.initialize`` at a
local TCP coordinator and selects the gloo CPU collective backend. Each
child is pinned to the CPU backend (``JAX_PLATFORMS``) and to ONE CPU
device (``XLA_FLAGS`` below), so the global device count equals the process
count and ``launch.mesh.make_data_mesh`` builds an N-way pure data-parallel
mesh — and no child reaches for an accelerator the parent may hold.

On a TPU host no spawning is needed: one process drives all its chips, and
``launch/train.py`` takes the data-parallel step over them whenever JAX
sees more than one device. Downstream of the mesh — :func:`shard_batch` /
:func:`replicate` placement, primary-only logging — the code is the same.

Placement invariants (multi-process jit refuses to reshard across
processes, so inputs must arrive with their final global sharding):

  * batch leaves:   sharded on the leading dim over the data axis
                    (:func:`shard_batch` — every process builds the SAME
                    global batch from the same PRNG key and device_puts its
                    addressable shard),
  * params/state:   replicated (:func:`replicate`), bitwise identical
                    across processes by construction (same seed),
  * step outputs:   carry the out_specs shardings (all replicated here),
                    so ``float(metric)`` works on every process.
"""
from __future__ import annotations

import os
import signal
import socket
import subprocess
import sys
import tempfile
import time
from typing import Any, Callable, Optional, Sequence

ENV_NUM = "REPRO_MULTIPROC_NUM"
ENV_ID = "REPRO_MULTIPROC_ID"
ENV_COORD = "REPRO_MULTIPROC_COORD"
# Attempt counter set by the supervisor: 0 on the first launch, k after the
# k-th restart. launch/faults.py gates injected faults on it so a fault
# that killed attempt 0 does not re-fire and kill every restart too.
ENV_RESTART = "REPRO_MULTIPROC_RESTART"
# Directory where workers touch per-process heartbeat files; the
# supervisor reads mtimes to detect hangs (a worker wedged in a dead
# collective stops beating but never exits on its own).
ENV_HEARTBEAT_DIR = "REPRO_MULTIPROC_HEARTBEAT"

# Exit code of a worker whose collective watchdog fired. Kept equal to
# core.collectives.EXIT_WATCHDOG (asserted in tests/test_faults.py);
# duplicated here so the supervisor never has to import jax.
EXIT_WATCHDOG = 87

# One CPU device per process: global devices == processes, and the gloo
# cross-process collectives carry ALL communication (nothing hides on an
# intra-process fast path).
_CHILD_XLA_FLAGS = "--xla_force_host_platform_device_count=1"


def _child_env(env: dict | None) -> dict:
    """The children's base environment: the CPU backend, one device each."""
    base = dict(os.environ if env is None else env)
    base["JAX_PLATFORMS"] = "cpu"
    base["XLA_FLAGS"] = _CHILD_XLA_FLAGS
    return base


def active() -> bool:
    """True in a child process spawned by :func:`spawn`."""
    return ENV_NUM in os.environ


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def spawn(
    num_processes: int,
    module: str,
    args: Sequence[str] = (),
    *,
    env: dict | None = None,
) -> None:
    """Run ``python -m module *args`` as ``num_processes`` coordinated procs.

    Process 0 inherits stdout/stderr (it is the logging primary); the
    others are captured and replayed only on failure. Raises RuntimeError
    if any child exits non-zero.
    """
    coord = f"127.0.0.1:{_free_port()}"
    base = _child_env(env)
    procs = []
    for pid in range(num_processes):
        child_env = dict(base)
        child_env[ENV_NUM] = str(num_processes)
        child_env[ENV_ID] = str(pid)
        child_env[ENV_COORD] = coord
        capture = pid != 0
        procs.append(subprocess.Popen(
            [sys.executable, "-m", module, *args],
            env=child_env,
            stdout=subprocess.PIPE if capture else None,
            stderr=subprocess.STDOUT if capture else None,
            text=True,
        ))
    rcs = [p.wait() for p in procs]
    if any(rcs):
        for pid, p in enumerate(procs):
            if rcs[pid] and p.stdout is not None:
                tail = p.stdout.read().splitlines()[-30:]
                print(f"--- process {pid} (exit {rcs[pid]}) ---", file=sys.stderr)
                print("\n".join(tail), file=sys.stderr)
        raise RuntimeError(f"multiproc children failed: exit codes {rcs}")


def restart_attempt() -> int:
    """Which supervisor attempt this worker belongs to (0 = first launch)."""
    return int(os.environ.get(ENV_RESTART, "0"))


def heartbeat(step: Optional[int] = None) -> None:
    """Touch this worker's heartbeat file (no-op outside supervision).

    Called from the TRAIN LOOP itself, once per step (and once after
    compile), never from a side thread — a thread would keep beating while
    the main thread sits wedged in a dead collective, which is exactly the
    condition the heartbeat exists to expose.
    """
    d = os.environ.get(ENV_HEARTBEAT_DIR)
    if not d:
        return
    path = os.path.join(d, f"hb-p{os.environ.get(ENV_ID, '0')}")
    try:
        with open(path, "w") as f:
            f.write(f"{'' if step is None else int(step)} {time.time()}\n")
    except OSError:
        pass  # a torn-down heartbeat dir must never kill the worker


def _newest_heartbeat(directory: str) -> float:
    newest = 0.0
    try:
        for name in os.listdir(directory):
            if name.startswith("hb-p"):
                newest = max(newest,
                             os.path.getmtime(os.path.join(directory, name)))
    except OSError:
        pass
    return newest


def _terminate_all(procs, grace_s: float = 5.0) -> None:
    """SIGTERM every live child (lets telemetry signal handlers flush),
    wait up to ``grace_s``, then SIGKILL whatever is left."""
    for p in procs:
        if p.poll() is None:
            try:
                p.send_signal(signal.SIGTERM)
            except OSError:
                pass
    deadline = time.time() + grace_s
    for p in procs:
        if p.poll() is None:
            try:
                p.wait(timeout=max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                pass
    for p in procs:
        if p.poll() is None:
            try:
                p.kill()
            except OSError:
                pass
            p.wait()


def _rc_desc(rc: int) -> str:
    if rc == EXIT_WATCHDOG:
        return f"exit {rc} (collective watchdog)"
    if rc < 0:
        try:
            return f"signal {signal.Signals(-rc).name}"
        except ValueError:
            return f"signal {-rc}"
    return f"exit {rc}"


def spawn_supervised(
    num_processes: int,
    module: str,
    args: Sequence[str] = (),
    *,
    max_restarts: int = 2,
    hang_timeout_s: Optional[float] = None,
    backoff_s: float = 1.0,
    poll_s: float = 0.2,
    heartbeat_dir: Optional[str] = None,
    env: dict | None = None,
    log: Callable[[str], None] = lambda m: print(m, file=sys.stderr),
) -> int:
    """:func:`spawn` under a liveness supervisor. Returns restarts used.

    Each attempt gets a fresh coordinator port (the old rendezvous is
    poisoned by the dead peer) and ``ENV_RESTART`` = attempt index. The
    supervisor polls child exits and, when ``hang_timeout_s`` is set,
    heartbeat-file mtimes; on a worker death, hang, or watchdog exit it
    tears the survivors down (SIGTERM → grace → SIGKILL: a gloo collective
    whose peer died never returns, so survivors cannot exit on their own),
    then re-launches everyone after exponential backoff — the *workers*
    resume from their last valid checkpoint (launch/train.py restore
    path); the supervisor only restarts processes, it holds no training
    state. A clean all-zero exit returns; exhausting ``max_restarts``
    raises RuntimeError with per-process exit codes and log tails.

    Hang staleness is measured from max(newest heartbeat, attempt launch
    time), so ``hang_timeout_s`` must cover worst-case first-step latency
    (gloo rendezvous + trace + compile), not just one step.
    """
    if heartbeat_dir is None:
        heartbeat_dir = tempfile.mkdtemp(prefix="repro-hb-")
    os.makedirs(heartbeat_dir, exist_ok=True)
    base = _child_env(env)
    base[ENV_HEARTBEAT_DIR] = heartbeat_dir

    last_failure = "never launched"
    for attempt in range(max_restarts + 1):
        coord = f"127.0.0.1:{_free_port()}"
        launched = time.time()
        procs, logs = [], []
        for pid in range(num_processes):
            child_env = dict(base)
            child_env[ENV_NUM] = str(num_processes)
            child_env[ENV_ID] = str(pid)
            child_env[ENV_COORD] = coord
            child_env[ENV_RESTART] = str(attempt)
            # Non-primaries append to files, not pipes: no drain thread
            # needed, nothing deadlocks on a full pipe buffer, and the
            # tail survives for the failure report.
            log_path = os.path.join(heartbeat_dir,
                                    f"log-p{pid}-a{attempt}.txt")
            logs.append(log_path)
            out = None if pid == 0 else open(log_path, "a")
            procs.append(subprocess.Popen(
                [sys.executable, "-m", module, *args],
                env=child_env, stdout=out,
                stderr=subprocess.STDOUT if out is not None else None,
            ))
            if out is not None:
                out.close()  # child holds its own fd

        failure = None
        while failure is None:
            time.sleep(poll_s)
            rcs = [p.poll() for p in procs]
            if all(rc == 0 for rc in rcs):
                return attempt
            dead = [(pid, rc) for pid, rc in enumerate(rcs)
                    if rc is not None and rc != 0]
            if dead:
                failure = ", ".join(f"process {pid}: {_rc_desc(rc)}"
                                    for pid, rc in dead)
            elif hang_timeout_s is not None:
                alive_since = max(_newest_heartbeat(heartbeat_dir), launched)
                if time.time() - alive_since > hang_timeout_s:
                    failure = (f"no heartbeat for {hang_timeout_s:.0f}s "
                               "(workers presumed hung)")

        log(f"[supervisor] attempt {attempt} failed: {failure}; "
            "tearing down survivors")
        _terminate_all(procs)
        last_failure = failure
        if attempt < max_restarts:
            delay = backoff_s * (2 ** attempt)
            log(f"[supervisor] restarting in {delay:.1f}s "
                f"(attempt {attempt + 1}/{max_restarts})")
            time.sleep(delay)

    for pid, log_path in enumerate(logs):
        if os.path.exists(log_path):
            with open(log_path) as f:
                tail = f.read().splitlines()[-30:]
            if tail:
                log(f"--- process {pid} (attempt {max_restarts}) ---")
                log("\n".join(tail))
    raise RuntimeError(
        f"multiproc supervision exhausted {max_restarts} restart(s); "
        f"last failure: {last_failure}")


def initialize_from_env() -> None:
    """Wire jax.distributed from the ``spawn`` env vars (no-op otherwise).

    Must run before anything touches jax devices — the CPU collective
    backend (gloo, the cross-process psum transport) is locked at backend
    init.
    """
    if not active():
        return
    import jax

    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=os.environ[ENV_COORD],
        num_processes=int(os.environ[ENV_NUM]),
        process_id=int(os.environ[ENV_ID]),
    )


def is_primary() -> bool:
    import jax

    return jax.process_index() == 0


def shard_batch(batch: Any, mesh, axis: str = "data"):
    """Place a (replicated host) batch with leading-dim sharding over ``axis``.

    Every process passes the SAME global batch (same PRNG); each leaf lands
    as one global jax.Array of which this process holds its addressable
    shard. Works identically single-process.
    """
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(mesh, P(axis))

    def put(x):
        return jax.device_put(np.asarray(x), sharding)

    return jax.tree_util.tree_map(put, batch)


def replicate(tree: Any, mesh):
    """Place a pytree fully-replicated over the whole mesh.

    Inputs must already be identical across processes (same-seed init);
    this just stamps the global replicated sharding so jit accepts them
    next to cross-process-sharded batches.
    """
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(mesh, P())

    def put(x):
        return jax.device_put(np.asarray(x), sharding)

    return jax.tree_util.tree_map(put, tree)
