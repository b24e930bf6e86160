"""Fault injection + supervision machinery (ISSUE 9 tentpole): spec
parsing, plan gating (process / restart-attempt), the collective
watchdog, and the spawn_supervised restart loop with real child
processes (the ``timeit`` trick from test_multiproc.py: a stdlib module
whose -s setup statement runs arbitrary code under the spawn env)."""
import os
import time

import pytest

from repro.core import collectives
from repro.launch import multiproc
from repro.launch.faults import (ENV_FAULTS, Fault, FaultPlan, corrupt_file,
                                 parse_faults)


class TestSpecParsing:
    def test_single(self):
        (f,) = parse_faults("kill@step=3,proc=1")
        assert f == Fault(kind="kill", step=3, proc=1)

    def test_multi_and_defaults(self):
        fs = parse_faults(
            "nan_batch@step=2; delay@step=1,secs=0.5,attempt=1 ;")
        assert fs[0] == Fault(kind="nan_batch", step=2, proc=None)
        assert fs[1] == Fault(kind="delay", step=1, secs=0.5, attempt=1)

    def test_spec_roundtrip(self):
        for s in ("kill@step=3,proc=1", "hang@step=0",
                  "delay@step=2,secs=0.25,attempt=2"):
            (f,) = parse_faults(s)
            assert parse_faults(f.spec()) == [f]

    def test_empty(self):
        assert parse_faults("") == []

    @pytest.mark.parametrize("bad", [
        "explode@step=1",        # unknown kind
        "kill@proc=1",           # missing step
        "kill@step=1,when=now",  # unknown field
        "kill",                  # missing @
    ])
    def test_bad_specs_raise(self, bad):
        with pytest.raises(ValueError):
            parse_faults(bad)


class TestFaultPlan:
    def _kill_calls(self, monkeypatch):
        calls = []
        monkeypatch.setattr(os, "_exit", lambda code: calls.append(code))
        return calls

    def test_proc_filter(self):
        faults = parse_faults("kill@step=1,proc=1;nan_batch@step=2")
        p0 = FaultPlan(faults, process_index=0)
        p1 = FaultPlan(faults, process_index=1)
        assert [f.kind for f in p0.faults] == ["nan_batch"]  # proc=None: all
        assert [f.kind for f in p1.faults] == ["kill", "nan_batch"]

    def test_attempt_gating(self):
        faults = parse_faults("kill@step=1,proc=0")
        assert FaultPlan(faults, 0, attempt=0).active()
        assert not FaultPlan(faults, 0, attempt=1).active()

    def test_from_env_reads_restart_attempt(self, monkeypatch):
        monkeypatch.setenv(ENV_FAULTS, "kill@step=1,proc=0")
        monkeypatch.setenv(multiproc.ENV_RESTART, "1")
        assert not FaultPlan.from_env(0).active()
        monkeypatch.setenv(multiproc.ENV_RESTART, "0")
        assert FaultPlan.from_env(0).active()

    def test_kill_fires_once_at_step(self, monkeypatch):
        calls = self._kill_calls(monkeypatch)
        plan = FaultPlan(parse_faults("kill@step=2,proc=0"), 0)
        plan.on_step_begin(0)
        plan.on_step_begin(1)
        assert calls == []
        plan.on_step_begin(2)
        assert calls == [1]
        plan.on_step_begin(2)  # fired-once: no re-fire
        assert calls == [1]

    def test_delay_sleeps(self):
        plan = FaultPlan(parse_faults("delay@step=0,secs=0.1"), 0)
        t0 = time.time()
        plan.on_step_begin(0)
        assert time.time() - t0 >= 0.1

    def test_poison_batch_floats_only(self):
        import jax.numpy as jnp
        import numpy as np
        plan = FaultPlan(parse_faults("nan_batch@step=1"), 0)
        batch = {"tokens": jnp.arange(4), "vision": jnp.ones((2, 3))}
        out = plan.poison_batch(0, batch)
        assert out is batch  # wrong step: untouched
        out = plan.poison_batch(1, batch)
        assert np.isnan(np.asarray(out["vision"])).all()
        np.testing.assert_array_equal(np.asarray(out["tokens"]),
                                      np.arange(4))

    def test_telemetry_emission(self):
        events = []

        class Sink:
            def emit(self, ev):
                events.append(ev)

        plan = FaultPlan(parse_faults("delay@step=0,secs=0.01"), 3,
                         telemetry=Sink())
        plan.on_step_begin(0)
        assert events and events[0]["ev"] == "fault"
        assert events[0]["kind"] == "delay"
        assert events[0]["injected"] is True
        assert events[0]["proc"] == 3

    def test_corrupt_checkpoint_hits_newest(self, tmp_path):
        from repro.checkpoint import (latest_valid_step, save_checkpoint,
                                      valid_steps)
        save_checkpoint(str(tmp_path), 1, {"w": [1.0, 2.0]})
        save_checkpoint(str(tmp_path), 2, {"w": [3.0, 4.0]})
        plan = FaultPlan(parse_faults("corrupt_ckpt@step=2"), 0)
        path = plan.corrupt_checkpoint(2, str(tmp_path))
        assert path and path.endswith("ckpt_00000002.npz")
        assert valid_steps(str(tmp_path)) == [1]
        assert latest_valid_step(str(tmp_path)) == 1


class TestCorruptFile:
    def test_changes_bytes_not_size(self, tmp_path):
        p = tmp_path / "blob.bin"
        p.write_bytes(bytes(range(256)) * 16)
        before = p.read_bytes()
        corrupt_file(str(p))
        after = p.read_bytes()
        assert len(after) == len(before) and after != before


class TestWatchdog:
    def test_fires_on_stuck_collective(self):
        fired = []
        wd = collectives.Watchdog(0.1, on_timeout=lambda t, w: fired.append(t),
                                  poll_s=0.02).start()
        wd.arm("grad_hvp")
        time.sleep(0.4)
        assert wd.fired and fired == ["grad_hvp"]
        wd.stop()

    def test_no_fire_when_disarmed(self):
        fired = []
        wd = collectives.Watchdog(0.1, on_timeout=lambda t, w: fired.append(t),
                                  poll_s=0.02).start()
        wd.arm("grad_hvp")
        wd.disarm("grad_hvp")
        time.sleep(0.3)
        assert not wd.fired and fired == []
        wd.stop()

    def test_fifo_pairing_per_tag(self):
        fired = []
        wd = collectives.Watchdog(0.15, on_timeout=lambda t, w: fired.append(t),
                                  poll_s=0.02).start()
        # two outstanding same-tag collectives; one completes — the other
        # (older) is re-covered by FIFO pop, so nothing should fire only
        # if BOTH complete
        wd.arm("loss")
        wd.arm("loss")
        wd.disarm("loss")
        wd.disarm("loss")
        time.sleep(0.3)
        assert not wd.fired
        wd.stop()

    def test_exit_code_constant_matches_launcher(self):
        assert collectives.EXIT_WATCHDOG == multiproc.EXIT_WATCHDOG

    def test_install_bakes_callbacks_into_preduce(self):
        """Trace a shard_map'd preduce under collective_watchdog: the
        compiled program arms/disarms per execution (balanced — nothing
        left outstanding), and tracing outside the context bakes nothing."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        mesh = jax.make_mesh((len(jax.devices()),), ("data",))
        events = []

        class Probe(collectives.Watchdog):
            def arm(self, tag):
                events.append(("arm", tag))
                super().arm(tag)

            def disarm(self, tag):
                events.append(("disarm", tag))
                super().disarm(tag)

        wd = Probe(30.0, on_timeout=lambda t, w: None, poll_s=1.0)
        collectives._watchdog = wd
        try:
            def f(x):
                return collectives.preduce(x, "data", tag="loss")
            sm = jax.shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P())
            out = jax.jit(sm)(jnp.arange(float(len(jax.devices()))))
            jax.block_until_ready(out)
        finally:
            collectives._watchdog = None
        arms = [e for e in events if e[0] == "arm"]
        disarms = [e for e in events if e[0] == "disarm"]
        assert arms and len(arms) == len(disarms)
        with wd._lock:
            assert all(not q for q in wd._outstanding.values())


_CHILD_SNIPPET = (
    "import os\n"
    "attempt = int(os.environ.get('REPRO_MULTIPROC_RESTART', '0'))\n"
)


class TestSpawnSupervised:
    """Real child processes via the stdlib ``timeit`` module (its -s setup
    statement runs arbitrary code under the spawn environment)."""

    def _spawn(self, code, **kw):
        return multiproc.spawn_supervised(
            2, "timeit", ["-n", "1", "-r", "1", "-s", code, "pass"],
            backoff_s=0.05, poll_s=0.05, log=lambda m: None, **kw)

    def test_clean_run_uses_zero_restarts(self, tmp_path):
        restarts = self._spawn("pass", max_restarts=2,
                               heartbeat_dir=str(tmp_path))
        assert restarts == 0

    def test_restart_after_worker_death(self, tmp_path):
        # worker 1 hard-exits on attempt 0 only; attempt 1 succeeds
        code = (_CHILD_SNIPPET +
                "wid = os.environ['REPRO_MULTIPROC_ID']\n"
                "if attempt == 0 and wid == '1': os._exit(9)\n")
        restarts = self._spawn(code, max_restarts=2,
                               heartbeat_dir=str(tmp_path))
        assert restarts == 1

    def test_budget_exhaustion_raises(self, tmp_path):
        code = _CHILD_SNIPPET + "os._exit(3)\n"
        with pytest.raises(RuntimeError, match="exhausted"):
            self._spawn(code, max_restarts=1, heartbeat_dir=str(tmp_path))

    def test_hang_detected_by_heartbeat_staleness(self, tmp_path):
        # attempt 0: both workers sleep forever without heartbeating —
        # only the liveness monitor can catch this (no exit code ever).
        code = (_CHILD_SNIPPET +
                "import time\n"
                "if attempt == 0: time.sleep(600)\n")
        t0 = time.time()
        restarts = self._spawn(code, max_restarts=1, hang_timeout_s=1.5,
                               heartbeat_dir=str(tmp_path))
        assert restarts == 1
        assert time.time() - t0 < 60  # detected by staleness, not timeout

    def test_heartbeat_resets_staleness(self, tmp_path):
        # attempt 0 worker 0 beats while working slowly; no restart needed
        code = (
            _CHILD_SNIPPET +
            "import time\n"
            "hbd = os.environ.get('REPRO_MULTIPROC_HEARTBEAT')\n"
            "wid = os.environ['REPRO_MULTIPROC_ID']\n"
            "for i in range(6):\n"
            "    open(os.path.join(hbd, 'hb-p' + wid), 'w').write(str(i))\n"
            "    time.sleep(0.4)\n"
        )
        restarts = self._spawn(code, max_restarts=1, hang_timeout_s=1.5,
                               heartbeat_dir=str(tmp_path))
        assert restarts == 0

    def test_heartbeat_writer_roundtrip(self, tmp_path, monkeypatch):
        monkeypatch.setenv(multiproc.ENV_HEARTBEAT_DIR, str(tmp_path))
        monkeypatch.setenv(multiproc.ENV_ID, "1")
        multiproc.heartbeat(5)
        hb = tmp_path / "hb-p1"
        assert hb.exists() and hb.read_text().startswith("5 ")

    def test_heartbeat_noop_outside_supervision(self, monkeypatch):
        monkeypatch.delenv(multiproc.ENV_HEARTBEAT_DIR, raising=False)
        multiproc.heartbeat(1)  # must not raise
