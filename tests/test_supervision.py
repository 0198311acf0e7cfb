"""Failure detection (SURVEY.md section 5.3 — absent in the reference).

Unit tests for the Supervisor plus a fault-injection integration test: an
env slot raises mid-run, the actor worker is restarted by the supervisor,
and threaded training still reaches its step target.
"""

import threading
import time

import pytest

from r2d2_tpu.config import tiny_test
from r2d2_tpu.envs.catch import CatchVecEnv
from r2d2_tpu.train import Trainer
from r2d2_tpu.utils.supervision import Supervisor, WorkerFatalError


def test_supervisor_restarts_crashing_worker():
    sup = Supervisor()
    calls = []

    def body():
        calls.append(1)
        if len(calls) == 2:
            raise RuntimeError("injected")
        if len(calls) > 5:
            sup.stop.set()
        time.sleep(0.01)

    w = sup.spawn("w", body, max_restarts=3)
    deadline = time.monotonic() + 10
    while not sup.stop.is_set() and time.monotonic() < deadline:
        sup.check()
        time.sleep(0.02)
    sup.shutdown()
    assert len(calls) > 5  # kept running after the injected crash
    assert w.restarts == 1
    assert "injected" in w.last_error


def test_supervisor_fatal_after_restart_budget():
    sup = Supervisor()

    def body():
        raise RuntimeError("always broken")

    sup.spawn("bad", body, max_restarts=2)
    deadline = time.monotonic() + 10
    with pytest.raises(WorkerFatalError, match="always broken"):
        while time.monotonic() < deadline:
            sup.check()
            time.sleep(0.02)
    sup.shutdown()


def test_on_restart_hook_failure_goes_fatal():
    """A failing recovery hook means the worker cannot be restored to a
    known-good state: the supervisor must go fatal immediately instead of
    restarting into corruption — and check() must surface BOTH tracebacks
    (the crash and the failed hook)."""
    sup = Supervisor()
    bodies = []

    def body():
        bodies.append(1)
        raise RuntimeError("worker crashed")

    def bad_hook():
        raise RuntimeError("hook is broken too")

    w = sup.spawn("w", body, max_restarts=5, on_restart=bad_hook)
    deadline = time.monotonic() + 10
    with pytest.raises(WorkerFatalError):
        while time.monotonic() < deadline:
            sup.check()
            time.sleep(0.02)
    assert w.fatal
    assert len(bodies) == 1  # never restarted after the hook failed
    assert any("hook is broken too" in e for e in w.errors)
    assert any("worker crashed" in e for e in w.errors)
    sup.shutdown()


def test_exit_codes_are_distinct():
    """The CLI contract's three-way exit distinction: clean (0), preempted
    (state CURRENT, restart with --resume), stalled (state possibly STALE,
    backend suspect). Supervisors key recovery policy off these."""
    from r2d2_tpu.utils.supervision import PREEMPT_EXIT_CODE, STALL_EXIT_CODE

    assert len({0, PREEMPT_EXIT_CODE, STALL_EXIT_CODE}) == 3
    # both fit in a POSIX exit byte and stay clear of shell/signal codes
    assert 1 <= PREEMPT_EXIT_CODE <= 125
    assert 1 <= STALL_EXIT_CODE <= 125


def test_supervisor_reports_stall():
    sup = Supervisor(heartbeat_timeout=0.05)
    release = threading.Event()

    def body():
        release.wait(5.0)

    sup.spawn("slow", body)
    time.sleep(0.2)
    stats = sup.check()
    assert stats["worker_stalls"] == 1
    release.set()
    sup.shutdown()


class FaultyCatchVecEnv(CatchVecEnv):
    """Raises once, after `fault_after` steps — a transient actor fault."""

    def __init__(self, *a, fault_after: int = 30, **kw):
        super().__init__(*a, **kw)
        self._steps = 0
        self._fault_after = fault_after
        self._fired = False

    def step(self, actions):
        self._steps += 1
        if not self._fired and self._steps >= self._fault_after:
            self._fired = True
            raise RuntimeError("injected env fault")
        return super().step(actions)


def test_fault_injected_actor_recovers():
    cfg = tiny_test().replace(
        env_name="catch",
        training_steps=12,
        learning_starts=48,
        save_interval=1000,
        checkpoint_dir="/tmp/sup_test_ckpt_unused",
    )
    vec_env = FaultyCatchVecEnv(
        num_envs=cfg.num_actors, height=12, width=12, seed=0, fault_after=40
    )
    trainer = Trainer(cfg, vec_env=vec_env)
    trainer.run_threaded()
    assert int(trainer.state.step) == cfg.training_steps
    assert vec_env._fired  # the fault actually triggered mid-run


def test_stalled_worker_escalates_to_fatal():
    """A thread wedged inside an unkillable call (a device readback
    that never returns) must fail the run loudly past
    stall_fatal_timeout instead of letting it limp forever."""
    sup = Supervisor(heartbeat_timeout=0.2, stall_fatal_timeout=3.0)
    release = threading.Event()
    sup.spawn("wedged", release.wait)  # blocks indefinitely, no heartbeat
    time.sleep(0.5)
    stats = sup.check()  # stale but below fatal: surfaced, not raised
    assert stats["worker_stalls"] == 1
    time.sleep(3.0)
    with pytest.raises(WorkerFatalError, match="stalled"):
        sup.check()
    release.set()
    sup.shutdown()


def test_stall_escalation_disabled_with_zero_timeout():
    sup = Supervisor(heartbeat_timeout=0.05, stall_fatal_timeout=0.0)
    release = threading.Event()
    sup.spawn("wedged", release.wait)
    time.sleep(0.4)
    stats = sup.check()  # never escalates, only reports
    assert stats["worker_stalls"] == 1
    release.set()
    sup.shutdown()


class WedgingCatchVecEnv(CatchVecEnv):
    """Blocks forever inside step() once `wedge_now` is set — models a
    thread stuck in a device readback that never returns."""

    wedge_now = False

    def step(self, actions):
        if self.wedge_now:
            threading.Event().wait()  # never set: unkillable from Python
        return super().step(actions)


def test_run_threaded_exits_on_wedged_actor(tmp_path):
    from r2d2_tpu.utils.supervision import WorkerStalledError

    cfg = tiny_test().replace(
        env_name="catch",
        training_steps=10_000,  # far more than the wedge allows
        learning_starts=48,
        heartbeat_timeout=0.2,
        stall_fatal_timeout=1.5,
        save_interval=100_000,
        checkpoint_dir=str(tmp_path / "ckpt"),
    )
    vec_env = WedgingCatchVecEnv(num_envs=cfg.num_actors, height=12, width=12, seed=0)
    trainer = Trainer(cfg, vec_env=vec_env)
    trainer.warmup()  # wedge only after sampling opens
    vec_env.wedge_now = True
    t0 = time.time()
    try:
        with pytest.raises(WorkerStalledError, match="stalled"):
            trainer.run_threaded()
        # exit skipped device-blocking cleanup: it must be prompt, not hung
        assert time.time() - t0 < 30.0
    finally:
        # the watchdog deliberately stays armed through the unwind (it
        # guards against atexit hangs); a caller keeping the process alive
        # must disarm — else it would hard-exit pytest minutes later
        trainer.disarm_watchdog()


def test_main_watchdog_hard_exits_wedged_process(tmp_path):
    """A wedge on the MAIN thread (e.g. the learner's own device readback)
    can't reach sup.check() — the watchdog must hard-exit the process with
    STALL_EXIT_CODE so an external restart can recover."""
    import subprocess
    import sys as _sys

    from r2d2_tpu.utils.supervision import STALL_EXIT_CODE

    script = """
import threading, time
from r2d2_tpu.utils.supervision import Supervisor
sup = Supervisor(heartbeat_timeout=0.2, stall_fatal_timeout=1.0,
                 main_stall_headroom=0.0)
sup.start_main_watchdog()
sup.main_beat()
threading.Event().wait()  # main thread wedges: no further beats
"""
    t0 = time.time()
    proc = subprocess.run(
        [_sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=60,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == STALL_EXIT_CODE, proc.stderr
    assert "MAIN thread stalled" in proc.stderr
    assert time.time() - t0 < 60
