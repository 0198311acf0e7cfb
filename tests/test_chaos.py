"""Chaos suite: kill-and-resume drills under deterministic fault injection
(utils/faults.py).

The contract under test is the preemption protocol end to end: an injected
SIGTERM (the stand-in for a real grace-window delivery) cuts a training run
at a step boundary, the run drains/captures its deferred priority
write-backs, snapshots the replay plane plus the mid-run carry (sampling
RNG, published params, actor/env episode streams), writes a finalized
checkpoint at the cut step, and a --resume run continues BIT-IDENTICALLY —
same learner state, same replay tree, same sampling stream — as a run that
was never interrupted.

All drills run on CPU (the tier-1 conftest's 8 fake devices) and are
deterministic: the fault plane fires as a pure function of per-site call
counts, and the tiered plane runs its synchronous `deterministic_staging`
mode so no staging-thread interleaving perturbs the draw order.
"""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from r2d2_tpu.config import tiny_test
from r2d2_tpu.replay.snapshot import save_replay
from r2d2_tpu.train import Trainer
from r2d2_tpu.utils import faults
from r2d2_tpu.utils.checkpoint import latest_checkpoint_step
from r2d2_tpu.utils.faults import FaultPlane
from r2d2_tpu.utils.supervision import PREEMPT_EXIT_CODE, STALL_EXIT_CODE

pytestmark = pytest.mark.chaos

STEPS = 12


@pytest.fixture(autouse=True)
def _clean_plane():
    faults.uninstall()
    faults.reset_retry_stats()
    yield
    faults.uninstall()
    faults.reset_retry_stats()


# extra config per replay plane under test; K=2 on tiered exercises the
# deferred-write-back capture/restore path (a pending pair exists at the cut)
_PLANE_CFG = {
    "host": {},
    "tiered": dict(
        replay_plane="tiered", deterministic_staging=True, updates_per_dispatch=2
    ),
    "device": dict(replay_plane="device"),
    # PR 51: the sharded plane captures and restores its deferred readback too
    "sharded": dict(
        replay_plane="sharded", updates_per_dispatch=2, dp_size=4, batch_size=8,
        buffer_capacity=16 * 40,
    ),
}


def _cfg(tmp_path, tag, plane="host", **overrides):
    (tmp_path / tag).mkdir(exist_ok=True)
    base = dict(
        env_name="catch",
        checkpoint_dir=str(tmp_path / tag / "ckpt"),
        metrics_path=str(tmp_path / tag / "metrics.jsonl"),
        snapshot_replay=True,
        training_steps=STEPS,
        save_interval=1000,  # only the preemption checkpoint exists
        learning_starts=48,
        **_PLANE_CFG[plane],
    )
    base.update(overrides)
    return tiny_test().replace(**base)


def _fingerprint(trainer, tmp_path, tag):
    """Everything the resume contract promises, as comparable numpy: the
    full learner state (params, target, opt state, step), the sampling RNG
    position, and the complete replay tree via its own snapshot writer."""
    path = str(tmp_path / f"fp_{tag}.npz")
    save_replay(trainer.replay, path)
    with np.load(path, allow_pickle=False) as d:
        replay = {k: np.asarray(d[k]) for k in d.files}
    state = [np.asarray(x) for x in jax.tree.leaves(trainer.state)]
    return state, trainer.sample_rng.bit_generator.state, replay


def _assert_identical(a, b):
    state_a, rng_a, replay_a = a
    state_b, rng_b, replay_b = b
    assert rng_a == rng_b
    assert len(state_a) == len(state_b)
    for x, y in zip(state_a, state_b):
        np.testing.assert_array_equal(x, y)
    assert sorted(replay_a) == sorted(replay_b)
    for k in replay_a:
        np.testing.assert_array_equal(replay_a[k], replay_b[k], err_msg=k)


def _next_draw_idxes(trainer):
    """One further draw through the plane's own sampling path: the resumed
    stream must continue exactly where the uninterrupted one is."""
    item = trainer.plane.sample()
    if item[0] == "staged":
        return np.asarray(item[1].idxes)
    if item[0] == "multi":
        # the HBM planes draw when the update dispatches: draw as it would
        return np.asarray(trainer.replay.sample_indices(trainer.sample_rng).idxes)
    return np.asarray(item[2])


def _run_clean(cfg):
    t = Trainer(cfg)
    t.run_inline(env_steps_per_update=4)
    assert not t.preempted
    assert t._step == cfg.training_steps
    return t

def _kill_and_resume(cfg, site, call):
    """Phase 1: train until the scheduled SIGTERM preempts the run.
    Phase 2: resume and train to completion. Returns (resumed trainer,
    cut step)."""
    faults.install(FaultPlane(schedule={site: {call: "sigterm"}}))
    try:
        t1 = Trainer(cfg)
        t1.run_inline(env_steps_per_update=4)
    finally:
        faults.uninstall()
    assert t1.preempted, f"sigterm at {site}@{call} did not preempt"
    cut = t1._step
    assert cut < cfg.training_steps
    # the commit point: a finalized checkpoint at exactly the cut step
    assert latest_checkpoint_step(cfg.checkpoint_dir) == cut
    # the replay snapshot (with the mid-run carry) is on disk too
    assert os.path.exists(os.path.join(cfg.checkpoint_dir, "replay_snapshot.npz"))

    t2 = Trainer(cfg, resume=True)
    assert t2._initial_step == cut
    t2.run_inline(env_steps_per_update=4)
    assert not t2.preempted
    assert t2._step == cfg.training_steps
    return t2, cut


@pytest.mark.parametrize(
    "plane,site,call",
    [
        ("host", "trainer.update", 4),
        ("host", "host_plane.h2d", 3),  # mid-sample delivery
        ("host", "actor.step", 5),  # warmup-phase delivery: cut at step 0
        ("tiered", "trainer.update", 3),
        ("tiered", "tiered.stage_h2d", 2),  # mid-stage delivery
        ("device", "trainer.update", 4),
        ("sharded", "trainer.update", 3),
    ],
)
def test_sigterm_resume_is_bit_identical(tmp_path, plane, site, call):
    clean = _run_clean(_cfg(tmp_path, "clean", plane))
    resumed, cut = _kill_and_resume(_cfg(tmp_path, "killed", plane), site, call)
    _assert_identical(
        _fingerprint(clean, tmp_path, "clean"),
        _fingerprint(resumed, tmp_path, "killed"),
    )
    np.testing.assert_array_equal(_next_draw_idxes(clean), _next_draw_idxes(resumed))


@pytest.mark.parametrize(
    "site,call",
    [
        ("disk.write", 2),    # mid-demotion: record bytes not yet landed
        ("disk.promote", 1),  # mid-gather off the mmap segments
        ("codec.decode", 1),  # inside a disk-record field decode
    ],
)
def test_disk_tier_sigterm_resume_is_bit_identical(tmp_path, site, call):
    """Kill sweep over the disk-tier fault sites. A SIGTERM landing before
    a demotion's bytes hit the segment file, mid-promote while a sample
    gathers disk rows, or inside a codec field decode must still resume
    bit-identically: the replay snapshot is the commit point, never the
    segment files themselves (they are rebuilt from the snapshot on
    restore)."""
    over = dict(
        buffer_capacity=64,        # 4 host blocks: demotions start early
        replay_disk_capacity=320,  # a 20-block disk ring under them
        block_codec="delta-zlib",
    )
    clean = _run_clean(
        _cfg(tmp_path, "clean", "tiered",
             replay_disk_dir=str(tmp_path / "clean" / "disk"), **over))
    resumed, _ = _kill_and_resume(
        _cfg(tmp_path, "killed", "tiered",
             replay_disk_dir=str(tmp_path / "killed" / "disk"), **over),
        site, call)
    _assert_identical(
        _fingerprint(clean, tmp_path, "clean"),
        _fingerprint(resumed, tmp_path, "killed"),
    )
    np.testing.assert_array_equal(
        _next_draw_idxes(clean), _next_draw_idxes(resumed))


def test_double_preemption_resumes_twice(tmp_path):
    """Two successive preemptions (kill, resume, kill again, resume again)
    still land bit-identical — the carry round-trips through its own
    restored form."""
    clean = _run_clean(_cfg(tmp_path, "clean"))
    cfg = _cfg(tmp_path, "killed")
    faults.install(FaultPlane(schedule={"trainer.update": {3: "sigterm"}}))
    try:
        t1 = Trainer(cfg)
        t1.run_inline(env_steps_per_update=4)
    finally:
        faults.uninstall()
    assert t1.preempted and t1._step == 3
    faults.install(FaultPlane(schedule={"trainer.update": {4: "sigterm"}}))
    try:
        t2 = Trainer(cfg, resume=True)
        t2.run_inline(env_steps_per_update=4)
    finally:
        faults.uninstall()
    assert t2.preempted and t2._step == 7
    t3 = Trainer(cfg, resume=True)
    t3.run_inline(env_steps_per_update=4)
    _assert_identical(
        _fingerprint(clean, tmp_path, "clean"), _fingerprint(t3, tmp_path, "killed")
    )


@pytest.mark.parametrize(
    "plane,site", [("host", "host_plane.h2d"), ("tiered", "tiered.stage_h2d")]
)
def test_transient_h2d_fault_absorbed_without_perturbing_stream(
    tmp_path, plane, site
):
    """A flaky host->device lift is retried WITHOUT re-drawing: the final
    run is bit-identical to a fault-free one, and the retry surfaces in
    retry_stats / the metrics stream instead of vanishing."""
    clean = _run_clean(_cfg(tmp_path, "clean", plane))
    faults.reset_retry_stats()
    faults.install(FaultPlane(schedule={site: {2: "error"}}))
    try:
        flaky = _run_clean(_cfg(tmp_path, "flaky", plane))
    finally:
        faults.uninstall()
    assert faults.retry_stats().get(site) == 1
    _assert_identical(
        _fingerprint(clean, tmp_path, "clean"),
        _fingerprint(flaky, tmp_path, "flaky"),
    )
    with open(flaky.cfg.metrics_path) as f:
        assert '"io_retries"' in f.read()


def test_checkpoint_save_and_restore_faults_absorbed(tmp_path):
    cfg = _cfg(tmp_path, "ckpt", save_interval=8)  # one crossing, at step 8
    faults.install(FaultPlane(schedule={"checkpoint.save": {1: "error"}}))
    try:
        t = _run_clean(cfg)
    finally:
        faults.uninstall()
    assert latest_checkpoint_step(cfg.checkpoint_dir) == 8
    assert faults.retry_stats().get("checkpoint.save") == 1

    faults.install(FaultPlane(schedule={"checkpoint.restore": {1: "error"}}))
    try:
        resumed = Trainer(cfg, resume=True)
    finally:
        faults.uninstall()
    assert resumed._initial_step == 8
    assert int(resumed.state.step) == 8
    assert faults.retry_stats().get("checkpoint.restore") == 1
    assert t._step == STEPS  # the flaky save never derailed the run


def test_snapshot_write_failure_does_not_mask_run(tmp_path):
    """An exit-time snapshot failure (ENOSPC class) is log-and-continue:
    the run still completes and no torn snapshot file is left behind."""
    cfg = _cfg(tmp_path, "snapfail")
    faults.install(FaultPlane(schedule={"snapshot.write": {1: "error"}}))
    try:
        t = _run_clean(cfg)  # must not raise despite the failed snapshot
    finally:
        faults.uninstall()
    assert t._step == STEPS
    assert not os.path.exists(os.path.join(cfg.checkpoint_dir, "replay_snapshot.npz"))


def test_snapshot_every_cadence(tmp_path):
    """snapshot_every crossings schedule periodic background snapshots;
    the previous snapshot survives until the new one lands (atomic write),
    and the exit snapshot always lands last."""
    cfg = _cfg(tmp_path, "periodic", snapshot_every=4)
    t = Trainer(cfg)
    calls = []
    orig = t.save_replay_snapshot

    def counting(extra=None):
        calls.append(t._step)
        return orig(extra=extra)

    t.save_replay_snapshot = counting
    t.run_inline(env_steps_per_update=4)
    # crossings at 4, 8, 12 (some may be skipped if the previous write is
    # still in flight) plus the unconditional exit snapshot
    assert len(calls) >= 2
    assert os.path.exists(os.path.join(cfg.checkpoint_dir, "replay_snapshot.npz"))


def test_serve_watcher_backs_off_on_transient_reload_failure(tmp_path):
    from r2d2_tpu.serve.server import PolicyServer, ServeConfig

    srv = PolicyServer(
        tiny_test(),
        ServeConfig(buckets=(2,), cache_capacity=8, poll_interval_s=0.01),
        checkpoint_dir=str(tmp_path / "no_ckpts_yet"),
    )
    faults.install(FaultPlane(schedule={"serve.reload": {1: "error", 2: "error"}}))
    try:
        srv._watch_iteration()
        srv._watch_iteration()
        assert srv.reload_errors == 2
        assert srv._watch_backoff.failures == 2  # escalating poll delay
        srv._watch_iteration()  # fault budget spent: poll succeeds
    finally:
        faults.uninstall()
    assert srv.reload_errors == 2
    assert srv._watch_backoff.failures == 0  # success resets the cadence
    assert "io_retries" in srv.stats()


def test_mid_reshard_crash_second_resume_converges(tmp_path):
    """Elastic-resume chaos: a sharded dp=2 run is preempted, then resumed
    onto a CHANGED topology (device plane, dp=1) with reshard_on_resume.
    Killing the first resume attempt mid-scatter must be recoverable —
    the reshard phases are read-only on the snapshot files, so a second
    resume converges to exactly the state an uninterrupted reshard-resume
    reaches."""
    import shutil

    from r2d2_tpu.replay.snapshot import TopologyMismatch
    from r2d2_tpu.utils.faults import InjectedFault

    cfg1 = _cfg(
        tmp_path, "elastic", "host",
        replay_plane="sharded", dp_size=2, batch_size=8,
    )
    faults.install(FaultPlane(schedule={"trainer.update": {6: "sigterm"}}))
    try:
        t1 = Trainer(cfg1)
        t1.run_inline(env_steps_per_update=4)
    finally:
        faults.uninstall()
    assert t1.preempted
    cut = t1._step
    assert latest_checkpoint_step(cfg1.checkpoint_dir) == cut

    def _resume_cfg(tag, **over):
        dst = str(tmp_path / tag / "ckpt")
        shutil.copytree(cfg1.checkpoint_dir, dst)
        return cfg1.replace(
            replay_plane="device", dp_size=1,
            checkpoint_dir=dst,
            metrics_path=str(tmp_path / tag / "metrics.jsonl"),
            **over,
        )

    # without --reshard the layout change is a structured, fatal mismatch
    with pytest.raises(TopologyMismatch, match="--reshard"):
        Trainer(_resume_cfg("noflag"), resume=True)

    # control: uninterrupted reshard-resume, trained to completion
    control_cfg = _resume_cfg("control", reshard_on_resume=True)
    control = Trainer(control_cfg, resume=True)
    assert control._initial_step == cut
    control.run_inline(env_steps_per_update=4)
    assert control._step == STEPS
    fp_control = _fingerprint(control, tmp_path, "control")

    # faulted: the first resume attempt dies mid-reshard...
    faulted_cfg = _resume_cfg("faulted", reshard_on_resume=True)
    faults.install(FaultPlane(schedule={"reshard.scatter": {1: "error"}}))
    try:
        with pytest.raises(InjectedFault):
            Trainer(faulted_cfg, resume=True)
    finally:
        faults.uninstall()
    # ...and the second attempt lands the identical learner + replay state
    retry = Trainer(faulted_cfg, resume=True)
    assert retry._initial_step == cut
    retry.run_inline(env_steps_per_update=4)
    _assert_identical(fp_control, _fingerprint(retry, tmp_path, "retry"))


def test_cli_preempt_exit_code_and_resume(tmp_path):
    """The full operator loop as subprocesses: R2D2_FAULTS delivers a real
    SIGTERM mid-run, the CLI exits with PREEMPT_EXIT_CODE (distinct from
    STALL_EXIT_CODE: state is guaranteed CURRENT), and a --resume run
    finishes training."""
    assert PREEMPT_EXIT_CODE != STALL_EXIT_CODE
    ckpt = str(tmp_path / "ckpt")
    args = [
        sys.executable, "-m", "r2d2_tpu.train",
        "--preset", "tiny_test", "--env", "catch", "--mode", "inline",
        "--steps", str(STEPS), "--snapshot-replay",
        "--set", f"checkpoint_dir={ckpt}",
        "--set", f"metrics_path={tmp_path / 'metrics.jsonl'}",
        "--set", "save_interval=1000",
        "--set", "learning_starts=48",
    ]
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p1 = subprocess.run(
        args, env={**env, "R2D2_FAULTS": "trainer.update@3=sigterm"},
        capture_output=True, text=True, timeout=300,
    )
    assert p1.returncode == PREEMPT_EXIT_CODE, p1.stderr
    cut = latest_checkpoint_step(ckpt)
    assert cut is not None and 0 < cut < STEPS
    p2 = subprocess.run(
        args + ["--resume"], env=env, capture_output=True, text=True, timeout=300
    )
    assert p2.returncode == 0, p2.stderr
    assert latest_checkpoint_step(ckpt) == cut  # no later save_interval hit


# ------------------------------------------------- transport chaos sweep


TRANSPORT_SITES = [
    "transport.connect",
    "transport.send",
    "transport.recv",
    "transport.spool",
    "ingest.accept",
    "ingest.dedup",
]


def _mk_transport_block(i, T=12):
    from r2d2_tpu.replay.block import Block

    rng = np.random.default_rng(i)
    B = 1
    return Block(
        obs=rng.normal(size=(T, B, 5, 5)).astype(np.float32),
        last_action=rng.integers(0, 3, (T, B)).astype(np.int32),
        last_reward=rng.normal(size=(T, B)).astype(np.float32),
        action=rng.integers(0, 3, (T, B)).astype(np.int32),
        n_step_reward=rng.normal(size=(T, B)).astype(np.float32),
        gamma=np.ones((T, B), np.float32),
        hidden=rng.normal(size=(2, B, 8)).astype(np.float32),
        num_sequences=B,
        burn_in_steps=np.zeros((B,), np.int32),
        learning_steps=np.full((B,), T, np.int32),
        forward_steps=np.zeros((B,), np.int32),
    )


def _podstream_run(tmp_path, tag, n_blocks=6):
    """One fixed publisher->ingest stream: spool-backed publisher pumped
    synchronously against a live ingest worker, every offer absorbed
    through the bridge's own retry wrapper (exactly how production feeds
    the publisher). Returns (ingested obs list, ingest stats)."""
    import time as _time

    from r2d2_tpu.transport.ingest import IngestService
    from r2d2_tpu.transport.publisher import BlockStreamPublisher
    from r2d2_tpu.utils.faults import with_retries

    cfg = tiny_test().replace(
        env_name="catch", action_dim=3, liveloop=True,
        transport_connect_timeout_s=2.0, transport_heartbeat_s=0.2,
        transport_dead_peer_s=10.0,
        transport_spool_dir=str(tmp_path / tag),
    ).validate()

    class _Sink:
        def __init__(self):
            self.items = []

        def add_blocks_batch(self, items):
            self.items.extend(items)

    sink = _Sink()
    svc = IngestService(cfg, sink, version_source=None)
    svc.start()
    pub = BlockStreamPublisher(cfg, ("127.0.0.1", svc.port), "h0", seed=0)
    try:
        for i in range(n_blocks):
            with_retries(
                lambda i=i: pub.add_block(
                    _mk_transport_block(i), np.ones((1,), np.float32), None
                ),
                "liveloop.ingest", sleep=lambda _: None,
            )
        deadline = _time.monotonic() + 30.0
        while _time.monotonic() < deadline and len(sink.items) < n_blocks:
            pub.pump(timeout=0.05)
        return [b.obs for (b, _, _) in sink.items], svc.stats()
    finally:
        pub.stop(flush_deadline_s=1.0)
        svc.stop()


@pytest.mark.parametrize("site", TRANSPORT_SITES)
def test_transport_chaos_every_site_bit_identical(tmp_path, site):
    """Kill (injected error, driven from the R2D2_FAULTS spec-string
    format) at EVERY transport/ingest fault site: the retry/reconnect/
    resume machinery must deliver the exact same block stream as a
    fault-free run — nothing lost, nothing duplicated, bit-identical
    content — and the fault must be visibly absorbed, not vanish."""
    clean_obs, clean_stats = _podstream_run(tmp_path, "clean")
    assert len(clean_obs) == 6 and clean_stats["ingest_duplicate_blocks"] == 0

    faults.reset_retry_stats()
    faults.install(FaultPlane.from_spec(f"{site}@1=error"))
    try:
        chaos_obs, chaos_stats = _podstream_run(tmp_path, f"chaos_{site}")
    finally:
        faults.uninstall()
    assert chaos_stats["ingest_blocks"] == 6
    assert chaos_stats["ingest_duplicate_blocks"] == 0
    assert len(chaos_obs) == len(clean_obs)
    for a, b in zip(chaos_obs, clean_obs):
        np.testing.assert_array_equal(a, b)
