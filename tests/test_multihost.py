"""Multi-host helpers (parallel/multihost.py) on the 8-fake-device CPU
platform: single-process no-op init, global mesh construction, and
local-shard enumeration (all shards local when there is one process)."""

import jax
import numpy as np
import pytest

from r2d2_tpu.parallel.multihost import (
    initialize_distributed,
    local_axis_indices,
    make_global_mesh,
)


def test_initialize_noop_single_process(monkeypatch):
    monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
    monkeypatch.delenv("JAX_NUM_PROCESSES", raising=False)
    assert initialize_distributed() is False


def test_global_mesh_defaults():
    mesh = make_global_mesh(tp=2)
    assert mesh.shape["tp"] == 2
    assert mesh.shape["dp"] == len(jax.devices()) // 2
    with pytest.raises(ValueError):
        make_global_mesh(tp=3)  # 8 % 3 != 0


def test_local_axis_indices_all_local():
    mesh = make_global_mesh(dp=4, tp=2)
    assert local_axis_indices(mesh, "dp") == [0, 1, 2, 3]
    assert local_axis_indices(mesh, "tp") == [0, 1]


def test_local_axis_indices_detects_foreign_and_split_shards():
    class FakeDev:
        def __init__(self, pid):
            self.process_index = pid

    mesh = make_global_mesh(dp=4, tp=2)

    # simulate 2 hosts owning dp halves: indices 0,1 local to process 0
    fake = np.array(
        [[FakeDev(i // 2)] * 2 for i in range(4)], dtype=object
    )

    class FakeMesh:
        devices = fake
        axis_names = ("dp", "tp")

    assert local_axis_indices(FakeMesh(), "dp") == [0, 1]

    # a dp shard split across hosts must raise
    split = np.array(
        [[FakeDev(0), FakeDev(1)]] + [[FakeDev(1)] * 2] * 3, dtype=object
    )

    class SplitMesh:
        devices = split
        axis_names = ("dp", "tp")

    with pytest.raises(ValueError):
        local_axis_indices(SplitMesh(), "dp")


def test_multihost_store_single_process():
    """MultiHostShardedReplay on a 4-device single-process mesh: fills,
    samples, trains, and applies priorities."""
    from multihost_child import build_and_run
    from r2d2_tpu.parallel.multihost import make_global_mesh

    mesh = make_global_mesh(dp=4, tp=1, devices=jax.devices()[:4])
    losses, checksum = build_and_run(mesh)
    # 3 K=1-dispatch losses + 2 K=2-dispatch losses (multihost_child)
    assert len(losses) == 5 and all(np.isfinite(l) for l in losses)
    assert np.isfinite(checksum)


def _run_two_process_children(mode: str, timeout: int = 600, extra_args=()):
    """Spawn 2 real jax.distributed CPU children running multihost_child
    in `mode` and harvest their CHILD_RESULT payloads. Children are
    killed on any failure path: a hung collective (the SPMD-deadlock
    class these tests exist to catch) must not leak processes holding
    the coordinator port into the rest of the pytest session."""
    import json
    import os
    import socket
    import subprocess
    import sys as _sys

    with socket.socket() as sock:  # OS-assigned free port, no collisions
        sock.bind(("localhost", 0))
        port = sock.getsockname()[1]
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = repo_root + ":" + env.get("PYTHONPATH", "")
    script = os.path.join(os.path.dirname(__file__), "multihost_child.py")
    procs = [
        subprocess.Popen(
            [_sys.executable, script, str(pid), "2", str(port), mode,
             *map(str, extra_args)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for pid in range(2)
    ]
    results = {}
    try:
        for p in procs:
            out, err = p.communicate(timeout=timeout)
            if p.returncode != 0 and (
                "Multiprocess computations aren't implemented" in err
            ):
                pytest.skip(
                    "this jax build's CPU backend cannot run cross-process "
                    "collectives — real 2-process coverage needs a newer jax "
                    "or a TPU platform"
                )
            assert p.returncode == 0, f"child failed:\n{out}\n{err[-2000:]}"
            for line in out.splitlines():
                if line.startswith("CHILD_RESULT "):
                    r = json.loads(line[len("CHILD_RESULT "):])
                    results[r["pid"]] = r
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    assert set(results) == {0, 1}
    return results


def test_two_process_run_matches_single_process():
    """REAL multi-host: 2 jax.distributed processes (2 CPU devices each)
    train the same blocks/draws as the single-process 4-device run and
    must produce the same losses — the whole multi-host stack (local
    stores, global array assembly, cross-process psum) end to end."""
    from multihost_child import build_and_run
    from r2d2_tpu.parallel.multihost import make_global_mesh

    mesh = make_global_mesh(dp=4, tp=1, devices=jax.devices()[:4])
    ref_losses, ref_checksum = build_and_run(mesh)

    for r in _run_two_process_children("basic").values():
        np.testing.assert_allclose(r["losses"], ref_losses, atol=1e-4)
        np.testing.assert_allclose(r["checksum"], ref_checksum, rtol=1e-5)


def test_multihost_data_plane_matches_sharded_store():
    """Cross-plane equivalence: identical block contents and the SAME
    sample coordinates through MultiHostShardedReplay's assembled global
    views and through ShardedDeviceReplay's native global stores must give
    the same loss from the same shard_map step."""
    from synth import synth_block
    from r2d2_tpu.config import tiny_test
    from r2d2_tpu.learner import init_train_state, make_sharded_fused_multi_train_step
    from r2d2_tpu.parallel.mesh import replicated_sharding
    from r2d2_tpu.parallel.multihost import make_global_mesh
    from r2d2_tpu.replay.multihost_store import MultiHostShardedReplay
    from r2d2_tpu.replay.sharded_store import ShardedDeviceReplay

    import jax.numpy as jnp

    mesh = make_global_mesh(dp=4, tp=1, devices=jax.devices()[:4])
    cfg = tiny_test().replace(batch_size=8)
    mh = MultiHostShardedReplay(cfg, mesh, seed=9)
    sh = ShardedDeviceReplay(cfg.replace(dp_size=4, replay_plane="sharded"), mesh)

    # identical fill: both planes round-robin blocks over shards 0..3
    rngs = {g: np.random.default_rng(300 + g) for g in range(4)}
    for _ in range(2):
        for g in range(4):
            block = synth_block(cfg, rngs[g])
            prios = np.asarray([1.0 + 0.5 * g + 0.1 * i for i in range(cfg.seqs_per_block)], np.float32)
            mh.add_block(block, prios, None)
            sh.add_block(block, prios, None)

    (b, s, raw_p), _ = mh.sample_global_k(1)
    net, state = init_train_state(cfg, jax.random.PRNGKey(0))
    state = jax.device_put(state, replicated_sharding(mesh))
    flagged = make_sharded_fused_multi_train_step(
        cfg, net, mesh, 1, donate=False, is_from_priorities=True
    )
    plain = make_sharded_fused_multi_train_step(cfg, net, mesh, 1, donate=False)

    # multihost path: assembled global views + in-step IS normalization
    _, m_mh, p_mh = flagged(state, mh.global_stores(), b, s, raw_p)

    # sharded path: native stores + HOST-computed weights (SumTree.sample
    # formula) from the SAME raw priorities — both stores and the in-step
    # pmin normalization must agree with the single-tree semantics
    p_np = np.asarray(raw_p).astype(np.float64)
    positive = p_np[p_np > 0.0]
    min_p = positive.min() if positive.size else 1.0
    w_host = np.power(np.maximum(p_np, min_p) / min_p, -cfg.is_exponent).astype(np.float32)
    coords = (jnp.asarray(np.asarray(b)), jnp.asarray(np.asarray(s)), jnp.asarray(w_host))
    _, m_sh, p_sh = sh.run_with_stores(lambda stores: plain(state, stores, *coords))

    np.testing.assert_allclose(float(m_mh["loss"]), float(m_sh["loss"]), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(p_mh), np.asarray(p_sh), atol=1e-5)


def test_trainer_multihost_plane(tmp_path):
    """Trainer with replay_plane='multihost' (single process, 8 fake
    devices all local): end-to-end training through the collective plane."""
    from r2d2_tpu.config import tiny_test
    from r2d2_tpu.train import Trainer

    cfg = tiny_test().replace(
        env_name="catch",
        replay_plane="multihost",
        batch_size=8,
        checkpoint_dir=str(tmp_path / "ckpt"),
        training_steps=6,
        save_interval=3,
        learning_starts=48,
    )
    trainer = Trainer(cfg)
    assert trainer.mesh.shape["dp"] == len(jax.devices())
    trainer.run_inline(env_steps_per_update=4)
    assert trainer._step == 6
    assert int(trainer.state.step) == 6
    n, r = trainer.replay.episode_totals()
    assert n > 0


def test_multihost_device_collector_and_run_step():
    """The on-device collector composes with the multihost plane: chunks
    pack on device and deal round-robin into this host's LOCAL shards via
    add_blocks_batch; the collective step then trains from them."""
    from r2d2_tpu.collect import DeviceCollector
    from r2d2_tpu.config import tiny_test
    from r2d2_tpu.envs.catch import CatchEnv
    from r2d2_tpu.learner import init_train_state, make_sharded_fused_multi_train_step
    from r2d2_tpu.parallel.mesh import replicated_sharding
    from r2d2_tpu.parallel.multihost import make_global_mesh
    from r2d2_tpu.replay.multihost_store import MultiHostShardedReplay

    cfg = tiny_test().replace(
        env_name="catch", obs_shape=(10, 8, 1), action_dim=3,
        num_actors=8, batch_size=8, max_episode_steps=8,
        block_length=16, buffer_capacity=1280, learning_starts=48,
        collector="device", replay_plane="multihost", dp_size=4,
    )
    mesh = make_global_mesh(dp=4, tp=1, devices=jax.devices()[:4])
    fn_env = CatchEnv(height=10, width=8)
    net, state = init_train_state(cfg, jax.random.PRNGKey(0))
    state = jax.device_put(state, replicated_sharding(mesh))
    replay = MultiHostShardedReplay(cfg, mesh, seed=3)

    class _P:
        def latest(self):
            return state.params, 0

    col = DeviceCollector(cfg, net, _P(), fn_env, replay, seed=5)
    while not replay.can_sample():
        col.step()
    assert replay.env_steps > 0
    # every local shard received blocks (round-robin dealing)
    assert all(len(replay.shards[g]) > 0 for g in replay.local_ids)
    step = make_sharded_fused_multi_train_step(cfg, net, mesh, 1, is_from_priorities=True)
    state2, m = replay.run_step_k(step, state, 1)
    replay.drain_pending()
    assert np.isfinite(float(m["loss"]))
    assert int(np.asarray(state2.step)) == 1


def test_multihost_snapshot_roundtrip(tmp_path):
    """Per-host snapshot: control planes + per-shard stores restore
    bit-identically (same draws afterward), and a layout mismatch is
    rejected before any mutation."""
    from r2d2_tpu.config import tiny_test
    from r2d2_tpu.parallel.multihost import make_global_mesh
    from r2d2_tpu.replay.multihost_store import MultiHostShardedReplay
    from r2d2_tpu.replay.snapshot import restore_replay, save_replay

    cfg = tiny_test().replace(
        obs_shape=(10, 8, 1), action_dim=3, num_actors=4, batch_size=8,
        block_length=16, buffer_capacity=1280, learning_starts=32,
        replay_plane="multihost", dp_size=4, collector="host",
    )
    mesh = make_global_mesh(dp=4, tp=1, devices=jax.devices()[:4])
    replay = MultiHostShardedReplay(cfg, mesh, seed=1)
    from synth import synth_block

    rng = np.random.default_rng(0)
    for _ in range(2 * 4):
        replay.add_block(
            synth_block(cfg, rng),
            rng.uniform(0.5, 2.0, cfg.seqs_per_block).astype(np.float32),
            1.0,
        )
    path = str(tmp_path / "snap.npz")
    save_replay(replay, path)

    fresh = MultiHostShardedReplay(cfg, mesh, seed=1)
    restore_replay(fresh, path)
    assert len(fresh) == len(replay) and fresh.env_steps == replay.env_steps
    (b1, _, w1), _ = replay.sample_global_k(1)
    (b2, _, w2), _ = fresh.sample_global_k(1)
    np.testing.assert_array_equal(np.asarray(b1), np.asarray(b2))
    np.testing.assert_array_equal(np.asarray(w1), np.asarray(w2))
    for g in replay.local_ids:
        np.testing.assert_array_equal(
            np.asarray(replay.stores[g]["obs"]), np.asarray(fresh.stores[g]["obs"])
        )


def test_multihost_priority_lap_stamp():
    """A FULL ring lap between draw and apply wraps each shard's pointer
    back to its draw-time value — invisible to the pointer-window mask —
    and only the ptr_advances stamp threaded through sample_global_k /
    drain_pending rejects the stale batch (the same guard every other
    plane has, control_plane.update_priorities)."""
    from synth import synth_block
    from r2d2_tpu.config import tiny_test
    from r2d2_tpu.parallel.multihost import make_global_mesh
    from r2d2_tpu.replay.multihost_store import MultiHostShardedReplay
    from jax.sharding import PartitionSpec as P

    cfg = tiny_test().replace(
        obs_shape=(10, 8, 1), action_dim=3, num_actors=4, batch_size=8,
        block_length=16, buffer_capacity=1280, learning_starts=32,
        replay_plane="multihost", dp_size=4, collector="host",
    )
    mesh = make_global_mesh(dp=4, tp=1, devices=jax.devices()[:4])
    replay = MultiHostShardedReplay(cfg, mesh, seed=7)
    rng = np.random.default_rng(1)

    def lap():
        for _ in range(cfg.num_blocks):
            replay.add_block(
                synth_block(cfg, rng),
                np.full(cfg.seqs_per_block, 1.0, np.float32),
                1.0,
            )

    lap()
    _, draws = replay.sample_global_k(1)
    idxes_by_shard = draws[0]["idxes"]
    lap()  # full lap: every slot overwritten, pointers back where they were
    for g in replay.local_ids:
        assert replay.shards[g].block_ptr == draws[0]["old_ptrs"][g]

    Bs = cfg.batch_size // replay.dp
    per = {
        g: jax.device_put(
            np.full((1, 1, Bs), 99.0, np.float32), replay._shard_device[g]
        )
        for g in replay.local_ids
    }
    prios = replay._assemble(per, (1, replay.dp, Bs), P(None, "dp"))

    before = {
        g: replay.shards[g].tree.priorities_of(idxes_by_shard[g]).copy()
        for g in replay.local_ids
    }
    # stamped path: the whole batch is stale (one full lap) -> rejected
    replay.drain_pending((prios, draws))
    for g in replay.local_ids:
        np.testing.assert_array_equal(
            replay.shards[g].tree.priorities_of(idxes_by_shard[g]), before[g]
        )

    # the window mask ALONE cannot see the lap: without the stamp the
    # stale batch is (wrongly) applied — documents why the stamp exists
    draws[0]["old_advances"] = dict.fromkeys(replay.local_ids)
    replay.drain_pending((prios, draws))
    for g in replay.local_ids:
        got = replay.shards[g].tree.priorities_of(idxes_by_shard[g])
        assert np.all(got != before[g])


def test_multihost_k_dispatch_matches_sequential():
    """One run_step_k K-scan dispatch must equal K sequential
    is_from_priorities dispatches of one on the SAME pre-drawn coordinates:
    identical per-update priorities out and identical final params (the
    make_fused_multi_train_step equivalence contract, now on the
    multihost plane's raw-priority pmin-normalized path)."""
    from synth import synth_block
    from r2d2_tpu.config import tiny_test
    from r2d2_tpu.learner import init_train_state, make_sharded_fused_multi_train_step
    from r2d2_tpu.parallel.mesh import replicated_sharding
    from r2d2_tpu.parallel.multihost import make_global_mesh
    from r2d2_tpu.replay.multihost_store import MultiHostShardedReplay

    import jax.numpy as jnp

    K = 4
    mesh = make_global_mesh(dp=4, tp=1, devices=jax.devices()[:4])
    cfg = tiny_test().replace(
        batch_size=8, updates_per_dispatch=K, replay_plane="multihost",
        training_steps=2 * K,
    )
    replay = MultiHostShardedReplay(cfg, mesh, seed=11)
    rng = np.random.default_rng(2)
    for _ in range(8):
        replay.add_block(
            synth_block(cfg, rng),
            rng.uniform(0.5, 2.0, cfg.seqs_per_block).astype(np.float32),
            1.0,
        )

    (b, s, w), draws = replay.sample_global_k(K)
    net, state0 = init_train_state(cfg, jax.random.PRNGKey(0))
    state0 = jax.device_put(state0, replicated_sharding(mesh))

    multi_fn = make_sharded_fused_multi_train_step(
        cfg, net, mesh, K, donate=False, is_from_priorities=True
    )
    state_k, m_k, prios_k = multi_fn(state0, replay.global_stores(), b, s, w)

    single_fn = make_sharded_fused_multi_train_step(
        cfg, net, mesh, 1, donate=False, is_from_priorities=True
    )
    state_seq = state0
    b_np, s_np, w_np = (np.asarray(x) for x in (b, s, w))
    for i in range(K):
        state_seq, m_i, p_i = single_fn(
            state_seq, replay.global_stores(),
            jnp.asarray(b_np[i : i + 1]), jnp.asarray(s_np[i : i + 1]), jnp.asarray(w_np[i : i + 1]),
        )
        np.testing.assert_allclose(
            np.asarray(prios_k)[i], np.asarray(p_i)[0], rtol=2e-5, atol=1e-6
        )
    np.testing.assert_allclose(float(m_k["loss"]), float(m_i["loss"]), rtol=1e-5)
    for a, bb in zip(jax.tree.leaves(state_k.params), jax.tree.leaves(state_seq.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb), atol=1e-5)


def test_trainer_multihost_plane_k_dispatch(tmp_path):
    """Trainer end to end with replay_plane='multihost' AND
    updates_per_dispatch=4: the lifted K restriction (config), the K-scan
    collective dispatch, and the deferred drain (finish_updates) all in
    one run."""
    from r2d2_tpu.config import tiny_test
    from r2d2_tpu.train import Trainer

    cfg = tiny_test().replace(
        env_name="catch",
        replay_plane="multihost",
        batch_size=8,
        updates_per_dispatch=4,
        checkpoint_dir=str(tmp_path / "ckpt"),
        training_steps=8,
        save_interval=4,
        learning_starts=48,
    )
    trainer = Trainer(cfg)
    trainer.run_inline()
    assert int(trainer.state.step) == 8
    assert trainer.plane.replay._pending is None  # final drain happened


def test_two_process_fused_runner_matches_single_process():
    """REAL multi-host coverage of MultiHostFusedRunner (round-3 verdict
    item 3): 2 jax.distributed processes drive the fused megastep runner
    — collective K-update + collection dispatches plus the HOST-LOCAL
    plumbing (per-shard slot reservation, addressable-piece chunk drain,
    stamped priority drain, deterministic collect cadence) — and must
    produce exactly the single-process 4-device run's losses, global env
    accounting, and tree mass."""
    from multihost_child import build_and_run_fused
    from r2d2_tpu.parallel.multihost import make_global_mesh

    mesh = make_global_mesh(dp=4, tp=1, devices=jax.devices()[:4])
    ref_losses, ref_checksum, ref_steps = build_and_run_fused(mesh)
    assert all(np.isfinite(l) for l in ref_losses) and ref_steps > 0

    for r in _run_two_process_children("fused").values():
        np.testing.assert_allclose(r["losses"], ref_losses, atol=1e-4)
        np.testing.assert_allclose(r["checksum"], ref_checksum, rtol=1e-5)
        assert r["env_steps"] == ref_steps


def test_elastic_resume_same_layout_bit_identical(tmp_path):
    """The elastic-resume acceptance bar, in-process: snapshot a multihost
    run mid-training, resume via reshard_replay (fresh replay + carried
    train state + restored draw epoch), and the resumed losses must be
    BIT-identical to the uninterrupted run's continuation — the exact
    path, same logical shard set."""
    from multihost_child import build_elastic
    from r2d2_tpu.parallel.multihost import make_global_mesh

    mesh = make_global_mesh(dp=4, tp=1, devices=jax.devices()[:4])
    ref_losses, ref_checksum = build_elastic(mesh, str(tmp_path), "save")
    losses, checksum = build_elastic(mesh, str(tmp_path), "resume")
    assert losses == ref_losses  # bit-identical, not just close
    assert checksum == ref_checksum


def test_elastic_resume_two_to_one_process(tmp_path):
    """Elastic topology, shrink direction: a 2-process run snapshots
    (per-process files + topology manifests), then a SINGLE process with
    all 4 devices resumes via reshard_replay. Same logical shard set =>
    the resumed losses and params must be bit-identical (to collective-
    reduction tolerance) to the 2-process run's own continuation."""
    from multihost_child import build_elastic
    from r2d2_tpu.parallel.multihost import make_global_mesh

    shared = str(tmp_path)
    save_results = _run_two_process_children("elastic_save", extra_args=[shared])

    mesh = make_global_mesh(dp=4, tp=1, devices=jax.devices()[:4])
    losses, checksum = build_elastic(mesh, shared, "resume")
    for r in save_results.values():
        np.testing.assert_allclose(losses, r["losses"], atol=1e-4)
        np.testing.assert_allclose(checksum, r["checksum"], rtol=1e-5)


def test_elastic_resume_one_to_two_process(tmp_path):
    """Elastic topology, grow direction: a single-process 4-device run
    snapshots one file owning all 4 shards; 2 real jax.distributed
    processes resume from it, each regathering only its local shards.
    Continuation losses must match the uninterrupted single-process run."""
    from multihost_child import build_elastic
    from r2d2_tpu.parallel.multihost import make_global_mesh

    shared = str(tmp_path)
    mesh = make_global_mesh(dp=4, tp=1, devices=jax.devices()[:4])
    ref_losses, ref_checksum = build_elastic(mesh, shared, "save")
    assert all(np.isfinite(l) for l in ref_losses)

    for r in _run_two_process_children("elastic_resume", extra_args=[shared]).values():
        np.testing.assert_allclose(r["losses"], ref_losses, atol=1e-4)
        np.testing.assert_allclose(r["checksum"], ref_checksum, rtol=1e-5)


def test_trainer_multihost_fused_megastep(tmp_path):
    """run_fused on the multihost plane: the collective megastep (K
    updates + per-shard collection + local slab writes in ONE shard_map
    dispatch over the global mesh) drives training end to end, with the
    deferred chunk/priority drains landing on local shards only."""
    from r2d2_tpu.config import tiny_test
    from r2d2_tpu.train import Trainer

    cfg = tiny_test().replace(
        env_name="catch",
        obs_shape=(12, 12, 1),
        action_dim=3,
        replay_plane="multihost",
        collector="device",
        num_actors=8,
        batch_size=8,
        updates_per_dispatch=2,
        block_length=16,
        buffer_capacity=16 * 16 * 8,
        learning_starts=64,
        max_episode_steps=10,
        training_steps=8,
        save_interval=4,
        checkpoint_dir=str(tmp_path / "ckpt"),
    )
    trainer = Trainer(cfg)
    trainer.run_fused()
    assert int(trainer.state.step) == 8
    assert trainer.replay.env_steps > 0
    n_ep, r_sum = trainer.replay.episode_totals()
    assert n_ep > 0 and np.isfinite(r_sum)
