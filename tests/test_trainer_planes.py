"""End-to-end Trainer runs over the device and sharded replay planes
(the host plane is covered by test_end_to_end.py). Both run the same
minimum slice on Catch: collection -> HBM block writes -> coordinate-only
sampling -> fused/jitted update -> priority round trip."""

import jax
import numpy as np
import pytest

from r2d2_tpu.config import tiny_test
from r2d2_tpu.envs.catch import CatchVecEnv
from r2d2_tpu.train import Trainer
from r2d2_tpu.utils import profiling


def run_trainer(cfg, steps=10):
    vec_env = CatchVecEnv(num_envs=cfg.num_actors, height=12, width=12, seed=0)
    trainer = Trainer(cfg, vec_env=vec_env)
    trainer.run_inline(env_steps_per_update=4)
    return trainer


def _assert_every_drawn_priority_reached_the_tree(tr, offered_before):
    """The HBM planes read an update's priorities back one dispatch late, at
    every K (K = 1 included: there is one path); after finish_updates()
    nothing is in flight and every row of every update was offered to a
    tree."""
    assert tr.plane._pending is None
    offered = profiling.counted("replay.priority_rows_offered") - offered_before
    assert offered == tr.cfg.training_steps * tr.cfg.batch_size


@pytest.mark.parametrize("K", [1, 2])
def test_device_plane_end_to_end(tmp_path, K):
    cfg = tiny_test().replace(
        env_name="catch",
        replay_plane="device",
        updates_per_dispatch=K,
        checkpoint_dir=str(tmp_path / "ckpt"),
        training_steps=10,
        save_interval=10,
        learning_starts=48,
    )
    offered = profiling.counted("replay.priority_rows_offered")
    tr = run_trainer(cfg)
    assert int(tr.state.step) == 10
    assert tr.replay.env_steps > 0
    # priorities actually landed in the tree (round trip exercised)
    assert tr.replay.tree.total > 0
    assert tr.plane.sample()[0] == "multi"  # drawn when the update dispatches
    _assert_every_drawn_priority_reached_the_tree(tr, offered)


def test_tiered_plane_end_to_end(tmp_path):
    """The tiered plane's full loop: collection -> host store -> staged
    K-batch chunks through the prefetch pipeline -> stacked K-update scan
    -> deferred priority round trip, with the overlap metric populated."""
    cfg = tiny_test().replace(
        env_name="catch",
        replay_plane="tiered",
        updates_per_dispatch=2,
        checkpoint_dir=str(tmp_path / "ckpt"),
        training_steps=10,
        save_interval=10,
        learning_starts=48,
    )
    tr = run_trainer(cfg)
    assert int(tr.state.step) == 10
    assert tr.replay.env_steps > 0
    # priorities actually landed in the tree (deferred round trip drained)
    assert tr.replay.tree.total > 0
    # the staging pipeline ran and the overlap accountant saw its chunks
    assert tr.plane.xfer.chunks > 0
    stats = tr.plane.xfer.stats()
    assert 0.0 <= stats["h2d_overlap_fraction"] <= 1.0
    # run_inline's finish_updates stopped the staging thread
    assert tr.plane._pipe is None
    assert tr.plane._pending is None


def test_tiered_plane_torn_shutdown_drain(tmp_path):
    """Stopping mid-pipeline with a priority readback still in flight:
    drain_pending applies the pending chunk under its staleness stamps and
    leaves the sum tree CONSISTENT (root == sum of leaves, all finite);
    a second drain and a dropped undelivered staged chunk are no-ops."""
    cfg = tiny_test().replace(
        env_name="catch",
        replay_plane="tiered",
        updates_per_dispatch=2,
        checkpoint_dir=str(tmp_path / "ckpt"),
        training_steps=50,
        save_interval=50,
        learning_starts=48,
    )
    vec_env = CatchVecEnv(num_envs=cfg.num_actors, height=12, width=12, seed=0)
    tr = Trainer(cfg, vec_env=vec_env)
    tr.warmup()
    # one update leaves its priority readback pending (deferred one
    # dispatch) and the pipeline's next staged chunk in flight
    tr.state, _ = tr.plane.update(tr.state, tr.plane.sample())
    assert tr.plane._pending is not None
    assert tr.plane._pipe is not None

    tr.finish_updates()  # the torn shutdown
    assert tr.plane._pending is None
    assert tr.plane._pipe is None

    tree = tr.replay.tree
    leaves = tree.tree[tree.leaf_offset : tree.leaf_offset + tree.capacity]
    assert np.all(np.isfinite(leaves)) and np.all(leaves >= 0)
    np.testing.assert_allclose(tree.total, leaves.sum(), rtol=1e-9)
    assert tree.total > 0
    tr.finish_updates()  # idempotent


@pytest.mark.parametrize("K", [1, 2])
def test_sharded_plane_end_to_end(tmp_path, K):
    assert len(jax.devices()) >= 8
    cfg = tiny_test().replace(
        env_name="catch",
        replay_plane="sharded",
        updates_per_dispatch=K,
        dp_size=4,
        tp_size=2,
        batch_size=8,  # 2 per dp shard
        buffer_capacity=16 * 40,  # 40 blocks -> 10 per shard
        checkpoint_dir=str(tmp_path / "ckpt"),
        training_steps=10,
        save_interval=10,
        learning_starts=48,
    )
    offered = profiling.counted("replay.priority_rows_offered")
    tr = run_trainer(cfg)
    assert tr.mesh is not None and tr.mesh.shape == {"dp": 4, "tp": 2}
    assert int(tr.state.step) == 10
    assert all(s.tree.total > 0 for s in tr.replay.shards)
    _assert_every_drawn_priority_reached_the_tree(tr, offered)
    # tp=2 on the sharded plane is REAL tensor parallelism now: the
    # core-agnostic probe kernel (tp_probe_kernel — resolves to core/wi
    # here since tiny_test uses the default LSTM core; it falls back to
    # enc/Dense_0 only for the LRU core, whose params are tp-replicated)
    # keeps its Megatron column sharding through 10 updates (manual-dp
    # shard_map with the tp axis GSPMD-auto), while the params stay
    # dp-replicated
    from r2d2_tpu.parallel.mesh import tp_probe_kernel

    wi = tp_probe_kernel(tr.state.params)
    assert wi.sharding.spec[-1] == "tp"
    assert all(
        "dp" not in str(l.sharding.spec) for l in jax.tree.leaves(tr.state.params)
    )


def _atari_v4_8_placement() -> dict:
    """What the preset atari_v4_8 decides about the path an update takes
    (sharded over dp 4, updates_per_dispatch left at its default), at
    tiny_test's sizes. `python -m r2d2_tpu.train --preset atari_v4_8` is
    threaded by default, so this is that command's path; a preset that moves
    to another K or plane needs its own default path driven here instead."""
    from r2d2_tpu.config import atari_v4_8

    preset = atari_v4_8()
    placement = dict(
        replay_plane=preset.replay_plane,
        dp_size=preset.dp_size,
        updates_per_dispatch=preset.updates_per_dispatch,
    )
    assert placement == dict(replay_plane="sharded", dp_size=4, updates_per_dispatch=1)
    return dict(placement, batch_size=8, buffer_capacity=16 * 40)  # 2 rows, 10 blocks a shard


@pytest.mark.parametrize(
    "placement",
    [lambda: dict(replay_plane="device"), _atari_v4_8_placement],
    ids=["device", "sharded-as-atari_v4_8"],
)
def test_device_plane_threaded_pipelined(tmp_path, placement):
    """Threaded mode on the one path, at the default K = 1: the sampler
    thread queues tokens, each update draws its coordinates when it
    dispatches, under the store's lock (a queued item holds nothing a block
    write could retarget), write-back lags one update, and run_threaded's
    exit drains the last one."""
    cfg = tiny_test().replace(
        env_name="catch",
        checkpoint_dir=str(tmp_path / "ckpt"),
        training_steps=6,
        save_interval=6,
        learning_starts=48,
        **placement(),
    )
    vec_env = CatchVecEnv(num_envs=cfg.num_actors, height=12, width=12, seed=0)
    offered = profiling.counted("replay.priority_rows_offered")
    trainer = Trainer(cfg, vec_env=vec_env)
    trainer.run_threaded()
    assert int(trainer.state.step) == 6
    _assert_every_drawn_priority_reached_the_tree(trainer, offered)


def test_sharded_plane_requires_mesh():
    with pytest.raises(ValueError, match="sharded"):
        tiny_test().replace(replay_plane="sharded")


def test_host_plane_with_mesh_auto_psum(tmp_path):
    """dp>1 on the HOST plane: batches shard over dp under plain jit and
    XLA inserts the gradient all-reduce (no shard_map)."""
    cfg = tiny_test().replace(
        env_name="catch",
        dp_size=8,
        batch_size=8,
        checkpoint_dir=str(tmp_path / "ckpt"),
        training_steps=6,
        save_interval=6,
        learning_starts=48,
    )
    tr = run_trainer(cfg, steps=6)
    assert int(tr.state.step) == 6
    leaf = jax.tree.leaves(tr.state.params)[0]
    assert leaf.sharding.is_fully_replicated


def test_sharded_plane_tp_resume(tmp_path):
    """Checkpoint -> resume on the dp x tp sharded plane: the restored
    state must carry the SAME tp shardings as a fresh placement (restore
    templates from the already-placed state), and training must continue
    from the saved step."""
    cfg = tiny_test().replace(
        env_name="catch",
        replay_plane="sharded",
        dp_size=4,
        tp_size=2,
        batch_size=8,
        buffer_capacity=16 * 40,
        checkpoint_dir=str(tmp_path / "ckpt"),
        training_steps=10,
        save_interval=5,
        learning_starts=48,
    )
    tr = run_trainer(cfg)
    assert int(tr.state.step) == 10

    resumed = Trainer(
        cfg.replace(training_steps=12),
        vec_env=CatchVecEnv(num_envs=cfg.num_actors, height=12, width=12, seed=1),
        resume=True,
    )
    assert int(resumed.state.step) == 10
    from r2d2_tpu.parallel.mesh import tp_probe_kernel

    wi = tp_probe_kernel(resumed.state.params)
    assert wi.sharding.spec[-1] == "tp", wi.sharding
    for a, b in zip(
        jax.tree.leaves(resumed.state.params), jax.tree.leaves(tr.state.params)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    resumed.run_inline(env_steps_per_update=4)
    assert int(resumed.state.step) == 12


def test_sharded_plane_preempt_restores_pending_readback(tmp_path):
    """A preempted run on the sharded plane serializes its deferred priority
    readback instead of applying it (the uninterrupted run's next draw
    happens BEFORE that write-back lands), and the resumed plane holds it
    again: same priorities, each draw's per-shard stamps as (dp,) arrays, and
    the same trees once it drains. Until PR 51 only the device plane did."""
    cfg = tiny_test().replace(
        env_name="catch",
        replay_plane="sharded",
        updates_per_dispatch=2,
        dp_size=4,
        batch_size=8,
        buffer_capacity=16 * 40,
        checkpoint_dir=str(tmp_path / "ckpt"),
        snapshot_replay=True,
        training_steps=100,
        save_interval=1000,
        learning_starts=48,
    )
    K, dp = 2, 4
    tr = Trainer(cfg, vec_env=CatchVecEnv(num_envs=cfg.num_actors, height=12, width=12, seed=0))
    tr.reset_clock()
    tr.warmup()
    for _ in range(2):  # the second update drains the first's readback
        tr._one_update(tr.plane.sample())
    assert tr.plane._pending is not None

    def leaves(replay):
        return [s.tree.priorities_of(np.arange(s.tree.capacity)).copy() for s in replay.shards]

    before = leaves(tr.replay)
    tr.preempted = True
    carry = tr._capture_carry_safe()
    assert tr.plane._pending is None
    for a, b in zip(leaves(tr.replay), before):  # captured, not applied
        np.testing.assert_array_equal(a, b)
    assert carry["pend_prios"].shape == (K, dp, cfg.batch_size // dp)
    assert carry["pend_old_ptr"].shape == carry["pend_old_advances"].shape == (K, dp)
    tr._snapshot_on_exit(extra=carry)
    tr._finalize_preempt()

    resumed = Trainer(
        cfg, vec_env=CatchVecEnv(num_envs=cfg.num_actors, height=12, width=12, seed=0), resume=True
    )
    assert resumed._initial_step == 2 * K
    prios, draws = resumed.plane._pending
    np.testing.assert_array_equal(prios, carry["pend_prios"])
    assert len(draws) == K
    for k, d in enumerate(draws):
        assert isinstance(d.old_ptr, np.ndarray) and d.old_ptr.shape == (dp,)
        np.testing.assert_array_equal(d.old_ptr, carry["pend_old_ptr"][k])
        np.testing.assert_array_equal(d.old_advances, carry["pend_old_advances"][k])
        np.testing.assert_array_equal(d.idxes, carry["pend_idxes"][k])
    for a, b in zip(leaves(resumed.replay), before):
        np.testing.assert_array_equal(a, b)

    # draining it moves both runs' trees the same way
    tr.plane.restore_pending({k[len("pend_"):]: v for k, v in carry.items() if k.startswith("pend_")})
    tr.finish_updates()
    resumed.finish_updates()
    after = leaves(resumed.replay)
    assert any((a != b).any() for a, b in zip(after, before))
    for a, b in zip(after, leaves(tr.replay)):
        np.testing.assert_array_equal(a, b)
