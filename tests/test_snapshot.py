"""Replay snapshots (replay/snapshot.py): a restored buffer is
bit-identical to the saved one across all three data planes — same
counters, same tree, and the same RNG stream draws the same batches."""

import os

import jax
import numpy as np
import pytest

from r2d2_tpu.config import tiny_test
from r2d2_tpu.replay.device_store import DeviceReplayBuffer
from r2d2_tpu.replay.replay_buffer import ReplayBuffer
from r2d2_tpu.replay.snapshot import restore_replay, save_replay
from r2d2_tpu.replay.sum_tree import SumTree


def _fill(replay, cfg, n_blocks=8, seed=0):
    from synth import synth_block

    rng = np.random.default_rng(seed)
    for _ in range(n_blocks):
        replay.add_block(
            synth_block(cfg, rng),
            rng.uniform(0.5, 2.0, cfg.seqs_per_block).astype(np.float32),
            float(rng.normal()),
        )


def test_sum_tree_leaves_round_trip():
    t = SumTree(37)
    rng = np.random.default_rng(0)
    t.update(rng.integers(0, 37, 60), rng.uniform(0.1, 3.0, 60))
    t2 = SumTree(37)
    t2.load_leaves(t.leaves())
    np.testing.assert_allclose(t2.tree, t.tree, rtol=1e-12)


@pytest.mark.parametrize("plane", ["host", "device"])
def test_snapshot_round_trip(tmp_path, plane):
    cfg = tiny_test()
    cls = ReplayBuffer if plane == "host" else DeviceReplayBuffer
    replay = cls(cfg)
    _fill(replay, cfg)
    path = str(tmp_path / "snap.npz")
    save_replay(replay, path)

    fresh = cls(cfg)
    restore_replay(fresh, path)
    assert len(fresh) == len(replay)
    assert fresh.env_steps == replay.env_steps
    assert fresh.block_ptr == replay.block_ptr
    assert fresh.episode_totals() == replay.episode_totals()
    np.testing.assert_allclose(fresh.tree.tree, replay.tree.tree, rtol=1e-12)

    if plane == "host":
        a = replay.sample_batch(np.random.default_rng(42))
        b = fresh.sample_batch(np.random.default_rng(42))
        np.testing.assert_array_equal(a.obs, b.obs)
        np.testing.assert_array_equal(a.idxes, b.idxes)
        np.testing.assert_allclose(a.is_weights, b.is_weights)
    else:
        a = replay.sample_indices(np.random.default_rng(42))
        b = fresh.sample_indices(np.random.default_rng(42))
        np.testing.assert_array_equal(a.idxes, b.idxes)
        np.testing.assert_allclose(a.is_weights, b.is_weights)
        for k, arr in replay.stores.items():
            np.testing.assert_array_equal(np.asarray(arr), np.asarray(fresh.stores[k]))


def test_snapshot_rejects_shape_mismatch(tmp_path):
    cfg = tiny_test()
    replay = ReplayBuffer(cfg)
    _fill(replay, cfg)
    path = str(tmp_path / "snap.npz")
    save_replay(replay, path)
    other = ReplayBuffer(cfg.replace(buffer_capacity=320))
    with pytest.raises(ValueError):
        restore_replay(other, path)
    wrong_plane = DeviceReplayBuffer(cfg)
    with pytest.raises(ValueError):
        restore_replay(wrong_plane, path)


def test_sharded_snapshot_round_trip(tmp_path):
    from r2d2_tpu.parallel.mesh import make_mesh
    from r2d2_tpu.replay.sharded_store import ShardedDeviceReplay

    dp = 4
    mesh = make_mesh(dp=dp, tp=1, devices=jax.devices()[:dp])
    cfg = tiny_test().replace(dp_size=dp, replay_plane="sharded", batch_size=8)
    replay = ShardedDeviceReplay(cfg, mesh)
    _fill(replay, cfg, n_blocks=2 * dp)
    path = str(tmp_path / "snap.npz")
    save_replay(replay, path)

    fresh = ShardedDeviceReplay(cfg, mesh)
    restore_replay(fresh, path)
    assert len(fresh) == len(replay)
    assert fresh._rr == replay._rr
    a = replay.sample_indices(np.random.default_rng(7))
    b = fresh.sample_indices(np.random.default_rng(7))
    np.testing.assert_array_equal(a.idxes, b.idxes)
    np.testing.assert_allclose(a.is_weights, b.is_weights)
    for k, arr in replay.stores.items():
        np.testing.assert_array_equal(np.asarray(arr), np.asarray(fresh.stores[k]))


def test_trainer_snapshot_resume(tmp_path):
    from r2d2_tpu.train import Trainer

    cfg = tiny_test().replace(
        env_name="catch",
        checkpoint_dir=str(tmp_path / "ckpt"),
        snapshot_replay=True,
        training_steps=6,
        save_interval=3,
        learning_starts=48,
    )
    t1 = Trainer(cfg)
    t1.run_inline(env_steps_per_update=4)
    saved_size = len(t1.replay)
    saved_env_steps = t1.replay.env_steps

    t2 = Trainer(cfg.replace(training_steps=8), resume=True)
    assert int(t2.state.step) == 6
    assert len(t2.replay) == saved_size
    # total env-step accounting doesn't double-count restored steps
    assert t2.replay.env_steps + t2.env_steps_offset == saved_env_steps
    # training continues with no warmup needed
    t2.run_inline(env_steps_per_update=4)
    assert int(t2.state.step) == 8


def test_restore_failure_leaves_buffer_untouched(tmp_path):
    """A mismatched snapshot must raise BEFORE mutating anything: the
    fresh buffer stays usable (empty) instead of half-restored."""
    cfg = tiny_test()
    replay = ReplayBuffer(cfg)
    _fill(replay, cfg)
    path = str(tmp_path / "snap.npz")
    save_replay(replay, path)
    other = ReplayBuffer(cfg.replace(obs_shape=(8, 8, 1)))
    with pytest.raises(ValueError):
        restore_replay(other, path)
    assert len(other) == 0
    assert other.tree.total == 0.0
    assert not other.occupied.any()


FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "replay_snapshot_pr23_device.npz")


def _fixture_replay(cfg, cls):
    """The buffer tests/fixtures/replay_snapshot_pr23_device.npz was saved
    from: written by the tree of PR 23 (commit 2dd2d55, whose device store
    held raw frames) with exactly these calls on a DeviceReplayBuffer."""
    from synth import synth_block

    replay = cls(cfg)
    rng = np.random.default_rng(24)
    for _ in range(3):
        replay.add_block(
            synth_block(cfg, rng), rng.uniform(0.5, 2.0, cfg.seqs_per_block).astype(np.float32), float(rng.normal()))
    return replay


@pytest.mark.parametrize("how", ["restore", "reshard_to_device", "reshard_to_sharded", "reshard_to_host"])
def test_a_snapshot_written_before_the_row_format_loads(how):
    """Snapshot files hold obs as FRAMES on every plane, before and after PR
    25: a file the parent wrote restores into today's row store, reshards
    onto any plane, and today's device plane writes the same bytes back."""
    from r2d2_tpu.replay.block import frames_to_rows
    from r2d2_tpu.replay.reshard import reshard_replay
    from r2d2_tpu.replay.sharded_store import ShardedDeviceReplay
    from r2d2_tpu.parallel.mesh import make_mesh

    cfg = tiny_test().replace(buffer_capacity=64)
    with np.load(FIXTURE) as npz:
        frames = npz["store_obs"]
    assert frames.shape == (cfg.num_blocks, cfg.block_slot_len, *cfg.obs_shape) and frames.any()
    want = _fixture_replay(cfg, DeviceReplayBuffer)
    if how == "restore":
        fresh = DeviceReplayBuffer(cfg)
        restore_replay(fresh, FIXTURE)
    elif how == "reshard_to_device":
        fresh = DeviceReplayBuffer(cfg)
        reshard_replay(fresh, [FIXTURE])
    elif how == "reshard_to_sharded":
        fresh = ShardedDeviceReplay(cfg, make_mesh(dp=1, tp=1, devices=jax.devices()[:1]))
        reshard_replay(fresh, [FIXTURE])
    else:
        fresh = ReplayBuffer(cfg)
        reshard_replay(fresh, [FIXTURE])
        np.testing.assert_array_equal(fresh.obs_store, frames)  # the host plane keeps frames
        return
    np.testing.assert_array_equal(np.asarray(fresh.stores["obs"]), frames_to_rows(frames, cfg.obs_shape))
    for k, v in want.stores.items():
        np.testing.assert_array_equal(np.asarray(fresh.stores[k]), np.asarray(v), err_msg=k)


def test_the_device_plane_still_writes_the_parents_file(tmp_path):
    cfg = tiny_test().replace(buffer_capacity=64)
    path = str(tmp_path / "snap.npz")
    save_replay(_fixture_replay(cfg, DeviceReplayBuffer), path)
    with np.load(FIXTURE) as old, np.load(path) as new:
        assert sorted(old.files) == sorted(new.files)
        for k in old.files:
            if k.startswith("store_") or k in ("tree_leaves", "block_ptr", "size", "occupied"):
                assert old[k].dtype == new[k].dtype and old[k].shape == new[k].shape, k
                np.testing.assert_array_equal(old[k], new[k], err_msg=k)


@pytest.mark.parametrize("how", ["restore", "reshard_to_device", "reshard_to_sharded"])
def test_the_parents_file_loads_into_a_blocked_store_and_is_written_back_as_it_was(how, tmp_path):
    """PR 38: under an encoder that publishes a block (the Nature trunk's 4,
    which divides the fixture's 12x12x1 frames) the device stores keep each
    frame's bytes in 4x4 blocks; the FILE stays canonical frames, so PR 23's
    fixture loads on every device plane and is saved back byte for byte."""
    from r2d2_tpu.parallel.mesh import make_mesh
    from r2d2_tpu.replay.block import frames_to_rows, rows_to_frames
    from r2d2_tpu.replay.reshard import reshard_replay
    from r2d2_tpu.replay.sharded_store import ShardedDeviceReplay

    cfg = tiny_test().replace(buffer_capacity=64, encoder="nature")
    assert cfg.resolved_frame_block == 4 and tiny_test().resolved_frame_block == 1
    with np.load(FIXTURE) as npz:
        frames = npz["store_obs"]
    if how == "reshard_to_sharded":
        fresh = ShardedDeviceReplay(cfg, make_mesh(dp=1, tp=1, devices=jax.devices()[:1]))
    else:
        fresh = DeviceReplayBuffer(cfg)
    restore_replay(fresh, FIXTURE) if how == "restore" else reshard_replay(fresh, [FIXTURE])
    held = np.asarray(fresh.stores["obs"])
    np.testing.assert_array_equal(held, frames_to_rows(frames, cfg.obs_shape, 4))
    assert (held != frames_to_rows(frames, cfg.obs_shape)).any()  # not the frames as they are
    np.testing.assert_array_equal(rows_to_frames(held, cfg.obs_shape, 4), frames)
    # one 4x4 block of frame (0, 0) is 16 consecutive stored bytes
    np.testing.assert_array_equal(held[0, 0].reshape(-1)[16:32], frames[0, 0, 0:4, 4:8, 0].reshape(-1))
    path = str(tmp_path / "snap.npz")
    save_replay(fresh, path)
    with np.load(FIXTURE) as old, np.load(path) as new:
        for k in old.files:
            if k.startswith("store_"):
                assert old[k].dtype == new[k].dtype and old[k].shape == new[k].shape, k
                np.testing.assert_array_equal(old[k], new[k], err_msg=k)
