"""Elastic resume: topology manifests, structured mismatch reporting, and
reshard_replay across every plane-family move the scheduler can force —
sharded->device, device->sharded at a different dp, device->host (dtype
cast across the family boundary), and the exact path, which must be
indistinguishable from a plain restore_replay."""

import json
import os

import jax
import numpy as np
import pytest

from synth import synth_block
from r2d2_tpu.config import tiny_test
from r2d2_tpu.parallel.mesh import make_mesh, slab_partition_map
from r2d2_tpu.replay.device_store import DeviceReplayBuffer
from r2d2_tpu.replay.replay_buffer import ReplayBuffer
from r2d2_tpu.replay.reshard import (
    gather_logical,
    main as reshard_main,
    reshard_replay,
    snapshot_paths,
)
from r2d2_tpu.replay.sharded_store import ShardedDeviceReplay
from r2d2_tpu.replay.snapshot import (
    TopologyMismatch,
    read_manifest,
    restore_replay,
    save_replay,
    snapshot_topology,
)
from r2d2_tpu.utils.faults import FaultPlane, InjectedFault, install, uninstall

NB = 40  # tiny_test: buffer_capacity 640 / block_length 16


def _fill(cfg, replay, n=10, seed=0):
    rng = np.random.default_rng(seed)
    for i in range(n):
        block = synth_block(cfg, rng)
        prios = rng.random(cfg.seqs_per_block).astype(np.float32) + 0.5
        replay.add_block(block, prios, float(i) if i % 3 == 0 else None)


def _fingerprint(replay):
    """Layout-independent content fingerprint: global counters, total tree
    mass, and the multiset of per-occupied-block obs sums."""
    if isinstance(replay, ShardedDeviceReplay):
        obs = np.asarray(replay.stores["obs"])
        bps = replay.blocks_per_shard
        sums, mass = [], 0.0
        for i, p in enumerate(replay.shards):
            mass += float(p.tree.leaves().sum())
            sums += [
                int(obs[i * bps + s].astype(np.int64).sum())
                for s in range(bps)
                if p.occupied[s]
            ]
        return (
            sum(p.env_steps for p in replay.shards),
            sum(p.size for p in replay.shards),
            sum(p.num_episodes for p in replay.shards),
            round(sum(float(p.episode_reward_sum) for p in replay.shards), 4),
            round(mass, 4),
            sorted(sums),
        )
    if isinstance(replay, DeviceReplayBuffer):
        obs = np.asarray(replay.stores["obs"])
    else:
        obs = np.asarray(replay.obs_store)
    sums = [
        int(obs[s].astype(np.int64).sum()) for s in range(NB) if replay.occupied[s]
    ]
    return (
        replay.env_steps,
        replay.size,
        replay.num_episodes,
        round(float(replay.episode_reward_sum), 4),
        round(float(replay.tree.leaves().sum()), 4),
        sorted(sums),
    )


@pytest.fixture(scope="module")
def saved_sharded(tmp_path_factory):
    """A filled sharded dp=4 replay snapshotted to disk, plus its
    fingerprint — the source for every cross-topology move below."""
    cfg = tiny_test()
    mesh = make_mesh(dp=4, tp=1, devices=jax.devices()[:4])
    replay = ShardedDeviceReplay(cfg, mesh)
    _fill(cfg, replay)
    d = tmp_path_factory.mktemp("sharded4")
    save_replay(
        replay,
        str(d / "replay_snapshot.npz"),
        extra={"carry_step": np.int64(7), "pend_idxes": np.arange(3)},
    )
    return cfg, str(d), _fingerprint(replay)


def test_manifest_contents(saved_sharded):
    cfg, d, _ = saved_sharded
    m = read_manifest(os.path.join(d, "replay_snapshot.npz"))
    assert m["plane"] == "sharded"
    assert m["dp"] == 4 and m["tp"] == 1 and m["process_count"] == 1
    assert m["num_blocks"] == NB and m["blocks_per_shard"] == NB // 4
    assert m["seqs_per_block"] == cfg.seqs_per_block
    assert m["local_ids"] == [0, 1, 2, 3]
    assert m["slab_ranges"] == [[g * 10, (g + 1) * 10] for g in range(4)]
    assert m["rng_streams"] == [0, 1, 2, 3]
    # the partition map helper agrees with what the manifest recorded
    mesh = make_mesh(dp=4, tp=1, devices=jax.devices()[:4])
    pmap = slab_partition_map(mesh, NB)
    assert m["slab_ranges"] == [list(pmap[g]) for g in range(4)]


def test_topology_mismatch_is_structured(saved_sharded):
    cfg, d, _ = saved_sharded
    dev = DeviceReplayBuffer(cfg)
    with pytest.raises(TopologyMismatch) as ei:
        restore_replay(dev, os.path.join(d, "replay_snapshot.npz"))
    e = ei.value
    assert isinstance(e, ValueError)  # callers catching ValueError still work
    assert e.saved["plane"] == "sharded" and e.saved["dp"] == 4
    assert e.current["plane"] == "device" and e.current["dp"] == 1
    assert "--reshard" in str(e)
    for frag in ("dp=4", "dp=1", "process_count=1"):
        assert frag in str(e)


def test_sharded_dp_mismatch_is_structured(saved_sharded):
    cfg, d, _ = saved_sharded
    mesh2 = make_mesh(dp=2, tp=1, devices=jax.devices()[:2])
    sh2 = ShardedDeviceReplay(cfg, mesh2)
    with pytest.raises(TopologyMismatch) as ei:
        restore_replay(sh2, os.path.join(d, "replay_snapshot.npz"))
    assert ei.value.saved["dp"] == 4 and ei.value.current["dp"] == 2


def test_reshard_sharded_to_device(saved_sharded):
    cfg, d, fp = saved_sharded
    dev = DeviceReplayBuffer(cfg)
    extras = reshard_replay(dev, snapshot_paths(d))
    assert _fingerprint(dev) == fp
    # layout-free carry survives, layout-bound (pend_*) is dropped
    assert int(extras["carry_step"]) == 7
    assert not any(k.startswith("pend_") for k in extras)
    # the re-dealt buffer samples
    dev.sample_indices(np.random.default_rng(0))


def test_reshard_device_to_sharded_dp2(saved_sharded, tmp_path):
    cfg, d, fp = saved_sharded
    dev = DeviceReplayBuffer(cfg)
    reshard_replay(dev, snapshot_paths(d))
    save_replay(dev, str(tmp_path / "replay_snapshot.npz"))
    mesh2 = make_mesh(dp=2, tp=1, devices=jax.devices()[:2])
    sh2 = ShardedDeviceReplay(cfg, mesh2)
    reshard_replay(sh2, snapshot_paths(str(tmp_path)))
    assert _fingerprint(sh2) == fp
    sh2.sample_indices(np.random.default_rng(0))


def test_reshard_device_to_host_casts_actions(saved_sharded, tmp_path):
    cfg, d, fp = saved_sharded
    dev = DeviceReplayBuffer(cfg)
    reshard_replay(dev, snapshot_paths(d))
    save_replay(dev, str(tmp_path / "replay_snapshot.npz"))
    host = ReplayBuffer(cfg)
    reshard_replay(host, snapshot_paths(str(tmp_path)))
    assert _fingerprint(host) == fp
    # device stores actions as int32; the host plane keeps uint8
    assert host.action_store.dtype == np.uint8
    assert host.last_action_store.dtype == np.uint8
    np.testing.assert_array_equal(
        np.asarray(host.action_store), np.asarray(dev.stores["action"])
    )


def test_exact_path_matches_plain_restore(saved_sharded):
    """Same logical shard set => reshard is bit-identical to restore: the
    sampling stream (and hence the learner loss) cannot tell them apart."""
    cfg, d, _ = saved_sharded
    path = os.path.join(d, "replay_snapshot.npz")
    mesh = make_mesh(dp=4, tp=1, devices=jax.devices()[:4])
    a = ShardedDeviceReplay(cfg, mesh)
    reshard_replay(a, [path])
    b = ShardedDeviceReplay(cfg, mesh)
    restore_replay(b, path)
    for k in a.stores:
        np.testing.assert_array_equal(np.asarray(a.stores[k]), np.asarray(b.stores[k]))
    for pa, pb in zip(a.shards, b.shards):
        np.testing.assert_array_equal(pa.tree.leaves(), pb.tree.leaves())
        assert pa.block_ptr == pb.block_ptr and pa.ptr_advances == pb.ptr_advances
    ra = a.sample_indices(np.random.default_rng(5))
    rb = b.sample_indices(np.random.default_rng(5))
    np.testing.assert_array_equal(np.asarray(ra.idxes), np.asarray(rb.idxes))
    np.testing.assert_allclose(np.asarray(ra.is_weights), np.asarray(rb.is_weights))


def test_gather_is_retry_safe(saved_sharded):
    """A crash mid-gather leaves the files untouched; the retry gathers the
    same logical state."""
    cfg, d, fp = saved_sharded
    plane = install(FaultPlane(schedule={"reshard.gather": {1: "error"}}))
    try:
        dev = DeviceReplayBuffer(cfg)
        with pytest.raises(InjectedFault):
            reshard_replay(dev, snapshot_paths(d))
        # nothing was mutated before the gather fault
        assert dev.size == 0 and not dev.occupied.any()
        reshard_replay(dev, snapshot_paths(d))  # call 2: passes through
        assert _fingerprint(dev) == fp
    finally:
        uninstall()
    assert ("reshard.gather", 1, "error") in plane.fired


def test_manifest_cli(saved_sharded, tmp_path, capsys):
    cfg, d, _ = saved_sharded
    assert reshard_main([d]) == 0
    out = json.loads(capsys.readouterr().out)
    (m,) = out["manifests"].values()
    assert m["plane"] == "sharded" and m["dp"] == 4
    assert reshard_main([d, "--expect-dp", "4", "--expect-process-count", "1"]) == 0
    capsys.readouterr()
    assert reshard_main([d, "--expect-dp", "2"]) == 2
    err = capsys.readouterr().err
    assert "dp=4" in err and "expected 2" in err
    # empty dir: nothing to assert, resume refills from scratch
    assert reshard_main([str(tmp_path), "--expect-dp", "8"]) == 0


def test_gather_rejects_duplicate_shards(saved_sharded):
    cfg, d, _ = saved_sharded
    path = os.path.join(d, "replay_snapshot.npz")
    with pytest.raises(ValueError, match="more than one"):
        gather_logical([path, path])


def test_capacity_shrink_drops_oldest(saved_sharded, tmp_path):
    """Re-deal into a smaller buffer keeps the newest blocks — the same
    eviction order a live run would have applied."""
    cfg, d, fp = saved_sharded
    import dataclasses

    small = dataclasses.replace(cfg, buffer_capacity=cfg.block_length * 8)
    dev = DeviceReplayBuffer(small)
    reshard_replay(dev, snapshot_paths(d))
    assert int(dev.occupied.sum()) == 8  # 10 saved, capacity 8
    # global totals still preserved exactly
    assert dev.env_steps == fp[0]
    assert dev.num_episodes == fp[2]
