"""dp-sharded device replay + shard_map fused train step on the 8-fake-
device CPU mesh (SURVEY.md section 4: distributed-without-a-cluster).

The load-bearing test is numerical parity: the sharded path (local gathers
per shard + explicit lax.pmean over dp) must produce the SAME loss,
priorities, and updated params as the single-device fused/host path run on
the equivalently assembled global batch.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from r2d2_tpu.config import R2D2Config, tiny_test
from r2d2_tpu.learner import (
    DeviceBatch,
    init_train_state,
    make_sharded_fused_multi_train_step,
    make_train_step,
)
from r2d2_tpu.parallel.mesh import make_mesh
from r2d2_tpu.replay.block import rows_to_frames
from r2d2_tpu.replay.sharded_store import ShardedDeviceReplay
from tests.test_replay_buffer import make_block


def sharded_cfg(**kw):
    base = dict(
        obs_shape=(3, 3, 1),
        action_dim=3,
        hidden_dim=4,  # make_block builds (2, 4) hidden states
        encoder="mlp",
        burn_in_steps=4,
        learning_steps=4,
        forward_steps=2,
        block_length=12,
        buffer_capacity=12 * 16,  # 16 blocks -> 2 per shard at dp=8
        learning_starts=24,
        batch_size=16,  # 2 sequences per shard
        use_native_replay=False,
    )
    base.update(kw)
    return R2D2Config(**base).validate()


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) >= 8, "conftest must force 8 fake devices"
    return make_mesh(dp=8, tp=1, devices=jax.devices()[:8])


def fill(replay, cfg, n_blocks=12):
    for i in range(n_blocks):
        block, prios, ep = make_block(
            cfg, steps=[12, 7, 12, 5][i % 4], start_step=17 * i,
            terminal=(i % 3 == 2), seed=100 + i,
        )
        replay.add_block(block, prios, ep)


def _one_update(cfg, net, mesh):
    """The sharded step at num_steps=1 over one draw: (dp, B/dp) coordinates
    in, (dp, B/dp) priorities out."""
    step = make_sharded_fused_multi_train_step(cfg, net, mesh, 1, donate=False)

    def one(state, stores, b, s, w):
        state, metrics, priorities = step(state, stores, b[None], s[None], w[None])
        return state, metrics, priorities[0]

    return one


def test_round_robin_and_accounting(mesh):
    cfg = sharded_cfg()
    replay = ShardedDeviceReplay(cfg, mesh)
    fill(replay, cfg, n_blocks=9)
    # 9 blocks round-robin over 8 shards: shard 0 got 2, others 1
    assert replay.shards[0].occupied.sum() == 2
    assert all(s.occupied.sum() == 1 for s in replay.shards[1:])
    assert len(replay) == sum(int(s.learning_sum.sum()) for s in replay.shards)


def test_sample_weights_match_global_min_semantics(mesh):
    cfg = sharded_cfg()
    replay = ShardedDeviceReplay(cfg, mesh)
    fill(replay, cfg)
    si = replay.sample_indices(np.random.default_rng(0))
    assert si.b.shape == (8, 2)
    # recompute weights from raw tree priorities with the batch-global min
    p = np.stack([
        shard.tree.priorities_of(idx_row)
        for shard, idx_row in zip(replay.shards, si.idxes)
    ])
    pos = p[p > 0]
    w = np.power(np.maximum(p, pos.min()) / pos.min(), -cfg.is_exponent)
    np.testing.assert_allclose(si.is_weights, w.astype(np.float32), rtol=1e-6)
    assert si.is_weights.max() == pytest.approx(1.0)


def test_sharded_step_matches_single_device(mesh):
    cfg = sharded_cfg()
    replay = ShardedDeviceReplay(cfg, mesh)
    fill(replay, cfg)

    net, state0 = init_train_state(cfg, jax.random.PRNGKey(3))
    sharded_step = _one_update(cfg, net, mesh)
    si = replay.sample_indices(np.random.default_rng(1))

    new_state, metrics, prio_sharded = replay.run_with_stores(
        lambda stores: sharded_step(
            state0, stores, jnp.asarray(si.b), jnp.asarray(si.s), jnp.asarray(si.is_weights)
        )
    )
    assert np.isfinite(float(metrics["loss"]))
    assert prio_sharded.shape == (8, 2)

    # --- reference: assemble the SAME batch on host from the global stores
    host = {k: np.asarray(v) for k, v in replay.stores.items()}
    L, T = cfg.learning_steps, cfg.seq_len
    gb = (np.arange(8)[:, None] * replay.blocks_per_shard + si.b).reshape(-1)
    s = si.s.reshape(-1)
    burn = host["burn_in"][gb, s]
    first_burn = host["burn_in"][gb, 0]
    start = first_burn + s * L
    rows = np.clip((start - burn)[:, None] + np.arange(T)[None, :], 0, cfg.block_slot_len - 1)
    lrow = s[:, None] * L + np.arange(L)[None, :]
    batch = DeviceBatch(
        obs=jnp.asarray(rows_to_frames(host["obs"][gb[:, None], rows], cfg.obs_shape)),
        last_action=jnp.asarray(host["last_action"][gb[:, None], rows]),
        last_reward=jnp.asarray(host["last_reward"][gb[:, None], rows]),
        hidden=jnp.asarray(host["hidden"][gb, s]),
        action=jnp.asarray(host["action"][gb[:, None], lrow]),
        n_step_reward=jnp.asarray(host["n_step_reward"][gb[:, None], lrow]),
        gamma=jnp.asarray(host["gamma"][gb[:, None], lrow]),
        burn_in_steps=jnp.asarray(burn),
        learning_steps=jnp.asarray(host["learning"][gb, s]),
        forward_steps=jnp.asarray(host["forward"][gb, s]),
        is_weights=jnp.asarray(si.is_weights.reshape(-1)),
    )
    ref_step = make_train_step(cfg, net, donate=False)
    ref_state, ref_metrics, ref_prio = ref_step(state0, batch)

    np.testing.assert_allclose(
        float(metrics["loss"]), float(ref_metrics["loss"]), rtol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(prio_sharded).reshape(-1), np.asarray(ref_prio), rtol=1e-5
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-6
        ),
        new_state.params,
        ref_state.params,
    )


def test_priority_roundtrip_per_shard_staleness(mesh):
    cfg = sharded_cfg()
    replay = ShardedDeviceReplay(cfg, mesh)
    fill(replay, cfg)
    si = replay.sample_indices(np.random.default_rng(2))
    before = [s.tree.total for s in replay.shards]
    # overwrite shard 0's next slot so its sampled idxes go stale
    block, prios, ep = make_block(cfg, steps=12, seed=999)
    for _ in range(replay.dp):  # one full round-robin lap -> shard 0 written
        replay.add_block(block, prios, ep)
    tds = np.full((8, 2), 7.7, np.float32)
    replay.update_priorities(si.idxes, tds, si.old_ptr)
    # every shard's tree changed (fresh priorities) but totals stay finite
    after = [s.tree.total for s in replay.shards]
    assert all(np.isfinite(a) for a in after)
    assert after != before


def _stack_block_fields(cfg, blocks):
    """Pad each block to store-slot shape and stack to (E, ...) device
    arrays — the collector's add_blocks_batch packing, shared by the
    batched-path tests."""
    from r2d2_tpu.replay.device_store import DeviceReplayBuffer

    padded = [DeviceReplayBuffer.pad_block_fields(cfg, blk) for blk in blocks]
    return {k: jnp.stack([jnp.asarray(p[k]) for p in padded]) for k in padded[0]}


def test_sharded_add_blocks_batch_matches_sequential():
    """The collector's batched scatter lands blocks in the same slots with
    the same accounting as E sequential add_block calls."""
    from synth import synth_block

    dp = 4
    mesh = make_mesh(dp=dp, tp=1, devices=jax.devices()[:dp])
    cfg = tiny_test().replace(dp_size=dp, replay_plane="sharded", batch_size=8)
    a = ShardedDeviceReplay(cfg, mesh)
    b = ShardedDeviceReplay(cfg, mesh)

    rng = np.random.default_rng(0)
    E = 6  # not a multiple of dp: exercises two blocks on some shards
    blocks = [synth_block(cfg, rng) for _ in range(E)]
    prios = rng.uniform(0.5, 2.0, (E, cfg.seqs_per_block)).astype(np.float32)
    rewards = rng.normal(size=E)
    dones = np.asarray([True, False, True, True, False, True])

    for blk, p, r, d in zip(blocks, prios, rewards, dones):
        a.add_block(blk, p, float(r) if d else None)

    fields = _stack_block_fields(cfg, blocks)
    b.add_blocks_batch(
        fields,
        np.asarray([blk.num_sequences for blk in blocks]),
        np.asarray([blk.learning_steps.sum() for blk in blocks]),
        prios,
        rewards,
        dones,
    )

    assert len(a) == len(b) and a.env_steps == b.env_steps
    assert a.episode_totals() == b.episode_totals()
    assert a._rr == b._rr
    for sa, sb in zip(a.shards, b.shards):
        assert sa.block_ptr == sb.block_ptr
        np.testing.assert_allclose(sa.tree.tree, sb.tree.tree, rtol=1e-12)
    for k in a.stores:
        np.testing.assert_array_equal(np.asarray(a.stores[k]), np.asarray(b.stores[k]))


def test_sharded_add_blocks_batch_post_wrap_tail_retirement():
    """AFTER a shard's local ring wraps, the batched path deliberately
    diverges from sequential add_block: _reserve_contiguous retires the
    ring tail so each slab stays contiguous (zeroed priorities, size
    deducted, slots freed), where the sequential path would wrap slot by
    slot without retiring. This pins the documented intended divergence
    (the add_blocks_batch docstring) instead of leaving it folklore."""
    from synth import synth_block

    dp = 2
    mesh = make_mesh(dp=dp, tp=1, devices=jax.devices()[:dp])
    # 640 capacity / 16 block = 40 slots -> 20 per shard
    cfg = tiny_test().replace(dp_size=dp, replay_plane="sharded", batch_size=8)
    sh = ShardedDeviceReplay(cfg, mesh)
    bps = sh.blocks_per_shard
    rng = np.random.default_rng(3)
    S = cfg.seqs_per_block

    def batch(n):
        blocks = [synth_block(cfg, rng) for _ in range(n)]
        fields = _stack_block_fields(cfg, blocks)
        prios = rng.uniform(0.5, 2.0, (n, S)).astype(np.float32)
        return fields, prios

    per = 3
    n = per * dp
    steps_per_block = cfg.block_length
    # lap 1: batches to slot 18 per shard, then SEQUENTIAL adds fill the
    # 2-slot tail (the sequential path has no contiguity constraint) —
    # every slot occupied, pointers wrapped to 0
    filled = 0
    while filled + per <= bps - 1:
        fields, prios = batch(n)
        sh.add_blocks_batch(
            fields, np.full(n, S), np.full(n, steps_per_block), prios,
            np.zeros(n), np.zeros(n, bool),
        )
        filled += per
    tail = bps - filled  # stranded tail per shard if only batches wrote
    assert 0 < tail < per
    for _ in range(dp * tail):
        sh.add_block(
            synth_block(cfg, rng),
            rng.uniform(0.5, 2.0, S).astype(np.float32), None,
        )
    assert all(s.block_ptr == 0 and s.occupied.all() for s in sh.shards)

    # lap 2: batches march back to slot 18 over the full ring
    for k in range(filled // per):
        fields, prios = batch(n)
        sh.add_blocks_batch(
            fields, np.full(n, S), np.full(n, steps_per_block), prios,
            np.zeros(n), np.zeros(n, bool),
        )
    size_before = len(sh)
    assert size_before == dp * bps * steps_per_block  # ring full
    assert all(s.block_ptr == filled for s in sh.shards)

    # this batch cannot fit the OCCUPIED tail: each shard wraps, RETIRES
    # the tail (sequential add_block would instead wrap slot by slot —
    # the documented intended divergence), and overwrites slots [0, per)
    fields, prios = batch(n)
    sh.add_blocks_batch(
        fields, np.full(n, S), np.full(n, steps_per_block), prios,
        np.zeros(n), np.zeros(n, bool),
    )
    for s in sh.shards:
        assert s.block_ptr == per  # wrapped to 0, wrote per blocks
        tail_slots = np.arange(filled, bps)
        assert not s.occupied[tail_slots].any()
        leaves = s.tree.priorities_of(
            (tail_slots[:, None] * S + np.arange(S)).ravel()
        )
        np.testing.assert_array_equal(leaves, 0.0)
    # net: the n new blocks evict n occupied slots (wash) and the
    # retirement removes dp*tail occupied blocks outright
    assert len(sh) == size_before - dp * tail * steps_per_block


def test_sharded_step_tp2_matches_single_device():
    """dp=4 x tp=2 on the 8-device mesh: the shard_map step is manual over
    dp ONLY (axis_names={"dp"}), the tp axis stays GSPMD-auto, and the
    Megatron param shardings (parallel/mesh.train_state_shardings)
    partition the per-dp-shard update body over tp. Loss, priorities, and
    the updated params must match the single-device step on the
    equivalently assembled global batch, and the updated params must
    RETAIN their tp shardings (real dpxtp composition, not replication)."""
    from r2d2_tpu.parallel.mesh import train_state_shardings

    cfg = sharded_cfg(dp_size=4, tp_size=2, replay_plane="sharded")
    mesh = make_mesh(dp=4, tp=2, devices=jax.devices()[:8])
    replay = ShardedDeviceReplay(cfg, mesh)
    fill(replay, cfg)

    net, state0 = init_train_state(cfg, jax.random.PRNGKey(3))
    state_tp = jax.device_put(state0, train_state_shardings(state0, mesh))
    sharded_step = _one_update(cfg, net, mesh)
    si = replay.sample_indices(np.random.default_rng(1))

    new_state, metrics, prio_sharded = replay.run_with_stores(
        lambda stores: sharded_step(
            state_tp, stores, jnp.asarray(si.b), jnp.asarray(si.s),
            jnp.asarray(si.is_weights),
        )
    )
    assert prio_sharded.shape == (4, 4)

    # reference: the SAME batch assembled on host, single-device step
    host = {k: np.asarray(v) for k, v in replay.stores.items()}
    L, T = cfg.learning_steps, cfg.seq_len
    gb = (np.arange(4)[:, None] * replay.blocks_per_shard + si.b).reshape(-1)
    s = si.s.reshape(-1)
    burn = host["burn_in"][gb, s]
    first_burn = host["burn_in"][gb, 0]
    start = first_burn + s * L
    rows = np.clip(
        (start - burn)[:, None] + np.arange(T)[None, :], 0, cfg.block_slot_len - 1
    )
    lrow = s[:, None] * L + np.arange(L)[None, :]
    batch = DeviceBatch(
        obs=jnp.asarray(rows_to_frames(host["obs"][gb[:, None], rows], cfg.obs_shape)),
        last_action=jnp.asarray(host["last_action"][gb[:, None], rows]),
        last_reward=jnp.asarray(host["last_reward"][gb[:, None], rows]),
        hidden=jnp.asarray(host["hidden"][gb, s]),
        action=jnp.asarray(host["action"][gb[:, None], lrow]),
        n_step_reward=jnp.asarray(host["n_step_reward"][gb[:, None], lrow]),
        gamma=jnp.asarray(host["gamma"][gb[:, None], lrow]),
        burn_in_steps=jnp.asarray(burn),
        learning_steps=jnp.asarray(host["learning"][gb, s]),
        forward_steps=jnp.asarray(host["forward"][gb, s]),
        is_weights=jnp.asarray(si.is_weights.reshape(-1)),
    )
    ref_step = make_train_step(cfg, net, donate=False)
    ref_state, ref_metrics, ref_prio = ref_step(state0, batch)

    np.testing.assert_allclose(
        float(metrics["loss"]), float(ref_metrics["loss"]), rtol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(prio_sharded).reshape(-1), np.asarray(ref_prio), rtol=2e-4
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
        ),
        new_state.params,
        ref_state.params,
    )
    # the tp shardings survive the update (donated in, sharded out);
    # core-agnostic probe (LSTM wi when present, encoder Dense_0 under lru)
    from r2d2_tpu.parallel.mesh import tp_probe_kernel

    wi = tp_probe_kernel(new_state.params)
    assert wi.sharding.spec[-1] == "tp"
