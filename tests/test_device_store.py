"""DeviceReplayBuffer + fused train step: must be numerically equivalent to
the host-assembled path on identical data and sampling streams."""

import jax
import numpy as np
import pytest

from r2d2_tpu.config import tiny_test
from r2d2_tpu.learner import DeviceBatch, init_train_state, make_fused_multi_train_step, make_train_step
from r2d2_tpu.replay.device_store import DeviceReplayBuffer
from r2d2_tpu.replay.replay_buffer import ReplayBuffer
from tests.test_replay_buffer import make_block, small_cfg


@pytest.fixture(scope="module")
def both_buffers():
    cfg = small_cfg(batch_size=6, hidden_dim=4)
    host = ReplayBuffer(cfg)
    dev = DeviceReplayBuffer(cfg)
    for k in range(4):
        block, prios, ep = make_block(cfg, seed=k, terminal=(k % 2 == 0))
        host.add_block(block, prios, ep)
        dev.add_block(block, prios, ep)
    return cfg, host, dev


def test_same_sampling_stream(both_buffers):
    cfg, host, dev = both_buffers
    hb = host.sample_batch(np.random.default_rng(7))
    di = dev.sample_indices(np.random.default_rng(7))
    np.testing.assert_array_equal(hb.idxes, di.idxes)
    np.testing.assert_allclose(hb.is_weights, di.is_weights, rtol=1e-6)
    assert hb.old_ptr == di.old_ptr
    assert hb.env_steps == di.env_steps


def _host_and_device_filled(cfg):
    """A host buffer and a device store holding the same episodes (ragged
    lengths, so windows meet the clip), block for block."""
    from r2d2_tpu.replay.accumulator import SequenceAccumulator

    host = ReplayBuffer(cfg)
    dev = DeviceReplayBuffer(cfg)
    rng = np.random.default_rng(0)
    acc = SequenceAccumulator(cfg)
    for ep in range(12):
        acc.reset(rng.integers(0, 255, size=cfg.obs_shape, dtype=np.uint8))
        n = int(rng.integers(5, 30))
        for t in range(n):
            acc.add(
                int(rng.integers(cfg.action_dim)),
                float(rng.normal()),
                rng.integers(0, 255, size=cfg.obs_shape, dtype=np.uint8),
                rng.normal(size=cfg.action_dim).astype(np.float32),
                rng.normal(size=(2, cfg.hidden_dim)).astype(np.float32),
            )
            if len(acc) == cfg.block_length or t == n - 1:
                block, prios, r = acc.finish(
                    None if t == n - 1 else rng.normal(size=cfg.action_dim).astype(np.float32)
                )
                host.add_block(block, prios, r)
                dev.add_block(block, prios, r)
    return host, dev


def test_fused_step_matches_host_step():
    cfg = tiny_test()
    host, dev = _host_and_device_filled(cfg)

    net, state0 = init_train_state(cfg, jax.random.PRNGKey(0))
    host_step = make_train_step(cfg, net, donate=False)
    fused_step = make_fused_multi_train_step(cfg, net, 1, donate=False)

    hb = host.sample_batch(np.random.default_rng(3))
    di = dev.sample_indices(np.random.default_rng(3))
    np.testing.assert_array_equal(hb.idxes, di.idxes)

    s_host, m_host, p_host = host_step(state0, DeviceBatch.from_sampled(hb))
    s_dev, m_dev, p_dev = fused_step(
        state0, dev.stores, np.asarray(di.b)[None], np.asarray(di.s)[None], np.asarray(di.is_weights)[None]
    )

    np.testing.assert_allclose(float(m_host["loss"]), float(m_dev["loss"]), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(p_host), np.asarray(p_dev)[0], rtol=1e-4, atol=1e-6)
    for a, b in zip(jax.tree.leaves(s_host.params), jax.tree.leaves(s_dev.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7)


def test_device_store_eviction_and_staleness(both_buffers):
    cfg, host, dev = both_buffers
    assert len(dev) == len(host)
    di = dev.sample_indices(np.random.default_rng(1))
    old_ptr = di.old_ptr
    for k in range(2):
        block, prios, ep = make_block(cfg, seed=20 + k)
        dev.add_block(block, prios, ep)
    before = dev.tree.priorities_of(np.arange(12)).copy()
    dev.update_priorities(np.arange(12, dtype=np.int64), np.full(12, 9.0), old_ptr)
    after = dev.tree.priorities_of(np.arange(12))
    np.testing.assert_allclose(after[:6], before[:6])  # overwritten slots masked
    np.testing.assert_allclose(after[6:], 9.0**cfg.prio_exponent)


@pytest.mark.parametrize("K", [1, 4])
def test_multi_step_matches_sequential_fused(K):
    """The one update on an HBM store, K updates over pre-drawn coordinates in
    one dispatch, against the independent reference: K sequential
    `make_train_step` calls on the host-assembled batches of the same draws.
    Same final params and targets (a sync falls mid-chunk), same priorities
    row for row, held to what `test_fused_step_matches_host_step` holds one
    update to."""
    cfg = tiny_test().replace(target_net_update_interval=2)
    host, dev = _host_and_device_filled(cfg)
    net, state0 = init_train_state(cfg, jax.random.PRNGKey(0))

    hbs = [host.sample_batch(np.random.default_rng(i)) for i in range(K)]
    draws = [dev.sample_indices(np.random.default_rng(i)) for i in range(K)]
    for hb, di in zip(hbs, draws):
        np.testing.assert_array_equal(hb.idxes, di.idxes)

    host_step = make_train_step(cfg, net, donate=False)
    state, prios_seq = state0, []
    for hb in hbs:
        state, m, p = host_step(state, DeviceBatch.from_sampled(hb))
        prios_seq.append(np.asarray(p))

    multi = make_fused_multi_train_step(cfg, net, K, donate=False)
    b, s, w = (np.stack([getattr(di, f) for di in draws]) for f in ("b", "s", "is_weights"))
    state_m, m_m, p_m = dev.run_with_stores(lambda stores: multi(state0, stores, b, s, w))

    assert int(state_m.step) == int(state.step) == K
    np.testing.assert_allclose(float(m_m["loss"]), float(m["loss"]), rtol=1e-5)
    for got, want in ((state_m.params, state.params), (state_m.target_params, state.target_params)):
        for a, bb in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(bb), rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(p_m), np.stack(prios_seq), rtol=1e-4, atol=1e-6)


# --------------------------------------------------------------------------
# The obs store's row format (replay/block.frames_to_rows): each frame is its
# bytes, zero-padded to a multiple of 128, as (R, 128). Writers and readers
# share ONE pair of helpers; nothing downstream of a batch sees rows.


@pytest.mark.parametrize("backend", ["numpy", "jax"])
@pytest.mark.parametrize("encoder", ["mlp", "nature"], ids=["block-of-mlp", "block-of-nature"])
@pytest.mark.parametrize(
    "obs_shape, rows",
    [((84, 84, 1), 56), ((16, 8), 1), ((50,), 1), ((12, 12, 1), 2), ((128, 2), 2)],
    ids=["nature-7056B", "exactly-128B", "vector-50B", "tiny-144B", "exactly-256B"],
)
def test_frames_to_rows_and_back_is_the_identity(obs_shape, rows, encoder, backend):
    """With the block the encoder publishes for the shape (PR 38: the Nature
    trunk's 4 where it divides an (H, W, C) frame's sides, else 1) the rows
    hold the frame's bytes in 4x4 blocks; with block 1, in order."""
    from r2d2_tpu.models.encoders import frame_block
    from r2d2_tpu.replay.block import LANES, frames_to_rows, obs_rows, rows_as_stored, rows_to_frames

    block = frame_block(encoder, obs_shape)
    assert block == (4 if encoder == "nature" and obs_shape in ((84, 84, 1), (12, 12, 1)) else 1)
    frames = np.random.default_rng(0).integers(1, 256, (3, 5, *obs_shape), dtype=np.uint8)
    given = frames if backend == "numpy" else jax.numpy.asarray(frames)
    packed = frames_to_rows(given, obs_shape, block)
    assert isinstance(packed, np.ndarray) == (backend == "numpy")
    assert obs_rows(obs_shape) == rows and packed.shape == (3, 5, rows, LANES) and packed.dtype == np.uint8
    flat = np.asarray(packed).reshape(3, 5, -1)
    n = int(np.prod(obs_shape))
    stored = frames
    if block > 1:  # (H/s, s, W/s, s, C) -> (H/s, W/s, s, s, C)
        H, W, C = obs_shape
        stored = frames.reshape(3, 5, H // block, block, W // block, block, C).transpose(0, 1, 2, 4, 3, 5, 6)
        assert (stored.reshape(3, 5, n) != frames.reshape(3, 5, n)).any()
        # byte (dy*s + dx)*C + c of block (i, j) is pixel (i*s + dy, j*s + dx, c)
        assert flat[1, 2, (2 * (W // block) + 1) * block * block * C + (3 * block + 2) * C] == frames[1, 2, 2 * block + 3, block + 2, 0]
    np.testing.assert_array_equal(flat[..., :n], stored.reshape(3, 5, n))  # the frame's bytes, in the block's order
    assert not flat[..., n:].any()                                         # the tail is zero
    np.testing.assert_array_equal(np.asarray(rows_to_frames(packed, obs_shape, block)), frames)
    as_stored = np.asarray(rows_as_stored(packed, obs_shape, block))
    assert as_stored.shape == ((3, 5, H // block, W // block, block * block * C) if block > 1 else frames.shape)
    np.testing.assert_array_equal(as_stored.reshape(3, 5, n), flat[..., :n])
    with pytest.raises(ValueError):
        frames_to_rows(given[..., :-1], obs_shape, block)
    with pytest.raises(ValueError):
        rows_to_frames(packed[..., :-1, :], obs_shape, block)


@pytest.mark.parametrize("obs_shape", [(3, 3, 1), (16, 8), (50,)], ids=["image", "exactly-128B", "vector"])
def test_gather_on_a_row_store_equals_the_frame_gather(obs_shape):
    """`gather_batch` reads rows; its batch carries the frames the parent's
    gather (`frames[bcol, rows]`, kept here on the host buffer's frame store)
    takes from the same blocks, bit for bit."""
    from r2d2_tpu.learner import make_store_gather
    from r2d2_tpu.replay.accumulator import SequenceAccumulator
    from r2d2_tpu.replay.block import store_field_specs

    cfg = small_cfg(obs_shape=obs_shape, batch_size=6)
    host, dev = ReplayBuffer(cfg), DeviceReplayBuffer(cfg)
    rng = np.random.default_rng(3)
    for k in range(4):
        acc = SequenceAccumulator(cfg)
        acc.reset(rng.integers(1, 256, obs_shape, dtype=np.uint8))
        for t in range(cfg.block_length - (k % 2)):
            acc.add(int(rng.integers(cfg.action_dim)), float(rng.normal()), rng.integers(1, 256, obs_shape, dtype=np.uint8),
                    rng.normal(size=cfg.action_dim).astype(np.float32), rng.normal(size=(2, cfg.hidden_dim)).astype(np.float32))
        block, prios, ep = acc.finish(rng.normal(size=cfg.action_dim).astype(np.float32))
        host.add_block(block, prios, ep)
        dev.add_block(block, prios, ep)
    assert dev.stores["obs"].shape == (cfg.num_blocks, *store_field_specs(cfg)["obs"][0])
    assert dev.stores["obs"].shape[-1] == 128 and dev.stores["obs"].dtype == np.uint8

    si = dev.sample_indices(np.random.default_rng(11))
    batch = dev.run_with_stores(
        lambda stores: jax.jit(make_store_gather(cfg))(stores, si.b, si.s, si.is_weights))
    assert batch.obs.shape == (cfg.batch_size, cfg.seq_len, *obs_shape) and batch.obs.dtype == np.uint8
    # the parent's gather, on frames
    L, T = cfg.learning_steps, cfg.seq_len
    burn = host.burn_in_store[si.b, si.s]
    win = host.burn_in_store[si.b, 0] + si.s * L - burn
    rows = np.clip(win[:, None] + np.arange(T)[None, :], 0, cfg.block_slot_len - 1)
    np.testing.assert_array_equal(np.asarray(batch.obs), host.obs_store[si.b[:, None], rows])
    hb = host.sample_batch(np.random.default_rng(11))
    np.testing.assert_array_equal(np.asarray(batch.obs), hb.obs)


@pytest.mark.parametrize("writer", ["pad_block_fields", "collector"])
@pytest.mark.parametrize("precision", ["fp32", "bf16"])
def test_every_writer_produces_store_field_specs(writer, precision):
    """Host blocks (`pad_block_fields`) and the on-device collector's packed
    chunk are what the donated writes receive: each field must match
    `store_field_specs` exactly, obs rows included (the analysis plane's
    rule, not a looser copy of it)."""
    from r2d2_tpu.analysis.jaxpr_rules import check_store_field_dtypes, compare_store_fields
    from r2d2_tpu.collect import make_collect_fn
    from r2d2_tpu.envs.fake import ScriptedFnEnv
    from r2d2_tpu.models.r2d2 import R2D2Network
    from r2d2_tpu.replay.block import obs_rows, store_field_specs

    if writer == "pad_block_fields":
        assert check_store_field_dtypes(precision) == []
        return
    cfg = tiny_test().replace(precision=precision)
    net = R2D2Network.from_config(cfg)
    fn_env = ScriptedFnEnv(obs_shape=cfg.obs_shape, action_dim=cfg.action_dim)
    collect = make_collect_fn(cfg, net, fn_env, cfg.num_actors, cfg.block_length)
    _, state = init_train_state(cfg, jax.random.PRNGKey(0))
    env_state = jax.eval_shape(lambda: jax.vmap(fn_env.reset)(jax.random.split(jax.random.PRNGKey(0), cfg.num_actors)))
    out = jax.eval_shape(collect, state.params, env_state, jax.numpy.zeros(cfg.num_actors), jax.random.PRNGKey(1))
    fields = out[0]
    specs = store_field_specs(cfg)
    assert specs["obs"][0] == (cfg.block_slot_len, obs_rows(cfg.obs_shape), 128)
    per_env = {k: jax.ShapeDtypeStruct(v.shape[1:], v.dtype) for k, v in fields.items()}
    assert {v.shape[0] for v in fields.values()} == {cfg.num_actors}
    assert compare_store_fields(per_env, specs, "collector") == []
    # and the rule still bites: frames where rows are expected are a finding
    per_env["obs"] = jax.ShapeDtypeStruct((cfg.block_slot_len, *cfg.obs_shape), np.uint8)
    assert [f.rule for f in compare_store_fields(per_env, specs, "collector")] == ["jaxpr-store-field-mismatch"]
