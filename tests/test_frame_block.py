"""The encoder's frame block and the device stores' byte order (PR 38).

The Nature trunk's first conv (8x8 at stride 4 over C channels) is a 2x2
stride-1 conv over frames in 4x4 blocks of 16 C values: same parameters, same
products, same sums. The encoder publishes that block, the device stores keep
each frame's bytes in block order, and the step programs hand the conv its
input by a reshape of the stored rows. What no loss can see, because program
and reference read the same gathered batch, is pinned here bit for bit: the
permutation, every writer and both forms of the gather, on the device and the
sharded plane; and that the presets whose encoder publishes block 1 trace to
the parent's step programs, character for character."""

import hashlib

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from r2d2_tpu.config import PRESETS, apply_model_preset, tiny_test
from r2d2_tpu.learner import (
    init_train_state,
    make_fused_multi_train_step,
    make_loss_fn,
    make_store_gather,
)
from r2d2_tpu.models.encoders import (
    BlockedConv,
    NatureEncoder,
    block_frames,
    blocked_shape,
    frame_block,
    unblock_frames,
)
from r2d2_tpu.replay.accumulator import SequenceAccumulator
from r2d2_tpu.replay.block import rows_as_stored, rows_to_frames
from r2d2_tpu.replay.device_store import DeviceReplayBuffer

OBS = (36, 36, 1)  # the smallest frame the Nature trunk takes whose sides its stride divides


def blocked_cfg(**kw):
    base = dict(
        encoder="nature", obs_shape=OBS, action_dim=3, block_length=12, buffer_capacity=12 * 16,
        learning_starts=24, num_actors=4, max_episode_steps=12, env_name="drift", use_native_replay=False,
    )
    base.update(kw)
    return tiny_test().replace(**base)


def numpy_blocked(frames, block):
    """The permutation, stated on its own: (.., H/s, s, W/s, s, C) -> (.., H/s, W/s, s, s, C)."""
    *lead, H, W, C = frames.shape
    n = len(lead)
    x = frames.reshape(*lead, H // block, block, W // block, block, C)
    x = np.transpose(x, (*range(n), n, n + 2, n + 1, n + 3, n + 4))
    return x.reshape(*lead, H // block, W // block, block * block * C)


# ------------------------------------------------------------ (a) the conv


@pytest.mark.parametrize(
    "shape, block", [((84, 84, 1), 4), ((84, 84, 4), 4), ((86, 86, 1), 1)],
    ids=["84x84x1", "84x84x4", "86x86x1-falls-back"],
)
def test_blocked_conv_is_the_strided_conv(shape, block):
    """Output and the gradients of kernel and bias against `nn.Conv(32, (8,
    8), 4, VALID)` on the same parameters, float32; canonical and
    pre-blocked frames give the same bits; where the stride does not divide
    the frame the block is 1 and the conv is the strided one."""
    with jax.default_matmul_precision("highest"):
        x = jax.random.normal(jax.random.PRNGKey(0), (3, *shape))
        plain = nn.Conv(32, (8, 8), strides=(4, 4), padding="VALID")
        mine = BlockedConv(32, 8, 4, shape)
        params = plain.init(jax.random.PRNGKey(1), x)
        params = jax.tree.map(lambda p: p + 0.1, params)  # a bias that is not zero
        assert jax.tree.map(jnp.shape, mine.init(jax.random.PRNGKey(1), x)) == jax.tree.map(jnp.shape, params)
        assert params["params"]["kernel"].shape == (8, 8, shape[-1], 32)
        assert frame_block("nature", shape) == block and blocked_shape(shape, block) == (
            (shape[0] // block, shape[1] // block, block * block * shape[2]))

        def loss(module, given):
            return lambda p: jnp.sum(jnp.sin(module.apply(p, given)))

        want, want_grad = plain.apply(params, x), jax.grad(loss(plain, x))(params)
        got, got_grad = mine.apply(params, x), jax.grad(loss(mine, x))(params)
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
        for name in ("kernel", "bias"):
            g, w = got_grad["params"][name], want_grad["params"][name]
            np.testing.assert_allclose(g, w, atol=1e-5 * float(jnp.abs(w).max()), rtol=0)
        stored = block_frames(x, shape, block)
        assert stored.shape == (3, *blocked_shape(shape, block))
        np.testing.assert_array_equal(np.asarray(stored), numpy_blocked(np.asarray(x), block))
        np.testing.assert_array_equal(np.asarray(unblock_frames(stored, shape, block)), np.asarray(x))
        np.testing.assert_array_equal(np.asarray(mine.apply(params, stored)), np.asarray(got))
        same = jax.tree.map(lambda a, b: bool((a == b).all()), jax.grad(loss(mine, stored))(params), got_grad)
        assert all(jax.tree.leaves(same))
        with pytest.raises(ValueError, match="neither"):
            mine.apply(params, x[:, :-4])


def test_nature_encoder_keeps_its_parameter_tree_and_takes_both_orders():
    """`Conv_0/kernel (8, 8, 1, 32)` and every other leaf as the parent's
    `nn.Conv` stack initialises them from the same key (checkpoints, the
    benchmark's reference and the sharding table read that tree)."""

    class Parent(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.relu(nn.Conv(32, (8, 8), strides=(4, 4), padding="VALID")(x))
            x = nn.relu(nn.Conv(64, (4, 4), strides=(2, 2), padding="VALID")(x))
            x = nn.relu(nn.Conv(64, (3, 3), strides=(1, 1), padding="VALID")(x))
            return nn.relu(nn.Dense(512)(x.reshape((x.shape[0], -1))))

    x = jax.random.uniform(jax.random.PRNGKey(2), (2, 84, 84, 1))
    enc = NatureEncoder(obs_shape=(84, 84, 1))
    want, got = Parent().init(jax.random.PRNGKey(5), x), enc.init(jax.random.PRNGKey(5), x)
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert all(jax.tree.leaves(jax.tree.map(lambda a, b: bool((a == b).all()), want, got)))
    np.testing.assert_allclose(enc.apply(got, x), Parent().apply(want, x), atol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(enc.apply(got, block_frames(x, (84, 84, 1), 4))), np.asarray(enc.apply(got, x)))


# ------------------------------------- (b) rows -> frames, whatever the lead


def _parents_rows_as_stored(rows, obs_shape, block):
    """`rows_as_stored` until PR 43: the rows flattened UNDER their leading
    axes (the chip tiled that over (T, bytes); PERF.md finding 43)."""
    n, R = int(np.prod(obs_shape)), rows.shape[-2]
    lead = rows.shape[:-2]
    return rows.reshape(*lead, R * 128)[..., :n].reshape(*lead, *blocked_shape(obs_shape, block))


@pytest.mark.parametrize("array", ["numpy", "jax"])
@pytest.mark.parametrize("block", [1, 4])
@pytest.mark.parametrize("lead", [(), (5,), (3, 5), (2, 3, 5)], ids=lambda l: "x".join(map(str, l)) or "scalar")
def test_rows_as_stored_merges_the_leading_axes_and_keeps_every_byte(lead, block, array):
    """PR 43 merges every leading axis before the rows become bytes. The
    result is the parent's formula byte for byte and shape for shape, for the
    host planes' `()` and `(N,)` as for the step programs' `(B, T)`, numpy in
    numpy out, and `rows_to_frames` still inverts `frames_to_rows`."""
    from r2d2_tpu.replay.block import frames_to_rows, obs_rows

    frames = np.random.default_rng(len(lead) + block).integers(0, 256, (*lead, *OBS), dtype=np.uint8)
    rows = frames_to_rows(frames, OBS, block)
    assert rows.shape == (*lead, obs_rows(OBS), 128) and 36 * 36 % 128  # a padded tail to slice off
    given = rows if array == "numpy" else jnp.asarray(rows)
    got = rows_as_stored(given, OBS, block)
    assert isinstance(got, np.ndarray if array == "numpy" else jax.Array)
    assert got.shape == (*lead, *blocked_shape(OBS, block)) and got.dtype == np.uint8
    np.testing.assert_array_equal(np.asarray(got), _parents_rows_as_stored(rows, OBS, block))
    np.testing.assert_array_equal(np.asarray(got), numpy_blocked(frames, block) if block > 1 else frames)
    np.testing.assert_array_equal(np.asarray(rows_to_frames(given, OBS, block)), frames)
    with pytest.raises(ValueError, match="do not end in"):
        rows_as_stored(given[..., :-1, :], OBS, block)


# ------------------------------------------------- (c) writers and gathers


def _random_block(cfg, rng, steps):
    frame = lambda: rng.integers(1, 256, cfg.obs_shape, dtype=np.uint8)
    acc = SequenceAccumulator(cfg)
    acc.reset(frame())
    for _ in range(steps):
        acc.add(int(rng.integers(cfg.action_dim)), float(rng.normal()), frame(),
                rng.normal(size=cfg.action_dim).astype(np.float32),
                rng.normal(size=(2, cfg.hidden_dim)).astype(np.float32))
    return acc.finish(rng.normal(size=cfg.action_dim).astype(np.float32))


def _collected(cfg, key=7):
    """One chunk of the on-device collector under fully random actions
    (epsilon 1: the frames do not depend on the network)."""
    from r2d2_tpu.collect import make_collect_fn
    from r2d2_tpu.train import build_fn_env

    fn_env = build_fn_env(cfg)
    net, state = init_train_state(cfg, jax.random.PRNGKey(0))
    collect = jax.jit(make_collect_fn(cfg, net, fn_env, cfg.num_actors, cfg.block_length))
    env_state = jax.vmap(fn_env.reset)(jax.random.split(jax.random.PRNGKey(key), cfg.num_actors))
    return collect(state.params, env_state, jnp.ones(cfg.num_actors), jax.random.PRNGKey(key + 1))[0]


def _both_orders(cfg):
    canonical, stored = make_store_gather(cfg), make_store_gather(cfg, as_stored=True)
    return lambda *a: (canonical(*a).obs, stored(*a).obs)


@pytest.mark.parametrize("plane", ["device", "sharded"])
@pytest.mark.parametrize("writer", ["host_block", "collection"])
def test_a_blocked_store_gathers_the_frames_that_were_written(writer, plane):
    """A store filled by a host `Block` (`pad_block_fields`) or by the
    collector's chunk, on the device plane and on the sharded plane (four
    devices, per-shard local gathers): the canonical gather returns the
    frames that were written, the as-stored gather the same frames in 4x4
    blocks, each a pure function of the other, bit for bit."""
    from r2d2_tpu.megastep import _slab_write

    dp = 4 if plane == "sharded" else 1
    cfg = blocked_cfg(batch_size=8, **(dict(dp_size=dp, replay_plane="sharded") if dp > 1 else {}))
    assert cfg.resolved_frame_block == 4 and blocked_shape(OBS, 4) == (9, 9, 16)
    nb, slot, E = cfg.num_blocks, cfg.block_slot_len, cfg.num_actors
    if plane == "device":
        replay = DeviceReplayBuffer(cfg)
    else:
        from jax.sharding import PartitionSpec as P

        from r2d2_tpu.parallel.jax_compat import shard_map
        from r2d2_tpu.parallel.mesh import dp_manual_axes, make_mesh
        from r2d2_tpu.replay.sharded_store import ShardedDeviceReplay

        mesh = make_mesh(dp=dp, tp=1, devices=jax.devices()[:dp])
        replay = ShardedDeviceReplay(cfg, mesh)

        def per_shard(fn):
            """fn over each shard's LOCAL view, as the sharded megastep maps its body."""
            return jax.jit(shard_map(fn, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
                                     axis_names=dp_manual_axes(mesh), check_vma=False))
    per = nb // dp

    # ---- fill, and say which frames global block g now holds
    frames_of = {}
    if writer == "host_block":
        rng = np.random.default_rng(5)
        for i in range(2 * dp):
            block, prios, ep = _random_block(cfg, rng, cfg.block_length - (i % 2))
            replay.add_block(block, prios, ep)
            # the device plane fills slot after slot; the sharded plane deals
            # blocks to its shards in turn, each filling its own slots in order
            frames_of[i if dp == 1 else (i % dp) * per + i // dp] = block.obs
    else:
        fields = _collected(cfg)
        # the same trajectories under an encoder that publishes block 1: its
        # rows are the frames as they are
        plain = _collected(cfg.replace(encoder="mlp"))
        assert cfg.replace(encoder="mlp").resolved_frame_block == 1
        want = rows_to_frames(np.asarray(plain["obs"]), OBS)
        assert len({want[e].tobytes() for e in range(E)}) > 1 and want.std() > 0
        np.testing.assert_array_equal(rows_to_frames(np.asarray(fields["obs"]), OBS, 4), want)
        np.testing.assert_array_equal(rows_as_stored(np.asarray(fields["obs"]), OBS, 4), numpy_blocked(want, 4))
        if dp == 1:
            with replay.lock:
                replay.stores = jax.jit(_slab_write)(replay.stores, fields, jnp.int32(0))
            frames_of = {e: want[e] for e in range(E)}
        else:
            write = per_shard(lambda st, f: _slab_write(st, f, jnp.int32(0)))
            with replay.lock:
                replay.stores = write(replay.stores, fields)  # E / dp envs per shard
            frames_of = {(e // (E // dp)) * per + e % (E // dp): want[e] for e in range(E)}

    # ---- the store holds the frames in block order, in lane-aligned rows
    held = np.asarray(replay.stores["obs"])
    for g, frames in frames_of.items():
        np.testing.assert_array_equal(rows_as_stored(held[g], OBS, 4)[: len(frames)], numpy_blocked(frames, 4))

    # ---- both gathers, on every written block and sequence 0 and 1
    blocks = np.repeat(np.array(sorted(frames_of), np.int32), 2)
    seqs = np.tile(np.array([0, 1], np.int32), len(frames_of))
    if dp == 1:
        w = jnp.ones(len(blocks), jnp.float32)
        canonical, stored = replay.run_with_stores(
            lambda st: jax.jit(_both_orders(cfg))(st, jnp.asarray(blocks), jnp.asarray(seqs), w))
    else:
        local = blocks.reshape(dp, -1) % per  # sorted: each shard's blocks are one row
        assert (blocks.reshape(dp, -1) // per == np.arange(dp)[:, None]).all()
        gather = _both_orders(cfg)
        body = lambda st, b, s, w: tuple(o[None] for o in gather(st, b[0], s[0], w[0]))
        canonical, stored = replay.run_with_stores(lambda st: per_shard(body)(
            st, jnp.asarray(local), jnp.asarray(seqs.reshape(dp, -1)), jnp.ones(local.shape, jnp.float32)))
        canonical, stored = (np.asarray(x).reshape(len(blocks), *x.shape[2:]) for x in (canonical, stored))
    canonical, stored = np.asarray(canonical), np.asarray(stored)
    T = cfg.seq_len
    assert canonical.shape == (len(blocks), T, *OBS) and stored.shape == (len(blocks), T, 9, 9, 16)
    assert canonical.dtype == stored.dtype == np.uint8
    np.testing.assert_array_equal(stored, numpy_blocked(canonical, 4))
    np.testing.assert_array_equal(np.asarray(unblock_frames(stored, OBS, 4)), canonical)
    for i, (g, s) in enumerate(zip(blocks, seqs)):
        frames = np.zeros((slot, *OBS), np.uint8)
        frames[: len(frames_of[g])] = frames_of[g]
        burn = int(np.asarray(replay.stores["burn_in"])[g, s])
        rows = np.clip(int(np.asarray(replay.stores["burn_in"])[g, 0]) + s * cfg.learning_steps - burn
                       + np.arange(T), 0, slot - 1)
        np.testing.assert_array_equal(canonical[i], frames[rows])


# ----------------------------------- (d) the step program against the loss


def test_fused_update_from_a_blocked_store_is_the_loss_on_the_canonical_batch():
    """One fused update through the step program (frames as stored, the conv
    fed by a reshape) against `make_loss_fn` on the canonical batch of the
    same store (the frames blocked in the graph): loss and priorities to
    float32 rounding, and the same gradient norm."""
    cfg = blocked_cfg(batch_size=6)
    replay = DeviceReplayBuffer(cfg)
    rng = np.random.default_rng(9)
    for i in range(5):
        replay.add_block(*_random_block(cfg, rng, cfg.block_length - (i % 2)))
    net, state = init_train_state(cfg, jax.random.PRNGKey(4))
    # a target network of its own, so both conv1 kernels matter
    state = state.replace(target_params=init_train_state(cfg, jax.random.PRNGKey(6))[1].params)
    si = replay.sample_indices(np.random.default_rng(2))
    b, s, w = jnp.asarray(si.b), jnp.asarray(si.s), jnp.asarray(si.is_weights)
    _, metrics, priorities = replay.run_with_stores(
        lambda st: make_fused_multi_train_step(cfg, net, 1, donate=False)(state, st, b[None], s[None], w[None]))
    priorities = priorities[0]

    batch = replay.run_with_stores(lambda st: jax.jit(make_store_gather(cfg))(st, b, s, w))
    assert batch.obs.shape == (6, cfg.seq_len, *OBS)
    denom = jnp.maximum(jnp.sum(jnp.minimum(batch.learning_steps, cfg.learning_steps)).astype(jnp.float32), 1.0)
    loss_fn = jax.jit(jax.value_and_grad(make_loss_fn(cfg, net), has_aux=True))
    (loss, (want_priorities, _)), grads = loss_fn(state.params, state.target_params, batch, denom)
    np.testing.assert_allclose(float(metrics["loss"]), float(loss), rtol=2e-6)
    np.testing.assert_allclose(np.asarray(priorities), np.asarray(want_priorities), rtol=2e-5, atol=1e-7)
    import optax

    np.testing.assert_allclose(float(metrics["grad_norm"]), float(optax.global_norm(grads)), rtol=2e-5)
    assert float(jnp.abs(grads["params"]["enc"]["Conv_0"]["kernel"]).max()) > 0


# ------------------------------------- (e) block 1 is the parent's program


def _step_program_texts(cfg):
    """The jaxpr text of the collecting and the update-only step program at
    the preset's own size (traced from shapes: nothing is allocated)."""
    from r2d2_tpu import learner, megastep
    from r2d2_tpu.collect import default_chunk_len
    from r2d2_tpu.models.r2d2 import R2D2Network
    from r2d2_tpu.replay.block import store_field_specs
    from r2d2_tpu.train import build_fn_env

    net, fn_env = R2D2Network.from_config(cfg), build_fn_env(cfg)
    E, K, B, chunk = cfg.num_actors, 2, cfg.batch_size, default_chunk_len(cfg)
    sds = jax.ShapeDtypeStruct
    state = jax.eval_shape(lambda: learner.init_train_state(cfg, jax.random.PRNGKey(0))[1])
    stores = {k: sds((cfg.num_blocks, *shape), dt) for k, (shape, dt) in store_field_specs(cfg).items()}
    env = jax.eval_shape(lambda: jax.vmap(fn_env.reset)(jax.random.split(jax.random.PRNGKey(0), E)))
    coords = [sds((K, B), jnp.int32), sds((K, B), jnp.int32), sds((K, B), jnp.float32)]
    mega = megastep.make_megastep(cfg, net, fn_env, E, chunk, K)
    multi = learner.make_fused_multi_train_step(cfg, net, K)
    return {
        "mega": str(jax.make_jaxpr(mega)(
            state, stores, env, sds((E,), jnp.float32), sds((2,), jnp.uint32), *coords, sds((), jnp.int32))),
        "multi": str(jax.make_jaxpr(multi)(state, stores, *coords)),
    }


# (characters, sha256) of each program's jaxpr text with the frames as they are
# (jax 0.9.0; taken by this function). First taken on PR 38's PARENT (commit
# 18bb6d7), which is what the name says: PR 38 left all six as they were.
# Taken again in PR 41, whose store gather is every preset's (the scalar
# fields read as windows, learner._windows: all six moved, +26 k to +56 k characters
# each) and which changed nothing else in them. Taken again in PR 43, whose
# `rows_as_stored` is every preset's too: the gathered rows are flattened with
# (B, T) merged, so in each program ONE reshape's `new_sizes`, ONE slice's
# `limit_indices` / `start_indices` and their two results lose an axis
# (`u8[8,10,256]` -> `u8[80,256]` in tiny_test) and every row is 3 (IMPALA) or
# 13 characters shorter; a token-by-token diff of parent against change shows
# those and nothing else. Taken again in PR 46, whose tail of `unroll` and loss
# are every preset's as well (`R2D2Network._dueling_window`, `learner._q_at`:
# the two `take_along_axis` of the core's outputs and the two of Q by action
# with their scatter-adds leave, a selection matmul, the heads on L + F rows and
# a few selects come in: every program 430 to 750 characters shorter, and every
# later variable renamed). Taken again in PR 49 for the three LSTM presets, whose
# `_core_input` behind the seam lost its `take_along_axis` and the two takes of
# actions and rewards for `r2d2._time_order` (two pads, two selects, one
# selection matmul, and an `optimization_barrier` on the result and on its
# cotangent: every program 1,900 to 2,600 characters shorter);
# `tiny_test-lru` was added then, from PR 49's PARENT (commit 3fa42b5): a core
# without a seam never reaches that branch and its programs did not move by a
# character. Taken again in PR 50 for the three LSTM presets, whose
# `_core_input` behind the seam hands each gathered part to the encoder
# behind an `optimization_barrier` and releases the others' bytes with the
# window's encoding (eight `optimization_barrier` equations a program where
# three were, the two nets' forward and the online net's cotangents: every
# program 946 to 976 characters longer); `tiny_test-lru` did not move by a character again. A
# jax release that prints a jaxpr differently moves
# every row at once: take them again from a tree known to be good
PARENT_PROGRAMS = {
    "procgen_impala": {
        "mega": (613578, "fa059eb090b3274dd9f8444550d774220b537f43002aae0d52eef87043c49a17"),
        "multi": (453563, "4807d6a5661984c5a07c56d1ce29dd1739b8c9f57ff6da755cfd122827ce9b59"),
    },
    "tiny_test": {
        "mega": (239476, "a12d9c760333bb6fbc120b0b2584b9703d6dd629ba858bf05cb55bc95b8e7d02"),
        "multi": (174605, "a7fa7ef6a6d3514c91121241d0a544cf835c7ef091a12b16815989466581258f"),
    },
    "tiny_test-deep-bf16": {
        "mega": (278467, "07c407ba9eabe3adea7fd88396429b9f06b13f1291b6ec81089de6a83ae931af"),
        "multi": (205524, "959051b2de4955066d59a975f5771f3bf80ad5bf194339cc7188616f5b8d35ea"),
    },
    "tiny_test-lru": {
        "mega": (314714, "3c9757abf7bcabd413820037a0e58911d6d9debd1177f7ffa10660b538d338a4"),
        "multi": (245987, "fa748b0ac2845d85742e3b9a4f4949c8c5a9f9b76a153290993d1ea0232818ad"),
    },
}


@pytest.mark.parametrize("case", sorted(PARENT_PROGRAMS))
def test_presets_that_publish_block_one_trace_to_the_parents_programs(case):
    """The IMPALA and the MLP trunks publish block 1: their stores keep
    frames as they are and their step programs are the parent's, character
    for character (the controls that no benchmark cell provides: all three
    cells run the Nature trunk on 84x84x1)."""
    cfg = {
        "procgen_impala": lambda: PRESETS["procgen_impala"](),
        "tiny_test": lambda: tiny_test().replace(env_name="scripted"),
        "tiny_test-deep-bf16": lambda: apply_model_preset(
            tiny_test().replace(env_name="scripted", precision="bf16"), "deep"),
        "tiny_test-lru": lambda: tiny_test().replace(env_name="scripted", recurrent_core="lru"),
    }[case]()
    assert cfg.resolved_frame_block == 1 and blocked_shape(cfg.obs_shape, 1) == tuple(cfg.obs_shape)
    got = {k: (len(t), hashlib.sha256(t.encode()).hexdigest()) for k, t in _step_program_texts(cfg).items()}
    assert got == PARENT_PROGRAMS[case]
    # and a Nature trunk on such frames does change them: the test can fail
    if case == "tiny_test":
        other = _step_program_texts(cfg.replace(encoder="nature", obs_shape=OBS))
        assert "9,9,16" in other["multi"] and "9,9,16" not in _step_program_texts(cfg)["multi"]
