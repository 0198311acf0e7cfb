"""The store gather reads a sampled sequence as one window of its slot (PR 41).

`learner.make_store_gather` reads the five per-step scalar fields as ONE run
of their row (`learner._windows`: B row reads and a barrel shifter, where the
parent indexed B x T entries one by one) and the frames by one clipped index
each. What no loss can see, because program and reference read the same
gathered batch, is pinned here bit for bit against the PARENT's formula, kept
below as the plain reference: every field, canonical and as stored, on the
device plane, under the sharded plane's shard_map and through GSPMD on four
host devices, for frames of 1, 56 and 96 rows, on sequences that meet the clip
(the last of a full block), that are short, and that start an episode or
continue one; one fused K-update against the same updates on the reference's
batches; and the geometry the docstring derives, over every preset."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from r2d2_tpu.config import PRESETS, tiny_test
from r2d2_tpu.learner import (
    DeviceBatch,
    _windows,
    init_train_state,
    make_fused_multi_train_step,
    make_store_gather,
    make_train_step,
)
from r2d2_tpu.replay.accumulator import SequenceAccumulator
from r2d2_tpu.replay.block import obs_rows, rows_as_stored, rows_to_frames
from r2d2_tpu.replay.device_store import DeviceReplayBuffer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCHMARK_CONFIGS = ("nature-lstm512", "lru-seq581", "nature-lstm512-dp4")


def parent_gather(cfg, as_stored=False):
    """learner.make_store_gather as the parent (commit 132c34c) had it: one
    index per frame through jnp.take's default mode, one (block, row) index
    pair per entry of every other per-step field."""
    L, T = cfg.learning_steps, cfg.seq_len
    slot, bl = cfg.block_slot_len, cfg.block_length
    to_frames = rows_as_stored if as_stored else rows_to_frames

    def gather_batch(stores, b, s, is_weights):
        burn = stores["burn_in"][b, s]
        win = stores["burn_in"][b, 0] + s * L - burn
        rows = jnp.clip(win[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :], 0, slot - 1)
        bcol = b[:, None]
        lrow = jnp.clip(s[:, None] * L + jnp.arange(L, dtype=jnp.int32)[None, :], 0, bl - 1)
        obs = stores["obs"]
        flat = obs.reshape(obs.shape[0] * slot, *obs.shape[2:])
        return DeviceBatch(
            obs=to_frames(jnp.take(flat, bcol * slot + rows, axis=0), cfg.obs_shape, cfg.resolved_frame_block),
            last_action=stores["last_action"][bcol, rows],
            last_reward=stores["last_reward"][bcol, rows],
            hidden=stores["hidden"][b, s],
            action=stores["action"][bcol, lrow],
            n_step_reward=stores["n_step_reward"][bcol, lrow],
            gamma=stores["gamma"][bcol, lrow],
            burn_in_steps=burn,
            learning_steps=stores["learning"][b, s],
            forward_steps=stores["forward"][b, s],
            is_weights=is_weights,
            task=stores["task"][b, s] if "task" in stores else None,
        )

    return gather_batch


def _bits(tree):
    """Every leaf as integers: -0.0 is not 0.0 and a NaN equals itself."""
    def leaf(x):
        x = np.asarray(x)
        return x.view(np.uint16 if x.dtype.itemsize == 2 else np.int32) if x.dtype.kind in "fV" else x
    return jax.tree.map(leaf, tree)


def assert_same_bits(got, want):
    got, want = _bits(got), _bits(want)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype, path
        np.testing.assert_array_equal(g, w, err_msg=str(path))


# ------------------------------------------------------------ (a) geometry


def _cell_config(name):
    from benchmark import harness

    return harness.build_config(harness.load_json(os.path.join(ROOT, "benchmark", "configs", name + ".json")), 1)


@pytest.mark.parametrize("name", [*sorted(PRESETS), *BENCHMARK_CONFIGS])
def test_the_first_rows_of_a_window_never_leave_their_slot(name):
    """For every first burn-in an accumulator can carry into a block and every
    sequence of the block: the window starts in [0, block_length - L] and its
    first T - F + 1 rows end inside the slot; the clip can only meet the last
    F - 1, and does in the last sequence of a full block that continues an
    episode (make_store_gather's docstring derives it from accumulator.finish)."""
    cfg = PRESETS[name]() if name in PRESETS else _cell_config(name)
    L, T, F, BI = cfg.learning_steps, cfg.seq_len, cfg.forward_steps, cfg.burn_in_steps
    first_burn, s = np.meshgrid(np.arange(BI + 1), np.arange(cfg.seqs_per_block), indexing="ij")
    burn = np.minimum(s * L + first_burn, BI)  # accumulator.finish
    win = first_burn + s * L - burn
    assert win.min() == 0 and win.max() == cfg.block_length - L
    assert (win + T - F + 1).max() == cfg.block_slot_len
    assert ((win + T).max() > cfg.block_slot_len) == (F > 1)


@pytest.mark.parametrize("n, length", [(1, 1), (5, 1), (5, 5), (7, 12), (64, 9), (441, 85)])
def test_windows_is_the_clipped_index_formula_for_any_start(n, length):
    """Starts inside the row, at its end and past it (no start is negative: a
    sequence's burn-in is never longer than what precedes it in the block)."""
    rng = np.random.default_rng(n * 100 + length)
    field = rng.normal(size=(6, n)).astype(np.float32)
    field[0, -1], field[1, 0] = -0.0, np.nan
    b = np.array([0, 1, 2, 5, 5, 0, 1, 3], np.int32)
    first = np.array([0, n - 1, n // 2, max(n - length, 0), n + 3, 2 * n + 500, 1 % n, n - 1], np.int32)
    want = field[b[:, None], np.clip(first[:, None] + np.arange(length)[None, :], 0, n - 1)]
    got = jax.jit(lambda f, b, first: _windows(f, b, first, length))(field, b, first)
    assert_same_bits(got, want)
    ints = rng.integers(-9, 9, (6, n)).astype(np.int32)
    assert_same_bits(_windows(jnp.asarray(ints), b, first, length),
                     ints[b[:, None], np.clip(first[:, None] + np.arange(length)[None, :], 0, n - 1)])


# ------------------------------------- (b) every field against the parent's


def pattern(gen, block, row, offset):
    """chip_smoke.py's byte encoding: neighbours along any coordinate differ."""
    return (gen * 101 + block * 131 + row * 31 + offset * 7 + (offset >> 7) * 3) & 0xFF


def _episode_blocks(cfg, sizes, rng):
    """One actor's stream as `SequenceAccumulator` packs it: a block per entry
    of `sizes`, an entry `(steps, ends)`: the episode ends with the block or
    is cut and continues into the next (whose first burn-in is then > 0).
    Frame `t` of block `g` is the byte pattern of (g, t)."""
    off = np.arange(int(np.prod(cfg.obs_shape)))
    frame = lambda g, t: pattern(0, g, t, off).astype(np.uint8).reshape(cfg.obs_shape)
    acc, out, fresh = SequenceAccumulator(cfg), [], True
    for g, (steps, ends) in enumerate(sizes):
        if fresh:
            acc.reset(frame(g, 0))
        for t in range(steps):
            acc.add(int(rng.integers(cfg.action_dim)), float(rng.normal()), frame(g, t + 1),
                    rng.normal(size=cfg.action_dim).astype(np.float32),
                    rng.normal(size=(2, cfg.hidden_dim)).astype(np.float32))
        out.append(acc.finish(None if ends else rng.normal(size=cfg.action_dim).astype(np.float32)))
        fresh = ends
    return out


def gather_cfg(obs_shape, **kw):
    encoder = "nature" if len(obs_shape) == 3 else "mlp"
    return tiny_test().replace(
        obs_shape=obs_shape, encoder=encoder, action_dim=3, hidden_dim=8, burn_in_steps=3, learning_steps=4,
        forward_steps=3, block_length=12, buffer_capacity=12 * 8, learning_starts=24, batch_size=8, num_actors=4,
        max_episode_steps=12, use_native_replay=False, **kw)


# full and cut (continues: the next block's first burn-in is 3), full and cut
# again, short with a short last sequence and the episode's end, then an
# episode's start: full, and one of a single short sequence
SIZES = [(12, False), (12, False), (10, True), (12, False), (5, True), (12, True), (3, True), (12, False)]
SHAPES = {"R1-vector": (50,), "R56-84x84x1": (84, 84, 1), "R96-64x64x3": (64, 64, 3)}


@pytest.mark.parametrize("plane", ["device", "sharded"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_gather_is_the_parents_batch_bit_for_bit(shape, plane):
    """Every (block, sequence) of a store filled by real blocks, sampled or
    not: every field of the batch is the parent's, canonical and as stored;
    on the sharded plane both under shard_map (LOCAL blocks, the sharded
    megastep's way) and through GSPMD with GLOBAL blocks (the benchmark's
    reference batch)."""
    dp = 4 if plane == "sharded" else 1
    obs_shape = SHAPES[shape]
    cfg = gather_cfg(obs_shape, **(dict(dp_size=dp, replay_plane="sharded") if dp > 1 else {}))
    assert obs_rows(obs_shape) == int(shape[1:].split("-")[0])
    assert cfg.resolved_frame_block == (4 if len(obs_shape) == 3 else 1)
    nb, S = cfg.num_blocks, cfg.seqs_per_block
    rng = np.random.default_rng(41)
    if dp == 1:
        replay = DeviceReplayBuffer(cfg)
    else:
        from jax.sharding import PartitionSpec as P

        from r2d2_tpu.parallel.jax_compat import shard_map
        from r2d2_tpu.parallel.mesh import dp_manual_axes, make_mesh
        from r2d2_tpu.replay.sharded_store import ShardedDeviceReplay

        mesh = make_mesh(dp=dp, tp=1, devices=jax.devices()[:dp])
        replay = ShardedDeviceReplay(cfg, mesh)
    for block, prios, ep in _episode_blocks(cfg, SIZES, rng):
        replay.add_block(block, prios, ep)
    burn = np.asarray(replay.stores["burn_in"])
    assert {0, cfg.burn_in_steps} <= set(burn[:, 0]) and (np.asarray(replay.stores["learning"]) == 2).any()
    # a window that meets the clip is among them: last sequence, burn-in carried over
    assert (burn[:, 0] + (S - 1) * cfg.learning_steps - burn[:, S - 1] + cfg.seq_len).max() > cfg.block_slot_len

    b = np.repeat(np.arange(nb, dtype=np.int32), S)
    s = np.tile(np.arange(S, dtype=np.int32), nb)
    w = rng.uniform(size=len(b)).astype(np.float32)

    def both(make):
        canonical, stored = make(cfg), make(cfg, as_stored=True)
        return lambda *a: (canonical(*a), stored(*a).obs)

    if dp == 1:
        run = lambda make: replay.run_with_stores(lambda st: jax.jit(both(make))(st, b, s, w))
        got, want = run(make_store_gather), run(parent_gather)
        assert got[0].obs.shape == (len(b), cfg.seq_len, *obs_shape)
        assert_same_bits(got, want)
        return
    per = nb // dp
    local = lambda x: x.reshape(dp, -1)

    def per_shard(make):
        body = lambda st, b, s, w: jax.tree.map(lambda x: x[None], both(make)(st, b[0], s[0], w[0]))
        fn = jax.jit(shard_map(body, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
                               axis_names=dp_manual_axes(mesh), check_vma=False))
        return replay.run_with_stores(lambda st: fn(st, local(b) % per, local(s), local(w)))

    want = per_shard(parent_gather)
    assert_same_bits(per_shard(make_store_gather), want)
    gspmd = replay.run_with_stores(lambda st: jax.jit(both(make_store_gather))(st, b, s, w))
    assert_same_bits(gspmd, jax.tree.map(lambda x: np.asarray(x).reshape(len(b), *x.shape[2:]), want))


# ------------------------------------- (c) K fused updates on such batches


def test_k_fused_updates_are_the_updates_on_the_parents_batches():
    """`make_fused_multi_train_step` (K = 2 updates, each gathering its batch
    in the scan) against `make_train_step` on the PARENT's batch of the same
    coordinates, one update after the other: losses, priorities and every
    parameter bit for bit (the gradients are what moved them)."""
    cfg = gather_cfg((50,))
    replay = DeviceReplayBuffer(cfg)
    rng = np.random.default_rng(7)
    for block, prios, ep in _episode_blocks(cfg, SIZES, rng):
        replay.add_block(block, prios, ep)
    net, state = init_train_state(cfg, jax.random.PRNGKey(3))
    K = 2
    draws = [replay.sample_indices(np.random.default_rng(k)) for k in range(K)]
    b, s, w = (jnp.asarray(np.stack([getattr(d, k) for d in draws])) for k in ("b", "s", "is_weights"))
    fused = make_fused_multi_train_step(cfg, net, K, donate=False)
    got_state, got_metrics, got_prios = replay.run_with_stores(lambda st: fused(state, st, b, s, w))

    step = make_train_step(cfg, net, donate=False)
    reference = jax.jit(parent_gather(cfg, as_stored=True))
    want_state, want_prios = state, []
    for k in range(K):
        batch = replay.run_with_stores(lambda st: reference(st, b[k], s[k], w[k]))
        want_state, want_metrics, prios = step(want_state, batch)
        want_prios.append(prios)
    assert float(want_metrics["loss"]) > 0 and float(want_metrics["grad_norm"]) > 0
    assert_same_bits(got_prios, jnp.stack(want_prios))
    assert_same_bits({k: got_metrics[k] for k in ("loss", "grad_norm")},
                     {k: want_metrics[k] for k in ("loss", "grad_norm")})
    assert_same_bits(got_state.params, want_state.params)
    assert int(got_state.step) == int(state.step) + K


def test_the_runtime_line_says_which_gather_runs():
    from r2d2_tpu.utils.runtime import describe_runtime

    assert describe_runtime(tiny_test())["store_gather"] == "frames+windows"
