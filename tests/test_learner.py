"""Learner tests: target math vs an independent numpy recomputation, learning
on a fixed batch, in-jit target sync, and single-vs-8-device dp equivalence
(the SURVEY.md section 4 'distributed-without-a-cluster' strategy)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from r2d2_tpu.config import tiny_test
from r2d2_tpu.learner import DeviceBatch, init_train_state, make_train_step
from r2d2_tpu.ops.priority import mixed_td_priorities_np
from r2d2_tpu.ops.value_rescale import inverse_value_rescale_np, value_rescale_np
from r2d2_tpu.parallel.mesh import make_mesh, shard_batch


@pytest.fixture(scope="module")
def cfg():
    return tiny_test()


@pytest.fixture(scope="module")
def setup(cfg):
    net, state = init_train_state(cfg, jax.random.PRNGKey(0))
    return net, state


def random_batch(cfg, seed=0):
    rng = np.random.default_rng(seed)
    B, T, L = cfg.batch_size, cfg.seq_len, cfg.learning_steps
    learn = np.full(B, L, np.int32)
    learn[-1] = L - 1  # one ragged row
    fwd = np.full(B, cfg.forward_steps, np.int32)
    fwd[-1] = 1
    return DeviceBatch(
        obs=jnp.asarray(rng.integers(0, 255, size=(B, T, *cfg.obs_shape), dtype=np.uint8)),
        last_action=jnp.asarray(rng.integers(0, cfg.action_dim, size=(B, T)), jnp.int32),
        last_reward=jnp.asarray(rng.normal(size=(B, T)).astype(np.float32)),
        hidden=jnp.asarray(rng.normal(size=(B, 2, cfg.hidden_dim)).astype(np.float32)),
        action=jnp.asarray(rng.integers(0, cfg.action_dim, size=(B, L)), jnp.int32),
        n_step_reward=jnp.asarray(rng.normal(size=(B, L)).astype(np.float32)),
        gamma=jnp.asarray(np.full((B, L), cfg.gamma**cfg.forward_steps, np.float32)),
        burn_in_steps=jnp.asarray(np.full(B, cfg.burn_in_steps, np.int32)),
        learning_steps=jnp.asarray(learn),
        forward_steps=jnp.asarray(fwd),
        is_weights=jnp.asarray(rng.uniform(0.3, 1.0, size=B).astype(np.float32)),
    )


def test_step_runs_and_metrics_finite(cfg, setup):
    net, state = setup
    step = make_train_step(cfg, net, donate=False)
    batch = random_batch(cfg)
    new_state, metrics, priorities = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert priorities.shape == (cfg.batch_size,)
    assert np.isfinite(np.asarray(priorities)).all()
    assert int(new_state.step) == 1


def test_target_math_matches_numpy(cfg, setup):
    """Recompute y, loss, priorities in numpy from the net's own Q outputs
    and compare to the jitted step's metrics (SURVEY.md section 2.6 target
    invariant)."""
    net, state = setup
    batch = random_batch(cfg, seed=1)

    q_learn, q_boot_online, mask = net.apply(
        state.params, batch.obs, batch.last_action, batch.last_reward, batch.hidden,
        batch.burn_in_steps, batch.learning_steps, batch.forward_steps,
    )
    _, q_boot_target, _ = net.apply(
        state.target_params, batch.obs, batch.last_action, batch.last_reward, batch.hidden,
        batch.burn_in_steps, batch.learning_steps, batch.forward_steps,
    )
    q_learn, q_boot_online, q_boot_target, mask = map(
        np.asarray, (q_learn, q_boot_online, q_boot_target, mask)
    )
    a_star = q_boot_online.argmax(-1)
    q_tgt = np.take_along_axis(q_boot_target, a_star[..., None], -1)[..., 0]
    y = value_rescale_np(
        np.asarray(batch.n_step_reward) + np.asarray(batch.gamma) * inverse_value_rescale_np(q_tgt)
    )
    q_taken = np.take_along_axis(q_learn, np.asarray(batch.action)[..., None], -1)[..., 0]
    td = y - q_taken
    w = np.asarray(batch.is_weights)[:, None]
    want_loss = (w * td**2 * mask).sum() / mask.sum()
    want_prios = mixed_td_priorities_np(np.abs(td) * mask, mask, cfg.td_mix_eta)

    step = make_train_step(cfg, net, donate=False)
    _, metrics, priorities = step(state, batch)
    np.testing.assert_allclose(float(metrics["loss"]), want_loss, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(priorities), want_prios, rtol=1e-3, atol=1e-5)


def test_loss_decreases_on_fixed_batch(cfg):
    fast_cfg = cfg.replace(lr=5e-3)
    net, state = init_train_state(fast_cfg, jax.random.PRNGKey(1))
    step = make_train_step(fast_cfg, net, donate=False)
    batch = random_batch(fast_cfg, seed=2)
    losses = []
    for _ in range(30):
        state, metrics, _ = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] * 0.5, losses


def test_target_sync_inside_jit(cfg):
    net, state = init_train_state(cfg, jax.random.PRNGKey(2))
    step = make_train_step(cfg, net, donate=False)
    batch = random_batch(cfg, seed=3)
    interval = cfg.target_net_update_interval
    for i in range(interval):
        state, _, _ = step(state, batch)
        online = jax.tree.leaves(state.params)[0]
        target = jax.tree.leaves(state.target_params)[0]
        if i + 1 < interval:
            assert not np.allclose(np.asarray(online), np.asarray(target))
    # at step == interval the target must have snapped to the online params
    chex_equal = jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        state.params, state.target_params,
    )
    del chex_equal


def test_dp8_equivalence(cfg):
    """Sharding the batch over an 8-device dp mesh must produce the same
    update as single-device (XLA psum == serial sum)."""
    assert len(jax.devices()) == 8, "conftest must force 8 cpu devices"
    net, state = init_train_state(cfg, jax.random.PRNGKey(3))
    step = make_train_step(cfg, net, donate=False)
    batch = random_batch(cfg, seed=4)

    single_state, single_metrics, single_prios = step(state, batch)

    mesh = make_mesh(dp=8, tp=1)
    sharded = DeviceBatch(*shard_batch(mesh, tuple(batch)))
    rep_state = jax.device_put(state, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()))
    multi_state, multi_metrics, multi_prios = step(rep_state, sharded)

    np.testing.assert_allclose(
        float(single_metrics["loss"]), float(multi_metrics["loss"]), rtol=1e-5
    )
    np.testing.assert_allclose(np.asarray(single_prios), np.asarray(multi_prios), rtol=1e-4, atol=1e-6)
    a = jax.tree.leaves(single_state.params)
    b = jax.tree.leaves(multi_state.params)
    for x, y in zip(a, b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-4, atol=1e-6)


def test_tensor_parallel_matches_single_device():
    """dp=2 x tp=2 with LSTM kernels sharded over tp must reproduce the
    single-device update exactly (GSPMD inserts the tp collectives from
    the param sharding annotations alone)."""
    from r2d2_tpu.parallel.mesh import shard_batch, train_state_shardings

    cfg = tiny_test().replace(lstm_backend="scan")
    net, state0 = init_train_state(cfg, jax.random.PRNGKey(0))
    batch = random_batch(cfg)  # includes a ragged row
    step = make_train_step(cfg, net, donate=False)

    ref_state, ref_m, ref_p = step(state0, batch)
    ref_state, ref_m, ref_p = step(ref_state, batch)

    mesh = make_mesh(dp=2, tp=2, devices=jax.devices()[:4])
    tp_state = jax.device_put(state0, train_state_shardings(state0, mesh))
    tp_batch = type(batch)(*shard_batch(mesh, tuple(batch)))
    # confirm the wide kernels really are tp-sharded
    wi = tp_state.params["params"]["core"]["wi"]
    assert len({sh.device for sh in wi.addressable_shards}) == 4
    tp_s, tp_m, tp_p = step(tp_state, tp_batch)
    tp_s, tp_m, tp_p = step(tp_s, tp_batch)

    np.testing.assert_allclose(float(tp_m["loss"]), float(ref_m["loss"]), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(tp_p), np.asarray(ref_p), atol=1e-5)
    for a, b in zip(jax.tree.leaves(tp_s.params), jax.tree.leaves(ref_state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

    # compile-level partition check: GSPMD kept every annotated kernel
    # SHARDED through the whole update (a silently-gathered weight would
    # come back replicated) — column pairs on the output axis, row pairs
    # on the contraction axis, column biases on their output axis
    from jax.sharding import PartitionSpec as P

    p = tp_s.params["params"]
    assert p["core"]["wi"].sharding.spec == P(None, "tp")
    assert p["core"]["wh"].sharding.spec == P(None, "tp")
    assert p["core"]["b"].sharding.spec == P("tp")
    assert p["adv_hidden"]["kernel"].sharding.spec == P(None, "tp")
    assert p["val_hidden"]["kernel"].sharding.spec == P(None, "tp")
    assert p["adv_out"]["kernel"].sharding.spec in (P("tp"), P("tp", None))
    assert p["val_out"]["kernel"].sharding.spec in (P("tp"), P("tp", None))
    assert p["enc"]["Dense_0"]["kernel"].sharding.spec == P(None, "tp")
    assert p["enc"]["Dense_0"]["bias"].sharding.spec == P("tp")
    # each tp shard holds HALF the annotated kernels' bytes (true
    # partitioning, not replication with a sharded-looking spec)
    for kern in (p["adv_hidden"]["kernel"], p["adv_out"]["kernel"]):
        shard_elems = {s.data.size for s in kern.addressable_shards}
        assert shard_elems == {kern.size // 2}


def test_zero_state_replay_ablation_matches_manual_zeroing(cfg):
    """cfg.zero_state_replay must equal running the normal step on a batch
    whose stored hidden was zeroed by hand — one flag, same math."""
    net, state = init_train_state(cfg, jax.random.PRNGKey(0))
    b = random_batch(cfg, seed=13)
    zeroed = b._replace(hidden=jnp.zeros_like(b.hidden))

    cfg_abl = cfg.replace(zero_state_replay=True)
    net_a, state_a = init_train_state(cfg_abl, jax.random.PRNGKey(0))
    s1, m1, p1 = make_train_step(cfg_abl, net_a, donate=False)(state_a, b)
    s2, m2, p2 = make_train_step(cfg, net, donate=False)(state, zeroed)
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))
    np.testing.assert_array_equal(np.asarray(m1["loss"]), np.asarray(m2["loss"]))
    # and it differs from the stored-state step (the flag is load-bearing)
    _, m3, _ = make_train_step(cfg, net, donate=False)(state, b)
    assert float(m3["loss"]) != float(m1["loss"])


def test_cosine_lr_schedule_decays_updates():
    """lr_schedule='cosine': the SAME gradient produces a much smaller
    param step near training_steps than at step 0 (lr_final_frac=0 floors
    at zero), while the default constant schedule does not; the schedule
    position rides the checkpointed opt_state count."""
    import pytest

    from r2d2_tpu.config import tiny_test

    base = tiny_test().replace(training_steps=10, lr_final_frac=0.0)
    batch = random_batch(base, seed=3)

    def step_sizes(cfg):
        net, state = init_train_state(cfg, jax.random.PRNGKey(0))
        step = make_train_step(cfg, net, donate=False)
        sizes = []
        for _ in range(10):
            prev = state.params
            state, _, _ = step(state, batch)
            sizes.append(
                float(
                    sum(
                        np.abs(np.asarray(a) - np.asarray(b)).sum()
                        for a, b in zip(
                            jax.tree.leaves(state.params), jax.tree.leaves(prev)
                        )
                    )
                )
            )
        return sizes

    cos = step_sizes(base.replace(lr_schedule="cosine"))
    const = step_sizes(base)
    # cosine: final step ~cos^2(pi/2 * 9.5/10) of the first; constant: flat
    assert cos[-1] < 0.05 * cos[0], (cos[0], cos[-1])
    assert const[-1] > 0.3 * const[0], (const[0], const[-1])

    with pytest.raises(ValueError, match="lr_schedule"):
        tiny_test().replace(lr_schedule="warmup")


# ---------------------------------------------------------------------------
# The loss picks Q by action as a select over A (PR 46): `learner._q_at` and
# the whole island against the `take_along_axis` they replaced, kept HERE as
# the oracle.


def _by_index(q, a):
    return jnp.take_along_axis(q, a[..., None], axis=-1)[..., 0]


@pytest.mark.parametrize("others", ["finite", "floor", "inf", "nan"])
@pytest.mark.parametrize("actions", [3, 4, 18])
def test_q_at_is_take_along_axis_to_the_bit_and_nothing_leaks_from_the_other_actions(actions, others):
    """Values and the gradient w.r.t. q, bit for bit; whatever the actions NOT
    taken hold (the multi-task floor, an overflow, a NaN) stays out of both."""
    from r2d2_tpu.learner import _q_at

    rng = np.random.default_rng(actions)
    B, L = 5, 7
    a = jnp.asarray(rng.integers(0, actions, size=(B, L)), jnp.int32)
    q = rng.normal(size=(B, L, actions)).astype(np.float32)
    taken = np.arange(actions) == np.asarray(a)[..., None]
    fill = {"finite": None, "floor": -1e9, "inf": np.inf, "nan": np.nan}[others]
    if fill is not None:
        q = np.where(taken, q, np.float32(fill))
    q = jnp.asarray(q)
    cot = jnp.asarray(rng.normal(size=(B, L)).astype(np.float32))

    got, got_dq = jax.value_and_grad(lambda q: jnp.sum(_q_at(q, a) * cot))(q)
    want, want_dq = jax.value_and_grad(lambda q: jnp.sum(_by_index(q, a) * cot))(q)
    assert np.isfinite(float(got)) and float(got) == float(want)
    np.testing.assert_array_equal(np.asarray(_q_at(q, a)), np.asarray(_by_index(q, a)))
    np.testing.assert_array_equal(np.asarray(got_dq), np.asarray(want_dq))
    assert (np.asarray(got_dq)[~taken] == 0).all() and np.isfinite(np.asarray(got_dq)).all()


class _GivenQ:
    """Stands where make_loss_fn expects the network and hands back the Q
    views it was given as `params` (as benchmark/correct.py reaches the
    island)."""

    @staticmethod
    def apply(given, obs, last_action, last_reward, hidden, burn_in, learning, forward, task=None):
        return given["q_learn"], given["q_boot"], given["mask"]


def _island_with_indices(cfg, q_learn, q_boot, q_boot_target, mask, b, denom):
    """make_loss_fn's island until PR 46, to the letter."""
    from r2d2_tpu.ops.priority import mixed_td_priorities
    from r2d2_tpu.ops.value_rescale import inverse_value_rescale, value_rescale

    eps = cfg.value_rescale_eps
    a_star = jnp.argmax(jax.lax.stop_gradient(q_boot), axis=-1)
    y = jax.lax.stop_gradient(value_rescale(
        b.n_step_reward + b.gamma * inverse_value_rescale(_by_index(q_boot_target, a_star), eps), eps))
    q_taken = _by_index(q_learn, b.action)
    td = y - q_taken
    loss = jnp.sum(b.is_weights[:, None] * jnp.square(td) * mask) / denom
    abs_td = jnp.abs(td) * mask
    return loss, (mixed_td_priorities(abs_td, mask, cfg.td_mix_eta), {
        "q_mean": jnp.sum(q_taken * mask) / denom, "target_mean": jnp.sum(y * mask) / denom,
        "td_abs_mean": jnp.sum(abs_td) / denom})


@pytest.mark.parametrize("floor", [False, True], ids=["single-task", "multi-task-floor"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_loss_island_is_the_indexed_islands(cfg, seed, floor):
    """dloss/dq_learn of the program's island equals the `take_along_axis`
    island's bit for bit, and loss, priorities and the aux means to the order
    of a sum, with the invalid actions of a multi-task row at the -1e9 floor
    too."""
    from r2d2_tpu.learner import make_loss_fn

    b = random_batch(cfg, seed)
    rng = np.random.default_rng(100 + seed)
    B, L, A = cfg.batch_size, cfg.learning_steps, cfg.action_dim
    views = {k: rng.normal(size=(B, L, A)).astype(np.float32) for k in ("q_learn", "q_boot", "q_boot_target")}
    if floor:
        valid = np.arange(A) < rng.integers(2, A + 1, size=(B, 1, 1))
        views = {k: np.where(valid, v, np.float32(-1e9)) for k, v in views.items()}
        b = b._replace(action=jnp.asarray(rng.integers(0, 2, size=(B, L)), jnp.int32))
    views = {k: jnp.asarray(v) for k, v in views.items()}
    mask = (jnp.arange(L)[None, :] < b.learning_steps[:, None]).astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(b.learning_steps).astype(jnp.float32), 1.0)
    loss_fn = make_loss_fn(cfg, _GivenQ)

    def program(q):
        online = {"q_learn": q, "q_boot": views["q_boot"], "mask": mask}
        target = {"q_learn": q, "q_boot": views["q_boot_target"], "mask": mask}
        return loss_fn(online, target, b, denom)

    def oracle(q):
        return _island_with_indices(cfg, q, views["q_boot"], views["q_boot_target"], mask, b, denom)

    (got, got_aux), got_dq = jax.jit(jax.value_and_grad(program, has_aux=True))(views["q_learn"])
    (want, want_aux), want_dq = jax.jit(jax.value_and_grad(oracle, has_aux=True))(views["q_learn"])
    # entry by entry the same bits; a sum over (B, L) is the compiler's to order
    np.testing.assert_array_equal(np.asarray(got_dq), np.asarray(want_dq))
    assert np.abs(np.asarray(got_dq)).max() > 0
    assert np.isfinite(float(got))
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    for g, w in zip(jax.tree.leaves(got_aux), jax.tree.leaves(want_aux)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-6, atol=1e-7)
