"""Model tests.

The central one is act/unroll parity: the reference's single-step forward
and sequence forwards are an UNCHECKED consistency assumption (SURVEY.md
section 4 'Model'); here it is pinned by test — stepping the network one
frame at a time must reproduce exactly the Q values the scan-based unroll
gathers, including the bootstrap view's edge-repeat clamp semantics.
"""

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from r2d2_tpu.config import R2D2Config, tiny_test
from r2d2_tpu.models.core import unpack_state
from r2d2_tpu.models.lstm import LSTM
from r2d2_tpu.models.r2d2 import R2D2Network, init_params


def make_net(cfg):
    net, params = init_params(jax.random.PRNGKey(0), cfg)
    return net, params


def rollout_sequential(net, params, obs, la, lr, hidden0):
    """Step `act` over every frame of (1, T, ...) inputs; return (T, A) Qs."""
    T = obs.shape[1]
    carry = (hidden0[:, 0], hidden0[:, 1])
    qs = []
    for t in range(T):
        q, carry = net.apply(params, obs[:, t], la[:, t], lr[:, t], carry, method=net.act)
        qs.append(np.asarray(q[0]))
    return np.stack(qs)


@pytest.fixture(scope="module")
def cfg():
    return tiny_test()


@pytest.fixture(scope="module")
def net_params(cfg):
    return make_net(cfg)


def random_inputs(cfg, rng, B=1):
    T = cfg.seq_len
    obs = rng.integers(0, 255, size=(B, T, *cfg.obs_shape), dtype=np.uint8)
    la = rng.integers(0, cfg.action_dim, size=(B, T)).astype(np.int32)
    lr = rng.normal(size=(B, T)).astype(np.float32)
    hid = rng.normal(size=(B, 2, cfg.hidden_dim)).astype(np.float32)
    return jnp.asarray(obs), jnp.asarray(la), jnp.asarray(lr), jnp.asarray(hid)


def test_act_unroll_parity_learning_view(cfg, net_params):
    net, params = net_params
    rng = np.random.default_rng(0)
    obs, la, lr, hid = random_inputs(cfg, rng)
    burn, learn, fwd = cfg.burn_in_steps, cfg.learning_steps, cfg.forward_steps

    qs_seq = rollout_sequential(net, params, obs, la, lr, hid)
    q_learn, q_boot, mask = net.apply(
        params, obs, la, lr, hid,
        jnp.array([burn], jnp.int32), jnp.array([learn], jnp.int32), jnp.array([fwd], jnp.int32),
    )
    for t in range(learn):
        np.testing.assert_allclose(np.asarray(q_learn[0, t]), qs_seq[burn + t], atol=2e-3)
    np.testing.assert_array_equal(np.asarray(mask[0]), np.ones(learn))


def test_bootstrap_view_edge_repeat(cfg, net_params):
    """forward < F_max: the bootstrap gather must clamp at the sequence's
    last valid output — the reference's edge-repeat (model.py:141-150)."""
    net, params = net_params
    rng = np.random.default_rng(1)
    obs, la, lr, hid = random_inputs(cfg, rng)
    burn, learn = cfg.burn_in_steps, cfg.learning_steps
    fwd = 1  # tail sequence: only 1 forward step available

    qs_seq = rollout_sequential(net, params, obs, la, lr, hid)
    _, q_boot, _ = net.apply(
        params, obs, la, lr, hid,
        jnp.array([burn], jnp.int32), jnp.array([learn], jnp.int32), jnp.array([fwd], jnp.int32),
    )
    seq_end = burn + learn + fwd
    for t in range(learn):
        want_idx = min(burn + cfg.forward_steps + t, seq_end - 1)
        np.testing.assert_allclose(np.asarray(q_boot[0, t]), qs_seq[want_idx], atol=2e-3)


def test_short_sequence_mask(cfg, net_params):
    net, params = net_params
    rng = np.random.default_rng(2)
    obs, la, lr, hid = random_inputs(cfg, rng)
    learn = 2  # ragged tail
    _, _, mask = net.apply(
        params, obs, la, lr, hid,
        jnp.array([0], jnp.int32), jnp.array([learn], jnp.int32), jnp.array([1], jnp.int32),
    )
    np.testing.assert_array_equal(np.asarray(mask[0]), [1, 1, 0, 0])


def test_batched_heterogeneous_windows(cfg, net_params):
    """Rows with different burn-in/learning/forward in one batch must each
    match their own sequential rollout (pack_padded_sequence replacement)."""
    net, params = net_params
    rng = np.random.default_rng(3)
    obs, la, lr, hid = random_inputs(cfg, rng, B=3)
    burn = jnp.array([0, 2, 4], jnp.int32)
    learn = jnp.array([4, 4, 2], jnp.int32)
    fwd = jnp.array([2, 2, 1], jnp.int32)

    q_learn, q_boot, mask = net.apply(params, obs, la, lr, hid, burn, learn, fwd)
    for i in range(3):
        qs_seq = rollout_sequential(net, params, obs[i : i + 1], la[i : i + 1], lr[i : i + 1], hid[i : i + 1])
        for t in range(int(learn[i])):
            np.testing.assert_allclose(np.asarray(q_learn[i, t]), qs_seq[int(burn[i]) + t], atol=2e-3)
            want = min(int(burn[i]) + cfg.forward_steps + t, int(burn[i] + learn[i] + fwd[i]) - 1)
            np.testing.assert_allclose(np.asarray(q_boot[i, t]), qs_seq[want], atol=2e-3)
        np.testing.assert_array_equal(np.asarray(mask[i]), (np.arange(cfg.learning_steps) < int(learn[i])))


def test_lstm_scan_chunk_equivalence():
    """Remat-chunked long scan must be numerically identical to the plain
    scan (long-context preset machinery, SURVEY.md section 5.7)."""
    H, B, T, D = 8, 2, 16, 5
    xs = jnp.asarray(np.random.default_rng(0).normal(size=(B, T, D)).astype(np.float32))
    carry = (jnp.zeros((B, H)), jnp.zeros((B, H)))
    plain = LSTM(H, in_dim=D)
    params = plain.init(jax.random.PRNGKey(0), xs, carry)
    out1, (h1, c1) = plain.apply(params, xs, carry)
    chunked = LSTM(H, in_dim=D, scan_chunk=4)
    out2, (h2, c2) = chunked.apply(params, xs, carry)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-6)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=1e-6)


def test_nature_encoder_reference_shapes():
    """84x84 trunk must flatten to 3136 features like the reference
    (model.py:55: Linear(3136, 512))."""
    from r2d2_tpu.models.encoders import NatureEncoder

    enc = NatureEncoder(latent_dim=512)
    x = jnp.zeros((2, 84, 84, 1))
    params = enc.init(jax.random.PRNGKey(0), x)
    # conv stack output before the dense: (2, 7, 7, 64) -> 3136
    dense_kernel = params["params"]["Dense_0"]["kernel"]
    assert dense_kernel.shape == (3136, 512)


def test_impala_encoder_runs():
    from r2d2_tpu.models.encoders import ImpalaEncoder

    enc = ImpalaEncoder(latent_dim=256)
    x = jnp.zeros((2, 64, 64, 3))
    params = enc.init(jax.random.PRNGKey(0), x)
    y = enc.apply(params, x)
    assert y.shape == (2, 256)


def test_bfloat16_compute_path():
    cfg = tiny_test().replace(compute_dtype="bfloat16")
    net, params = make_net(cfg)
    rng = np.random.default_rng(4)
    obs, la, lr, hid = random_inputs(cfg, rng)
    ones = jnp.ones((1,), jnp.int32)
    q_learn, q_boot, mask = net.apply(
        params, obs, la, lr, hid, ones * cfg.burn_in_steps, ones * cfg.learning_steps, ones * cfg.forward_steps
    )
    # heads must still emit float32 (loss math stays f32)
    assert q_learn.dtype == jnp.float32
    assert np.isfinite(np.asarray(q_learn)).all()


def test_model_presets_grow_the_brain():
    """config.MODEL_PRESETS: named model sizes. Applying one changes
    exactly the fields it names; encoder_depth grows real Dense layers."""
    from r2d2_tpu.config import MODEL_PRESETS, apply_model_preset

    base = tiny_test()
    assert apply_model_preset(base, "base") .hidden_dim == base.hidden_dim
    wide = apply_model_preset(base, "wide")
    assert wide.hidden_dim == 1024 and wide.model_preset == "wide"
    deep = apply_model_preset(base, "deep")
    assert deep.encoder_depth == 2 and deep.hidden_dim == base.hidden_dim
    assert set(MODEL_PRESETS) >= {"base", "wide", "xl", "deep", "deep_wide"}
    with pytest.raises(ValueError, match="model_preset"):
        base.replace(model_preset="nope")


def test_encoder_depth_adds_dense_layers():
    cfg = tiny_test().replace(encoder_depth=2)
    net, params = make_net(cfg)
    enc = params["params"]["enc"]
    assert {"Dense_0", "Dense_1", "Dense_2"} <= set(enc)
    # extra layers are square latent->latent and REPLICATED under tp (no
    # sharding rule claims Dense_1+ — pinned so the manual-tp step's
    # grad psum grouping stays correct)
    from r2d2_tpu.parallel.sharding_map import DEFAULT_RULES, match_axes

    assert enc["Dense_1"]["kernel"].shape == (cfg.hidden_dim, cfg.hidden_dim)
    assert match_axes("params.enc.Dense_1.kernel", DEFAULT_RULES) == ()
    rng = np.random.default_rng(7)
    obs, la, lr, hid = random_inputs(cfg, rng)
    ones = jnp.ones((1,), jnp.int32)
    q_learn, _, _ = net.apply(
        params, obs, la, lr, hid,
        ones * cfg.burn_in_steps, ones * cfg.learning_steps, ones * cfg.forward_steps,
    )
    assert np.isfinite(np.asarray(q_learn)).all()


# ---------------------------------------------------------------------------
# Behind the LSTM's burn-in seam the encoder differentiates each row's L + F
# frames from the seam only (PR 32). The one-call form `unroll` had until then
# lives HERE, not in the package: it is what the split has to equal, values
# and gradients.


def _views_from_core_input(m, x, hid, burn, learn, fwd):
    """Core + both Q views from a time-ordered core input (B, T, D)."""
    outs, _ = m.core(x, (hid[:, 0], hid[:, 1]), burn_in=burn)
    q_learn, q_boot = indexed_tail(m, outs, burn, learn, fwd)
    t = jnp.arange(m.learning_steps, dtype=jnp.int32)
    return q_learn, q_boot, (t[None, :] < learn[:, None]).astype(jnp.float32)


def one_call_unroll(m, obs, la, lr, hid, burn, learn, fwd):
    """Every frame of the batch through the encoder in ONE call, all of it
    differentiated: `unroll` as it was before the split."""
    B, T = obs.shape[:2]
    x = m._core_input(
        obs.reshape(B * T, *obs.shape[2:]), la.reshape(B * T), lr.reshape(B * T)
    ).reshape(B, T, -1)
    return _views_from_core_input(m, x, hid, burn, learn, fwd)


def learner_like_loss(views, action, reward, boot_weight=None):
    """Double-Q TD loss as learner.make_loss_fn reads the views: `q_learn`
    is differentiated, `q_boot` selects and evaluates under stop_gradient.
    With `boot_weight` (B, L, A) a term that differentiates `q_boot` too:
    `unroll` must serve a loss that does."""
    q_learn, q_boot, mask = views
    extra = 0.0 if boot_weight is None else jnp.mean(q_boot * boot_weight)
    q_boot = jax.lax.stop_gradient(q_boot)
    a_star = jnp.argmax(q_boot, axis=-1)
    y = reward + 0.99 * jnp.take_along_axis(q_boot, a_star[..., None], axis=-1)[..., 0]
    td = y - jnp.take_along_axis(q_learn, action[..., None], axis=-1)[..., 0]
    return jnp.sum(jnp.square(td) * mask) / jnp.sum(mask) + extra


@pytest.mark.parametrize("encoder", ["nature", "mlp"])
def test_window_split_equals_the_one_call_form_in_values_and_every_gradient(encoder):
    # the benchmark's window geometry (burn-in 40 + learning 40 + n-step 5)
    # at a width the CPU unrolls in seconds
    cfg = tiny_test().replace(
        burn_in_steps=40, learning_steps=40, forward_steps=5, block_length=80,
        encoder=encoder, obs_shape=(36, 36, 1) if encoder == "nature" else (12, 12, 1),
    )
    net, params = make_net(cfg)
    assert net.core.cuts_at_burn_in
    rng = np.random.default_rng(32)
    B, L = 4, cfg.learning_steps
    obs, la, lr, hid = random_inputs(cfg, rng, B=B)
    burn = jnp.array([0, 40, 17, 40], jnp.int32)   # per-row seams in one batch
    learn = jnp.array([40, 40, 40, 23], jnp.int32)  # last row: learning < L
    fwd = jnp.array([5, 5, 5, 2], jnp.int32)
    action = jnp.asarray(rng.integers(0, cfg.action_dim, size=(B, L)).astype(np.int32))
    reward = jnp.asarray(rng.normal(size=(B, L)).astype(np.float32))
    boot_weight = jnp.asarray(rng.normal(size=(B, L, cfg.action_dim)).astype(np.float32))

    def run(method):
        def loss(p):
            views = net.apply(p, obs, la, lr, hid, burn, learn, fwd, method=method)
            return learner_like_loss(views, action, reward, boot_weight), views

        return jax.jit(jax.value_and_grad(loss, has_aux=True))(params)

    (loss_split, views_split), g_split = run(net.unroll)
    (loss_one, views_one), g_one = run(one_call_unroll)
    for got, want in zip(views_split, views_one):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(loss_split), float(loss_one), rtol=1e-5)
    flat_split = jax.tree_util.tree_leaves_with_path(g_split)
    flat_one = jax.tree_util.tree_leaves_with_path(g_one)
    assert [p for p, _ in flat_split] == [p for p, _ in flat_one]
    for (path, got), (_, want) in zip(flat_split, flat_one):
        want = np.asarray(want)
        assert np.abs(want).max() > 0, jax.tree_util.keystr(path)  # every parameter is reached
        np.testing.assert_allclose(
            np.asarray(got), want, rtol=1e-5, atol=1e-5 * np.abs(want).max(),
            err_msg=jax.tree_util.keystr(path),
        )


@pytest.mark.parametrize("core", ["scan", "pallas", "lru"])
def test_core_input_cotangent_is_zero_outside_the_window_only_behind_a_seam(core):
    """The premise of the split, in the one-call form: behind the LSTM's
    burn-in seam (scan and Pallas alike) the core's input has a cotangent of
    exactly 0.0 outside [burn_in[b], burn_in[b] + L + F) whatever the loss
    does with the views, and outside [burn_in[b], burn_in[b] + L) under the
    learner's loss (`q_boot` behind stop_gradient: the n-step tail's five
    frames that the split still differentiates). The LRU core publishes no
    seam and back-propagates through burn-in: it must keep the one call, and
    a later core without a seam cannot take the split unnoticed."""
    cfg = tiny_test()
    cfg = (
        cfg.replace(recurrent_core="lru") if core == "lru"
        else cfg.replace(lstm_backend=core)
    )
    net, params = make_net(cfg)
    rng = np.random.default_rng(5)
    B, T, L, F = 3, cfg.seq_len, cfg.learning_steps, cfg.forward_steps
    _, _, _, hid = random_inputs(cfg, rng, B=B)
    x = jnp.asarray(rng.normal(size=(B, T, cfg.hidden_dim + cfg.action_dim + 1)).astype(np.float32))
    burn = jnp.array([0, 2, 4], jnp.int32)
    learn = jnp.array([4, 4, 3], jnp.int32)
    fwd = jnp.array([2, 2, 1], jnp.int32)
    action = jnp.asarray(rng.integers(0, cfg.action_dim, size=(B, L)).astype(np.int32))
    reward = jnp.asarray(rng.normal(size=(B, L)).astype(np.float32))
    boot_weight = jnp.asarray(rng.normal(size=(B, L, cfg.action_dim)).astype(np.float32))
    t = np.arange(T)[None, :]
    b = np.asarray(burn)[:, None]

    for weight, width in ((boot_weight, L + F), (None, L)):
        def loss(x):
            views = net.apply(params, x, hid, burn, learn, fwd, method=_views_from_core_input)
            return learner_like_loss(views, action, reward, weight)

        dx = np.asarray(jax.grad(loss)(x))
        inside = (t >= b) & (t < b + width)
        assert all(np.abs(dx[i][inside[i]]).max() > 0 for i in range(B))
        after = np.broadcast_to((t >= b + width)[:, :, None], dx.shape)
        below = np.broadcast_to((t < b)[:, :, None], dx.shape)
        assert (dx[after] == 0.0).all()  # an output's cotangent never moves forward in time
        if core == "lru":
            assert np.abs(dx[below]).max() > 0  # no seam: burn-in frames carry gradient
        else:
            assert (dx[below] == 0.0).all()


def test_parameter_tree_is_the_parents():
    """Names and shapes as every checkpoint, `tests/fixtures/` snapshot and
    `benchmark/reference/model.py` read them: two encoder calls share ONE set
    of encoder parameters."""
    from r2d2_tpu.config import default_atari

    cfg = default_atari()
    shapes = jax.eval_shape(lambda k: init_params(k, cfg)[1], jax.random.PRNGKey(0))
    got = {
        jax.tree_util.keystr(p): tuple(v.shape)
        for p, v in jax.tree_util.tree_leaves_with_path(shapes)
    }
    H, A = cfg.hidden_dim, cfg.action_dim
    assert (H, A) == (512, 9)
    want = {}
    for name, kernel in [
        ("enc']['Conv_0", (8, 8, 1, 32)), ("enc']['Conv_1", (4, 4, 32, 64)),
        ("enc']['Conv_2", (3, 3, 64, 64)), ("enc']['Dense_0", (3136, H)),
        ("adv_hidden", (H, H)), ("adv_out", (H, A)), ("val_hidden", (H, H)), ("val_out", (H, 1)),
    ]:
        want[f"['params']['{name}']['kernel']"] = kernel
        want[f"['params']['{name}']['bias']"] = kernel[-1:]
    want["['params']['core']['wi']"] = (H + A + 1, 4 * H)
    want["['params']['core']['wh']"] = (H, 4 * H)
    want["['params']['core']['b']"] = (4 * H,)
    assert got == want


# ---------------------------------------------------------------------------
# `_core_input` behind the LSTM's seam (PR 49): the encoder's two sub-batches
# go back to time order with no index (`r2d2._time_order`: two static slices
# of the no-gradient part under a select, the window by a selection matmul),
# and the one-hot action and the reward never leave time order. The indexed
# re-ordering it replaced lives HERE, as the oracle.


def indexed_core_input(m, obs, last_action, last_reward, burn_in):
    """`_core_input(..., burn_in)` until PR 49, to the letter: frames, actions
    and rewards gathered by each part's flat index, the two 516-wide parts
    concatenated and `take_along_axis` over B x T rows."""
    dtype = jnp.dtype(m.compute_dtype)

    def encode(obs, last_action, last_reward):
        latent = m.enc(obs.astype(dtype) / 255.0)
        onehot = jax.nn.one_hot(last_action, m.action_dim, dtype=dtype)
        return jnp.concatenate([latent, onehot, last_reward.astype(dtype)[:, None]], axis=-1)

    B, T = obs.shape[:2]
    W = m.learning_steps + m.forward_steps
    start = jnp.clip(burn_in, 0, T - W).astype(jnp.int32)[:, None]
    window = start + jnp.arange(W, dtype=jnp.int32)[None, :]
    c = jnp.arange(T - W, dtype=jnp.int32)[None, :]
    others = jnp.where(c < start, c, c + W)
    row0 = jnp.arange(B, dtype=jnp.int32)[:, None] * T
    frames = obs.reshape(B * T, -1)
    actions, rewards = last_action.reshape(B * T), last_reward.reshape(B * T)

    def encode_at(idx):
        flat = (row0 + idx).reshape(-1)
        take = lambda a: jnp.take(a, flat, axis=0, mode="clip")
        return encode(
            take(frames).reshape(-1, *obs.shape[2:]), take(actions), take(rewards)
        ).reshape(B, idx.shape[1], -1)

    x = jnp.concatenate([encode_at(window), jax.lax.stop_gradient(encode_at(others))], axis=1)
    t = jnp.arange(T, dtype=jnp.int32)[None, :]
    pos = jnp.where(t < start, W + t, jnp.where(t < start + W, t - start, t))
    return jnp.take_along_axis(x, pos[:, :, None], axis=1)


# geometry -> (burn-in, learning, n-step): T = 10 with a window of 6, and the
# benchmark cells' T = 85 with a window of 45 behind a toy encoder
_SEAM_GEOMETRY = {"T10-W6": (4, 4, 2), "T85-W45": (40, 40, 5)}


def _seam_burn_in(rows, T, W):
    """The seams of one batch: both ends of the clip's range, one past it
    (no preset stores it; the clip answers all the same), or all in one batch."""
    return {
        "seam0": [0, 0, 0], "seamT-W": [T - W] * 3, "past-the-clip": [T - W + 3, T, T - W + 1],
        "mixed": [0, T - W, (T - W) // 2, T - W + 2, 1],
    }[rows]


@functools.lru_cache(maxsize=None)
def _seam_case(geometry, dtype):
    burn, learn, fwd = _SEAM_GEOMETRY[geometry]
    cfg = tiny_test().replace(
        burn_in_steps=burn, learning_steps=learn, forward_steps=fwd, block_length=2 * learn,
        encoder="mlp", obs_shape=(12, 12, 1), precision="bf16" if dtype == "bfloat16" else "fp32")
    net, params = make_net(cfg)
    assert net.core.cuts_at_burn_in and cfg.seq_len > learn + fwd

    def compiled(method):
        def weighed(p, obs, la, lr, burn_in, weight):
            x = net.apply(p, obs, la, lr, burn_in, method=method)
            return jnp.sum(x.astype(jnp.float32) * weight), x

        return jax.jit(jax.value_and_grad(weighed, has_aux=True))

    return cfg, params, compiled(R2D2Network._core_input), compiled(indexed_core_input)


@pytest.mark.parametrize("rows", ["seam0", "seamT-W", "past-the-clip", "mixed"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("geometry", list(_SEAM_GEOMETRY))
def test_core_input_behind_the_seam_is_the_indexed_formula_in_values_and_gradients(geometry, dtype, rows):
    cfg, params, committed, indexed = _seam_case(geometry, dtype)
    T, W = cfg.seq_len, cfg.learning_steps + cfg.forward_steps
    assert (T, W) == {"T10-W6": (10, 6), "T85-W45": (85, 45)}[geometry]
    burn_in = jnp.asarray(_seam_burn_in(rows, T, W), jnp.int32)
    rng = np.random.default_rng(49)
    obs, la, lr, _ = random_inputs(cfg, rng, B=len(burn_in))
    weight = jnp.asarray(rng.normal(size=(len(burn_in), T, cfg.hidden_dim + cfg.action_dim + 1)).astype(np.float32))
    ((_, got), g_got), ((_, want), g_want) = (
        f(params, obs, la, lr, burn_in, weight) for f in (committed, indexed))
    assert got.dtype == jnp.dtype(dtype) and got.shape == weight.shape
    # every entry is the one the index read (`==` takes a zero of either sign)
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    reached = 0
    for (path, g), (_, w) in zip(
            jax.tree_util.tree_leaves_with_path(g_got), jax.tree_util.tree_leaves_with_path(g_want)):
        g, w = np.asarray(g), np.asarray(w)
        reached += bool(np.abs(w).max() > 0)
        # each cotangent row is the one term the scatter-add wrote
        np.testing.assert_array_equal(g, w, err_msg=jax.tree_util.keystr(path))
    assert reached >= 2  # the encoder's kernel and bias, at least


@pytest.mark.parametrize("geometry", list(_SEAM_GEOMETRY))
def test_core_inputs_last_columns_are_the_time_ordered_actions_and_rewards(geometry):
    cfg, params, committed, _ = _seam_case(geometry, "float32")
    T, W, A = cfg.seq_len, cfg.learning_steps + cfg.forward_steps, cfg.action_dim
    burn_in = jnp.asarray(_seam_burn_in("mixed", T, W), jnp.int32)
    rng = np.random.default_rng(50)
    obs, la, lr, _ = random_inputs(cfg, rng, B=len(burn_in))
    (_, x), _ = committed(params, obs, la, lr, burn_in, jnp.zeros((len(burn_in), T, cfg.hidden_dim + A + 1)))
    x = np.asarray(x)
    np.testing.assert_array_equal(x[..., -A - 1:-1], np.eye(A, dtype=np.float32)[np.asarray(la)])
    np.testing.assert_array_equal(x[..., -1], np.asarray(lr))
    # and the latent columns are each frame's own encoding, whatever its seam
    net = R2D2Network.from_config(cfg)
    one_call = net.apply(
        params, obs.reshape(-1, *cfg.obs_shape), la.reshape(-1), lr.reshape(-1), method="_core_input")
    np.testing.assert_allclose(x, np.asarray(one_call).reshape(x.shape), rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Behind the seam each part's gathered frames reach conv1 as bytes in frame
# shape, behind an `optimization_barrier` (PR 50: on the chip the convert then
# sits inside the conv fusions, not in a bf16 copy both nets read). A barrier
# changes no value: the form without it, where the compiler was free to make
# the convert once on the flat rows, lives HERE as the plain reference.


def shared_convert_core_input(m, obs, last_action, last_reward, burn_in=None):
    """`_core_input` until PR 50, to the letter."""
    from r2d2_tpu.models.r2d2 import _time_order

    dtype = jnp.dtype(m.compute_dtype)

    def encode(obs):
        return m.enc(obs.astype(dtype) / 255.0)

    def beside(latent):
        onehot = jax.nn.one_hot(last_action, m.action_dim, dtype=dtype)
        return jnp.concatenate([latent, onehot, last_reward.astype(dtype)[..., None]], axis=-1)

    if burn_in is None:
        return beside(encode(obs))
    B, T = obs.shape[:2]
    W = m.learning_steps + m.forward_steps
    start = jnp.clip(burn_in, 0, T - W).astype(jnp.int32)[:, None]
    window = start + jnp.arange(W, dtype=jnp.int32)[None, :]
    c = jnp.arange(T - W, dtype=jnp.int32)[None, :]
    others = jnp.where(c < start, c, c + W)
    row0 = jnp.arange(B, dtype=jnp.int32)[:, None] * T
    frames = obs.reshape(B * T, -1)

    def encode_at(idx):
        taken = jnp.take(frames, (row0 + idx).reshape(-1), axis=0, mode="clip")
        return encode(taken.reshape(-1, *obs.shape[2:])).reshape(B, idx.shape[1], -1)

    x = beside(_time_order(encode_at(window), jax.lax.stop_gradient(encode_at(others)), start[:, 0]))
    return jax.lax.optimization_barrier(x)


def shared_convert_unroll(m, obs, la, lr, hid, burn, learn, fwd):
    """`unroll` around the reference above: the seam's split where the core
    cuts at burn-in, the one call (`burn_in=None`) where it does not."""
    B, T = obs.shape[:2]
    if m.core.cuts_at_burn_in:
        x = shared_convert_core_input(m, obs, la, lr, burn)
    else:
        x = shared_convert_core_input(
            m, obs.reshape(B * T, *obs.shape[2:]), la.reshape(B * T), lr.reshape(B * T)
        ).reshape(B, T, -1)
    outs, _ = m.core(x, unpack_state(hid), burn_in=burn)
    q_learn, q_boot = m._dueling_window(outs, burn, learn, fwd)
    mask = (jnp.arange(m.learning_steps, dtype=jnp.int32)[None, :] < learn[:, None]).astype(jnp.float32)
    return q_learn, q_boot, mask


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("burn_in", ["given", "None"])
def test_unroll_is_the_shared_convert_forms_in_outputs_and_gradients_bit_for_bit(burn_in, dtype):
    """`burn_in` given: the LSTM core, whose seam takes the changed branch of
    `_core_input`; `None`: the LRU core, whose one call must be what it was."""
    cfg = tiny_test().replace(
        encoder="nature", obs_shape=(36, 36, 1), precision="bf16" if dtype == "bfloat16" else "fp32",
        recurrent_core="lstm" if burn_in == "given" else "lru")
    net, params = make_net(cfg)
    assert net.core.cuts_at_burn_in == (burn_in == "given")
    assert jnp.dtype(net.compute_dtype) == jnp.dtype(dtype)
    T, L, F = cfg.seq_len, cfg.learning_steps, cfg.forward_steps
    assert T > L + F  # the seam's split engages
    rng = np.random.default_rng(50)
    B = 4
    obs, la, lr, hid = random_inputs(cfg, rng, B=B)
    burn = jnp.array([0, T - L - F, 1, T - L - F + 2], jnp.int32)  # the last: past the clip
    learn = jnp.array([L, L, L - 1, L], jnp.int32)
    fwd = jnp.array([F, F, 1, F], jnp.int32)
    action = jnp.asarray(rng.integers(0, cfg.action_dim, size=(B, L)).astype(np.int32))
    reward = jnp.asarray(rng.normal(size=(B, L)).astype(np.float32))
    boot_weight = jnp.asarray(rng.normal(size=(B, L, cfg.action_dim)).astype(np.float32))

    def run(method):
        def loss(p):
            views = net.apply(p, obs, la, lr, hid, burn, learn, fwd, method=method)
            return learner_like_loss(views, action, reward, boot_weight), views

        return jax.jit(jax.value_and_grad(loss, has_aux=True))(params)

    (loss_got, views_got), g_got = run(net.unroll)
    (loss_want, views_want), g_want = run(shared_convert_unroll)
    for got, want in zip(views_got, views_want):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert float(loss_got) == float(loss_want)
    reached = 0
    for (path, g), (_, w) in zip(
            jax.tree_util.tree_leaves_with_path(g_got), jax.tree_util.tree_leaves_with_path(g_want)):
        reached += bool(np.abs(np.asarray(w)).max() > 0)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=jax.tree_util.keystr(path))
    assert reached == len(jax.tree_util.tree_leaves(g_want))  # every parameter is reached


# ---------------------------------------------------------------------------
# The tail of `unroll` (PR 46): each row's learning and bootstrap positions are
# ONE window of the core's outputs, moved without an index per (row, step), and
# the heads run once over it. The indexed formula it replaced lives HERE, as
# the oracle, written out with `take_along_axis`.

_TAIL_CFG = tiny_test()  # burn-in 4 + learning 4 + n-step 2: T = 10, window 6
_TAIL_ROWS = list(itertools.product(
    (0, 1, _TAIL_CFG.burn_in_steps - 1, _TAIL_CFG.burn_in_steps),      # burn_in
    (1, _TAIL_CFG.learning_steps - 1, _TAIL_CFG.learning_steps),       # learning
    (0, 1, _TAIL_CFG.forward_steps),                                   # forward
))


def _tail_indices(m, T, burn, learn, fwd):
    L, F = m.learning_steps, m.forward_steps
    t = jnp.arange(L, dtype=jnp.int32)
    learn_idx = jnp.clip(burn[:, None] + t[None, :], 0, T - 1)
    boot_idx = jnp.minimum(burn[:, None] + F + t[None, :], (burn + learn + fwd)[:, None] - 1)
    return learn_idx, jnp.clip(boot_idx, 0, T - 1)


def indexed_tail(m, outs, burn, learn, fwd, task=None):
    """`unroll`'s tail until PR 46, to the letter: two gathers of B x L rows,
    the heads over each."""
    learn_idx, boot_idx = _tail_indices(m, outs.shape[1], burn, learn, fwd)
    return (m._dueling(jnp.take_along_axis(outs, learn_idx[:, :, None], axis=1), task),
            m._dueling(jnp.take_along_axis(outs, boot_idx[:, :, None], axis=1), task))


def indexed_tail_one_head_call(m, outs, burn, learn, fwd, task=None):
    """The same positions, every one by its index, with the heads called on the
    rows `_dueling_window` calls them on (each row's window of L + F): a CPU
    matmul rounds a row's last bit by how many rows it is given, so THIS is
    what can be equal to the bit."""
    T = outs.shape[1]
    W = m.learning_steps + m.forward_steps
    at = jnp.clip(burn[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :], 0, T - 1)
    q = m._dueling(jnp.take_along_axis(outs, at[:, :, None], axis=1), task)
    # where in the window each index sits: the first position that holds it
    return tuple(
        jnp.take_along_axis(q, jnp.argmax(at[:, None, :] == idx[:, :, None], axis=-1)[:, :, None], axis=1)
        for idx in _tail_indices(m, T, burn, learn, fwd))


@functools.lru_cache(maxsize=None)
def _tail_case(core, tasks, dtype):
    """One batch whose rows are `_TAIL_ROWS`, its core's outputs, and jitted
    values and per-row gradients of the tail, the oracle and the oracle with
    the tail's head call."""
    cfg = _TAIL_CFG.replace(
        recurrent_core=core, precision="bf16" if dtype == "bfloat16" else "fp32",
        **({"num_tasks": 2, "task_action_dims": (2, 4)} if tasks == "multi" else {}))
    net, params = make_net(cfg)
    rng = np.random.default_rng(46)
    B, T = len(_TAIL_ROWS), cfg.seq_len
    burn, learn, fwd = (jnp.asarray(c, jnp.int32) for c in zip(*_TAIL_ROWS))
    task = jnp.asarray(rng.integers(0, 2, size=B), jnp.int32) if tasks == "multi" else None
    x = jnp.asarray(rng.normal(size=(B, T, cfg.hidden_dim + cfg.action_dim + 1)).astype(np.float32))
    hid = jnp.asarray(rng.normal(size=(B, 2, cfg.hidden_dim)).astype(np.float32))
    outs = net.apply(params, x, hid, method=lambda m, x, hid: m.core(x, unpack_state(hid), burn_in=burn)[0])
    assert outs.dtype == jnp.dtype(dtype) and outs.shape == (B, T, cfg.hidden_dim)
    weights = [jnp.asarray(rng.normal(size=(B, cfg.learning_steps, cfg.action_dim)).astype(np.float32))
               for _ in range(2)]

    def compiled(method):
        views = lambda p, o: net.apply(p, o, burn, learn, fwd, task, method=method)

        def of_row(p, o, row):
            # the -1e9 floor is a constant under the select: keep it out of the sum's scale
            return sum(jnp.sum(jnp.where(q > -1e8, q * w, 0.0) * row[:, None, None])
                       for q, w in zip(views(p, o), weights))

        return jax.jit(views), jax.jit(jax.grad(of_row, argnums=(0, 1)))

    forms = {name: compiled(method) for name, method in [
        ("tail", R2D2Network._dueling_window), ("indexed", indexed_tail),
        ("one_head_call", indexed_tail_one_head_call)]}
    values = {name: jax.device_get(views(params, outs)) for name, (views, _) in forms.items()}
    return cfg, net, params, outs, task, values, {name: g for name, (_, g) in forms.items()}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("tasks", ["single", "multi"])
@pytest.mark.parametrize("core", ["lstm", "lru"])
@pytest.mark.parametrize("row", range(len(_TAIL_ROWS)), ids=["burn{}-learn{}-fwd{}".format(*r) for r in _TAIL_ROWS])
def test_the_window_tail_is_the_indexed_formula_in_values_and_gradients(row, core, tasks, dtype):
    cfg, net, params, outs, task, values, grads = _tail_case(core, tasks, dtype)
    burn, learn, fwd = _TAIL_ROWS[row]
    for view in (0, 1):
        got = values["tail"][view][row]
        assert got.dtype == np.float32 and got.shape == (cfg.learning_steps, cfg.action_dim)
        # to the bit where the heads are called on the same rows ...
        np.testing.assert_array_equal(got, values["one_head_call"][view][row])
        # ... and to a matmul's last bit against the formula as it stood
        np.testing.assert_allclose(got, values["indexed"][view][row], rtol=2e-6, atol=2e-6)
    if tasks == "multi":  # the floor survives the select, on the invalid actions only
        floor = np.arange(cfg.action_dim) >= (2, 4)[int(task[row])]
        assert (values["tail"][1][row][:, floor] == -1e9).all()
        assert (values["tail"][1][row][:, ~floor] > -1e8).all()
    # the held bootstrap tail: from the row's last valid step on, one Q repeated
    last = learn + fwd - 1
    held = [l for l in range(cfg.learning_steps) if cfg.forward_steps + l >= last]
    assert held and all((values["tail"][1][row][l] == values["tail"][1][row][held[0]]).all() for l in held)

    only = jnp.zeros(len(_TAIL_ROWS), jnp.float32).at[row].set(1.0)
    (gp, go), (wp, wo) = grads["tail"](params, outs, only), grads["indexed"](params, outs, only)
    # a bf16 `outs` takes a bf16 cotangent: where views share a row (the held
    # tail: up to L + 1 of them) the oracle's scatter-add rounds after every
    # term, the band's matmul sums them in f32 and rounds once
    tol = 1e-6 if dtype == "float32" else 2.0 ** -5
    go, wo = np.asarray(go, np.float32), np.asarray(wo, np.float32)
    assert np.abs(wo[row]).max() > 0 and (go[np.arange(len(go)) != row] == 0).all()
    np.testing.assert_allclose(go, wo, rtol=0, atol=tol * np.abs(wo).max())
    # relative to the gradient's scale: a bias's entry is a sum that cancels
    scale = max(float(np.abs(leaf).max()) for leaf in jax.tree.leaves(wp))
    assert scale > 0
    for (path, got), (_, want) in zip(
            jax.tree_util.tree_leaves_with_path(gp), jax.tree_util.tree_leaves_with_path(wp)):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=0, atol=1e-6 * scale, err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("core", ["lstm", "lru"])
@pytest.mark.parametrize("burn", [5, 8, 9, 12, -1])
def test_the_window_tail_keeps_the_clips_answer_for_any_burn_in(burn, core):
    """No preset stores a `burn_in` with `burn_in + L + F > T`, and nothing in
    the tail rests on that: past the end (or before the start) of the sequence
    it reads what the clipped index read."""
    cfg, net, params, outs, _, _, _ = _tail_case(core, "single", "float32")
    assert cfg.seq_len == 10 and (burn + cfg.learning_steps + cfg.forward_steps > cfg.seq_len or burn < 0)
    B = outs.shape[0]
    b = jnp.full((B,), burn, jnp.int32)
    learn, fwd = (jnp.asarray(c, jnp.int32) for c in list(zip(*_TAIL_ROWS))[1:])
    got = net.apply(params, outs, b, learn, fwd, method="_dueling_window")
    want = net.apply(params, outs, b, learn, fwd, method=indexed_tail_one_head_call)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("preset", ["atari", "atari_v4_8", "procgen_impala", "long_context", "tiny_test"])
def test_every_presets_window_lies_inside_its_sequence(preset):
    """What the band's clip never has to repair: a stored `burn_in` is at most
    `cfg.burn_in_steps` (replay/accumulator.py), and T = burn-in + L + F."""
    from r2d2_tpu.config import PRESETS

    cfg = PRESETS[preset]()
    assert cfg.seq_len == cfg.burn_in_steps + cfg.learning_steps + cfg.forward_steps
