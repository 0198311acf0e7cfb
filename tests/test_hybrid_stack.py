"""The hybrid_stack core (models/hybrid_stack.py) at tiny widths on the CPU:
each layer and the whole unroll against the plain float32 reference
(benchmark/reference/nemotron_h.py) on seeded weights; `step` applied T times
against `unroll`; attention through the carried memory against full causal
attention; the share test (every chip's routed part plus the shared expert once
is the uncut layer); the static capacity's drops, counted; the mixture with
no queue where every token fits against the reference and the queue; the
collector that keeps the carry at the window starts alone against the one that
stacks it at every step, bit for bit, for every core; the collector that scans over the
stack's OPENED form (its layers' parts) against the one that steps the flat
row, bit for bit, and the joins it makes, counted; what the fused runner
publishes."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from r2d2_tpu.config import tiny_test
from r2d2_tpu.models import hybrid_stack as hs
from r2d2_tpu.models.core import close_carry, core_class, open_carry, state_spec, zero_carry
from r2d2_tpu.models.r2d2 import init_params

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY_CORE = dict(
    hidden_size=64, hybrid_override_pattern="EMEMEM*", mamba_num_heads=4, mamba_head_dim=16, ssm_state_size=16,
    n_groups=2, conv_kernel=4, chunk_size=8, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    n_routed_experts=16, num_experts_per_tok=2, moe_intermediate_size=32, moe_shared_expert_intermediate_size=64,
    routed_scaling_factor=2.5, norm_eps=1e-5, num_experts_held=4,
    time_step_min=0.001, time_step_max=0.1, time_step_floor=1e-4)


def tiny_cfg(**core):
    return tiny_test().replace(recurrent_core="hybrid_stack", hidden_dim=64, max_episode_steps=16,
                               core_config=dict(TINY_CORE, **core))


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(ROOT, "benchmark", "reference", "nemotron_h.py")
    spec = importlib.util.spec_from_file_location("reference_nemotron_h", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def built():
    cfg = tiny_cfg()
    net, params = init_params(jax.random.PRNGKey(0), cfg)
    # norm weights, biases and skips away from their initial 1 / 0, so that a
    # layer that forgot one is caught
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 200))
    params = jax.tree.map(lambda v: v + 0.1 * jax.random.normal(next(keys), v.shape), params)
    return cfg, net, params


def _inputs(cfg, B, T, seed=0, seen=5):
    rng = np.random.default_rng(seed)
    spec = hs.StackSpec.of(cfg)
    x = jnp.asarray(rng.normal(size=(B, T, cfg.hidden_dim + cfg.action_dim + 1)), jnp.float32)
    hidden = rng.normal(size=(B, 1, spec.state_size)).astype(np.float32) * 0.3
    n = sum(int(np.prod(shape)) for _, _, shape in spec.segments())
    hidden[:, 0, n - 2:n] = [0.0, float(seen)]
    hidden[:, 0, n:] = 0.0
    return x, jnp.asarray(hidden)


# ------------------------------------------------------------ config and state


def test_core_config_is_hashable_pairs_and_only_the_stack_takes_it():
    cfg = tiny_cfg()
    assert isinstance(cfg.core_config, tuple) and dict(cfg.core_config)["hidden_size"] == 64
    hash(cfg)
    with pytest.raises(ValueError, match="core_config"):
        tiny_test().replace(core_config={"hidden_size": 64})
    with pytest.raises(ValueError, match="core_config"):
        tiny_test().replace(recurrent_core="hybrid_stack")


@pytest.mark.parametrize("change,match", [
    (dict(expansion=2), "unknown keys"), (dict(hidden_size=32), "hidden_size"),
    (dict(hybrid_override_pattern="EMX"), "letters"), (dict(first_expert_held=14), "held experts"),
    (dict(n_groups=3), "divide")])
def test_a_wrong_core_config_is_refused_by_the_class(change, match):
    with pytest.raises(ValueError, match=match):
        hs.StackSpec.of(tiny_cfg(**change))
    missing = {k: v for k, v in TINY_CORE.items() if k != "head_dim"}
    with pytest.raises(ValueError, match="missing keys"):
        hs.StackSpec.of(tiny_test().replace(recurrent_core="hybrid_stack", hidden_dim=64, core_config=missing))


def test_the_carry_is_one_flat_row_of_whole_lanes_and_splits_back():
    cfg = tiny_cfg()
    spec = hs.StackSpec.of(cfg)
    (n, S), _ = state_spec(cfg)
    raw = 3 * (4 * 16 * 16 + 3 * (64 + 2 * 2 * 16)) + 2 * 16 * 2 * 16 + 2
    assert n == 1 and S == 128 * -(-raw // 128) and len(zero_carry(cfg, 3)) == 1
    flat = jnp.arange(2 * S, dtype=jnp.float32).reshape(2, S).at[:, raw:].set(0.0)
    assert np.array_equal(hs.join_state(spec, hs.split_state(spec, flat)), flat)
    # the count survives a bfloat16 store at any episode length a block holds
    for count in (0, 448, 513, 1023, 1605):
        pair = hs._count_pair(jnp.asarray([count])).astype(jnp.bfloat16).astype(jnp.float32)
        assert int(hs._count_of(pair)[0]) == count


# ----------------------------------------------------- layers against the reference


def _params_of(built, name):
    return built[2]["params"]["core"][name]


def _layer(built, name):
    spec = hs.StackSpec.of(built[0])
    kinds = {"ssm": hs.Mamba2Mixer, "moe": hs.ExpertMixture, "attention": hs.EpisodeAttention}
    return spec, kinds[name.split("_")[0]](spec, jnp.float32), _params_of(built, name)


def test_another_pattern_runs_and_matches_its_reference(ref):
    cfg = tiny_cfg(hybrid_override_pattern="M*E")
    net, params = init_params(jax.random.PRNGKey(3), cfg)
    assert set(params["params"]["core"]) == {"in_proj", "ssm_0", "attention_1", "moe_2", "final_norm"}
    x, hidden = _inputs(cfg, 2, 9, seen=4)
    outs, _ = net.core.apply({"params": params["params"]["core"]}, x, (hidden[:, 0],))
    np.testing.assert_allclose(outs, ref.stack_outputs(params["params"]["core"], x, hidden, ref.stack_of(cfg)),
                               rtol=2e-5, atol=2e-5)


def test_mamba_unroll_and_steps_against_the_reference_recurrence(built, ref):
    spec, layer, p = _layer(built, "ssm_1")
    s = ref.stack_of(built[0])
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(3, 21, 64)), jnp.float32)      # 21: not whole chunks of 8
    ssm = jnp.asarray(rng.normal(size=(3, 4, 16, 16)), jnp.float32)
    tail = jnp.asarray(rng.normal(size=(3, 3, spec.conv_dim)), jnp.float32)
    want, (want_ssm, want_tail) = ref.mamba_layer(p, x, ssm, tail, s)   # 21 steps: its blocks of 32 are padded too
    got, ssm_T, tail_T = layer.apply({"params": p}, x, ssm, tail)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(ssm_T, want_ssm, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(tail_T, want_tail, rtol=1e-6, atol=1e-6)
    h, c, ys = ssm, tail, []
    for t in range(x.shape[1]):
        y, h, c = layer.apply({"params": p}, x[:, t], h, c, method=layer.step)
        ys.append(y)
    np.testing.assert_allclose(jnp.stack(ys, 1), want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(ssm_T, h, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(tail_T, c, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("groups,width", [(1, 64), (2, 64), (8, 4096), (8, 200), (4, 132)])
def test_the_grouped_norm_on_x_as_it_lies_is_the_norm_of_the_reshaped_groups(groups, width):
    """`rms_norm(groups > 1)` takes the groups' statistics without the `(..,
    groups, width / groups)` view (PR 55): against that view, at the cell's
    4096 / 8, at widths that are no whole lane tiles, and at groups = 1 (the
    other callers' path, which keeps the view of one group)."""
    rng = np.random.default_rng(groups * width)
    x = jnp.asarray(rng.normal(size=(3, 7, width)) * rng.uniform(0.1, 10.0, size=(3, 7, 1)), jnp.float32)
    weight = jnp.asarray(rng.normal(size=(width,)), jnp.float32)
    parts = x.reshape(3, 7, groups, width // groups)
    want = (parts * jax.lax.rsqrt(jnp.mean(parts * parts, axis=-1, keepdims=True) + 1e-5)).reshape(x.shape) * weight
    np.testing.assert_allclose(hs.rms_norm(x, weight, 1e-5, groups=groups), want, rtol=2e-6, atol=2e-6)
    grad = lambda f: jax.grad(lambda v: jnp.sum(jnp.sin(f(v))))(x)
    np.testing.assert_allclose(
        grad(lambda v: hs.rms_norm(v, weight, 1e-5, groups=groups)),
        grad(lambda v: (v.reshape(parts.shape) * jax.lax.rsqrt(jnp.mean(
            v.reshape(parts.shape) ** 2, axis=-1, keepdims=True) + 1e-5)).reshape(x.shape) * weight),
        rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("steps", [1, 8, 128])
def test_the_time_minor_running_sum_is_cumsum(steps):
    rng = np.random.default_rng(steps)
    a = jnp.asarray(-rng.uniform(1e-4, 2.0, size=(2, 3, 4, steps)), jnp.float32)   # -exp(A_log) dt: one sign
    np.testing.assert_allclose(hs.running_sum(a), jnp.cumsum(a, axis=-1), rtol=1e-6, atol=0)


def _recurrence(x, dt, a_log, b, c, h0):
    """The recurrence itself over per-channel arrays, a step at a time."""
    B, T, _ = x.shape
    H, (_, _, P, N) = dt.shape[-1], h0.shape
    G = b.shape[-1] // N
    heads = lambda v: jnp.repeat(v.reshape(B, G, N), H // G, axis=1)

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = jnp.exp(-jnp.exp(a_log) * dt_t)[..., None, None] * h \
            + (dt_t[..., None] * x_t.reshape(B, H, P))[..., None] * heads(b_t)[:, :, None, :]
        return h, jnp.einsum("bhpn,bhn->bhp", h, heads(c_t)).reshape(B, H * P)

    h, y = jax.lax.scan(step, h0, tuple(jnp.swapaxes(v, 0, 1) for v in (x, dt, b, c)))
    return jnp.swapaxes(y, 0, 1), h


@pytest.mark.parametrize("T,chunk", [(21, 8), (5, 8), (16, 8), (1, 8)],
                         ids=["not_whole_chunks", "shorter_than_a_chunk", "whole_chunks", "one_step"])
def test_the_chunked_scan_and_its_gradient_against_the_recurrence(T, chunk):
    """`ssd_chunked` on per-channel arrays (time-minor inside, PR 55) against
    the loop over time, forward and `jax.grad`, where the sequence is padded
    to whole chunks, where it is shorter than one, and where it is neither."""
    B, H, P, N, G = 3, 4, 16, 16, 2
    rng = np.random.default_rng(T)
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    x, b, c, h0 = normal(B, T, H * P), normal(B, T, G * N), normal(B, T, G * N), normal(B, H, P, N)
    dt = jnp.asarray(rng.uniform(0.001, 0.5, size=(B, T, H)), jnp.float32)
    a_log = jnp.log(jnp.asarray(rng.uniform(1.0, 16.0, size=(H,)), jnp.float32))
    args = (x, dt, a_log, b, c, h0)
    chunked = lambda *a: hs.ssd_chunked(*a, chunk, jnp.float32)
    for got, want in zip(chunked(*args), _recurrence(*args)):
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    weigh = normal(B, T, H * P), normal(B, H, P, N)
    scalar = lambda f: lambda *a: sum(jnp.sum(w * o) for w, o in zip(weigh, f(*a)))
    got, want = (jax.grad(scalar(f), argnums=tuple(range(6)))(*args) for f in (chunked, _recurrence))
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5 * float(jnp.max(jnp.abs(w))))


@pytest.mark.parametrize("capacity_factor,drops", [(2.0, False), (0.02, True)])
def test_mixture_against_the_reference_with_and_without_drops(built, ref, capacity_factor, drops):
    cfg = tiny_cfg(capacity_factor=capacity_factor)
    spec, s = hs.StackSpec.of(cfg), ref.stack_of(cfg)
    p = _params_of(built, "moe_2")
    layer = hs.ExpertMixture(spec, jnp.float32)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(12, 100, 64)), jnp.float32)   # 1,200 tokens: C = 384, or 128 at the least
    got, counts = layer.apply({"params": p}, x)
    np.testing.assert_allclose(got, ref.moe_layer(p, x, s), rtol=2e-5, atol=2e-5)
    offered, dropped, load_max, load_mean = (float(v) for v in counts)
    assert spec.capacity(1200) == (128 if drops else 384) and load_mean == 1200 * 2 / 16 and load_max >= load_mean
    assert (dropped > 0) == drops and dropped <= offered
    if drops:  # by hand: each held expert keeps its first 128 askers in (b, t) order
        tokens = ref.rms_norm(x, p["pre_norm"], 1e-5).reshape(-1, 64)
        biased = jax.nn.sigmoid(tokens @ p["router"]) + p["e_score_correction_bias"]
        chosen = np.argsort(-np.asarray(biased), axis=-1)[:, :2]
        asks = [(chosen == e).any(-1).sum() for e in range(4)]
        assert offered == sum(asks) and dropped == sum(max(a - 128, 0) for a in asks)
        assert not np.allclose(got, ref.moe_layer(p, x, s, drop=False), atol=1e-3)


def _mixture_through(form):
    """The mixture layer with its held experts' part made by `form`
    (`queued` / `unqueued`) whatever the shape -> (x', held, kept)."""
    def layer(m, x):
        flat = hs.rms_norm(x, m.pre_norm, m.sizes.eps).reshape(-1, x.shape[-1])
        y, held, kept = getattr(m, form)(flat, *m.routing(flat))
        return x + (y + m.shared(flat)).reshape(x.shape), held, kept
    return layer


def check_the_mixture_where_every_token_fits(layer, p, shape, reference):
    """`layer` on seeded tokens of `shape` (no more than a held expert's rows;
    one of them chose two held experts, one none) builds no slot table, is
    `reference(p, x)` and is the queue's result on the same input, forward and
    gradient, with the queue's counts and nothing dropped."""
    N = shape[0] * shape[1]
    assert N <= hs._sizes(layer.spec, "E").capacity(N)
    queue = lambda p, x: layer.apply({"params": p}, x, method=_mixture_through("queued"))
    for seed in range(64):
        x = jnp.asarray(np.random.default_rng(seed).normal(size=shape), jnp.float32)
        want, held, kept = queue(p, x)
        asked = np.asarray(held).sum(axis=1)
        if asked.max() == 2 and asked.min() == 0:
            break
    assert asked.max() == 2 and asked.min() == 0
    assert "scatter" not in str(jax.make_jaxpr(lambda p, x: layer.apply({"params": p}, x))(p, x))
    got, counts = layer.apply({"params": p}, x)
    np.testing.assert_allclose(got, reference(p, x), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert np.array_equal(held, kept) and float(counts[0]) == asked.sum() > 0 and float(counts[1]) == 0.0
    assert float(counts[3]) == N * 2 / 16 and float(counts[2]) >= float(counts[3])
    weigh = lambda fn: lambda p, x: jnp.sum(fn(p, x) * jnp.cos(jnp.arange(x.size, dtype=jnp.float32).reshape(x.shape)))
    grads = [jax.grad(weigh(fn), argnums=(0, 1))(p, x)
             for fn in (lambda p, x: layer.apply({"params": p}, x)[0], lambda p, x: queue(p, x)[0], reference)]
    for other in grads[1:]:
        for g, w in zip(jax.tree.leaves(grads[0]), jax.tree.leaves(other)):
            np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5 * max(float(jnp.max(jnp.abs(w))), 1e-3))


@pytest.mark.parametrize("tokens,first", [(16, 0), (16, 8), (128, 0), (128, 12)])
def test_where_every_token_fits_the_mixture_takes_no_queue_and_is_the_reference_and_the_queues_result(
        built, ref, tokens, first):
    cfg = tiny_cfg(first_expert_held=first)
    layer = hs.ExpertMixture(hs.StackSpec.of(cfg), jnp.float32)
    check_the_mixture_where_every_token_fits(layer, _params_of(built, "moe_2"), (2, tokens // 2, 64),
                                             lambda p, x: ref.moe_layer(p, x, ref.stack_of(cfg)))


def check_one_token_more_than_a_held_experts_rows_takes_the_queue(layer, p, reference):
    x = jnp.asarray(np.random.default_rng(8).normal(size=(1, 129, 64)), jnp.float32)
    capacity = hs._sizes(layer.spec, "E").capacity
    assert capacity(129) == 128 == capacity(128)
    text = lambda x: str(jax.make_jaxpr(lambda x: layer.apply({"params": p}, x))(x))
    assert "scatter-add" in text(x) and "scatter" not in text(x[:, :128])
    np.testing.assert_allclose(layer.apply({"params": p}, x)[0], reference(p, x), rtol=2e-5, atol=2e-5)


def test_one_token_more_than_a_held_experts_rows_takes_the_queue(built, ref):
    cfg = tiny_cfg()
    check_one_token_more_than_a_held_experts_rows_takes_the_queue(
        hs.ExpertMixture(hs.StackSpec.of(cfg), jnp.float32), _params_of(built, "moe_2"),
        lambda p, x: ref.moe_layer(p, x, ref.stack_of(cfg)))


def test_the_shares_routed_parts_and_the_shared_expert_once_are_the_uncut_layer(built, ref):
    """16 experts on 4 chips: what chips 0..3 add, each holding 4, plus the
    shared expert counted once, is the layer that holds all 16, under the same
    per-expert capacity (here tight enough to drop)."""
    rng = np.random.default_rng(4)
    whole_cfg = tiny_cfg(num_experts_held=16, capacity_factor=0.5)
    whole = hs.ExpertMixture(hs.StackSpec.of(whole_cfg), jnp.float32)
    x = jnp.asarray(rng.normal(size=(6, 100, 64)), jnp.float32)
    p = dict(_params_of(built, "moe_0"))
    p["experts"] = {"up": jnp.asarray(rng.normal(size=(16, 64, 32)) / 8, jnp.float32),
                    "down": jnp.asarray(rng.normal(size=(16, 32, 64)) / 6, jnp.float32)}
    flat = hs.rms_norm(x, p["pre_norm"], 1e-5).reshape(-1, 64)
    total, counts = whole.apply({"params": p}, flat, method=whole.routed)
    assert float(counts[1]) > 0  # some expert is over its capacity
    parts, dropped = 0.0, 0.0
    for chip in range(4):
        share_cfg = tiny_cfg(num_experts_held=4, first_expert_held=4 * chip, capacity_factor=0.5)
        share = hs.ExpertMixture(hs.StackSpec.of(share_cfg), jnp.float32)
        mine = dict(p, experts=jax.tree.map(lambda v: v[4 * chip:4 * chip + 4], p["experts"]))
        part, c = share.apply({"params": mine}, flat, method=share.routed)
        parts, dropped = parts + part, dropped + float(c[1])
        np.testing.assert_allclose(x + (part + share.apply({"params": mine}, flat, method=share.shared)).reshape(x.shape),
                                   ref.moe_layer(mine, x, ref.stack_of(share_cfg)), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(parts, total, rtol=2e-5, atol=2e-5)
    assert dropped == float(counts[1])
    uncut, _ = whole.apply({"params": p}, x)
    np.testing.assert_allclose(x + (parts + whole.apply({"params": p}, flat, method=whole.shared)).reshape(x.shape),
                               uncut, rtol=2e-5, atol=2e-5)


def test_attention_through_the_memory_is_full_causal_attention(built, ref):
    spec, layer, p = _layer(built, "attention_6")
    s = ref.stack_of(built[0])
    x = jnp.asarray(np.random.default_rng(5).normal(size=(2, 14, 64)), jnp.float32)
    empty = jnp.zeros((2, 16, 2, 16), jnp.float32)
    zero = jnp.zeros((2,), jnp.int32)
    want = ref.attention_layer(p, x, empty, empty, zero, s)           # the whole episode at once
    got, _, _ = layer.apply({"params": p}, x, empty, empty, zero)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # 9 positions, then the other 5 from the carried keys and values
    first, k, v = layer.apply({"params": p}, x[:, :9], empty, empty, zero)
    second, k, v = layer.apply({"params": p}, x[:, 9:], k, v, zero + 9)
    np.testing.assert_allclose(jnp.concatenate([first, second], 1), want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(second, ref.attention_layer(p, x[:, 9:], *layer.apply(
        {"params": p}, x[:, :9], empty, empty, zero)[1:], zero + 9, s), rtol=2e-5, atol=2e-5)
    # one position at a time, the ring wrapping past its 16 slots changes nothing seen
    k, v, outs = empty, empty, []
    for t in range(14):
        y, k, v = layer.apply({"params": p}, x[:, t:t + 1], k, v, zero + t)
        outs.append(y)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), want, rtol=2e-5, atol=2e-5)


def test_the_ring_keeps_the_last_window_positions():
    memory = jnp.zeros((2, 4, 1))
    new = jnp.arange(1.0, 7.0).reshape(1, 6, 1) * jnp.ones((2, 1, 1))
    got = hs._ring_write(memory, new, jnp.asarray([0, 3]))[..., 0]
    assert got.tolist() == [[5.0, 6.0, 3.0, 4.0], [6.0, 3.0, 4.0, 5.0]]   # step t sits at (count + t) % 4


# ------------------------------------------------------------ the whole stack


def test_unroll_is_the_steps_and_carries_on_from_a_stored_state(built):
    cfg, net, params = built
    core, cp = net.core, {"params": params["params"]["core"]}
    x, hidden = _inputs(cfg, 3, 13, seen=3)   # 3 + 13 positions: within the window of 16
    outs, carry_T = core.apply(cp, x, (hidden[:, 0],))
    c, ys = (hidden[:, 0],), []
    for t in range(x.shape[1]):
        y, c = core.apply(cp, x[:, t], c, method=core.step)
        ys.append(y)
    np.testing.assert_allclose(jnp.stack(ys, 1), outs, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(c[0], carry_T[0], rtol=1e-4, atol=1e-4)
    first, c1 = core.apply(cp, x[:, :6], (hidden[:, 0],))
    second, c2 = core.apply(cp, x[:, 6:], c1)
    np.testing.assert_allclose(jnp.concatenate([first, second], 1), outs, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(c2[0], carry_T[0], rtol=1e-4, atol=1e-4)


def _batch(cfg, B, seed=0):
    rng = np.random.default_rng(seed)
    T, L = cfg.seq_len, cfg.learning_steps
    _, hidden = _inputs(cfg, B, T, seed)
    ones = jnp.ones((B,), jnp.int32)
    return dict(
        obs=jnp.asarray(rng.integers(0, 255, (B, T, *cfg.obs_shape), dtype=np.uint8)),
        last_action=jnp.asarray(rng.integers(0, cfg.action_dim, (B, T)), jnp.int32),
        last_reward=jnp.asarray(rng.normal(size=(B, T)), jnp.float32), hidden=hidden,
        action=jnp.asarray(rng.integers(0, cfg.action_dim, (B, L)), jnp.int32),
        n_step_reward=jnp.asarray(rng.normal(size=(B, L)), jnp.float32), gamma=jnp.full((B, L), 0.9, jnp.float32),
        burn_in=ones * cfg.burn_in_steps, learning=ones * L - jnp.arange(B) % 2, forward=ones * cfg.forward_steps,
        is_weights=jnp.asarray(rng.uniform(0.5, 1.0, (B,)), jnp.float32))


def test_forward_loss_and_gradient_against_the_reference(built, ref):
    import optax

    from r2d2_tpu.learner import DeviceBatch, make_loss_fn

    cfg, net, params = built
    target = jax.tree.map(lambda v: v * 1.02, params)
    b = _batch(cfg, 4)
    batch = DeviceBatch(obs=b["obs"], last_action=b["last_action"], last_reward=b["last_reward"], hidden=b["hidden"],
                        action=b["action"], n_step_reward=b["n_step_reward"], gamma=b["gamma"],
                        burn_in_steps=b["burn_in"], learning_steps=b["learning"], forward_steps=b["forward"],
                        is_weights=b["is_weights"], task=None)
    denom = jnp.sum(b["learning"]).astype(jnp.float32)
    loss_fn = make_loss_fn(cfg, net)
    (loss, (_, aux)), grads = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(params, target, batch, denom)
    q = net.apply(params, b["obs"], b["last_action"], b["last_reward"], b["hidden"], b["burn_in"], b["learning"],
                  b["forward"])[0]
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, t, bb: ref.loss_q_gradnorm(p, t, bb, ref.sizes_of(cfg)))(
            params["params"], target["params"], b)
    np.testing.assert_allclose(q, want[1], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(loss, want[0], rtol=1e-4)
    np.testing.assert_allclose(optax.global_norm(grads), want[2], rtol=1e-3)
    # what the mixtures counted in the ONLINE unroll leaves with the metrics, under names of the table
    from r2d2_tpu.utils.profiling import SPANS

    counted = {k: float(v) for k, v in aux.items() if k.startswith("moe.")}
    assert set(counted) == {n for n in SPANS if n.startswith("moe.")}
    tokens = 4 * cfg.seq_len
    assert counted["moe.rows_dropped"] == 0 and 0 < counted["moe.rows_offered"] <= 3 * tokens * 2
    assert counted["moe.load_max_over_mean"] >= 1.0 and counted["moe.dropped_share"] == 0.0


@pytest.mark.parametrize("family", ["nemotron_h", "qwen3_next"])
def test_the_update_has_no_loop_whose_bound_is_data_and_no_dynamic_shape(built, family):
    """Static work whatever the routed load, in either family: scans with
    static lengths, no `while`, and a compiled text without a bounded-dynamic
    dimension."""
    cfg, net, params = built
    b = _batch(cfg, 2)
    if family == "qwen3_next":
        import test_qwen3_next_stack as second

        cfg = second.tiny_qwen_cfg()
        (net, params), b = init_params(jax.random.PRNGKey(0), cfg), second._batch(cfg, 2)
    fn = lambda p: jnp.sum(net.apply(p, b["obs"], b["last_action"], b["last_reward"], b["hidden"], b["burn_in"],
                                     b["learning"], b["forward"])[0])
    jaxpr = str(jax.make_jaxpr(jax.grad(fn))(params))
    assert "while[" not in jaxpr and "cond[" not in jaxpr and "scan[" in jaxpr
    text = jax.jit(jax.grad(fn)).lower(params).compile().as_text()
    assert "<=" not in "".join(line.split("metadata=")[0] for line in text.splitlines())


def test_published_widths_give_the_issues_counts():
    """One row's state and the parameter count at published widths, by hand
    (no array is made: shapes only)."""
    conf = json.load(open(os.path.join(ROOT, "benchmark", "configs", "nemotron-twotower-30b-a3b-ep16.json")))
    from benchmark import harness

    cfg = harness.build_config(conf, 0)
    spec = hs.StackSpec.of(cfg)
    per_m, per_a = 64 * 64 * 128 + 3 * 6144, 1024 * 2 * 128 * 2
    assert (per_m, per_a) == (542720, 524288)
    assert spec.state_size == 128 * -(-(3 * per_m + per_a + 2) // 128) == 2152576
    assert spec.capacity(16 * 581) == 896 and spec.capacity(8 * 581) == 512 and spec.capacity(16) == 128
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)[1])["params"]
    count = lambda tree: sum(int(np.prod(v.shape)) for v in jax.tree.leaves(tree))
    m = 2688 * 10304 + 4 * 6144 + 6144 + 4096 * 2688 + 4096 + 3 * 64 + 2688
    a = 2688 * 4096 * 2 + 2688 * 256 * 2 + 2688
    e = 2688 * 128 + 128 + 2 * 2688 * 3712 + 2688 + 8 * 2 * 2688 * 1856
    assert count(shapes["core"]["ssm_1"]) == m and count(shapes["core"]["attention_6"]) == a
    assert count(shapes["core"]["moe_0"]) == e
    assert count(shapes["core"]) == 3 * m + a + 3 * e + 2688 + 2692 * 2688
    assert 469e6 < count(shapes) < 471e6      # the issue's 470.2 M: 9.40 GB at 20 bytes a parameter
    # the file's copy of the source's config and the core's copy say the same
    for key, value in conf["overrides"]["core_config"].items():
        if key in conf and key != "hybrid_override_pattern":
            assert conf[key] == value, key
    assert conf["hybrid_override_pattern"][6:13] == conf["overrides"]["core_config"]["hybrid_override_pattern"]


# ------------------------------------------------------------------ collector


@pytest.mark.parametrize("core", ["lstm", "lru", "hybrid_stack"])
def test_the_collector_stores_the_same_carries_at_the_window_starts_alone(core, monkeypatch):
    """The segmented scan that keeps the carry at the static window starts
    against the scan that stacks it at every step: every field of every block
    bit for bit, whichever the core's class asks for."""
    from r2d2_tpu.collect import make_collect_fn
    from r2d2_tpu.train import build_fn_env

    over = dict(env_name="drift", action_dim=3, max_episode_steps=16, block_length=16, learning_steps=4,
                burn_in_steps=2, forward_steps=2, num_actors=3, recurrent_core=core)
    if core == "hybrid_stack":
        over.update(hidden_dim=64, core_config=TINY_CORE)
    cfg = tiny_test().replace(**over)
    net, params = init_params(jax.random.PRNGKey(0), cfg)
    fn_env = build_fn_env(cfg)
    key = jax.random.PRNGKey(7)
    env_state = jax.vmap(fn_env.reset)(jax.random.split(key, 3))
    eps = jnp.asarray([0.0, 0.3, 1.0], jnp.float32)
    got = {}
    for starts_only in (False, True):
        monkeypatch.setattr(core_class(cfg), "keeps_window_starts", starts_only, raising=False)
        got[starts_only] = make_collect_fn(cfg, net, fn_env, 3, 16)(params, env_state, eps, key)
    assert cfg.seqs_per_block == 4 and got[True][0]["hidden"].shape == (3, 4, *state_spec(cfg)[0])
    assert float(jnp.abs(got[True][0]["hidden"][:, 1:].astype(jnp.float32)).max()) > 0
    for a, b in zip(jax.tree.leaves(got[False]), jax.tree.leaves(got[True])):
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("statement", ["keeps_window_starts", "open_carry", "close_carry", "step_open"])
def test_only_the_stack_asks_for_window_starts(statement):
    """...and only the stack states an opened form: the LSTM and the LRU state
    nothing, and the seam gives them the identity and their own `step`."""
    asks = {name: bool(getattr(core_class(tiny_test().replace(recurrent_core=name) if name != "hybrid_stack"
                                          else tiny_cfg()), statement, False))
            for name in ("lstm", "lru", "hybrid_stack")}
    assert asks == {"lstm": False, "lru": False, "hybrid_stack": True}


# ------------------------------------------------- the opened form (models/core.py)


class _Countdown:
    """A functional env whose episode ends after a number of steps drawn at
    reset from `limits`: some slots end inside a chunk, some outlive it."""

    def __init__(self, obs_shape, limits):
        self.obs_shape, self.limits = obs_shape, jnp.asarray(limits, jnp.int32)

    def reset(self, key):
        k1, k2 = jax.random.split(key)
        return {"t": jnp.int32(0), "limit": jax.random.choice(k1, self.limits),
                "salt": jax.random.randint(k2, (), 0, 251)}

    def step(self, state, action):
        t = state["t"] + 1
        return dict(state, t=t), (action == t % 3).astype(jnp.float32), t >= state["limit"]

    def render(self, state):
        n = int(np.prod(self.obs_shape))
        return ((jnp.arange(n) * (state["t"] + 3) + state["salt"]) % 251).astype(jnp.uint8).reshape(self.obs_shape)


E_OPEN = 4
OPEN_CFG = dict(action_dim=3, max_episode_steps=16, block_length=16, learning_steps=4, burn_in_steps=2,
                forward_steps=2, num_actors=E_OPEN, recurrent_core="hybrid_stack", hidden_dim=64,
                core_config=TINY_CORE)


@pytest.fixture(scope="module")
def open_built():
    cfg = tiny_test().replace(**OPEN_CFG)
    return (cfg, *init_params(jax.random.PRNGKey(0), cfg), _Countdown(cfg.obs_shape, (5, 11, 40)))


def _window_starts(T):
    """collect.py's, for OPEN_CFG's block: 0, 2, 6, 10, held to the chunk."""
    return np.clip(np.arange(4) * 4 - np.minimum(np.arange(4) * 4, 2), 0, T)


def _collector_inputs(cfg, fn_env, carry_episodes, seed=7):
    from r2d2_tpu.collect import initial_carry

    key = jax.random.PRNGKey(seed)
    if carry_episodes:  # a chunk in mid-episode: a carried state, action and reward that are not zero
        first = initial_carry(cfg, fn_env, E_OPEN, key)
        rng = np.random.default_rng(seed)
        row = np.asarray(_inputs(cfg, E_OPEN, 1, seed=seed, seen=3)[1][:, 0])
        env_state = first._replace(core=(jnp.asarray(row),), last_action=jnp.asarray(rng.integers(0, 3, E_OPEN), jnp.int32),
                                   last_reward=jnp.asarray(rng.normal(size=E_OPEN), jnp.float32),
                                   prefix_reward=jnp.ones(E_OPEN, jnp.float32), ep_steps=jnp.full(E_OPEN, 3, jnp.int32))
    else:
        env_state = jax.vmap(fn_env.reset)(jax.random.split(key, E_OPEN))
    return env_state, jnp.asarray([0.0, 0.3, 0.6, 1.0], jnp.float32), key


@pytest.mark.parametrize("carry_episodes", [False, True], ids=["fresh_chunks", "carry_episodes"])
def test_the_collector_over_the_opened_form_stores_what_a_loop_of_steps_on_the_flat_row_stores(
        carry_episodes, open_built, monkeypatch):
    """The scan over the stack's parts, joined at the segments' ends, against
    (i) the same collector with the class's three statements taken away, whose
    scan carries the flat row through `HybridStack.step` at every env step (the
    parent's program), every output bit for bit: each field of `fields`,
    `priorities`, `num_seq`, sizes, dones, rewards, the returned carry; and
    (ii) a plain Python loop of `net.act_select` on the flat row over the same
    keys, for what the core itself hands on: the state before each stored
    window and the state the chunk leaves. A slot that ends early is among them."""
    from r2d2_tpu.collect import make_collect_fn

    T = 8 if carry_episodes else 16
    cfg, net, params, fn_env = open_built
    env_state, eps, key = _collector_inputs(cfg, fn_env, carry_episodes)
    opened = make_collect_fn(cfg, net, fn_env, E_OPEN, T, carry_episodes)(params, env_state, eps, key)
    with monkeypatch.context() as m:
        for statement in ("open_carry", "close_carry", "step_open"):
            m.delattr(core_class(cfg), statement)
        flat = make_collect_fn(cfg, net, fn_env, E_OPEN, T, carry_episodes)(params, env_state, eps, key)
    sizes = np.asarray(opened[3])
    assert sizes.min() < T and sizes.max() == T, sizes       # one slot ends early, one runs the chunk out
    assert jax.tree.structure(opened) == jax.tree.structure(flat)
    for a, b in zip(jax.tree.leaves(flat), jax.tree.leaves(opened)):
        assert a.dtype == b.dtype and a.shape == b.shape and np.array_equal(np.asarray(a), np.asarray(b))
    assert float(jnp.abs(opened[0]["hidden"][:, 1:].astype(jnp.float32)).max()) > 0

    # (ii) the loop: collect.py's body by hand, one jitted act_select on the flat row a step
    act = jax.jit(lambda p, o, la, lr, c, ex, ra: net.apply(p, o, la, lr, c, ex, ra, method=net.act_select))
    vrender, vstep = jax.vmap(fn_env.render), jax.vmap(fn_env.step)
    if carry_episodes:
        env, core, la, lr = env_state.env_state, env_state.core, env_state.last_action, env_state.last_reward
    else:
        env, core, la, lr = env_state, zero_carry(cfg, E_OPEN), jnp.zeros(E_OPEN, jnp.int32), jnp.zeros(E_OPEN)
    active, keys, before = jnp.ones(E_OPEN, bool), jax.random.split(key, T + 2), []
    for t in range(T):
        before.append(core[0])
        ke, ka = jax.random.split(keys[t])
        explore, rand_a = jax.random.uniform(ke, (E_OPEN,)) < eps, jax.random.randint(ka, (E_OPEN,), 0, 3)
        _, a, core = act(params, vrender(env), la, lr, core, explore, rand_a)
        new_env, reward, done = vstep(env, a)
        env = jax.tree.map(lambda new, old: jnp.where(active.reshape(-1, *[1] * (new.ndim - 1)), new, old), new_env, env)
        la, lr = jnp.where(active, a, la), jnp.where(active, reward.astype(jnp.float32), lr)
        active = active & ~done
    starts = _window_starts(T)
    stored = np.asarray(opened[0]["hidden"])
    for e in range(E_OPEN):
        for w in range(-(-int(sizes[e]) // 4)):
            assert np.array_equal(stored[e, w, 0], np.asarray(before[starts[w]][e])), (e, w)
    if carry_episodes:
        cont = np.asarray(active & (3 + opened[3] < cfg.max_episode_steps))
        assert cont.any() and not cont.all()
        assert np.array_equal(np.asarray(opened[6].core[0]), np.where(cont[:, None], np.asarray(core[0]), 0.0))


def _scan_bodies(jaxpr):
    """Every scan's body under `jaxpr`, nested ones too."""
    for eqn in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eqn.params):
            if eqn.primitive.name == "scan":
                yield sub
            yield from _scan_bodies(sub)


def _shapes_in(jaxpr):
    for eqn in jaxpr.eqns:
        yield from (tuple(v.aval.shape) for v in (*eqn.invars, *eqn.outvars) if hasattr(v.aval, "shape"))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _shapes_in(sub)


def _count_outside_scans(jaxpr, wanted):
    n = 0
    for eqn in jaxpr.eqns:
        n += wanted(eqn)
        if eqn.primitive.name != "scan":
            n += sum(_count_outside_scans(sub, wanted) for sub in jax.core.jaxprs_in_params(eqn.params))
    return n


@pytest.mark.parametrize("carry_episodes", [False, True], ids=["fresh_chunks", "carry_episodes"])
def test_the_traced_collector_joins_the_stacks_carry_at_the_segments_ends_and_nowhere_inside_a_scan(
        carry_episodes, open_built):
    """The engagement counter, static: inside the env steps' scans no value
    has the flat row's shape (E, state_size), or its length before the pad;
    outside them the carry is joined (one `concatenate` of the parts and one
    `pad` to whole lanes) once for each segment's end (the window starts
    after 0 that `_pack` stores, and the chunk's end for `q_final` and the
    returned carry) and once more inside `q_final`'s `HybridStack.step` on the
    stored form, whose new carry nobody reads: ISSUE 54's `len(set(
    window_starts)) + 1` where the chunk's end is no window start, T = 16 here
    (3 a chunk in the nemotron cell, starts 0 and 448 of 1,024 steps), where
    the flat-row collector joined at every env step."""
    from r2d2_tpu.collect import make_collect_core

    cfg, net, params, fn_env = open_built
    spec = hs.StackSpec.of(cfg)
    T = 8 if carry_episodes else 16
    env_state, eps, key = _collector_inputs(cfg, fn_env, carry_episodes)
    collect = make_collect_core(cfg, net, fn_env, E_OPEN, T, carry_episodes)
    jaxpr = jax.make_jaxpr(collect)(params, env_state, eps, key).jaxpr
    raw = sum(int(np.prod(shape)) for _, _, shape in spec.segments())
    flat_rows = {(E_OPEN, spec.state_size), (E_OPEN, raw)}
    bodies = list(_scan_bodies(jaxpr))
    window_starts = set(_window_starts(T).tolist())
    ends = (window_starts | {T}) - {0}
    assert len([b for b in bodies if len(b.eqns) > 50]) >= len(ends)      # a scan of env steps a segment
    for body in bodies:
        assert not flat_rows & set(_shapes_in(body))
    joins = _count_outside_scans(jaxpr, lambda e: e.primitive.name == "concatenate"
                                 and tuple(e.outvars[0].aval.shape) == (E_OPEN, raw))
    pads = _count_outside_scans(jaxpr, lambda e: e.primitive.name == "pad"
                                and tuple(e.outvars[0].aval.shape) == (E_OPEN, spec.state_size))
    assert joins == pads == len(ends) + 1 == (4 if carry_episodes else 5)
    assert T in window_starts or joins == len(window_starts) + 1


def test_close_of_open_is_the_carry_and_step_is_close_of_the_opened_step_of_open(built):
    cfg, net, params = built
    spec = hs.StackSpec.of(cfg)
    x, hidden = _inputs(cfg, 3, 1, seed=4, seen=7)
    carry, core = (hidden[:, 0],), {"params": params["params"]["core"]}
    opened = open_carry(net.core, carry)
    assert [p.shape[1:] for p in opened] == [shape for _, _, shape in spec.segments()]
    assert all(p.dtype == jnp.float32 for p in opened)
    assert np.array_equal(close_carry(net.core, opened)[0], carry[0])
    out, after = jax.jit(lambda c: net.core.apply(core, x[:, 0], c, method="step"))(carry)
    out_o, after_o = jax.jit(lambda o: net.core.apply(core, x[:, 0], o, method="step_open"))(opened)
    assert np.array_equal(out, out_o) and np.array_equal(after[0], close_carry(net.core, after_o)[0])
    assert float(jnp.abs(after[0] - carry[0]).max()) > 0
    # two steps on the opened form, closed once, are two steps on the flat row
    twice = jax.jit(lambda c: net.core.apply(core, x[:, 0] * 0.5, net.core.apply(core, x[:, 0], c, method="step")[1],
                                             method="step"))(carry)
    twice_o = jax.jit(lambda o: net.core.apply(core, x[:, 0] * 0.5, net.core.apply(core, x[:, 0], o, method="step_open")[1],
                                               method="step_open"))(opened)
    assert np.array_equal(twice[0], twice_o[0]) and np.array_equal(twice[1][0], close_carry(net.core, twice_o[1])[0])


@pytest.mark.parametrize("core", ["lstm", "lru"])
def test_a_core_that_states_nothing_is_opened_and_closed_by_the_identity_and_steps_as_it_steps(core):
    """`act` on the opened form is `act`: one traced program either way."""
    cfg = tiny_test().replace(recurrent_core=core)
    net, params = init_params(jax.random.PRNGKey(0), cfg)
    carry = tuple(jnp.full((2, cfg.hidden_dim), v, jnp.float32) for v in (0.25, -0.5))
    assert open_carry(net.core, carry) is carry and close_carry(net.core, carry) is carry
    obs = jnp.zeros((2, *cfg.obs_shape), jnp.uint8)
    la, lr = jnp.asarray([1, 2], jnp.int32), jnp.asarray([0.5, -1.0], jnp.float32)
    texts = [str(jax.make_jaxpr(lambda c: net.apply(params, obs, la, lr, c, opened=opened, method=net.act))(carry))
             for opened in (False, True)]
    assert texts[0] == texts[1]
