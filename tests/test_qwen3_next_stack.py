"""The hybrid_stack core's second family, `qwen3_next` (models/hybrid_stack.py),
at tiny widths on the CPU, each piece against the plain float32 reference
(benchmark/reference/qwen3_next.py) on seeded weights: the chunked delta rule
and its gradient against the recurrence; the mixer's unroll against its steps;
gated attention through the ring against full causal attention with the
rotary embedding at absolute positions; the softmax mixture with and without
drops, and with no queue where every token fits; the share test; the whole
forward, loss and gradient; the hand counts at published widths; what the spec
refuses; and that the first family's layers trace to the jaxprs they had
before the second came."""

import dataclasses
import hashlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from r2d2_tpu.config import tiny_test
from r2d2_tpu.models import hybrid_stack as hs
from r2d2_tpu.models.core import state_spec
from r2d2_tpu.models.r2d2 import init_params

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY_QWEN = dict(
    model_type="qwen3_next", hidden_size=64, num_hidden_layers=4, full_attention_interval=4,
    linear_num_key_heads=2, linear_key_head_dim=16, linear_num_value_heads=4, linear_value_head_dim=16,
    linear_conv_kernel_dim=4, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    partial_rotary_factor=0.25, rope_theta=1e7, num_experts=16, num_experts_per_tok=2, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, norm_topk_prob=True, rms_norm_eps=1e-6, num_experts_held=4)


def tiny_qwen_cfg(**core):
    return tiny_test().replace(recurrent_core="hybrid_stack", hidden_dim=64, max_episode_steps=16,
                               core_config=dict(TINY_QWEN, **core))


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(ROOT, "benchmark", "reference", "qwen3_next.py")
    spec = importlib.util.spec_from_file_location("reference_qwen3_next", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def built():
    cfg = tiny_qwen_cfg()
    net, params = init_params(jax.random.PRNGKey(0), cfg)
    # norm weights away from their initial 0 / 1, so that a layer that forgot one (or its `1 +`) is caught
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 200))
    params = jax.tree.map(lambda v: v + 0.1 * jax.random.normal(next(keys), v.shape), params)
    return cfg, net, params


def _inputs(cfg, B, T, seed=0, seen=5):
    rng = np.random.default_rng(seed)
    spec = hs.spec_of(cfg)
    x = jnp.asarray(rng.normal(size=(B, T, cfg.hidden_dim + cfg.action_dim + 1)), jnp.float32)
    hidden = rng.normal(size=(B, 1, spec.state_size)).astype(np.float32) * 0.3
    n = sum(int(np.prod(shape)) for _, _, shape in spec.segments())
    hidden[:, 0, n - 2:n] = [0.0, float(seen)]
    hidden[:, 0, n:] = 0.0
    return x, jnp.asarray(hidden)


def _layer(built, name, **sizes):
    spec = hs.spec_of(built[0])
    kind = {"gdn": "D", "moe": "E", "attention": "*"}[name.split("_")[0]]
    module = hs.KINDS[kind][1](dataclasses.replace(spec.sizes(kind), **sizes), jnp.float32)
    return spec, module, built[2]["params"]["core"][name]


# ------------------------------------------------------------ config and state


def test_the_family_is_data_inside_core_config_and_the_blocks_follow_from_two_numbers():
    spec = hs.spec_of(tiny_qwen_cfg())
    assert isinstance(spec, hs.Qwen3NextSpec)
    assert spec.blocks == (("D", 0), ("E", 0), ("D", 1), ("E", 1), ("D", 2), ("E", 2), ("*", 3), ("E", 3))
    six = hs.spec_of(tiny_qwen_cfg(num_hidden_layers=6, full_attention_interval=3))
    assert "".join(kind for kind, _ in six.blocks) == "DEDE*EDEDE*E"
    # absent, or the first family's name: the first family's spec, as before
    from test_hybrid_stack import tiny_cfg

    assert isinstance(hs.spec_of(tiny_cfg()), hs.StackSpec)
    assert hs.spec_of(tiny_cfg(model_type="nemotron_h")) == dataclasses.replace(hs.spec_of(tiny_cfg()))
    _, params = init_params(jax.random.PRNGKey(3), tiny_qwen_cfg())
    assert set(params["params"]["core"]) == {"in_proj", "final_norm", "gdn_0", "moe_0", "gdn_1", "moe_1", "gdn_2",
                                             "moe_2", "attention_3", "moe_3"}
    assert set(params["params"]["core"]["moe_0"]) == {"pre_norm", "router", "experts", "shared_gate", "shared_up",
                                                      "shared_down", "shared_expert_gate"}
    assert set(params["params"]["core"]["moe_0"]["experts"]) == {"gate", "up", "down"}
    assert set(params["params"]["core"]["attention_3"]) == {"pre_norm", "q_proj", "k_proj", "v_proj", "o_proj",
                                                            "q_norm", "k_norm"}
    assert set(params["params"]["core"]["gdn_1"]) == {"pre_norm", "in_proj_qkvz", "in_proj_ba", "conv_weight", "A_log",
                                                      "dt_bias", "norm", "out_proj"}


@pytest.mark.parametrize("change,match", [
    (dict(expansion=2), "unknown keys"), (dict(hybrid_override_pattern="EM*"), "unknown keys"),
    (dict(n_routed_experts=16), "unknown keys"), (dict(hidden_size=32), "hidden_size"),
    (dict(first_expert_held=14), "held experts"), (dict(linear_num_key_heads=3), "divide"),
    (dict(num_key_value_heads=3), "divide"), (dict(full_attention_interval=0), "1 or more"),
    (dict(partial_rotary_factor=0.2), "pairs"), (dict(model_type="llama"), "model_type"),
    (dict(norm_topk_prob=False), "norm_topk_prob")])
def test_a_wrong_missing_or_other_familys_key_is_refused_by_the_class(change, match):
    with pytest.raises(ValueError, match=match):
        hs.spec_of(tiny_qwen_cfg(**change))
    missing = {k: v for k, v in TINY_QWEN.items() if k != "rope_theta"}
    with pytest.raises(ValueError, match="missing keys"):
        hs.spec_of(tiny_test().replace(recurrent_core="hybrid_stack", hidden_dim=64, core_config=missing))
    # the first family's keys under the second's name, and the second's under the first's
    from test_hybrid_stack import TINY_CORE

    with pytest.raises(ValueError, match="unknown keys"):
        hs.spec_of(tiny_test().replace(recurrent_core="hybrid_stack", hidden_dim=64,
                                       core_config=dict(TINY_CORE, model_type="qwen3_next")))
    with pytest.raises(ValueError, match="unknown keys"):
        hs.spec_of(tiny_test().replace(recurrent_core="hybrid_stack", hidden_dim=64,
                                       core_config={k: v for k, v in TINY_QWEN.items() if k != "model_type"}))


def test_the_carry_is_one_flat_row_of_whole_lanes_and_splits_back():
    cfg = tiny_qwen_cfg()
    spec = hs.spec_of(cfg)
    (n, S), _ = state_spec(cfg)
    raw = 3 * (4 * 16 * 16 + 3 * (2 * 2 * 16 + 4 * 16)) + 2 * 16 * 2 * 16 + 2
    assert n == 1 and S == 128 * -(-raw // 128)
    assert [(i, name) for i, name, _ in spec.segments()] == [
        (0, "delta"), (0, "conv"), (1, "delta"), (1, "conv"), (2, "delta"), (2, "conv"), (3, "keys"), (3, "values"),
        (-1, "count")]
    flat = jnp.arange(2 * S, dtype=jnp.float32).reshape(2, S).at[:, raw:].set(0.0)
    assert np.array_equal(hs.join_state(spec, hs.split_state(spec, flat)), flat)


# ------------------------------------------------------------- the delta rule


def _recurrence(q, k, v, g, beta, s0):
    """The delta rule one step at a time: q, k (B, T, Hk, dk); v (B, T, Hv, dv); g, beta (B, T, Hv)."""
    R = v.shape[2] // k.shape[2]
    q, k = jnp.repeat(q, R, axis=2), jnp.repeat(k, R, axis=2)

    def step(S, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        S = jnp.exp(g_t)[..., None, None] * S
        r = jnp.einsum("bhkv,bhk->bhv", S, k_t)
        S = S + jnp.einsum("bhk,bhv->bhkv", k_t, b_t[..., None] * (v_t - r))
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    S, o = jax.lax.scan(step, s0, tuple(jnp.swapaxes(a, 0, 1) for a in (q, k, v, g, beta)))
    return jnp.swapaxes(o, 0, 1), S


@pytest.mark.parametrize("stored", [False, True], ids=["from_zero", "from_a_stored_state"])
@pytest.mark.parametrize("T,chunk", [(21, 8), (5, 8), (16, 8), (1, 8), (70, 64)],
                         ids=["not_whole_chunks", "below_a_chunk", "whole_chunks", "one_step", "the_cells_chunk"])
def test_the_chunked_delta_rule_and_its_gradient_against_the_recurrence(T, chunk, stored):
    B, Hk, Hv, dk, dv = 2, 2, 4, 16, 8
    rng = np.random.default_rng(T + stored)
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    unit = lambda a: a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
    q, k, v = unit(normal(B, T, Hk, dk)) * dk ** -0.5, unit(normal(B, T, Hk, dk)), normal(B, T, Hv, dv)
    g, beta = -jnp.abs(normal(B, T, Hv)) * 0.5, jax.nn.sigmoid(normal(B, T, Hv))
    s0 = normal(B, Hv, dk, dv) * (0.5 if stored else 0.0)
    flat = lambda a: a.reshape(B, T, -1)

    def chunked(q, k, v, g, beta, s0):
        o, S = hs.delta_rule_chunked(flat(q), flat(k), flat(v), g, beta, s0, chunk, jnp.float32)
        return o.reshape(B, T, Hv, dv), S

    with jax.default_matmul_precision("highest"):
        want, got = _recurrence(q, k, v, g, beta, s0), chunked(q, k, v, g, beta, s0)
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
        weigh = lambda fn: lambda *a: sum(jnp.sum(out * jnp.cos(jnp.arange(out.size).reshape(out.shape))) for out in fn(*a))
        d_want = jax.grad(weigh(_recurrence), argnums=tuple(range(6)))(q, k, v, g, beta, s0)
        d_got = jax.grad(weigh(chunked), argnums=tuple(range(6)))(q, k, v, g, beta, s0)
    for a, b in zip(d_got, d_want):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


def test_the_mixers_unroll_is_its_steps_and_both_are_the_references_loop(built, ref):
    spec, layer, p = _layer(built, "gdn_1", chunk=8)
    s = ref.stack_of(built[0])
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(3, 21, 64)), jnp.float32)       # 21: not whole chunks of 8
    delta = jnp.asarray(rng.normal(size=(3, 4, 16, 16)), jnp.float32) * 0.5
    tail = jnp.asarray(rng.normal(size=(3, 3, 128)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, (want_delta, want_tail) = ref.delta_layer(p, x, delta, tail, s)
        got, got_delta, got_tail = layer.apply({"params": p}, x, delta, tail)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(got_delta, want_delta, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(got_tail, want_tail, rtol=2e-5, atol=2e-5)
        # step by step from the same stored state; and a sequence cut in two carries on
        state, outs = (delta, tail), []
        for t in range(21):
            out, *state = layer.apply({"params": p}, x[:, t], *state, method="step")
            outs.append(out)
        np.testing.assert_allclose(jnp.stack(outs, 1), want, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(state[0], want_delta, rtol=2e-5, atol=2e-5)
        first, *middle = layer.apply({"params": p}, x[:, :9], delta, tail)
        second, end_delta, _ = layer.apply({"params": p}, x[:, 9:], *middle)
        np.testing.assert_allclose(jnp.concatenate([first, second], 1), want, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(end_delta, want_delta, rtol=2e-5, atol=2e-5)


def test_the_chunked_delta_rule_holds_on_keys_that_hardly_differ_from_step_to_step():
    """An agent's consecutive frames give nearly the same key at every step
    and beta near one: `k_i . k_j` is near 1 and the chunk's triangular matrix
    near all ones. The series `(I - L)(I + L^2)(I + L^4) ...` loses every digit
    there in float32 (its terms reach 1e10 where the inverse is of order one;
    a run on the chip went to NaN, PERF.md finding 56); forward substitution
    does not."""
    B, T, Hk, Hv, dk, dv = 2, 128, 1, 2, 16, 8
    rng = np.random.default_rng(11)
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    unit = lambda a: a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
    k = unit(normal(B, 1, Hk, dk) + 0.05 * normal(B, T, Hk, dk))
    q, v = unit(normal(B, T, Hk, dk)) * dk ** -0.5, normal(B, T, Hv, dv)
    g, beta = -0.01 * jnp.abs(normal(B, T, Hv)), jax.nn.sigmoid(4.0 + normal(B, T, Hv))
    s0 = normal(B, Hv, dk, dv)
    flat = lambda a: a.reshape(B, T, -1)
    with jax.default_matmul_precision("highest"):
        want_o, want_s = _recurrence(q, k, v, g, beta, s0)
        got_o, got_s = hs.delta_rule_chunked(flat(q), flat(k), flat(v), g, beta, s0, 64, jnp.float32)
    assert float(jnp.min(jnp.einsum("bihd,bjhd->bij", k, k))) > 0.9
    np.testing.assert_allclose(got_o.reshape(B, T, Hv, dv), want_o, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(got_s, want_s, rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------------ attention


def test_gated_attention_through_the_ring_is_full_causal_attention_with_rotary_at_absolute_positions(built, ref):
    spec, layer, p = _layer(built, "attention_3")
    s = ref.stack_of(built[0])
    rng = np.random.default_rng(3)
    B, T, W = 2, 14, 16
    x = jnp.asarray(rng.normal(size=(B, T, 64)), jnp.float32)
    empty = jnp.zeros((B, W, 2, 16), jnp.float32)
    zero = jnp.zeros((B,), jnp.int32)
    with jax.default_matmul_precision("highest"):
        want, want_keys = ref.attention_layer(p, x, empty, empty, zero, s)
        whole, keys, values = layer.apply({"params": p}, x, empty, empty, zero)
        np.testing.assert_allclose(whole, want, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(keys[:, :T], want_keys, rtol=2e-5, atol=2e-5)   # stored after the rotation
        # cut in two: the second part's positions start where the count stands
        first, keys, values = layer.apply({"params": p}, x[:, :9], empty, empty, zero)
        second, keys2, _ = layer.apply({"params": p}, x[:, 9:], keys, values, zero + 9)
        np.testing.assert_allclose(jnp.concatenate([first, second], 1), want, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(keys2[:, :T], want_keys, rtol=2e-5, atol=2e-5)
        # the reference from a stored ring, too
        np.testing.assert_allclose(ref.attention_layer(p, x[:, 9:], keys, values, zero + 9, s)[0], want[:, 9:],
                                   rtol=2e-5, atol=2e-5)
        # one step at `count`
        k, v, outs = empty, empty, []
        for t in range(T):
            out, k, v = layer.apply({"params": p}, x[:, t:t + 1], k, v, zero + t)
            outs.append(out[:, 0])
        np.testing.assert_allclose(jnp.stack(outs, 1), want, rtol=2e-5, atol=2e-5)
        # each mechanism is there: without it the reference reads something else
        for off in ("gated", "rotated"):
            other = ref.attention_layer(p, x, empty, empty, zero, s, **{off: False})[0]
            assert float(jnp.max(jnp.abs(other - want))) > 1e-2, off


# -------------------------------------------------------------------- mixture


@pytest.mark.parametrize("capacity_factor,drops", [(2.0, False), (0.02, True)])
def test_the_softmax_mixture_against_the_reference_with_and_without_drops(built, ref, capacity_factor, drops):
    cfg = tiny_qwen_cfg(capacity_factor=capacity_factor)
    spec, layer, p = _layer((cfg, *built[1:]), "moe_2")
    s = ref.stack_of(cfg)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(8, 300, 64)), jnp.float32)   # 300 rows an expert, C = 128 or 640
    with jax.default_matmul_precision("highest"):
        got, counts = layer.apply({"params": p}, x)
        np.testing.assert_allclose(got, ref.moe_layer(p, x, s), rtol=2e-5, atol=2e-5)
        undropped = ref.moe_layer(p, x, s, drop=False)
    offered, dropped, load_max, load_mean = (float(c) for c in counts)
    assert offered > 0 and (dropped > 0) == drops and load_max >= load_mean == 8 * 300 * 2 / 16
    assert (float(jnp.max(jnp.abs(got - undropped))) > 1e-3) == drops


@pytest.mark.parametrize("tokens,first", [(16, 0), (16, 8), (128, 0), (128, 12)])
def test_where_every_token_fits_the_softmax_mixture_takes_no_queue_and_is_the_reference_and_the_queues_result(
        built, ref, tokens, first):
    from test_hybrid_stack import check_the_mixture_where_every_token_fits

    cfg = tiny_qwen_cfg(first_expert_held=first)
    _, layer, p = _layer((cfg, *built[1:]), "moe_2")
    with jax.default_matmul_precision("highest"):
        check_the_mixture_where_every_token_fits(layer, p, (2, tokens // 2, 64),
                                                 lambda p, x: ref.moe_layer(p, x, ref.stack_of(cfg)))


def test_one_token_more_than_a_held_experts_rows_takes_the_queue(built, ref):
    from test_hybrid_stack import check_one_token_more_than_a_held_experts_rows_takes_the_queue

    _, layer, p = _layer(built, "moe_2")
    with jax.default_matmul_precision("highest"):
        check_one_token_more_than_a_held_experts_rows_takes_the_queue(
            layer, p, lambda p, x: ref.moe_layer(p, x, ref.stack_of(built[0])))


def test_the_shares_routed_parts_and_the_shared_expert_once_are_the_uncut_layer(built, ref):
    """Four chips of four experts each: what each holds, summed, plus the
    shared expert once, is the layer that holds all sixteen."""
    cfg = tiny_qwen_cfg(capacity_factor=16.0)   # room for every assignment: the uncut layer drops nothing
    _, _, p = _layer(built, "moe_0")
    x = jnp.asarray(np.random.default_rng(6).normal(size=(2, 24, 64)), jnp.float32)
    rng = np.random.default_rng(7)
    experts = {name: jnp.asarray(rng.normal(size=(16, *p["experts"][name].shape[1:])), jnp.float32) / 8.0
               for name in ("gate", "up", "down")}
    with jax.default_matmul_precision("highest"):
        whole_cfg = tiny_qwen_cfg(capacity_factor=16.0, num_experts_held=16)
        whole_p = dict(p, experts=experts)
        whole = hs.ExpertMixture(hs.spec_of(whole_cfg).sizes("E"), jnp.float32).apply({"params": whole_p}, x)[0]
        np.testing.assert_allclose(whole, ref.moe_layer(whole_p, x, ref.stack_of(whole_cfg)), rtol=2e-5, atol=2e-5)
        flat = hs.rms_norm(x, 1.0 + p["pre_norm"], 1e-6).reshape(-1, 64)
        parts = []
        for first in (0, 4, 8, 12):
            share = hs.ExpertMixture(hs.spec_of(tiny_qwen_cfg(capacity_factor=16.0, first_expert_held=first)).sizes("E"),
                                     jnp.float32)
            share_p = dict(p, experts={name: w[first:first + 4] for name, w in experts.items()})
            parts.append(share.apply({"params": share_p}, flat, method="routed")[0])
        shared = share.apply({"params": share_p}, flat, method="shared")
    np.testing.assert_allclose(x + (sum(parts) + shared).reshape(x.shape), whole, rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------------ the whole


def _batch(cfg, B, seed=0):
    rng = np.random.default_rng(seed)
    T = cfg.seq_len
    _, hidden = _inputs(cfg, B, T, seed=seed, seen=3)
    return {
        "obs": jnp.asarray(rng.integers(0, 256, size=(B, T, *cfg.obs_shape)), jnp.uint8),
        "last_action": jnp.asarray(rng.integers(0, cfg.action_dim, size=(B, T)), jnp.int32),
        "last_reward": jnp.asarray(rng.normal(size=(B, T)), jnp.float32),
        "hidden": hidden, "action": jnp.asarray(rng.integers(0, cfg.action_dim, size=(B, cfg.learning_steps)), jnp.int32),
        "n_step_reward": jnp.asarray(rng.normal(size=(B, cfg.learning_steps)), jnp.float32),
        "gamma": jnp.full((B, cfg.learning_steps), 0.99 ** cfg.forward_steps, jnp.float32),
        "burn_in": jnp.full((B,), cfg.burn_in_steps, jnp.int32),
        "learning": jnp.full((B,), cfg.learning_steps, jnp.int32) - jnp.arange(B) % 2,
        "forward": jnp.full((B,), cfg.forward_steps, jnp.int32),
        "is_weights": jnp.asarray(rng.uniform(0.5, 1.0, (B,)), jnp.float32),
    }


def test_unroll_is_the_steps_and_the_whole_stack_is_the_references(built, ref):
    cfg, net, params = built
    core = {"params": params["params"]["core"]}
    x, hidden = _inputs(cfg, 2, 11, seed=4, seen=5)
    with jax.default_matmul_precision("highest"):
        outs, (end,) = net.core.apply(core, x, (hidden[:, 0],))
        np.testing.assert_allclose(outs, ref.stack_outputs(core["params"], x, hidden, ref.stack_of(cfg), drop=False),
                                   rtol=3e-5, atol=3e-5)
        carry, steps = (hidden[:, 0],), []
        for t in range(11):
            out, carry = net.core.apply(core, x[:, t], carry, method="step")
            steps.append(out)
        np.testing.assert_allclose(jnp.stack(steps, 1), outs, rtol=3e-5, atol=3e-5)
        np.testing.assert_allclose(carry[0], end, rtol=3e-5, atol=3e-5)
        opened = net.core.apply(core, carry, method="open_carry")
        out, opened = net.core.apply(core, x[:, 0], opened, method="step_open")
        closed = net.core.apply(core, opened, method="close_carry")
        np.testing.assert_array_equal(closed[0], net.core.apply(core, x[:, 0], carry, method="step")[1][0])


def test_forward_loss_and_gradient_against_the_reference(built, ref):
    import optax

    from r2d2_tpu.learner import DeviceBatch, make_loss_fn

    cfg, net, params = built
    target = jax.tree.map(lambda v: v * 1.02, params)
    b = _batch(cfg, 4, seed=8)
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
    # the four mixtures publish through the counters the first family's do
    counted = {k: float(v) for k, v in aux.items() if k.startswith("moe.")}
    assert set(counted) == {"moe.rows_offered", "moe.rows_dropped", "moe.dropped_share", "moe.load_max_over_mean"}
    assert 0 < counted["moe.rows_offered"] <= 4 * 4 * cfg.seq_len * 2 and counted["moe.load_max_over_mean"] >= 1.0


# ------------------------------------------------------------ published widths


def test_published_widths_give_the_hand_counts():
    """One row's state, the capacity and the parameter count by kind at
    published widths, by hand (no array is made: shapes only), and the file's
    two copies of the source's numbers against each other."""
    from benchmark import harness

    conf = json.load(open(os.path.join(ROOT, "benchmark", "configs", "qwen3-next-80b-a3b-ep32.json")))
    cfg = harness.build_config(conf, 0)
    spec = hs.spec_of(cfg)
    per_d, per_a = 32 * 128 * 128 + 3 * 8192, 1024 * 2 * 256 * 2
    assert (per_d, per_a) == (548864, 1048576)
    assert 3 * per_d + per_a + 2 == 2695170 and spec.state_size == 128 * -(-2695170 // 128) == 2695296
    assert spec.capacity(8 * 581) == 256 and spec.capacity(16 * 581) == 384 and spec.capacity(16) == 128
    assert spec.sizes("*").rotary_dim == 64 and spec.sizes("D").chunk == 64
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)[1])["params"]
    count = lambda tree: sum(int(np.prod(v.shape)) for v in jax.tree.leaves(tree))
    d = 2048 * 12288 + 2048 * 64 + 4 * 8192 + 32 + 32 + 128 + 4096 * 2048 + 2048
    a = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 256 + 2048
    e = 16 * 3 * 2048 * 512 + 2048 * 512 + 3 * 2048 * 512 + 2048 + 2048
    assert (d, a, e) == (33720512, 27265536, 54530048)
    assert count(shapes["core"]["gdn_1"]) == d and count(shapes["core"]["attention_3"]) == a
    assert count(shapes["core"]["moe_0"]) == e
    assert 3 * d + a + 4 * e == 346547264 and count(shapes["core"]) == 346547264 + 2048 + 2052 * 2048
    assert 365e6 < count(shapes) < 367e6      # 7.3 GB at 20 bytes a parameter
    for key, value in conf["overrides"]["core_config"].items():
        if key in conf:
            assert conf[key] == value, key


# -------------------------------------- the first family, as it was before this one

# sha256 of str(make_jaxpr(grad(sum of the layer's output))) at test_hybrid_stack's tiny widths, read on the
# parent commit (PR 55) and on this tree: the same text. jax 0.9.0. The mixture's is of 140 tokens since PR 59
# (read on ITS parent, 1883efd, and on its tree: the same text): 18 fit a held expert's 128 rows and take no queue.
BEFORE = {"attention": "5f3b602abab04f023833b54e9540cdf0596cd1c684cb7438cce1a92aa2ecfecb",
          "mixture": "80f552b893e5dbcc640278a55a494c3b277f3ef55cc7021081139761236b6e22"}


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_with_its_options_off_a_shared_layer_traces_to_the_jaxpr_it_had(name):
    """`EpisodeAttention` with no query / key norm, no rotary and no gate, and
    `ExpertMixture` as `nemotron_h` has it on more tokens than a held expert's
    rows (the queue), op for op what they were."""
    from test_hybrid_stack import tiny_cfg

    spec = hs.StackSpec.of(tiny_cfg())
    x, kv, count = jnp.zeros((2, 9, 64)), jnp.zeros((2, 16, 2, 16)), jnp.zeros((2,), jnp.int32)
    layer, args = {"attention": (hs.EpisodeAttention(spec, jnp.float32), (x, kv, kv, count)),
                   "mixture": (hs.ExpertMixture(spec, jnp.float32), (jnp.zeros((2, 70, 64)),))}[name]
    sizes = spec.sizes("*")
    assert (sizes.qk_norm, sizes.rotary_dim, sizes.output_gate, sizes.norm_offset) == (False, 0, False, 0.0)
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), *args)
    text = str(jax.make_jaxpr(jax.grad(lambda p, *a: jnp.sum(layer.apply(p, *a)[0])))(params, *args))
    assert hashlib.sha256(text.encode()).hexdigest() == BEFORE[name]
