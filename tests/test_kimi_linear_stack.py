"""The hybrid_stack core's third family, `kimi_linear` (models/hybrid_stack.py),
at tiny widths on the CPU, each piece against the plain float32 reference
(benchmark/reference/kimi_linear.py) on seeded weights: the chunked delta rule
whose decay is per key channel, and its gradient, against the recurrence (T
not whole chunks nor whole sub-chunks, keys that hardly differ, gates of -20 a
step; a gate constant over a head's channels is Gated DeltaNet's recurrence);
the mixer's unroll against its steps; latent attention's absorbed step, its
sequence form and the reference's one softmax across a ring that wraps, and
that the step builds no per-head key or value over the ring; the dense MLP;
the sigmoid mixture with gated experts, with and without drops, and with no
queue where every token fits; the share test; the whole forward, loss and
gradient; the hand counts at published widths; and what the spec refuses."""

import dataclasses
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
from test_qwen3_next_stack import TINY_QWEN, _batch, _inputs  # any stack's stored row and batch, whatever the family

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY_LINEAR = dict(kda_layers=[1, 2, 3, 5, 6, 7], full_attn_layers=[4, 8], num_heads=4, head_dim=16,
                   short_conv_kernel_size=4)
TINY_KIMI = dict(
    model_type="kimi_linear", hidden_size=64, num_hidden_layers=5, linear_attn_config=TINY_LINEAR,
    num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    first_k_dense_replace=1, intermediate_size=96, num_experts=16, num_experts_per_token=2, moe_intermediate_size=32,
    num_shared_experts=1, routed_scaling_factor=2.446, moe_renormalize=True, rms_norm_eps=1e-5, num_experts_held=4)
NAMES = {"kda": "K", "mla": "L", "mlp": "F", "moe": "E"}


def tiny_kimi_cfg(**core):
    return tiny_test().replace(recurrent_core="hybrid_stack", hidden_dim=64, max_episode_steps=16,
                               core_config=dict(TINY_KIMI, **core))


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(ROOT, "benchmark", "reference", "kimi_linear.py")
    spec = importlib.util.spec_from_file_location("reference_kimi_linear", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def built():
    cfg = tiny_kimi_cfg()
    net, params = init_params(jax.random.PRNGKey(0), cfg)
    # norm weights away from their initial 1, so that a layer that forgot one is caught
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 300))
    params = jax.tree.map(lambda v: v + 0.1 * jax.random.normal(next(keys), v.shape), params)
    return cfg, net, params


def _layer(built, name, **sizes):
    spec = hs.spec_of(built[0])
    kind = NAMES[name.split("_")[0]]
    module = hs.KINDS[kind][1](dataclasses.replace(spec.sizes(kind), **sizes), jnp.float32)
    return spec, module, built[2]["params"]["core"][name]


# ------------------------------------------------------------ config and state


def test_the_family_is_data_inside_core_config_and_the_blocks_follow_from_two_published_lists():
    cfg = tiny_kimi_cfg()
    hash(cfg)   # the published group of keys is held as pairs: the config stays hashable
    spec = hs.spec_of(cfg)
    assert isinstance(spec, hs.KimiLinearSpec)
    assert spec.blocks == (("K", 0), ("F", 0), ("K", 1), ("E", 1), ("K", 2), ("E", 2), ("L", 3), ("E", 3),
                           ("K", 4), ("E", 4))
    eight = hs.spec_of(tiny_kimi_cfg(num_hidden_layers=8, first_k_dense_replace=2))
    assert "".join(kind for kind, _ in eight.blocks) == "KFKFKELEKEKEKELE"
    assert "".join(k for k, _ in hs.spec_of(tiny_kimi_cfg(first_k_dense_replace=0)).blocks) == "KEKEKELEKE"
    _, params = init_params(jax.random.PRNGKey(3), cfg)
    core = params["params"]["core"]
    assert set(core) == {"in_proj", "final_norm", "kda_0", "mlp_0", "kda_1", "moe_1", "kda_2", "moe_2", "mla_3",
                         "moe_3", "kda_4", "moe_4"}
    assert set(core["kda_1"]) == {"pre_norm", "q_proj", "k_proj", "v_proj", "q_conv", "k_conv", "v_conv", "f_a", "f_b",
                                  "g_a", "g_b", "b_proj", "A_log", "dt_bias", "norm", "o_proj"}
    assert core["kda_1"]["A_log"].shape == (4,) and core["kda_1"]["dt_bias"].shape == (64,)   # a head; a key channel
    assert set(core["mla_3"]) == {"pre_norm", "q_proj", "kv_a_proj", "kv_a_norm", "kv_b_proj", "o_proj"}
    assert core["mla_3"]["kv_a_proj"].shape == (64, 40) and core["mla_3"]["kv_b_proj"].shape == (32, 4 * 32)
    assert set(core["mlp_0"]) == {"pre_norm", "gate", "up", "down"}
    # nemotron's router with qwen3-next's experts, and an ungated shared expert
    assert set(core["moe_1"]) == {"pre_norm", "router", "e_score_correction_bias", "experts", "shared_gate", "shared_up",
                                  "shared_down"}
    assert set(core["moe_1"]["experts"]) == {"gate", "up", "down"}
    sizes = spec.sizes("E")
    assert (sizes.softmax, sizes.gated, sizes.shared_gate, sizes.scale, sizes.norm_offset) == (False, True, False, 2.446, 0.0)
    assert hs.spec_of(tiny_kimi_cfg(num_shared_experts=2)).sizes("E").shared_width == 64


@pytest.mark.parametrize("change,match", [
    (dict(expansion=2), "unknown keys"), (dict(hybrid_override_pattern="EM*"), "unknown keys"),
    (dict(full_attention_interval=4), "unknown keys"), (dict(hidden_size=32), "hidden_size"),
    (dict(first_expert_held=14), "held experts"), (dict(model_type="llama"), "model_type"),
    (dict(moe_renormalize=False), "moe_renormalize"), (dict(first_k_dense_replace=6), "first_k_dense_replace"),
    (dict(num_shared_experts=0), "num_shared_experts"), (dict(num_hidden_layers=9), "every layer"),
    (dict(linear_attn_config=dict(TINY_LINEAR, full_attn_layers=[3, 4])), "not both"),
    (dict(linear_attn_config={k: v for k, v in TINY_LINEAR.items() if k != "head_dim"}), "linear_attn_config"),
    (dict(linear_attn_config=dict(TINY_LINEAR, window=4)), "linear_attn_config")])
def test_a_wrong_missing_or_other_familys_key_is_refused_by_the_class(change, match):
    with pytest.raises(ValueError, match=match):
        hs.spec_of(tiny_kimi_cfg(**change))
    missing = {k: v for k, v in TINY_KIMI.items() if k != "kv_lora_rank"}
    with pytest.raises(ValueError, match="missing keys"):
        hs.spec_of(tiny_test().replace(recurrent_core="hybrid_stack", hidden_dim=64, core_config=missing))
    # the second family's keys under the third's name, and the third's under the first's
    with pytest.raises(ValueError, match="unknown keys"):
        hs.spec_of(tiny_test().replace(recurrent_core="hybrid_stack", hidden_dim=64,
                                       core_config=dict(TINY_QWEN, model_type="kimi_linear")))
    with pytest.raises(ValueError, match="unknown keys"):
        hs.spec_of(tiny_test().replace(recurrent_core="hybrid_stack", hidden_dim=64,
                                       core_config={k: v for k, v in TINY_KIMI.items() if k != "model_type"}))


def test_the_carry_is_one_flat_row_of_whole_lanes_and_the_attention_stores_the_latent():
    cfg = tiny_kimi_cfg()
    spec = hs.spec_of(cfg)
    (n, S), _ = state_spec(cfg)
    raw = 4 * (4 * 16 * 16 + 3 * 3 * 64) + 16 * (32 + 8) + 2
    assert n == 1 and S == 128 * -(-raw // 128)
    assert [(i, name) for i, name, _ in spec.segments()] == [
        (0, "delta"), (0, "conv"), (1, "delta"), (1, "conv"), (2, "delta"), (2, "conv"), (3, "latent"), (4, "delta"),
        (4, "conv"), (-1, "count")]
    # kv_lora_rank + qk_rope_head_dim numbers a position, where the heads' keys and values would be 4 x (24 + 16)
    assert dict((name, shape) for _, name, shape in spec.segments())["latent"] == (16, 40)
    flat = jnp.arange(2 * S, dtype=jnp.float32).reshape(2, S).at[:, raw:].set(0.0)
    assert np.array_equal(hs.join_state(spec, hs.split_state(spec, flat)), flat)


# ------------------------------------------- the delta rule, a decay a channel


def _recurrence(q, k, v, g, beta, s0):
    """The delta rule one step at a time: q, k, g (B, T, H, dk); v (B, T, H, dv); beta (B, T, H)."""
    def step(S, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        S = jnp.exp(g_t)[..., None] * S
        r = jnp.einsum("bhkv,bhk->bhv", S, k_t)
        S = S + jnp.einsum("bhk,bhv->bhkv", k_t, b_t[..., None] * (v_t - r))
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    S, o = jax.lax.scan(step, s0, tuple(jnp.swapaxes(a, 0, 1) for a in (q, k, v, g, beta)))
    return jnp.swapaxes(o, 0, 1), S


def _draws(T, seed, B=2, H=3, dk=16, dv=8, gate=0.5, stored=True):
    rng = np.random.default_rng(seed)
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    unit = lambda a: a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
    q, k, v = unit(normal(B, T, H, dk)) * dk ** -0.5, unit(normal(B, T, H, dk)), normal(B, T, H, dv)
    g, beta = -jnp.abs(normal(B, T, H, dk)) * gate, jax.nn.sigmoid(normal(B, T, H))
    return q, k, v, g, beta, normal(B, H, dk, dv) * (0.5 if stored else 0.0)


def _chunked(chunk, sub):
    def run(q, k, v, g, beta, s0):
        B, T, H, dv = v.shape
        flat = lambda a: a.reshape(B, T, -1)
        o, S = hs.kda_chunked(flat(q), flat(k), flat(v), flat(g), beta, s0, chunk, jnp.float32, sub)
        return o.reshape(B, T, H, dv), S

    return run


@pytest.mark.parametrize("stored", [False, True], ids=["from_zero", "from_a_stored_state"])
@pytest.mark.parametrize("T,chunk,sub", [(21, 8, 4), (5, 8, 4), (16, 8, 4), (1, 8, 4), (70, 64, 16), (37, 32, 16)],
                         ids=["not_whole_chunks", "below_a_chunk", "whole_chunks", "one_step", "the_cells_chunk",
                              "not_whole_sub_chunks"])
def test_the_chunked_per_channel_delta_rule_and_its_gradient_against_the_recurrence(T, chunk, sub, stored):
    args = _draws(T, T + stored, stored=stored)
    with jax.default_matmul_precision("highest"):
        want, got = _recurrence(*args), _chunked(chunk, sub)(*args)
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
        weigh = lambda fn: lambda *a: sum(jnp.sum(out * jnp.cos(jnp.arange(out.size).reshape(out.shape))) for out in fn(*a))
        d_want = jax.grad(weigh(_recurrence), argnums=tuple(range(6)))(*args)
        d_got = jax.grad(weigh(_chunked(chunk, sub)), argnums=tuple(range(6)))(*args)
    for a, b in zip(d_got, d_want):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


def test_gates_of_minus_twenty_a_step_over_whole_chunks_stay_finite_and_are_the_recurrence():
    """Every exponent the chunked form evaluates is <= 0 by construction: with
    g = -20 on every channel of every step of two whole chunks, `exp(-G_j)`
    would be e^1280 by the chunk's last row, and a form that split the pair
    term's decay there overflows float32. This one reads the recurrence."""
    q, k, v, g, beta, s0 = _draws(128, 60, stored=True)
    g = jnp.full_like(g, -20.0)
    with jax.default_matmul_precision("highest"):
        want, got = _recurrence(q, k, v, g, beta, s0), _chunked(64, 16)(q, k, v, g, beta, s0)
        grads = jax.grad(lambda *a: sum(jnp.sum(o) for o in _chunked(64, 16)(*a)), argnums=tuple(range(6)))(
            q, k, v, g, beta, s0)
    for a, b in zip(got, want):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    assert all(bool(jnp.all(jnp.isfinite(d))) for d in grads)


def test_no_exponent_the_chunked_form_evaluates_is_positive(monkeypatch):
    """Run op by op (no jit: the scans are Python loops over concrete values)
    with `exp` watched: on strongly negative gates, and on ordinary ones,
    nothing positive is ever exponentiated, inside a sub-chunk's columns,
    between sub-chunks or between chunks."""
    seen = []
    real = jnp.exp

    def watched(a):
        seen.append(float(jnp.max(a)))
        return real(a)

    monkeypatch.setattr(hs.jnp, "exp", watched)
    monkeypatch.setattr(hs.jax, "checkpoint", lambda fn: fn)   # it traces its function even where nothing is jitted
    for gate in (0.5, 20.0):
        q, k, v, g, beta, s0 = _draws(70, 61, B=1, H=2, gate=gate)
        with jax.disable_jit():
            o, S = _chunked(64, 16)(q, k, v, g, beta, s0)
        assert bool(jnp.all(jnp.isfinite(o))) and bool(jnp.all(jnp.isfinite(S)))
    assert len(seen) > 2 * (16 + 3 + 3) and max(seen) <= 0.0


def test_the_chunked_form_holds_on_keys_that_hardly_differ_and_gates_that_hardly_forget():
    """An agent's consecutive frames give nearly the same key at every step
    and beta near one: the chunk's triangular matrix is near all ones, where
    a series of squarings loses every digit (PERF.md finding 56.3)."""
    B, T, H, dk, dv = 2, 128, 2, 16, 8
    rng = np.random.default_rng(11)
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    unit = lambda a: a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
    k = unit(normal(B, 1, H, dk) + 0.05 * normal(B, T, H, dk))
    q, v = unit(normal(B, T, H, dk)) * dk ** -0.5, normal(B, T, H, dv)
    g, beta = -0.01 * jnp.abs(normal(B, T, H, dk)), jax.nn.sigmoid(4.0 + normal(B, T, H))
    s0 = normal(B, H, dk, dv)
    with jax.default_matmul_precision("highest"):
        want, got = _recurrence(q, k, v, g, beta, s0), _chunked(64, 16)(q, k, v, g, beta, s0)
    assert float(jnp.min(jnp.einsum("bihd,bjhd->bhij", k, k))) > 0.9
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_a_gate_that_is_constant_over_a_heads_channels_is_gated_deltanets_recurrence():
    """One number a head in every key channel: `Diag(exp(g)) S` is `exp(g) S`,
    and the per-channel chunked form gives what `delta_rule_chunked` gives on
    the same inputs (and a gate that differs by channel does not)."""
    q, k, v, g, beta, s0 = _draws(70, 21)
    scalar = jnp.mean(g, axis=-1)
    B, T, H, dv = v.shape
    flat = lambda a: a.reshape(B, T, -1)
    with jax.default_matmul_precision("highest"):
        want = hs.delta_rule_chunked(flat(q), flat(k), flat(v), scalar, beta, s0, 64, jnp.float32)
        got = _chunked(64, 16)(q, k, v, jnp.broadcast_to(scalar[..., None], g.shape), beta, s0)
        other = _chunked(64, 16)(q, k, v, g, beta, s0)
    np.testing.assert_allclose(got[0].reshape(B, T, -1), want[0], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got[1], want[1], rtol=2e-5, atol=2e-5)
    assert float(jnp.max(jnp.abs(other[1] - want[1]))) > 1e-2


def test_the_mixers_unroll_is_its_steps_and_both_are_the_references_loop(built, ref):
    spec, layer, p = _layer(built, "kda_1", chunk=8)
    s = ref.stack_of(built[0])
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(3, 21, 64)), jnp.float32)       # 21: not whole chunks of 8
    delta = jnp.asarray(rng.normal(size=(3, 4, 16, 16)), jnp.float32) * 0.5
    tail = jnp.asarray(rng.normal(size=(3, 3, 192)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, (want_delta, want_tail) = ref.kda_layer(p, x, delta, tail, s)
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
        # the mechanism is there: with one gate a head the reference reads something else
        scalar = ref.kda_layer(p, x, delta, tail, s, scalar_gate=True)[0]
    assert float(jnp.max(jnp.abs(scalar - want))) > 1e-2


# ------------------------------------------------------------ latent attention


def test_latent_attention_through_the_ring_is_full_causal_attention_over_up_projected_keys_and_values(built, ref):
    spec, layer, p = _layer(built, "mla_3")
    s = ref.stack_of(built[0])
    rng = np.random.default_rng(3)
    B, T, W = 2, 14, 16
    x = jnp.asarray(rng.normal(size=(B, T, 64)), jnp.float32)
    empty, zero = jnp.zeros((B, W, 40), jnp.float32), jnp.zeros((B,), jnp.int32)
    with jax.default_matmul_precision("highest"):
        want, want_new = ref.mla_layer(p, x, empty, zero, s)
        whole, ring = layer.apply({"params": p}, x, empty, zero)
        np.testing.assert_allclose(whole, want, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(ring[:, :T], want_new, rtol=2e-5, atol=2e-5)       # the latent, after its norm
        # cut in two: the second part sees the first in the ring
        first, ring = layer.apply({"params": p}, x[:, :9], empty, zero)
        second, ring2 = layer.apply({"params": p}, x[:, 9:], ring, zero + 9)
        np.testing.assert_allclose(jnp.concatenate([first, second], 1), want, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(ring2[:, :T], want_new, rtol=2e-5, atol=2e-5)
        # the reference from a stored ring, too
        np.testing.assert_allclose(ref.mla_layer(p, x[:, 9:], ring, zero + 9, s)[0], want[:, 9:], rtol=2e-5, atol=2e-5)
        # each mechanism is there (or rightly absent): otherwise the reference reads something else
        for other in (dict(rotated=True), dict(ring_k_pe=False)):
            out = ref.mla_layer(p, x[:, 9:], ring, zero + 9, s, **other)[0]
            assert float(jnp.max(jnp.abs(out - want[:, 9:]))) > 1e-2, other


def test_the_absorbed_step_is_the_sequence_form_and_the_reference_across_a_ring_that_wraps(built, ref):
    """From a stored ring and counts on both sides of its length, one position
    at a time for 20 positions (W = 16): `step` (W_UK absorbed into the query,
    W_UV after the weighted sum of latents), `__call__` at T = 1 (keys and
    values up-projected) and the reference's one softmax agree at every
    position, and write the same ring."""
    spec, layer, p = _layer(built, "mla_3")
    s = ref.stack_of(built[0])
    rng = np.random.default_rng(4)
    B, W = 2, 16
    ring = jnp.asarray(rng.normal(size=(B, W, 40)), jnp.float32)
    xs = jnp.asarray(rng.normal(size=(B, 20, 64)), jnp.float32)
    count = jnp.asarray([3, 12], jnp.int32)
    absorbed = unrolled = ring
    with jax.default_matmul_precision("highest"):
        for t in range(20):
            want = ref.mla_layer(p, xs[:, t:t + 1], unrolled, count + t, s)[0][:, 0]
            a, absorbed = layer.apply({"params": p}, xs[:, t], absorbed, count + t, method="step")
            b, unrolled = layer.apply({"params": p}, xs[:, t:t + 1], unrolled, count + t)
            np.testing.assert_allclose(a, want, rtol=2e-5, atol=2e-5)
            np.testing.assert_allclose(b[:, 0], want, rtol=2e-5, atol=2e-5)
            np.testing.assert_allclose(absorbed, unrolled, rtol=1e-6, atol=1e-6)
    assert int(count[1]) + 20 > 2 * W - 4 and not np.allclose(absorbed, ring)    # the second row went round


def test_the_acting_step_builds_no_per_head_key_or_value_over_the_ring(built):
    """On the step's jaxpr: no array holds the ring's positions AND the heads
    AND a head's width (the sequence form's `(B, W + T, H, nope + value)` is
    exactly that); what the step holds over the ring is the latent itself and
    the scores, `(B, H, W + 1)`."""
    spec, layer, p = _layer(built, "mla_3", max_episode_steps=20)    # a ring length that is no other size here
    sizes = layer.spec
    B, W, H = 3, sizes.max_episode_steps, sizes.heads
    x, ring, count = jnp.zeros((B, 64)), jnp.zeros((B, W, sizes.stored)), jnp.zeros((B,), jnp.int32)

    def shapes(fn, *args):
        found = set()

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                found.update(tuple(v.aval.shape) for v in eqn.outvars)
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

        walk(jax.make_jaxpr(fn)(*args).jaxpr)
        return found

    per_head_over_ring = lambda shape: (any(n in (W, W + 1) for n in shape) and H in shape
                                        and any(n in (sizes.nope_dim, sizes.value_dim, sizes.nope_dim + sizes.value_dim,
                                                      sizes.nope_dim + sizes.rope_dim) for n in shape[-1:]))
    step = shapes(lambda x, r, c: layer.apply({"params": p}, x, r, c, method="step"), x, ring, count)
    assert not [shape for shape in step if per_head_over_ring(shape)]
    assert (B, H, W + 1) in step and (B, H, sizes.latent) in step
    sequence = shapes(lambda x, r, c: layer.apply({"params": p}, x, r, c), x[:, None], ring, count)
    assert [shape for shape in sequence if per_head_over_ring(shape)]      # the same test finds them where they are


# ---------------------------------------------------------- the MLPs


def test_the_dense_mlp_is_the_references(built, ref):
    _, layer, p = _layer(built, "mlp_0")
    x = jnp.asarray(np.random.default_rng(9).normal(size=(2, 7, 64)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(layer.apply({"params": p}, x), ref.mlp_layer(p, x, ref.stack_of(built[0])),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("capacity_factor,drops", [(2.0, False), (0.02, True)])
def test_the_sigmoid_mixture_of_gated_experts_against_the_reference_with_and_without_drops(built, ref, capacity_factor,
                                                                                           drops):
    cfg = tiny_kimi_cfg(capacity_factor=capacity_factor)
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
def test_where_every_token_fits_the_mixture_takes_no_queue_and_is_the_reference_and_the_queues_result(
        built, ref, tokens, first):
    from test_hybrid_stack import check_the_mixture_where_every_token_fits

    cfg = tiny_kimi_cfg(first_expert_held=first)
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
    """Four chips of four experts each (the cell: 32 of 8): what each holds,
    summed, plus the shared expert once, is the layer that holds all sixteen."""
    _, _, p = _layer(built, "moe_1")
    x = jnp.asarray(np.random.default_rng(6).normal(size=(2, 24, 64)), jnp.float32)
    rng = np.random.default_rng(7)
    experts = {name: jnp.asarray(rng.normal(size=(16, *p["experts"][name].shape[1:])), jnp.float32) / 8.0
               for name in ("gate", "up", "down")}
    with jax.default_matmul_precision("highest"):
        whole_cfg = tiny_kimi_cfg(capacity_factor=16.0, num_experts_held=16)   # room for every assignment: nothing drops
        whole_p = dict(p, experts=experts)
        whole = hs.ExpertMixture(hs.spec_of(whole_cfg).sizes("E"), jnp.float32).apply({"params": whole_p}, x)[0]
        np.testing.assert_allclose(whole, ref.moe_layer(whole_p, x, ref.stack_of(whole_cfg)), rtol=2e-5, atol=2e-5)
        flat = hs.rms_norm(x, p["pre_norm"], 1e-5).reshape(-1, 64)
        parts = []
        for first in (0, 4, 8, 12):
            share = hs.ExpertMixture(hs.spec_of(tiny_kimi_cfg(capacity_factor=16.0, first_expert_held=first)).sizes("E"),
                                     jnp.float32)
            share_p = dict(p, experts={name: w[first:first + 4] for name, w in experts.items()})
            parts.append(share.apply({"params": share_p}, flat, method="routed")[0])
        shared = share.apply({"params": share_p}, flat, method="shared")
    np.testing.assert_allclose(x + (sum(parts) + shared).reshape(x.shape), whole, rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------------ the whole


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
        assert len(opened) == len(hs.spec_of(cfg).segments())
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
    # no gradient reaches the correction bias: it enters the choice of experts alone
    assert all(float(jnp.max(jnp.abs(grads["params"]["core"][f"moe_{i}"]["e_score_correction_bias"]))) == 0.0
               for i in (1, 2, 3, 4))
    # the four mixtures publish through the counters the other families' do
    counted = {k: float(v) for k, v in aux.items() if k.startswith("moe.")}
    assert set(counted) == {"moe.rows_offered", "moe.rows_dropped", "moe.dropped_share", "moe.load_max_over_mean"}
    assert 0 < counted["moe.rows_offered"] <= 4 * 4 * cfg.seq_len * 2 and counted["moe.load_max_over_mean"] >= 1.0


# ------------------------------------------------------------ published widths


def test_published_widths_give_the_hand_counts():
    """One row's state, the capacity and the parameter count by kind at
    published widths, by hand (no array is made: shapes only), and the file's
    two copies of the source's numbers against each other, key by key."""
    from benchmark import harness

    conf = json.load(open(os.path.join(ROOT, "benchmark", "configs", "kimi-linear-48b-a3b-ep32.json")))
    cfg = harness.build_config(conf, 0)
    spec = hs.spec_of(cfg)
    assert "".join(kind for kind, _ in spec.blocks) == "KFKEKELEKE"
    per_k, per_l = 32 * 128 * 128 + 3 * 12288, 1024 * 576
    assert (per_k, per_l) == (561152, 589824)
    assert 4 * per_k + per_l + 2 == 2834434 and spec.state_size == 128 * -(-2834434 // 128) == 2834560
    assert spec.sizes("L").stored == 576 and 32 * (128 + 64 + 128) == 10240   # what a position stores; what its heads' keys and values would be
    assert spec.capacity(8 * 581) == 384 and spec.capacity(4 * 581) == 256 and spec.capacity(16) == 128
    assert spec.sizes("K").chunk == 64 and hs.KDA_SUB == 16
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)[1])["params"]
    count = lambda tree: sum(int(np.prod(v.shape)) for v in jax.tree.leaves(tree))
    k = (3 * 2304 * 4096 + 3 * 4 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32 + 32 + 4096 + 128 + 4096 * 2304
         + 2304)
    l = 2304 * 6144 + 2304 * 576 + 512 + 512 * 8192 + 4096 * 2304 + 2304
    f = 3 * 2304 * 9216 + 2304
    e = 8 * 3 * 2304 * 1024 + 3 * 2304 * 1024 + 2304 * 256 + 256 + 2304
    assert (k, l, f, e) == (39516576, 29117184, 63703296, 64293376)
    core = shapes["core"]
    assert count(core["kda_1"]) == k and count(core["mla_3"]) == l and count(core["mlp_0"]) == f
    assert count(core["moe_1"]) == e
    assert 4 * k + l + f + 4 * e == 508060288 and count(core) == 508060288 + 2304 + 2308 * 2304
    assert 530e6 < count(shapes) < 532e6      # 12.7 GB at 24 bytes a parameter
    published = {key: value for key, value in conf.items() if key in conf["overrides"]["core_config"]}
    assert set(published) == set(conf["overrides"]["core_config"]) - {"num_experts_held", "capacity_factor"}
    for key, value in conf["overrides"]["core_config"].items():
        if key in conf:
            assert conf[key] == value, key
    assert conf["linear_attn_config"]["kda_layers"][:4] == [1, 2, 3, 5] and conf["linear_attn_config"]["full_attn_layers"][0] == 4
