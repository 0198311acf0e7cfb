"""ops/pallas_delta.py under the Pallas interpreter, at tiny sizes: the chunk
solve's kernel against `solve_triangular` (`hybrid_stack.unit_lower_solve`, its
fallback and its oracle), and `delta_rule_chunked` with the kernel against the
recurrence one step at a time. What the chip's compiler makes of the kernel at
the qwen3-next cell's shape is `tests/test_v5e_compile.py`'s."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from r2d2_tpu.models import hybrid_stack as hs
from r2d2_tpu.ops import pallas_delta as pd
from test_qwen3_next_stack import _recurrence


def _independent(rng, lead, Q, m):
    """Triangles of independent normal draws, scaled so the inverse stays of order one."""
    L = np.tril(rng.normal(size=(*lead, Q, Q)) / np.sqrt(Q), -1)
    return jnp.asarray(L, jnp.float32), jnp.asarray(rng.normal(size=(*lead, Q, m)), jnp.float32)


def _an_agents(rng, lead, Q, m):
    """What a chunk of an agent's keys gives: `k_i . k_j > 0.9` and beta 0.9-0.99, L near `beta` times all
    ones (the regime of test_the_chunked_delta_rule_holds_on_keys_that_hardly_differ_from_step_to_step)."""
    k = rng.normal(size=(*lead, 1, 16)) + 0.05 * rng.normal(size=(*lead, Q, 16))
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    kk = np.einsum("...id,...jd->...ij", k, k)
    assert kk.min() > 0.9
    beta = rng.uniform(0.9, 0.99, size=(*lead, Q, 1))
    G = np.cumsum(-0.01 * np.abs(rng.normal(size=(*lead, Q))), axis=-1)
    L = np.tril(beta * kk * np.exp(G[..., :, None] - G[..., None, :]), -1)
    return jnp.asarray(L, jnp.float32), jnp.asarray(rng.normal(size=(*lead, Q, m)), jnp.float32)


DRAWS = {"independent_draws": _independent, "an_agents_keys": _an_agents}
SHAPES = {"one_block_of_lanes": ((2, 64), 16, 24), "two_blocks_the_cells_chunk": ((4, 2, 32), 64, 8)}


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("draw", sorted(DRAWS))
def test_the_kernels_solve_is_solve_triangulars(draw, shape):
    lead, Q, m = SHAPES[shape]
    L, rhs = DRAWS[draw](np.random.default_rng(Q + len(draw)), lead, Q, m)
    assert pd.kernel_fits(int(np.prod(lead)), Q)
    got, want = pd.unit_lower_solve(L, rhs), hs.unit_lower_solve(L, rhs)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5 * scale)
    # what it solves: (I + L) X = rhs, to substitution's own residual
    back = jnp.einsum("...ij,...jk->...ik", jnp.eye(Q) + L, got, precision="highest")
    np.testing.assert_allclose(back, rhs, rtol=0, atol=2e-5 * scale)


@pytest.mark.parametrize("draw", sorted(DRAWS))
def test_the_gradient_through_the_custom_vjp_is_solve_triangulars_for_every_input(draw):
    lead, Q, m = (2, 64), 16, 24
    L, rhs = DRAWS[draw](np.random.default_rng(7), lead, Q, m)
    weights = jnp.cos(jnp.arange(rhs.size, dtype=jnp.float32)).reshape(rhs.shape)
    grads = lambda solve: jax.grad(lambda L, rhs: jnp.sum(weights * solve(L, rhs)), argnums=(0, 1))(L, rhs)
    for got, want in zip(grads(pd.unit_lower_solve), grads(hs.unit_lower_solve)):
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-5 * float(jnp.max(jnp.abs(want))))
    # nothing on or above the diagonal reaches L: the kernel never read it
    assert float(jnp.max(jnp.abs(jnp.triu(grads(pd.unit_lower_solve)[0])))) == 0.0


def test_the_kernel_does_not_read_the_diagonal_or_above_it():
    L, rhs = _independent(np.random.default_rng(3), (128,), 8, 4)
    junk = L + jnp.triu(jnp.full_like(L, 7.0))
    np.testing.assert_array_equal(pd.unit_lower_solve(junk, rhs), pd.unit_lower_solve(L, rhs))


@pytest.mark.parametrize("triangles,Q,fits", [(2560, 64, True), (128, 8, True), (48, 8, False), (128, 12, False),
                                              (0, 64, False), (2560, 0, False)])
def test_the_shape_test_asks_for_whole_lanes_of_triangles_and_whole_vregs_a_row(triangles, Q, fits):
    assert pd.kernel_fits(triangles, Q) is fits
    if not fits and triangles and Q:
        with pytest.raises(ValueError, match="_gdn_inverse_call"):
            pd.unit_lower_inverse(jnp.zeros((triangles, Q, Q)))


@pytest.mark.parametrize("stored", [False, True], ids=["from_zero", "from_a_stored_state"])
@pytest.mark.parametrize("T", [32, 29], ids=["whole_chunks", "not_whole_chunks"])
def test_the_chunked_delta_rule_with_the_kernel_and_its_gradient_against_the_recurrence(T, stored, monkeypatch):
    B, Hk, Hv, dk, dv, chunk = 4, 4, 8, 16, 8, 8           # 4 x 4 chunks x 8 value heads: 128 triangles
    calls = []
    monkeypatch.setattr(pd, "unit_lower_solve", lambda *a, solve=pd.unit_lower_solve: calls.append(1) or solve(*a))
    monkeypatch.setattr(hs, "unit_lower_solve", None)      # the fallback is not what runs here
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
    assert len(calls) == 2                                  # the forward, and the gradient's


# sha256 of str(make_jaxpr(grad(sum of delta_rule_chunked's outputs))) at 2 x 3 chunks x 4 value heads = 24
# triangles, read on the parent commit (PR 56) and on this tree: the same text. jax 0.9.0.
BEFORE = "7fd49276935b818bbd8c736d95e14fd943d930fe9feaf76b252fe29dc7b85686"


def test_a_width_the_kernel_does_not_fit_takes_solve_triangular_and_traces_to_the_jaxpr_it_had():
    B, T, Hk, Hv, dk, dv, chunk = 2, 21, 2, 4, 16, 8, 8
    assert not pd.kernel_fits(B * -(-T // chunk) * Hv, chunk)
    z = lambda *s: jnp.zeros(s, jnp.float32)
    args = (z(B, T, Hk * dk), z(B, T, Hk * dk), z(B, T, Hv * dv), z(B, T, Hv), z(B, T, Hv), z(B, Hv, dk, dv))
    fn = lambda *a: sum(jnp.sum(o) for o in hs.delta_rule_chunked(*a, chunk, jnp.float32))
    text = str(jax.make_jaxpr(jax.grad(fn, argnums=tuple(range(6))))(*args))
    assert "triangular_solve" in text and "pallas_call" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == BEFORE


def test_at_the_cells_shape_the_layer_takes_the_kernel_and_no_triangular_solve():
    """B 8, T 581 in ten chunks of 64, 32 value heads: 2,560 triangles. Traced, not run."""
    B, T, Hk, Hv, dk, dv, chunk = 8, 581, 16, 32, 128, 128, 64
    z = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    args = (z(B, T, Hk * dk), z(B, T, Hk * dk), z(B, T, Hv * dv), z(B, T, Hv), z(B, T, Hv), z(B, Hv, dk, dv))
    fn = lambda *a: sum(jnp.sum(o) for o in hs.delta_rule_chunked(*a, chunk, jnp.bfloat16))
    text = str(jax.make_jaxpr(jax.grad(fn, argnums=tuple(range(6))))(*args))
    assert "triangular_solve" not in text
    assert text.count("pallas_call") == 1 and "name=_gdn_inverse_call" in text
