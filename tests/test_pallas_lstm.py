"""Parity of the fused Pallas LSTM unroll (ops/pallas_lstm.py) against the
lax.scan reference implementation (models/lstm.py), values AND gradients.

Runs in Pallas interpret mode on the CPU test backend — the same kernel
code path that compiles on TPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from r2d2_tpu.config import tiny_test
from r2d2_tpu.models.lstm import LSTM
from r2d2_tpu.ops.pallas_lstm import lstm_seq_unroll, lstm_unroll

pytestmark = pytest.mark.kernels


def _scan_reference(proj_t, wh, h0, c0):
    """Plain-JAX unroll over time-major projections (the scan semantics)."""
    H = h0.shape[-1]

    def step(carry, p):
        h, c = carry
        z = p + h @ wh
        i = jax.nn.sigmoid(z[..., :H])
        f = jax.nn.sigmoid(z[..., H : 2 * H])
        g = jnp.tanh(z[..., 2 * H : 3 * H])
        o = jax.nn.sigmoid(z[..., 3 * H :])
        c = f * c + i * g
        h = o * jnp.tanh(c)
        return (h, c), h

    (h, c), outs = jax.lax.scan(step, (h0, c0), proj_t)
    return outs, (h, c)


def _rand_inputs(rng, T=6, B=8, H=16):
    proj_t = jnp.asarray(rng.normal(size=(T, B, 4 * H)).astype(np.float32))
    wh = jnp.asarray((rng.normal(size=(H, 4 * H)) / np.sqrt(H)).astype(np.float32))
    h0 = jnp.asarray(rng.normal(size=(B, H)).astype(np.float32) * 0.3)
    c0 = jnp.asarray(rng.normal(size=(B, H)).astype(np.float32) * 0.3)
    return proj_t, wh, h0, c0


def test_forward_matches_scan():
    proj_t, wh, h0, c0 = _rand_inputs(np.random.default_rng(0))
    outs_p, (hT_p, cT_p) = lstm_unroll(proj_t, wh, h0, c0)
    outs_s, (hT_s, cT_s) = _scan_reference(proj_t, wh, h0, c0)
    np.testing.assert_allclose(np.asarray(outs_p), np.asarray(outs_s), atol=1e-5)
    np.testing.assert_allclose(np.asarray(hT_p), np.asarray(hT_s), atol=1e-5)
    np.testing.assert_allclose(np.asarray(cT_p), np.asarray(cT_s), atol=1e-5)


@pytest.mark.parametrize("wrt", [0, 1, 2, 3])  # proj, wh, h0, c0
def test_grads_match_scan(wrt):
    proj_t, wh, h0, c0 = _rand_inputs(np.random.default_rng(1))
    rng = np.random.default_rng(2)
    # random cotangent over outputs only (the learner's real use: the final
    # carry is discarded by R2D2Network.unroll)
    ct = jnp.asarray(rng.normal(size=(6, 8, 16)).astype(np.float32))

    def loss_pallas(*args):
        outs, _ = lstm_unroll(*args)
        return jnp.sum(outs * ct)

    def loss_scan(*args):
        outs, _ = _scan_reference(*args)
        return jnp.sum(outs * ct)

    g_p = jax.grad(loss_pallas, argnums=wrt)(proj_t, wh, h0, c0)
    g_s = jax.grad(loss_scan, argnums=wrt)(proj_t, wh, h0, c0)
    np.testing.assert_allclose(np.asarray(g_p), np.asarray(g_s), rtol=1e-4, atol=1e-5)


def test_final_carry_grads_match_scan():
    """Cotangents through (h_T, c_T) too — exercises the dcT seed path."""
    proj_t, wh, h0, c0 = _rand_inputs(np.random.default_rng(3))

    def loss(fn, *args):
        outs, (hT, cT) = fn(*args)
        return jnp.sum(outs) * 0.1 + jnp.sum(hT * cT)

    for wrt in range(4):
        g_p = jax.grad(lambda *a: loss(lstm_unroll, *a), argnums=wrt)(proj_t, wh, h0, c0)
        g_s = jax.grad(lambda *a: loss(_scan_reference, *a), argnums=wrt)(proj_t, wh, h0, c0)
        np.testing.assert_allclose(
            np.asarray(g_p), np.asarray(g_s), rtol=1e-4, atol=1e-5,
        )


def test_lstm_module_backend_parity():
    """The full flax LSTM module agrees between backend='scan' and
    backend='pallas' (same params), values and input grads."""
    cfg = tiny_test()
    B, T, D, H = 4, 6, 24, cfg.hidden_dim
    scan_mod = LSTM(hidden_dim=H, in_dim=D, backend="scan")
    pallas_mod = LSTM(hidden_dim=H, in_dim=D, backend="pallas")
    rng = np.random.default_rng(4)
    xs = jnp.asarray(rng.normal(size=(B, T, D)).astype(np.float32))
    carry = (
        jnp.asarray(rng.normal(size=(B, H)).astype(np.float32) * 0.2),
        jnp.asarray(rng.normal(size=(B, H)).astype(np.float32) * 0.2),
    )
    params = scan_mod.init(jax.random.PRNGKey(0), xs, carry)

    outs_s, carry_s = scan_mod.apply(params, xs, carry)
    outs_p, carry_p = pallas_mod.apply(params, xs, carry)
    np.testing.assert_allclose(np.asarray(outs_p), np.asarray(outs_s), atol=1e-5)
    np.testing.assert_allclose(np.asarray(carry_p[0]), np.asarray(carry_s[0]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(carry_p[1]), np.asarray(carry_s[1]), atol=1e-5)

    def loss(mod, p, xs):
        outs, _ = mod.apply(p, xs, carry)
        return jnp.sum(jnp.tanh(outs))

    g_s = jax.grad(lambda p: loss(scan_mod, p, xs))(params)
    g_p = jax.grad(lambda p: loss(pallas_mod, p, xs))(params)
    flat_s = jax.tree.leaves(g_s)
    flat_p = jax.tree.leaves(g_p)
    for a, b in zip(flat_p, flat_s):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


# --------------------------------------------------------------------------
# fused sequence kernel (lstm_seq_unroll): per-row stop-gradient seam
# --------------------------------------------------------------------------


def _seam_scan_reference(proj_t, wh, h0, c0, burn):
    """Scan with the R2D2 seam: per-row stop_gradient cut at t == burn[b]
    entering the step, plus a no-cotangent mask on burn-in outputs — the
    operator-equivalent of the kernel's backward masks."""
    H = h0.shape[-1]

    def step(carry, inp):
        t, p = inp
        h, c = carry
        cut = (t == burn)[:, None]
        h = jnp.where(cut, jax.lax.stop_gradient(h), h)
        c = jnp.where(cut, jax.lax.stop_gradient(c), c)
        z = p + h @ wh
        i = jax.nn.sigmoid(z[..., :H])
        f = jax.nn.sigmoid(z[..., H : 2 * H])
        g = jnp.tanh(z[..., 2 * H : 3 * H])
        o = jax.nn.sigmoid(z[..., 3 * H :])
        c = f * c + i * g
        h = o * jnp.tanh(c)
        keep = (t >= burn)[:, None]
        out = jnp.where(keep, h, jax.lax.stop_gradient(h))
        return (h, c), out

    T = proj_t.shape[0]
    (h, c), outs = jax.lax.scan(step, (h0, c0), (jnp.arange(T, dtype=jnp.int32), proj_t))
    return outs, (h, c)


# one seam per batch row, spanning the contract range [0, T-1] for T=6
_BURN = np.array([0, 2, 5, 3, 5, 1, 0, 4], np.int32)


class TestFusedSequence:
    def test_forward_bit_identical_to_per_step_path(self):
        """The seam only gates gradients: forward values must match the
        existing Pallas path BIT FOR BIT (fp32 acceptance criterion)."""
        proj_t, wh, h0, c0 = _rand_inputs(np.random.default_rng(10))
        burn = jnp.asarray(_BURN)
        outs_a, (hT_a, cT_a) = lstm_unroll(proj_t, wh, h0, c0)
        outs_b, (hT_b, cT_b) = lstm_seq_unroll(proj_t, wh, h0, c0, burn)
        assert np.array_equal(np.asarray(outs_a), np.asarray(outs_b))
        assert np.array_equal(np.asarray(hT_a), np.asarray(hT_b))
        assert np.array_equal(np.asarray(cT_a), np.asarray(cT_b))

    @pytest.mark.parametrize("wrt", [0, 1])  # proj, wh (h0/c0 are exact zeros)
    def test_grads_match_seam_scan(self, wrt):
        proj_t, wh, h0, c0 = _rand_inputs(np.random.default_rng(11))
        burn = jnp.asarray(_BURN)
        rng = np.random.default_rng(12)
        ct = jnp.asarray(rng.normal(size=(6, 8, 16)).astype(np.float32))
        cth = jnp.asarray(rng.normal(size=(8, 16)).astype(np.float32))
        ctc = jnp.asarray(rng.normal(size=(8, 16)).astype(np.float32))

        def loss(fn, *args):
            outs, (hT, cT) = fn(*args)
            return jnp.sum(outs * ct) + jnp.sum(hT * cth) + jnp.sum(cT * ctc)

        g_k = jax.grad(lambda *a: loss(lstm_seq_unroll, *a, burn), argnums=wrt)(
            proj_t, wh, h0, c0
        )
        g_s = jax.grad(lambda *a: loss(_seam_scan_reference, *a, burn), argnums=wrt)(
            proj_t, wh, h0, c0
        )
        np.testing.assert_allclose(np.asarray(g_k), np.asarray(g_s), rtol=1e-4, atol=1e-5)

    def test_burn_in_boundary_grads_exactly_zero(self):
        """dproj rows strictly below each row's seam are EXACT zeros, and
        the initial-state grads are exact zeros for every row — the seam
        is a hard cut, not a small number."""
        proj_t, wh, h0, c0 = _rand_inputs(np.random.default_rng(13))
        burn = jnp.asarray(_BURN)

        def loss(proj_t, wh, h0, c0):
            outs, (hT, cT) = lstm_seq_unroll(proj_t, wh, h0, c0, burn)
            return jnp.sum(outs**2) + jnp.sum(hT * cT)

        dproj, dwh, dh0, dc0 = jax.grad(loss, argnums=(0, 1, 2, 3))(proj_t, wh, h0, c0)
        dproj = np.asarray(dproj)
        for b, bi in enumerate(_BURN):
            assert not dproj[:bi, b, :].any(), f"row {b}: grads leak below seam {bi}"
            if bi < dproj.shape[0]:
                assert dproj[bi:, b, :].any(), f"row {b}: train segment got no grads"
        assert not np.asarray(dh0).any() and not np.asarray(dc0).any()
        assert np.asarray(dwh).any()

    def test_zero_burn_matches_full_backprop(self):
        """burn_in == 0 everywhere reduces the seam op to lstm_unroll's
        gradients exactly (the cut only removes the h0/c0 path, which the
        all-zero seam also cuts — checked against plain scan)."""
        proj_t, wh, h0, c0 = _rand_inputs(np.random.default_rng(14))
        zero = jnp.zeros(8, jnp.int32)

        def loss(fn, *args):
            outs, _ = fn(*args)
            return jnp.sum(jnp.tanh(outs))

        g_k = jax.grad(lambda p, w: loss(lstm_seq_unroll, p, w, h0, c0, zero), argnums=(0, 1))(proj_t, wh)
        g_u = jax.grad(lambda p, w: loss(lstm_unroll, p, w, h0, c0), argnums=(0, 1))(proj_t, wh)
        for a, b in zip(g_k, g_u):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_module_backend_parity_with_seam(self, dtype):
        """Full LSTM module, scan vs pallas backends, seam active: fp32 is
        tight, bf16 drift-bounded (the precision plane's parity class)."""
        B, T, D, H = 8, 6, 24, tiny_test().hidden_dim
        scan_mod = LSTM(hidden_dim=H, in_dim=D, dtype=dtype, backend="scan")
        pallas_mod = LSTM(hidden_dim=H, in_dim=D, dtype=dtype, backend="pallas")
        rng = np.random.default_rng(15)
        xs = jnp.asarray(rng.normal(size=(B, T, D)).astype(np.float32))
        carry = (
            jnp.asarray(rng.normal(size=(B, H)).astype(np.float32) * 0.2),
            jnp.asarray(rng.normal(size=(B, H)).astype(np.float32) * 0.2),
        )
        burn = jnp.asarray(np.minimum(_BURN, T - 1))
        params = scan_mod.init(jax.random.PRNGKey(1), xs, carry)

        outs_s, _ = scan_mod.apply(params, xs, carry, burn_in=burn)
        outs_p, _ = pallas_mod.apply(params, xs, carry, burn_in=burn)
        fwd_tol = 1e-5 if dtype == jnp.float32 else 3e-2
        np.testing.assert_allclose(
            np.asarray(outs_p, np.float32), np.asarray(outs_s, np.float32), atol=fwd_tol
        )

        def loss(mod, p):
            outs, _ = mod.apply(p, xs, carry, burn_in=burn)
            return jnp.sum(jnp.tanh(outs.astype(jnp.float32)))

        g_s = jax.tree.leaves(jax.grad(lambda p: loss(scan_mod, p))(params))
        g_p = jax.tree.leaves(jax.grad(lambda p: loss(pallas_mod, p))(params))
        for a, b in zip(g_p, g_s):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            if dtype == jnp.float32:
                np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
            else:
                # bf16: bounded relative L2 drift, not elementwise equality
                denom = np.linalg.norm(b) + 1e-6
                assert np.linalg.norm(a - b) / denom < 0.05

    def test_scan_chunk_seam_parity(self):
        """The remat'd chunked scan threads the global t through chunks:
        same function as the unchunked seam scan, values and grads."""
        B, T, D, H = 4, 8, 12, 16
        plain = LSTM(hidden_dim=H, in_dim=D, backend="scan")
        chunked = LSTM(hidden_dim=H, in_dim=D, backend="scan", scan_chunk=2)
        rng = np.random.default_rng(16)
        xs = jnp.asarray(rng.normal(size=(B, T, D)).astype(np.float32))
        carry = (jnp.zeros((B, H), jnp.float32), jnp.zeros((B, H), jnp.float32))
        burn = jnp.asarray([0, 3, 5, 7], jnp.int32)
        params = plain.init(jax.random.PRNGKey(2), xs, carry)

        def loss(mod, p):
            outs, _ = mod.apply(p, xs, carry, burn_in=burn)
            return jnp.sum(outs**2)

        np.testing.assert_allclose(
            np.asarray(plain.apply(params, xs, carry, burn_in=burn)[0]),
            np.asarray(chunked.apply(params, xs, carry, burn_in=burn)[0]),
            atol=1e-6,
        )
        g_a = jax.tree.leaves(jax.grad(lambda p: loss(plain, p))(params))
        g_b = jax.tree.leaves(jax.grad(lambda p: loss(chunked, p))(params))
        for a, b in zip(g_a, g_b):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)

    def test_one_launch_per_train_step(self):
        """Compile-count gate, shared with the analysis plane: ONE
        pallas_call per sequence unroll, exactly three (online fwd +
        target fwd + backward) per train step — never O(T) launches."""
        from r2d2_tpu.analysis.jaxpr_rules import (
            count_pallas_launches,
            fused_train_step_jaxpr,
            fused_unroll_jaxpr,
            scan_fused_unroll,
        )

        assert scan_fused_unroll("fp32") == []
        assert count_pallas_launches(fused_unroll_jaxpr("fp32")) == 1
        assert count_pallas_launches(fused_train_step_jaxpr("fp32")) == 3


# --------------------------------------------------------------------------
# the sequence op's parity ladder at training shapes (PR 37: the kernel has
# ONE backward; these cases stand where the fused-dWh and checkpointed arms'
# cases stood)
# --------------------------------------------------------------------------


def _seam_loss(fn, proj_t, wh, h0, c0, burn):
    outs, (hT, cT) = fn(proj_t, wh, h0, c0, burn)
    return jnp.sum(outs.astype(jnp.float32) ** 2) + jnp.sum(
        hT.astype(jnp.float32) * cT.astype(jnp.float32)
    )


_LADDER_SHAPES = [(10, 8, 128), (85, 16, 512), (45, 64, 128), (83, 32, 256)]
_LADDER_SEAMS = {"0": lambda T: 0, "1": lambda T: 1, "half": lambda T: T // 2,
                 "last": lambda T: T - 1}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("seam", list(_LADDER_SEAMS))
@pytest.mark.parametrize("shape", _LADDER_SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_seq_unroll_parity_ladder(shape, seam, dtype):
    """lstm_seq_unroll at (T, B, H) with every row's seam at 0, 1, T/2 or
    T-1: forward bit-identical to lstm_unroll; gradients against the scan
    LSTM with the same seam (f32: tight on each tensor's own scale; bf16:
    bounded relative L2, the precision plane's class); exact zeros below
    the seam and into the initial state."""
    T, B, H = shape
    burn_at = _LADDER_SEAMS[seam](T)
    args = tuple(a.astype(dtype) for a in _rand_inputs(
        np.random.default_rng(37 + T + B + H + burn_at), T=T, B=B, H=H))
    burn = jnp.full((B,), burn_at, jnp.int32)

    outs_a, (hT_a, cT_a) = lstm_unroll(*args)
    outs_b, (hT_b, cT_b) = lstm_seq_unroll(*args, burn)
    assert np.array_equal(np.asarray(outs_a, np.float32), np.asarray(outs_b, np.float32))
    assert np.array_equal(np.asarray(hT_a), np.asarray(hT_b))
    assert np.array_equal(np.asarray(cT_a), np.asarray(cT_b))

    g_k = jax.grad(lambda *a: _seam_loss(lstm_seq_unroll, *a, burn), argnums=(0, 1, 2, 3))(*args)
    g_s = jax.jit(jax.grad(
        lambda *a: _seam_loss(_seam_scan_reference, *a, burn), argnums=(0, 1)))(*args)
    for k, s in zip(g_k[:2], g_s):
        k, s = np.asarray(k, np.float32), np.asarray(s, np.float32)
        assert np.isfinite(k).all()
        if dtype == jnp.float32:
            # sums over up to T*B terms: measured on the tensor's own scale
            assert np.max(np.abs(k - s)) <= 1e-4 * (np.max(np.abs(s)) + 1e-6)
        else:
            assert np.linalg.norm(k - s) / (np.linalg.norm(s) + 1e-6) < 0.05
    dproj = np.asarray(g_k[0], np.float32)
    assert not dproj[:burn_at].any(), "gradient below the seam"
    assert dproj[burn_at:].any(), "the train segment got no gradient"
    assert not np.asarray(g_k[2], np.float32).any() and not np.asarray(g_k[3], np.float32).any()
    assert np.asarray(g_k[1], np.float32).any()


class TestScanChunkRemainder:
    """scan_chunk no longer requires chunk | T: the tail runs as one
    shorter remat'd chunk (models/lstm.py), so live-loop sequence lengths
    don't have to be multiples of the checkpoint chunk."""

    @pytest.mark.parametrize("chunk", [3, 4, 5, 7, 10, 11])
    def test_remainder_chunks_match_plain_scan(self, chunk):
        B, T, D, H = 4, 10, 12, 16
        plain = LSTM(hidden_dim=H, in_dim=D, backend="scan")
        chunked = LSTM(hidden_dim=H, in_dim=D, backend="scan", scan_chunk=chunk)
        rng = np.random.default_rng(50)
        xs = jnp.asarray(rng.normal(size=(B, T, D)).astype(np.float32))
        carry = (jnp.zeros((B, H), jnp.float32), jnp.zeros((B, H), jnp.float32))
        burn = jnp.asarray([0, 3, 6, 9], jnp.int32)
        params = plain.init(jax.random.PRNGKey(4), xs, carry)

        def loss(mod, p):
            outs, _ = mod.apply(p, xs, carry, burn_in=burn)
            return jnp.sum(outs**2)

        np.testing.assert_allclose(
            np.asarray(plain.apply(params, xs, carry, burn_in=burn)[0]),
            np.asarray(chunked.apply(params, xs, carry, burn_in=burn)[0]),
            atol=1e-6,
        )
        g_a = jax.tree.leaves(jax.grad(lambda p: loss(plain, p))(params))
        g_b = jax.tree.leaves(jax.grad(lambda p: loss(chunked, p))(params))
        for a, b in zip(g_a, g_b):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
            )

    def test_remainder_without_burn_in(self):
        B, T, D, H = 2, 7, 8, 16
        plain = LSTM(hidden_dim=H, in_dim=D, backend="scan")
        chunked = LSTM(hidden_dim=H, in_dim=D, backend="scan", scan_chunk=4)
        rng = np.random.default_rng(51)
        xs = jnp.asarray(rng.normal(size=(B, T, D)).astype(np.float32))
        carry = (jnp.zeros((B, H), jnp.float32), jnp.zeros((B, H), jnp.float32))
        params = plain.init(jax.random.PRNGKey(5), xs, carry)
        outs_a, (h_a, c_a) = plain.apply(params, xs, carry)
        outs_b, (h_b, c_b) = chunked.apply(params, xs, carry)
        np.testing.assert_allclose(np.asarray(outs_a), np.asarray(outs_b), atol=1e-6)
        np.testing.assert_allclose(np.asarray(h_a), np.asarray(h_b), atol=1e-6)
        np.testing.assert_allclose(np.asarray(c_a), np.asarray(c_b), atol=1e-6)
