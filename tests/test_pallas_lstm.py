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
from r2d2_tpu.ops.pallas_lstm import (
    lstm_seq_unroll,
    lstm_seq_unroll_ckpt,
    lstm_seq_unroll_fused_dwh,
    lstm_unroll,
    seq_backward_residual_bytes,
)

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
# alternative backward arms (ISSUE 14): fused-dWh and checkpointed kernels
# --------------------------------------------------------------------------


def _seam_loss(fn, proj_t, wh, h0, c0, burn):
    outs, (hT, cT) = fn(proj_t, wh, h0, c0, burn)
    return jnp.sum(outs.astype(jnp.float32) ** 2) + jnp.sum(
        hT.astype(jnp.float32) * cT.astype(jnp.float32)
    )


class TestFusedDwhArm:
    """lstm_seq_unroll_fused_dwh: dWh accumulated in VMEM scratch inside
    the reversed backward kernel — no outside (T·B,H)ᵀ@(T·B,4H) matmul,
    no full-size f32 dz in HBM. Forward and dproj are the SAME program as
    the default arm, so those are bitwise; dWh differs only in summation
    order (per-step scratch += vs one big matmul)."""

    def test_forward_bit_identical_to_default_arm(self):
        proj_t, wh, h0, c0 = _rand_inputs(np.random.default_rng(20))
        burn = jnp.asarray(_BURN)
        outs_a, (hT_a, cT_a) = lstm_seq_unroll(proj_t, wh, h0, c0, burn)
        outs_b, (hT_b, cT_b) = lstm_seq_unroll_fused_dwh(proj_t, wh, h0, c0, burn)
        assert np.array_equal(np.asarray(outs_a), np.asarray(outs_b))
        assert np.array_equal(np.asarray(hT_a), np.asarray(hT_b))
        assert np.array_equal(np.asarray(cT_a), np.asarray(cT_b))

    def test_grads_match_default_arm_fp32(self):
        """dproj is bitwise (identical dz program); dWh within a few ulp
        (summation order only); dh0/dc0 exact zeros on both arms."""
        proj_t, wh, h0, c0 = _rand_inputs(np.random.default_rng(21))
        burn = jnp.asarray(_BURN)
        g_d = jax.grad(
            lambda *a: _seam_loss(lstm_seq_unroll, *a, burn), argnums=(0, 1, 2, 3)
        )(proj_t, wh, h0, c0)
        g_f = jax.grad(
            lambda *a: _seam_loss(lstm_seq_unroll_fused_dwh, *a, burn),
            argnums=(0, 1, 2, 3),
        )(proj_t, wh, h0, c0)
        assert np.array_equal(np.asarray(g_d[0]), np.asarray(g_f[0]))  # dproj
        np.testing.assert_allclose(
            np.asarray(g_d[1]), np.asarray(g_f[1]), rtol=1e-5, atol=1e-6
        )
        assert not np.asarray(g_f[2]).any() and not np.asarray(g_f[3]).any()

    def test_exact_zero_below_seam(self):
        """The seam contract carries over verbatim: dproj rows strictly
        below each row's burn are EXACT zeros (the masked dz contributes
        exact zeros to the scratch dWh too)."""
        proj_t, wh, h0, c0 = _rand_inputs(np.random.default_rng(22))
        burn = jnp.asarray(_BURN)
        dproj = jax.grad(
            lambda *a: _seam_loss(lstm_seq_unroll_fused_dwh, *a, burn)
        )(proj_t, wh, h0, c0)
        dproj = np.asarray(dproj)
        for b, bi in enumerate(_BURN):
            assert not dproj[:bi, b, :].any(), f"row {b}: leak below seam {bi}"
            if bi < dproj.shape[0]:
                assert dproj[bi:, b, :].any()

    def test_grads_match_seam_scan_reference(self):
        proj_t, wh, h0, c0 = _rand_inputs(np.random.default_rng(23))
        burn = jnp.asarray(_BURN)
        for wrt in (0, 1):
            g_k = jax.grad(
                lambda *a: _seam_loss(lstm_seq_unroll_fused_dwh, *a, burn),
                argnums=wrt,
            )(proj_t, wh, h0, c0)
            g_s = jax.grad(
                lambda *a: _seam_loss(_seam_scan_reference, *a, burn), argnums=wrt
            )(proj_t, wh, h0, c0)
            np.testing.assert_allclose(
                np.asarray(g_k), np.asarray(g_s), rtol=1e-4, atol=1e-5
            )


class TestCheckpointedArm:
    """lstm_seq_unroll_ckpt(S): residuals are every-S-step (h, c) carries
    only — O((T/S)·B·H) instead of O(T·B·H) — and the backward kernel
    recomputes each segment's gates from its checkpoint before walking it
    in reverse. dWh is inherently fused (the full h sequence never exists
    in HBM)."""

    def test_forward_bit_identical_to_default_arm(self):
        proj_t, wh, h0, c0 = _rand_inputs(np.random.default_rng(30))
        burn = jnp.asarray(_BURN)
        outs_a, (hT_a, cT_a) = lstm_seq_unroll(proj_t, wh, h0, c0, burn)
        outs_b, (hT_b, cT_b) = lstm_seq_unroll_ckpt(2)(proj_t, wh, h0, c0, burn)
        assert np.array_equal(np.asarray(outs_a), np.asarray(outs_b))
        assert np.array_equal(np.asarray(hT_a), np.asarray(hT_b))
        assert np.array_equal(np.asarray(cT_a), np.asarray(cT_b))

    @pytest.mark.parametrize("S", [1, 2, 3, 6])
    def test_grads_match_default_arm_fp32(self, S):
        """Every divisor segment length, including the degenerate S=1
        (checkpoint every step — pure recompute overhead, same math) and
        S=T (one segment — the whole unroll recomputed from h0/c0). The
        recompute replays identical f32 ops, but XLA fuses the two
        programs differently, so parity is one-ulp-tight, not bitwise."""
        proj_t, wh, h0, c0 = _rand_inputs(np.random.default_rng(31))
        burn = jnp.asarray(_BURN)
        g_d = jax.grad(
            lambda *a: _seam_loss(lstm_seq_unroll, *a, burn), argnums=(0, 1, 2, 3)
        )(proj_t, wh, h0, c0)
        g_c = jax.grad(
            lambda *a: _seam_loss(lstm_seq_unroll_ckpt(S), *a, burn),
            argnums=(0, 1, 2, 3),
        )(proj_t, wh, h0, c0)
        np.testing.assert_allclose(
            np.asarray(g_d[0]), np.asarray(g_c[0]), rtol=1e-5, atol=1e-6
        )
        np.testing.assert_allclose(
            np.asarray(g_d[1]), np.asarray(g_c[1]), rtol=1e-5, atol=1e-6
        )
        assert not np.asarray(g_c[2]).any() and not np.asarray(g_c[3]).any()

    @pytest.mark.parametrize(
        "burn_vec",
        [
            # seams ON segment boundaries (S=2 over T=6: boundaries 0/2/4)
            np.array([0, 2, 4, 2, 4, 0, 2, 4], np.int32),
            # seams strictly INSIDE recomputed segments
            np.array([1, 3, 5, 1, 3, 5, 1, 3], np.int32),
            # mixed, plus the all-learn and nearly-all-burn extremes
            np.array([0, 5, 1, 4, 2, 3, 0, 5], np.int32),
        ],
    )
    def test_seam_exact_zero_at_and_inside_segment_boundaries(self, burn_vec):
        """The hard case the segment recompute must not soften: a seam
        landing exactly on an S-boundary (the carry cut coincides with a
        checkpoint reload) or mid-segment (the cut applies inside the
        recomputed walk). Below-seam dproj must be EXACT zeros either
        way."""
        proj_t, wh, h0, c0 = _rand_inputs(np.random.default_rng(32))
        burn = jnp.asarray(burn_vec)
        dproj, dwh, dh0, dc0 = jax.grad(
            lambda *a: _seam_loss(lstm_seq_unroll_ckpt(2), *a, burn),
            argnums=(0, 1, 2, 3),
        )(proj_t, wh, h0, c0)
        dproj = np.asarray(dproj)
        for b, bi in enumerate(burn_vec):
            assert not dproj[:bi, b, :].any(), f"row {b}: leak below seam {bi}"
            if bi < dproj.shape[0]:
                assert dproj[bi:, b, :].any(), f"row {b}: train segment empty"
        assert not np.asarray(dh0).any() and not np.asarray(dc0).any()
        assert np.asarray(dwh).any()

    def test_grads_match_seam_scan_reference(self):
        proj_t, wh, h0, c0 = _rand_inputs(np.random.default_rng(33))
        burn = jnp.asarray(_BURN)
        for wrt in (0, 1):
            g_k = jax.grad(
                lambda *a: _seam_loss(lstm_seq_unroll_ckpt(3), *a, burn),
                argnums=wrt,
            )(proj_t, wh, h0, c0)
            g_s = jax.grad(
                lambda *a: _seam_loss(_seam_scan_reference, *a, burn), argnums=wrt
            )(proj_t, wh, h0, c0)
            np.testing.assert_allclose(
                np.asarray(g_k), np.asarray(g_s), rtol=1e-4, atol=1e-5
            )

    def test_rejects_non_divisor_segment(self):
        proj_t, wh, h0, c0 = _rand_inputs(np.random.default_rng(34))
        burn = jnp.asarray(_BURN)
        with pytest.raises(ValueError, match="not divisible"):
            jax.grad(
                lambda *a: _seam_loss(lstm_seq_unroll_ckpt(4), *a, burn)
            )(proj_t, wh, h0, c0)

    def test_residual_bytes_scale_with_segment_length(self):
        """The measurable claim behind the arm: carry residuals shrink by
        exactly T/S (h at proj dtype + c at f32, per the vjp_fwd's
        concatenated checkpoint tensors)."""
        T, B, H = 80, 32, 512
        full = seq_backward_residual_bytes(T, B, H, jnp.bfloat16)
        ck = seq_backward_residual_bytes(T, B, H, jnp.bfloat16, ckpt_every=5)
        assert full["carry_residual_bytes"] == T * B * H * (2 + 4)
        assert ck["carry_residual_bytes"] == (T // 5) * B * H * (2 + 4)
        assert full["carry_residual_bytes"] == 5 * ck["carry_residual_bytes"]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("arm", ["fused_dwh", "ckpt"])
def test_backward_arm_module_parity(arm, dtype):
    """Full LSTM module with an arm enabled vs the default pallas path:
    identical params, seam active, both precisions. fp32 is one-ulp
    tight; bf16 recompute parity holds by construction (bf16 h round-trip
    is identity, c checkpoints are f32-exact), so bf16 is ALSO tight
    against the default arm — the drift-vs-scan class does not widen."""
    B, T, D, H = 8, 6, 24, tiny_test().hidden_dim
    kw = dict(hidden_dim=H, in_dim=D, dtype=dtype, backend="pallas")
    default_mod = LSTM(**kw)
    arm_mod = LSTM(**kw, fused_dwh=True) if arm == "fused_dwh" else LSTM(
        **kw, grad_checkpoint=3
    )
    rng = np.random.default_rng(40)
    xs = jnp.asarray(rng.normal(size=(B, T, D)).astype(np.float32))
    carry = (
        jnp.asarray(rng.normal(size=(B, H)).astype(np.float32) * 0.2),
        jnp.asarray(rng.normal(size=(B, H)).astype(np.float32) * 0.2),
    )
    burn = jnp.asarray(np.minimum(_BURN, T - 1))
    params = default_mod.init(jax.random.PRNGKey(3), xs, carry)

    outs_d, _ = default_mod.apply(params, xs, carry, burn_in=burn)
    outs_a, _ = arm_mod.apply(params, xs, carry, burn_in=burn)
    assert np.array_equal(np.asarray(outs_d), np.asarray(outs_a))  # fwd bitwise

    def loss(mod, p):
        outs, _ = mod.apply(p, xs, carry, burn_in=burn)
        return jnp.sum(jnp.tanh(outs.astype(jnp.float32)))

    g_d = jax.tree.leaves(jax.grad(lambda p: loss(default_mod, p))(params))
    g_a = jax.tree.leaves(jax.grad(lambda p: loss(arm_mod, p))(params))
    for a, b in zip(g_a, g_d):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=2e-5, atol=2e-6,
        )


def test_backward_arm_launch_budget():
    """Each armed train step holds the default path's exact 3-launch
    budget — the fused dWh and the segment recompute live INSIDE the one
    backward launch, they do not buy extra launches."""
    from r2d2_tpu.analysis.jaxpr_rules import (
        backward_arm_train_step_jaxpr,
        count_pallas_launches,
        scan_backward_arms,
    )

    assert scan_backward_arms("fp32") == []
    for arm in ("fused_dwh", "ckpt"):
        assert count_pallas_launches(backward_arm_train_step_jaxpr("fp32", arm)) == 3


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


class TestChooseBackwardArm:
    """choose_backward_arm (ops/pallas_lstm.py) + config.resolve_backward_arm:
    the auto-selector that picks the sequence backward from the peak-
    residual-bytes budget (ISSUE 16 satellite). Pure shape math — no
    kernel runs."""

    T, B, H = 84, 8, 512

    def _peaks(self, dtype):
        d = seq_backward_residual_bytes(self.T, self.B, self.H, dtype)
        dz_f32 = self.T * self.B * 4 * self.H * 4
        dz_proj = self.T * self.B * 4 * self.H * jnp.dtype(dtype).itemsize
        return d["carry_residual_bytes"], dz_f32, dz_proj

    def test_auto_prefers_default_when_budget_fits(self):
        from r2d2_tpu.ops.pallas_lstm import choose_backward_arm

        carry, dz_f32, _ = self._peaks(jnp.bfloat16)
        arm, stride = choose_backward_arm(
            self.T, self.B, self.H, jnp.bfloat16, carry + dz_f32
        )
        assert (arm, stride) == ("default", 0)

    def test_auto_steps_down_to_fused_dwh_then_ckpt(self):
        from r2d2_tpu.ops.pallas_lstm import choose_backward_arm

        carry, dz_f32, dz_proj = self._peaks(jnp.bfloat16)
        # budget excludes the f32 dz residual but fits the bf16 one
        arm, stride = choose_backward_arm(
            self.T, self.B, self.H, jnp.bfloat16, carry + dz_f32 - 1
        )
        assert (arm, stride) == ("fused_dwh", 0)
        # budget below even the fused arm: checkpointing, with the
        # SMALLEST divisor stride of T=84 whose peak fits
        arm, stride = choose_backward_arm(
            self.T, self.B, self.H, jnp.bfloat16, carry + dz_proj - 1
        )
        assert arm == "ckpt"
        assert stride >= 2 and self.T % stride == 0
        ck = seq_backward_residual_bytes(self.T, self.B, self.H, jnp.bfloat16, stride)
        assert ck["carry_residual_bytes"] + dz_proj <= carry + dz_proj - 1

    def test_explicit_modes_pass_through(self):
        from r2d2_tpu.ops.pallas_lstm import choose_backward_arm

        assert choose_backward_arm(10, 4, 16, jnp.float32, 1, "default") == ("default", 0)
        assert choose_backward_arm(10, 4, 16, jnp.float32, 1, "fused_dwh") == ("fused_dwh", 0)
        arm, stride = choose_backward_arm(10, 4, 16, jnp.float32, 1, "ckpt")
        assert arm == "ckpt" and 10 % stride == 0
        with pytest.raises(ValueError, match="backward-arm"):
            choose_backward_arm(10, 4, 16, jnp.float32, 1, "nope")

    def test_auto_never_offers_a_stride_that_cannot_fit_vmem(self):
        """The residual budget alone walks B=256 fp32 to one whole-sequence
        segment — (85, 256, 2048) f32 blocks, refused by the compiler on a
        chip. Given the device's VMEM the smallest fitting stride is the
        answer, and a shape nothing fits raises here, by name."""
        from r2d2_tpu.ops.pallas_lstm import choose_backward_arm

        shape = (85, 256, 512, jnp.float32, 128 << 20)
        assert choose_backward_arm(*shape) == ("ckpt", 85)
        assert choose_backward_arm(*shape, vmem_bytes=128 << 20) == ("ckpt", 5)
        with pytest.raises(ValueError, match="VMEM"):
            choose_backward_arm(*shape, vmem_bytes=16 << 20)

    def test_config_resolution_legacy_knobs_win(self):
        cfg = tiny_test().replace(lstm_backend="pallas", seq_fused_dwh=True)
        assert cfg.resolve_backward_arm() == ("fused_dwh", 0)
        cfg = tiny_test().replace(lstm_backend="pallas", seq_grad_checkpoint=5)
        assert cfg.resolve_backward_arm() == ("ckpt", 5)

    def test_config_resolution_non_pallas_is_default(self):
        # scan backend (and the CPU test backend's auto resolution) has no
        # Pallas sequence backward to pick between
        assert tiny_test().replace(lstm_backend="scan").resolve_backward_arm() == ("default", 0)
        assert tiny_test().resolve_backward_arm() == ("default", 0)
        lru = tiny_test().replace(recurrent_core="lru", lstm_backend="auto")
        assert lru.resolve_backward_arm() == ("default", 0)

    def test_config_resolution_budget_divides_by_data_shards(self):
        """The per-device residual budget sees B/(dp*fsdp) under manual
        partitioning — a model that needs ckpt on one chip can ride the
        default arm once the batch shards."""
        carry, dz_f32, _ = self._peaks(jnp.bfloat16)
        budget_mb = -(-(carry + dz_f32) // (1 << 20))  # ceil to MB: fits 1 shard
        base = dict(
            lstm_backend="pallas",
            precision="bf16",
            hidden_dim=self.H,
            batch_size=8 * self.B,
            burn_in_steps=40,
            learning_steps=40,
            block_length=40,
            forward_steps=4,  # seq_len = 84
            backward_residual_budget_mb=int(budget_mb),
        )
        crowded = tiny_test().replace(**base)
        arm_1chip, _ = crowded.resolve_backward_arm()
        assert arm_1chip != "default"  # 8x the batch per device
        sharded = tiny_test().replace(
            **base, dp_size=4, fsdp_size=2, replay_plane="host",
            partitioning="manual",
        )
        assert sharded.resolved_partitioning == "manual"
        assert sharded.resolve_backward_arm() == ("default", 0)
