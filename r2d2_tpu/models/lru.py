"""Time-parallel linear recurrent core (LRU) — the long-context option.

The reference framework has exactly one recurrent core, an LSTM
(reference model.py:59). An LSTM's recurrence is nonlinear, so its unroll
is inherently sequential — T steps cost T dependent iterations no matter
the hardware (models/lstm.py runs it as a remat-chunked lax.scan; that IS
the ceiling). This module adds the TPU-first alternative the literature
reached for the same reason: a DIAGONAL LINEAR complex recurrence

    h_t = lambda * h_{t-1} + gamma * (B x_t)        (elementwise in C^H)

per the Linear Recurrent Unit design (Orvieto et al. 2023, "Resurrecting
Recurrent Neural Networks for Long Sequences" — public literature;
pattern only, no code copied). Linearity is what the LSTM lacks, and it is
used twice here, by two implementations of the SAME recurrence that the
config picks between from what it observes (platform, placement, shape:
`config.resolved_lru_recurrence`, the rule the LSTM's kernel sits under):

- ON THE CHIP, outside any GSPMD-partitioned mesh axis, with H a multiple
  of 128 and a multiple of 8 rows per device: one sequential Pallas pass
  over time (ops/pallas_lru.py), the carry in VMEM, `u` read once and `h`
  written once; the backward is the same pass over reversed time. The
  recurrence is elementwise, so T dependent steps cost T x a few VPU
  instructions and the pass runs at the pace of its bytes (PERF.md finding
  34). The module runs time-major around it: input and output are
  transposed in the compute dtype, never an f32 state-sized array.
- ELSEWHERE (CPU, GSPMD meshes, other shapes, e.g. a B = 1 evaluation
  unroll): the recurrence is ASSOCIATIVE, so the whole unroll is one
  `jax.lax.associative_scan`: O(log T) dependent steps instead of O(T), at
  the price of ~2 log2 T passes over four f32 (B, T, H) arrays (on the v5e
  at T = 581 some 3,000 instructions where the kernel path has three calls:
  the reason the kernel exists).

`lru_chunk > 0` selects a third formulation, the chunked MXU form
(`_chunked_states`), wherever it is set; the acting `step` is one
elementwise multiply-add per call in every case. Expressivity lost to
linearity is bought back the standard way: a nonlinear readout of the state
plus an input skip, with stability guaranteed by parameterizing
|lambda| < 1 through exp(-exp(nu_log)).

Drop-in contract (models/core.py is the rule):
- carry is a pair of (B, H) real arrays — here (Re h, Im h) instead of
  the LSTM's (h, c) — stated by `state_shape`, so the replay planes, the
  actors' carries, burn-in, and zero-state ablation take it as they take
  the LSTM's.
- `__call__(xs (B,T,D), carry, burn_in=None) -> (outs (B,T,H), carry)` and
  `step(x (B,D), carry) -> (out, carry)` mirror models/lstm.py.

Numerics: input/readout matmuls run in the configured compute dtype
(bf16 on TPU — MXU work); the elementwise recurrence runs in float32 in
every formulation (f32 keeps 1000-step cumulative products honest; the
kernel sums in `step`'s sequential order, the scan in a tree's). Complex
math is spelled out over (re, im) real pairs — no complex dtypes, so
XLA:TPU sees plain f32 elementwise ops.

Select with `recurrent_core="lru"` (config.py); params deliberately use
none of the Megatron-annotated names in parallel/mesh.train_state_shardings
(wi/wh/b), so under tp the LRU core stays replicated — its recurrence is
elementwise and its projections are (D, H): cheap relative to the encoder.
"""

from __future__ import annotations

from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from r2d2_tpu.models.lstm import _uniform_init
from r2d2_tpu.ops.pallas_lru import kernel_fits, lru_scan

Carry = Tuple[jnp.ndarray, jnp.ndarray]  # (re, im), each (B, H) float32


def _ring_init(r_min: float, r_max: float):
    """nu_log such that |lambda| = exp(-exp(nu_log)) ~ U(r_min, r_max)."""

    def init(key, shape, dtype=jnp.float32):
        u = jax.random.uniform(key, shape, dtype)
        r = r_min + (r_max - r_min) * u
        return jnp.log(-jnp.log(r))

    return init


def _phase_init(max_phase: float):
    """theta_log such that theta = exp(theta_log) ~ U(~0, max_phase)."""

    def init(key, shape, dtype=jnp.float32):
        u = jax.random.uniform(key, shape, dtype, 1e-4, 1.0)
        return jnp.log(u * max_phase)

    return init


class LRU(nn.Module):
    hidden_dim: int
    in_dim: int
    dtype: jnp.dtype = jnp.float32
    r_min: float = 0.9          # eigenvalue ring: slowest-forgetting init
    r_max: float = 0.999
    max_phase: float = 6.283    # full circle of rotation frequencies
    # chunk > 0: the MXU formulation of the same recurrence. The plain
    # associative_scan is O(log T) DEPTH but each of its ~log2(T) sweeps
    # reads+writes four f32 (B, T, H) arrays — HBM bandwidth, the
    # measured reason the core trails scan-LSTM per step at trained
    # shapes (runs/lru_breakdown.jsonl). With chunking, the within-chunk
    # prefix B_t = sum_{s<=t} lambda^(t-s) u_s becomes a causal
    # triangular matmul against precomputed lambda powers (per-feature
    # (C, C, H) operator — batched GEMMs on the MXU), and only the
    # Nc = T/C chunk-final states go through a sequential carry scan.
    # Same math, same params, different summation order (f32 throughout —
    # the chunk GEMMs run at Precision.HIGHEST so the MXU does not round
    # the f32 operands to bf16; see _chunked_states for the cost note).
    chunk: int = 0
    # which implementation runs the plain (chunk == 0) recurrence: "scan" is
    # jax.lax.associative_scan, "pallas" the sequential kernel
    # (ops/pallas_lru.py) wherever the shapes it is called with are whole
    # (8, 128) tiles, and the associative scan where they are not (a B = 1
    # evaluation unroll). from_config takes it from the config's placement
    # rule (config.resolved_lru_recurrence); off a TPU "pallas" runs the
    # kernel under the Pallas interpreter, which is what the CPU tests do.
    backend: str = "scan"

    # the seam's statements (models/core.py): the associative scan has no
    # per-row seam, so __call__ ignores burn_in and backpropagates through
    # burn-in; the stored state is the (Re h, Im h) pair
    cuts_at_burn_in = False

    @staticmethod
    def state_shape(cfg):
        return (2, cfg.hidden_dim)

    @classmethod
    def from_config(cls, cfg, in_dim: int, tp_size: int = 1) -> "LRU":
        # every param is replicated under the sharding table, so the
        # shard-local net (tp_size > 1) takes the global module unchanged
        return cls(
            cfg.hidden_dim,
            in_dim=in_dim,
            dtype=jnp.dtype(cfg.resolved_compute_dtype),
            chunk=cfg.lru_chunk,
            backend="pallas" if cfg.resolved_lru_recurrence == "pallas" else "scan",
            r_min=cfg.lru_r_min,
            r_max=cfg.lru_r_max,
        )

    def setup(self):
        H, D = self.hidden_dim, self.in_dim
        self.nu_log = self.param("nu_log", _ring_init(self.r_min, self.r_max), (H,))
        self.theta_log = self.param("theta_log", _phase_init(self.max_phase), (H,))
        s_in = 1.0 / np.sqrt(D)
        self.in_re = self.param("in_re", _uniform_init(s_in), (D, H))
        self.in_im = self.param("in_im", _uniform_init(s_in), (D, H))
        s_h = 1.0 / np.sqrt(H)
        self.out_re = self.param("out_re", _uniform_init(s_h), (H, H))
        self.out_im = self.param("out_im", _uniform_init(s_h), (H, H))
        self.skip = self.param("skip", _uniform_init(s_in), (D, H))

    def _polar(self):
        """(|lambda|, arg lambda) — the ONE place the parameterization
        exp(-exp(nu_log)) / exp(theta_log) is spelled out; both unroll
        formulations derive from it."""
        return jnp.exp(-jnp.exp(self.nu_log)), jnp.exp(self.theta_log)

    def _decay(self):
        """lambda = exp(-exp(nu_log) + i exp(theta_log)), |lambda| < 1 by
        construction; gamma = sqrt(1 - |lambda|^2) normalizes the input so
        the state variance is O(1) at every decay rate."""
        mod, theta = self._polar()
        lam_re = mod * jnp.cos(theta)
        lam_im = mod * jnp.sin(theta)
        gamma = jnp.sqrt(jnp.maximum(1.0 - mod * mod, 1e-8))
        return lam_re, lam_im, gamma

    def _project_in(self, xs: jnp.ndarray, gamma: jnp.ndarray):
        """(…, D) -> gamma-scaled complex input (re, im), f32."""
        xd = xs.astype(self.dtype)
        u_re = (xd @ self.in_re.astype(self.dtype)).astype(jnp.float32)
        u_im = (xd @ self.in_im.astype(self.dtype)).astype(jnp.float32)
        return u_re * gamma, u_im * gamma

    def _readout(self, h_re: jnp.ndarray, h_im: jnp.ndarray, xs: jnp.ndarray):
        """Nonlinear readout of the complex state + input skip: the
        standard recipe for buying back the expressivity the linear
        recurrence gives up. Re(h C) for complex C spelled out in reals."""
        hr = h_re.astype(self.dtype)
        hi = h_im.astype(self.dtype)
        y = hr @ self.out_re.astype(self.dtype) - hi @ self.out_im.astype(self.dtype)
        return nn.gelu(y) + xs.astype(self.dtype) @ self.skip.astype(self.dtype)

    def _scan_states(self, u_re, u_im, carry, kernel: bool = False):
        """All T states of h_t = lambda h_{t-1} + u_t from `carry`, by one of
        two implementations of the same recurrence. `kernel`: u is TIME-major
        (T, B, H) and one sequential Pallas pass walks it (ops/pallas_lru.py;
        its backward is the same pass reversed). Otherwise u is (B, T, H) and
        ONE associative scan combines elements (a, b) of h_t = a_t h_{t-1} +
        b_t with a_t = lambda under (a1,b1) o (a2,b2) = (a2 a1, a2 b1 + b2),
        whose prefix (A_t, B_t) satisfies h_t = A_t h0 + B_t: O(log T) depth,
        but every level re-reads and re-writes four f32 (B, T, H) arrays."""
        lam_re, lam_im, _ = self._decay()
        h0_re = carry[0].astype(jnp.float32)
        h0_im = carry[1].astype(jnp.float32)
        if kernel:
            return lru_scan(lam_re, lam_im, u_re, u_im, h0_re, h0_im)
        B, T, H = u_re.shape
        a_re = jnp.broadcast_to(lam_re, (B, T, H))
        a_im = jnp.broadcast_to(lam_im, (B, T, H))

        def combine(e1, e2):
            a1r, a1i, b1r, b1i = e1
            a2r, a2i, b2r, b2i = e2
            ar = a2r * a1r - a2i * a1i
            ai = a2r * a1i + a2i * a1r
            br = a2r * b1r - a2i * b1i + b2r
            bi = a2r * b1i + a2i * b1r + b2i
            return ar, ai, br, bi

        A_re, A_im, B_re, B_im = jax.lax.associative_scan(
            combine, (a_re, a_im, u_re, u_im), axis=1
        )
        h0_re, h0_im = h0_re[:, None], h0_im[:, None]
        h_re = A_re * h0_re - A_im * h0_im + B_re
        h_im = A_re * h0_im + A_im * h0_re + B_im
        return h_re, h_im

    def _chunked_states(self, u_re, u_im, carry):
        """All T states via per-chunk causal triangular matmuls (MXU)
        plus a length-T/C carry scan — the `chunk` docstring's
        formulation. T is zero-padded up to a chunk multiple (padded
        tail sliced off; zero inputs after T never reach a kept state)."""
        C = self.chunk
        B, T, H = u_re.shape
        pad = (C - T % C) % C
        if pad:
            u_re = jnp.pad(u_re, ((0, 0), (0, pad), (0, 0)))
            u_im = jnp.pad(u_im, ((0, 0), (0, pad), (0, 0)))
        Nc = (T + pad) // C

        # lambda^d for d = 0..C in polar form (elementwise per feature)
        mod, theta = self._polar()
        d = jnp.arange(C + 1, dtype=jnp.float32)[:, None]
        P_re = (mod**d) * jnp.cos(theta * d)  # (C+1, H)
        P_im = (mod**d) * jnp.sin(theta * d)
        i = jnp.arange(C)
        dm = i[:, None] - i[None, :]
        causal = dm >= 0
        dm = jnp.where(causal, dm, 0)
        T_re = jnp.where(causal[:, :, None], P_re[dm], 0.0)  # (C, C, H)
        T_im = jnp.where(causal[:, :, None], P_im[dm], 0.0)

        ur = u_re.reshape(B, Nc, C, H)
        ui = u_im.reshape(B, Nc, C, H)
        # within-chunk prefix W_t = sum_{s<=t} lambda^(t-s) u_s, complex
        # product spelled out over (re, im): 4 batched GEMMs over H.
        # Precision.HIGHEST: the TPU MXU's default contraction rounds f32
        # operands to bf16, which would break the module contract (f32
        # recurrence throughout — long-horizon cumulative products). The
        # cost is ~3 MXU passes per GEMM instead of 1; accepted, because
        # correctness of the recurrence is the point of the f32 contract
        # and the GEMMs are (C, C, H)-small relative to the encoder.
        hi_p = jax.lax.Precision.HIGHEST
        Wr = jnp.einsum("tsh,bnsh->bnth", T_re, ur, precision=hi_p) - jnp.einsum(
            "tsh,bnsh->bnth", T_im, ui, precision=hi_p
        )
        Wi = jnp.einsum("tsh,bnsh->bnth", T_re, ui, precision=hi_p) + jnp.einsum(
            "tsh,bnsh->bnth", T_im, ur, precision=hi_p
        )

        # cross-chunk carries: c_n = lambda^C c_{n-1} + W_last_n, scanned
        # over the Nc chunk-final states only; emit the carry INTO chunk n
        lamC_re, lamC_im = P_re[C], P_im[C]

        def body(c, w):
            cr, ci = c
            wr, wi = w
            nr = lamC_re * cr - lamC_im * ci + wr
            ni = lamC_re * ci + lamC_im * cr + wi
            return (nr, ni), (cr, ci)

        h0 = (carry[0].astype(jnp.float32), carry[1].astype(jnp.float32))
        _, (pr, pi) = jax.lax.scan(
            body, h0,
            (jnp.moveaxis(Wr[:, :, -1], 1, 0), jnp.moveaxis(Wi[:, :, -1], 1, 0)),
        )
        # h at offset t of chunk n: W_t + lambda^(t+1) * carry_in(n)
        Q_re, Q_im = P_re[1:], P_im[1:]  # (C, H)
        pr = jnp.moveaxis(pr, 0, 1)[:, :, None]  # (B, Nc, 1, H)
        pi = jnp.moveaxis(pi, 0, 1)[:, :, None]
        hr = Wr + Q_re[None, None] * pr - Q_im[None, None] * pi
        hi = Wi + Q_re[None, None] * pi + Q_im[None, None] * pr
        return (
            hr.reshape(B, T + pad, H)[:, :T],
            hi.reshape(B, T + pad, H)[:, :T],
        )

    def __call__(
        self, xs: jnp.ndarray, carry: Carry, burn_in=None
    ) -> Tuple[jnp.ndarray, Carry]:
        """Unroll over (B, T, D) from carry; returns ((B, T, H), final
        carry). Same math by three formulations: chunk > 0 = chunked MXU
        matmuls; else the sequential kernel where `backend` and the shapes
        allow it, and one associative scan elsewhere. The kernel wants time
        leading, so that path runs the whole module time-major: the input is
        transposed once in the compute dtype, the output once likewise, and
        no f32 state-sized array is ever transposed.
        `burn_in` is ignored (cuts_at_burn_in = False)."""
        _, _, gamma = self._decay()
        kernel = (
            self.chunk == 0
            and self.backend == "pallas"
            and kernel_fits(xs.shape[0], self.hidden_dim)
        )
        if kernel:
            xs = jnp.swapaxes(xs.astype(self.dtype), 0, 1)  # (T, B, D)
        u_re, u_im = self._project_in(xs, gamma)  # f32, (B, T, H) or (T, B, H)
        if self.chunk > 0:
            h_re, h_im = self._chunked_states(u_re, u_im, carry)
        else:
            h_re, h_im = self._scan_states(u_re, u_im, carry, kernel)
        outs = self._readout(h_re, h_im, xs)
        if kernel:
            return jnp.swapaxes(outs, 0, 1), (h_re[-1], h_im[-1])
        return outs, (h_re[:, -1], h_im[:, -1])

    def step(self, x: jnp.ndarray, carry: Carry) -> Tuple[jnp.ndarray, Carry]:
        """Single acting step on (B, D): one elementwise complex
        multiply-add — the actor-side cost is O(H), cheaper than the
        LSTM's (B,H)x(H,4H) recurrent matmul."""
        lam_re, lam_im, gamma = self._decay()
        u_re, u_im = self._project_in(x, gamma)
        h_re, h_im = carry
        h_re = h_re.astype(jnp.float32)
        h_im = h_im.astype(jnp.float32)
        new_re = lam_re * h_re - lam_im * h_im + u_re
        new_im = lam_re * h_im + lam_im * h_re + u_im
        out = self._readout(new_re, new_im, x)
        return out, (new_re, new_im)
