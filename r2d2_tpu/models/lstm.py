"""Hand-rolled LSTM under lax.scan.

XLA has no cuDNN-style packed-sequence LSTM (the reference leans on
`pack_padded_sequence`, reference model.py:133); instead sequences are
fixed-shape and padded, the recurrence runs the full length, and output
gathers with clamped indices reproduce the variable-length semantics
(see models/r2d2.py).

TPU-first structure: the input projection x @ Wi for ALL timesteps is one
big (B*T, D) x (D, 4H) matmul — large, batched, MXU-friendly — so the
sequential scan body is only the (B, H) x (H, 4H) recurrent matmul plus
elementwise gates. For long-context configs the scan is chunked and each
chunk rematerialized (jax.checkpoint), trading FLOPs for HBM
(SURVEY.md section 5.7: an RNN recurrence parallelizes over batch, never
over time).

Gate order follows i, f, g, o. Weights use the same uniform(-1/sqrt(H),
1/sqrt(H)) scale family as the reference's recurrent core so Q-value
magnitudes start in a comparable regime.
"""

from __future__ import annotations

from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

Carry = Tuple[jnp.ndarray, jnp.ndarray]  # (h, c), each (B, H)


def _uniform_init(scale):
    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -scale, scale)

    return init


class LSTM(nn.Module):
    hidden_dim: int
    in_dim: int
    dtype: jnp.dtype = jnp.float32
    # remat chunk length for long unrolls; None = single un-remat'd scan
    scan_chunk: Optional[int] = None
    # "scan": lax.scan unroll. "pallas": fused Pallas kernel (ops/
    # pallas_lstm.py) — recurrent weights + carry stay VMEM-resident for
    # the whole unroll. "auto": pallas on TPU, scan elsewhere.
    backend: str = "auto"
    # Manual tensor parallelism (learner.make_manual_train_step's
    # shard_map): > 1 builds the SHARD-LOCAL module — wi/wh/b carry this
    # device's contiguous 4H/tp column slice, matching the sharding_map
    # table's column-parallel layout — and _gates re-gathers the
    # per-shard gate pre-activations over `tp_axis` before the
    # (replicated) gate/carry math. Scan backend only: the fused Pallas
    # kernel computes gates in-kernel and cannot host the seam.
    tp_size: int = 1
    tp_axis: str = "tp"

    # the seam's statements (models/core.py): __call__ cuts the gradient
    # at each row's burn_in, and the stored state is the (h, c) pair
    cuts_at_burn_in = True

    @staticmethod
    def state_shape(cfg):
        return (2, cfg.hidden_dim)

    @classmethod
    def from_config(cls, cfg, in_dim: int, tp_size: int = 1) -> "LSTM":
        # "auto" is resolved HERE, once, by the config's own rule — the
        # module never picks a backend at trace time on this path
        backend = cfg.resolved_core_backend
        # precision="bf16" forces bfloat16 compute; fp32 precision
        # defers to the legacy compute_dtype knob (config.py)
        dtype = jnp.dtype(cfg.resolved_compute_dtype)
        if backend == "pallas" and jax.default_backend() == "tpu":
            # compiled kernels live under the device's VMEM (the
            # interpreter has none to respect): a training shape the
            # sequence kernel cannot hold is refused here, by shape
            from r2d2_tpu.ops import pallas_lstm

            pallas_lstm.require_seq_backward_fits(
                cfg.seq_len, cfg._rows_per_device(), cfg.hidden_dim, dtype,
                pallas_lstm.vmem_capacity_bytes(),
            )
        return cls(
            cfg.hidden_dim,
            in_dim=in_dim,
            dtype=dtype,
            scan_chunk=cfg.scan_chunk,
            backend=backend,
            tp_size=tp_size,
        )

    def setup(self):
        H = self.hidden_dim
        scale = 1.0 / np.sqrt(H)
        if (4 * H) % self.tp_size != 0:
            raise ValueError(
                f"LSTM gate width 4*{H} must divide by tp_size={self.tp_size}"
            )
        cols = 4 * H // self.tp_size
        self.wi = self.param("wi", _uniform_init(scale), (self.in_dim, cols))
        self.wh = self.param("wh", _uniform_init(scale), (H, cols))
        self.b = self.param("b", _uniform_init(scale), (cols,))

    def _params(self):
        return self.wi, self.wh, self.b

    def _gates(self, proj: jnp.ndarray, h: jnp.ndarray, wh: jnp.ndarray, c: jnp.ndarray):
        H = self.hidden_dim
        z = proj + h @ wh
        if self.tp_size > 1:
            # tp seam: each shard holds a contiguous 4H/tp column slice
            # of the gate pre-activations (column-parallel wi/wh/b). One
            # tiled all-gather reconstructs the full z BIT-exactly — the
            # within-shard matmul reductions are untouched, the gather
            # only concatenates finished columns — after which gate math
            # and the (h, c) carry are replicated across tp.
            z = jax.lax.all_gather(z, self.tp_axis, axis=z.ndim - 1, tiled=True)
        i = jax.nn.sigmoid(z[..., :H])
        f = jax.nn.sigmoid(z[..., H : 2 * H])
        g = jnp.tanh(z[..., 2 * H : 3 * H])
        o = jax.nn.sigmoid(z[..., 3 * H :])
        c_new = f * c + i * g
        h_new = o * jnp.tanh(c_new)
        return h_new, c_new

    def __call__(
        self,
        xs: jnp.ndarray,
        carry: Carry,
        burn_in: Optional[jnp.ndarray] = None,
    ) -> Tuple[jnp.ndarray, Carry]:
        """Unroll over (B, T, D) inputs from carry; returns (B, T, H) + carry.

        `burn_in` (B,) int32, when given, places a per-row stop-gradient
        seam at step burn_in[b]: forward values are unchanged, but the
        backward pass treats steps t < burn_in[b] as state-refresh only
        (zero grads into the weights and into the initial carry). Both
        backends implement the same seam — the Pallas path inside its
        backward kernel (ops/pallas_lstm.py lstm_seq_unroll), the scan
        path via the operator-equivalent where/stop_gradient masks below —
        so the trained function is backend-independent.
        """
        B, T, D = xs.shape
        wi, wh, b = self._params()
        xs = xs.astype(self.dtype)
        wi, wh, b = wi.astype(self.dtype), wh.astype(self.dtype), b.astype(self.dtype)
        h, c = carry
        h, c = h.astype(self.dtype), c.astype(self.dtype)

        # one MXU-sized matmul for every timestep's input projection
        # (wi.shape[-1] = 4H/tp — the shard-local column count)
        proj = (xs.reshape(B * T, D) @ wi + b).reshape(B, T, wi.shape[-1])
        proj_t = jnp.swapaxes(proj, 0, 1)  # (T, B, 4H/tp) time-major for scan

        use_pallas = self.backend == "pallas" or (
            self.backend == "auto" and jax.default_backend() == "tpu"
        )
        if use_pallas and self.tp_size > 1:
            raise ValueError(
                "the shard-local (manual-tp) LSTM needs its all-gather "
                "seam inside the step body; use the scan backend "
                "(config.validate routes tp here via tp_shards_params)"
            )
        if use_pallas:
            from r2d2_tpu.ops.pallas_lstm import lstm_seq_unroll, lstm_unroll

            if burn_in is None:
                outs_t, (hT, cT) = lstm_unroll(proj_t, wh, h, c)
            else:
                outs_t, (hT, cT) = lstm_seq_unroll(
                    proj_t, wh, h, c, burn_in.astype(jnp.int32)
                )
            return (
                jnp.swapaxes(outs_t, 0, 1),
                (hT.astype(self.dtype), cT.astype(self.dtype)),
            )

        if burn_in is None:

            def step(carry, p):
                h, c = carry
                h, c = self._gates(p, h, wh, c)
                return (h, c), h

            xs_scan = proj_t
        else:
            bi = burn_in.astype(jnp.int32)

            def step(carry, inp):
                t, p = inp
                h, c = carry
                # seam: the carry entering step burn_in[b] is state-refresh
                # only — identical values, no gradient across the boundary
                cut = (t == bi)[:, None]
                h = jnp.where(cut, jax.lax.stop_gradient(h), h)
                c = jnp.where(cut, jax.lax.stop_gradient(c), c)
                h, c = self._gates(p, h, wh, c)
                # burn-in outputs carry no cotangent into the weights
                keep = (t >= bi)[:, None]
                out = jnp.where(keep, h, jax.lax.stop_gradient(h))
                return (h, c), out

            xs_scan = (jnp.arange(T, dtype=jnp.int32), proj_t)

        if self.scan_chunk is None or T <= self.scan_chunk:
            (h, c), outs = jax.lax.scan(step, (h, c), xs_scan)
        else:
            # T > chunk: remat each full chunk; a non-divisible tail runs
            # as ONE shorter remat'd chunk (same step fn, same remat
            # boundary semantics), so burn-in/learning-window geometries
            # are not constrained to divisible sequence lengths.
            chunk = self.scan_chunk
            n_full = T // chunk
            main_len = n_full * chunk

            @jax.checkpoint
            def run_chunk(carry, chunk_xs):
                return jax.lax.scan(step, carry, chunk_xs)

            p_chunks = proj_t[:main_len].reshape(
                n_full, chunk, B, proj_t.shape[-1]
            )
            ts = jnp.arange(T, dtype=jnp.int32)
            if burn_in is None:
                chunk_xs = p_chunks
            else:
                chunk_xs = (ts[:main_len].reshape(n_full, chunk), p_chunks)
            (h, c), outs = jax.lax.scan(run_chunk, (h, c), chunk_xs)
            outs = outs.reshape(main_len, B, self.hidden_dim)
            if main_len < T:
                tail_xs = (
                    proj_t[main_len:]
                    if burn_in is None
                    else (ts[main_len:], proj_t[main_len:])
                )
                (h, c), tail_outs = run_chunk((h, c), tail_xs)
                outs = jnp.concatenate([outs, tail_outs], axis=0)

        return jnp.swapaxes(outs, 0, 1), (h, c)

    def step(self, x: jnp.ndarray, carry: Carry) -> Tuple[jnp.ndarray, Carry]:
        """Single acting step on (B, D) input (reference model.py:83)."""
        wi, wh, b = self._params()
        x = x.astype(self.dtype)
        wi, wh, b = wi.astype(self.dtype), wh.astype(self.dtype), b.astype(self.dtype)
        h, c = carry
        proj = x @ wi + b
        h_new, c_new = self._gates(proj, h.astype(self.dtype), wh, c.astype(self.dtype))
        return h_new, (h_new, c_new)
