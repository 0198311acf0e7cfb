"""Fused Pallas LSTM unroll — the TPU kernel for the framework's hot op.

The learner's sequence unroll (reference model.py:59,133-139 leans on a
cuDNN packed-sequence LSTM) is the latency-bound part of the jitted update:
T=85 strictly sequential recurrent steps whose per-step matmul
(B, H) x (H, 4H) is far too small to amortize HBM traffic if the loop body
re-fetches operands. This kernel runs the WHOLE unroll as one `pallas_call`
with a sequential grid over time:

- the recurrent weights `wh` (H, 4H) are fetched into VMEM once and stay
  resident for all T steps (the index_map pins the same block every
  iteration, so the pipeline does not re-copy it),
- the (h, c) carry lives in VMEM scratch across grid steps (TPU grid
  iterations execute sequentially, scratch persists),
- per step: one MXU matmul (B,H)x(H,4H) + VPU gate math, fused — nothing
  touches HBM except streaming in proj_t and streaming out h_t/c_t.

The input projection x @ Wi + b for ALL timesteps is deliberately NOT in
the kernel: it is one big (B*T, D) x (D, 4H) matmul that XLA already maps
perfectly onto the MXU (models/lstm.py does it), and keeping it outside
lets autodiff handle dWi/db for free.

Backward is a second Pallas kernel walking the grid in reverse time order,
carrying (dh, dc) in scratch and emitting per-step pre-activation grads dz;
the weight gradient dWh = h_prev^T @ dz then falls out as one big MXU
matmul outside the kernel (same trick as forward). Residuals saved: the
h_t and c_t sequences — gates are recomputed in the backward kernel (one
extra matmul per step, cheaper than storing 4H activations).

Numerics: gate math and the carry accumulate in float32 regardless of the
compute dtype; matmuls run in the weights' dtype with
preferred_element_type=float32 (bfloat16 feeds the MXU at double rate).

On non-TPU backends the kernels run in Pallas interpret mode, which is how
the CPU test suite pins forward/gradient parity against the lax.scan
reference implementation (models/lstm.py).

VMEM: Mosaic's default scoped-VMEM limit (16 MiB) is below what the
backward kernels need at the production shape — the first compile on a
v5e refused every fp32 backward at B=64, H=512 ("Scoped allocation with
size 21.41M and limit 16.00M" for the default arm, 28.22M fused-dWh,
33.78M ckpt). Every call therefore passes `vmem_limit_bytes` sized from
its own blocks (`_compiler_params`), and a call whose blocks cannot fit
the device's VMEM at all raises here, by name, instead of inside Mosaic.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _nbytes(shape, dtype) -> int:
    return int(np.prod(shape, dtype=np.int64)) * jnp.dtype(dtype).itemsize


def kernel_vmem_bytes(blocks, scratch, B: int, H: int, wh_dtype) -> int:
    """Upper estimate of one call's scoped-VMEM need: every pipelined
    in/out block double-buffered, the scratch, and the kernel body's
    temporaries — the loaded `wh` value plus its transposed copy (the
    dz @ wh.T carry matmul) plus slack, and a dozen (B, 4H) f32 gate /
    pre-activation-grad arrays. `blocks` and `scratch` are (shape, dtype)
    lists. Checked against the v5e compiler's own totals at T=85, B=64,
    H=512 fp32: it asked 21.4 / 28.2 / 33.8 MiB (default / fused-dWh /
    ckpt S=5) where this gives 31 / 43 / 53."""
    pipelined = 2 * sum(_nbytes(s, d) for s, d in blocks)
    held = sum(_nbytes(s, d) for s, d in scratch)
    body = 3 * _nbytes((H, 4 * H), wh_dtype) + 12 * _nbytes((B, 4 * H), jnp.float32)
    return pipelined + held + body + (1 << 20)


def vmem_capacity_bytes() -> int:
    """Per-core VMEM of the attached TPU, from jax's own device table
    (an unknown device kind raises there — no assumed default)."""
    return int(pltpu.get_tpu_info().vmem_capacity_bytes)


def _compiler_params(what: str, blocks, scratch, B: int, H: int, wh_dtype,
                     interpret: bool):
    """CompilerParams carrying this call's VMEM limit; None under the
    interpreter (no VMEM to budget, and no TPU to ask for its size)."""
    if interpret:
        return None
    need = kernel_vmem_bytes(blocks, scratch, B, H, wh_dtype)
    cap = vmem_capacity_bytes()
    if need > cap:
        raise ValueError(
            f"{what}: blocks need ~{need >> 20} MiB of VMEM at B={B}, H={H} "
            f"but the device has {cap >> 20} MiB — shard the batch (dp), "
            "or for the checkpointed backward pick a shorter segment"
        )
    return pltpu.CompilerParams(vmem_limit_bytes=need)


def _split_gates(z: jnp.ndarray, H: int):
    i = jax.nn.sigmoid(z[..., :H])
    f = jax.nn.sigmoid(z[..., H : 2 * H])
    g = jnp.tanh(z[..., 2 * H : 3 * H])
    o = jax.nn.sigmoid(z[..., 3 * H :])
    return i, f, g, o


# --------------------------------------------------------------------------
# forward kernel
# --------------------------------------------------------------------------


def _fwd_kernel(proj_ref, wh_ref, h0_ref, c0_ref, outs_ref, cs_ref, h_s, c_s):
    H = h_s.shape[-1]
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        h_s[:] = h0_ref[:].astype(jnp.float32)
        c_s[:] = c0_ref[:].astype(jnp.float32)

    wh = wh_ref[:]
    z = proj_ref[0].astype(jnp.float32) + jnp.dot(
        h_s[:].astype(wh.dtype), wh, preferred_element_type=jnp.float32
    )
    i, f, g, o = _split_gates(z, H)
    c_new = f * c_s[:] + i * g
    h_new = o * jnp.tanh(c_new)
    h_s[:] = h_new
    c_s[:] = c_new
    outs_ref[0] = h_new.astype(outs_ref.dtype)
    cs_ref[0] = c_new


@functools.partial(jax.jit, static_argnames=("interpret",))
def _lstm_fwd_call(proj_t, wh, h0, c0, *, interpret: bool):
    T, B, fourH = proj_t.shape
    H = fourH // 4
    f32 = jnp.float32
    params = _compiler_params(
        "lstm forward kernel",
        [((1, B, 4 * H), proj_t.dtype), ((H, 4 * H), wh.dtype),
         ((B, H), h0.dtype), ((B, H), c0.dtype),
         ((1, B, H), proj_t.dtype), ((1, B, H), f32)],
        [((B, H), f32)] * 2, B, H, wh.dtype, interpret,
    )
    outs, cs = pl.pallas_call(
        _fwd_kernel,
        name="_lstm_fwd_call",
        grid=(T,),
        compiler_params=params,
        in_specs=[
            pl.BlockSpec((1, B, 4 * H), lambda t: (t, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((H, 4 * H), lambda t: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((B, H), lambda t: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((B, H), lambda t: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, B, H), lambda t: (t, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, B, H), lambda t: (t, 0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, H), proj_t.dtype),
            jax.ShapeDtypeStruct((T, B, H), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((B, H), jnp.float32),
            pltpu.VMEM((B, H), jnp.float32),
        ],
        interpret=interpret,
    )(proj_t, wh, h0, c0)
    return outs, cs


# --------------------------------------------------------------------------
# backward kernel (reverse time order via index_map t -> T-1-t)
# --------------------------------------------------------------------------


def _bwd_kernel(
    dout_ref, proj_ref, hprev_ref, cprev_ref, cs_ref, wh_ref, dcT_ref,
    dz_ref, dh0_ref, dc0_ref, dh_s, dc_s,
):
    H = dh_s.shape[-1]
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        # dh seed (the h_T cotangent) is folded into dout[-1] by the caller;
        # the c_T cotangent seeds the cell-grad carry here.
        dh_s[:] = jnp.zeros_like(dh_s)
        dc_s[:] = dcT_ref[:]

    wh = wh_ref[:]
    # recompute this step's gates from saved h_{t-1}, c_{t-1}
    z = proj_ref[0].astype(jnp.float32) + jnp.dot(
        hprev_ref[0].astype(wh.dtype), wh, preferred_element_type=jnp.float32
    )
    i, f, g, o = _split_gates(z, H)
    tanh_c = jnp.tanh(cs_ref[0])

    dh = dout_ref[0].astype(jnp.float32) + dh_s[:]
    do = dh * tanh_c
    dc = dh * o * (1.0 - tanh_c * tanh_c) + dc_s[:]
    di = dc * g
    df = dc * cprev_ref[0]
    dg = dc * i
    dz = jnp.concatenate(
        [
            di * i * (1.0 - i),
            df * f * (1.0 - f),
            dg * (1.0 - g * g),
            do * o * (1.0 - o),
        ],
        axis=-1,
    )
    dz_ref[0] = dz
    # carry to step t-1
    dh_s[:] = jnp.dot(dz.astype(wh.dtype), wh.T, preferred_element_type=jnp.float32)
    dc_s[:] = dc * f
    # after the last grid step (real t=0) these hold d h0 / d c0
    dh0_ref[:] = dh_s[:]
    dc0_ref[:] = dc_s[:]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _lstm_bwd_call(dout, proj_t, hprev, cprev, cs, wh, dcT, *, interpret: bool):
    T, B, H = cs.shape
    rev3 = lambda t: (T - 1 - t, 0, 0)
    pinned = lambda t: (0, 0)
    f32 = jnp.float32
    params = _compiler_params(
        "lstm backward kernel",
        [((1, B, H), dout.dtype), ((1, B, 4 * H), proj_t.dtype),
         ((1, B, H), hprev.dtype), ((1, B, H), f32), ((1, B, H), f32),
         ((H, 4 * H), wh.dtype), ((B, H), f32),
         ((1, B, 4 * H), f32), ((B, H), f32), ((B, H), f32)],
        [((B, H), f32)] * 2, B, H, wh.dtype, interpret,
    )
    dz, dh0, dc0 = pl.pallas_call(
        _bwd_kernel,
        name="_lstm_bwd_call",
        grid=(T,),
        compiler_params=params,
        in_specs=[
            pl.BlockSpec((1, B, H), rev3, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, B, 4 * H), rev3, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, B, H), rev3, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, B, H), rev3, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, B, H), rev3, memory_space=pltpu.VMEM),
            pl.BlockSpec((H, 4 * H), pinned, memory_space=pltpu.VMEM),
            pl.BlockSpec((B, H), pinned, memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, B, 4 * H), rev3, memory_space=pltpu.VMEM),
            pl.BlockSpec((B, H), pinned, memory_space=pltpu.VMEM),
            pl.BlockSpec((B, H), pinned, memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, 4 * H), jnp.float32),
            jax.ShapeDtypeStruct((B, H), jnp.float32),
            jax.ShapeDtypeStruct((B, H), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((B, H), jnp.float32),
            pltpu.VMEM((B, H), jnp.float32),
        ],
        interpret=interpret,
    )(dout, proj_t, hprev, cprev, cs, wh, dcT)
    return dz, dh0, dc0


# --------------------------------------------------------------------------
# custom-VJP public op
# --------------------------------------------------------------------------


@jax.custom_vjp
def lstm_unroll(
    proj_t: jnp.ndarray,  # (T, B, 4H) time-major input projections x@Wi+b
    wh: jnp.ndarray,      # (H, 4H) recurrent weights
    h0: jnp.ndarray,      # (B, H)
    c0: jnp.ndarray,      # (B, H)
) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Fused LSTM unroll: returns (outs (T, B, H), (h_T, c_T))."""
    outs, cs = _lstm_fwd_call(proj_t, wh, h0, c0, interpret=_interpret())
    return outs, (outs[-1].astype(jnp.float32), cs[-1])


def _vjp_fwd(proj_t, wh, h0, c0):
    outs, cs = _lstm_fwd_call(proj_t, wh, h0, c0, interpret=_interpret())
    return (outs, (outs[-1].astype(jnp.float32), cs[-1])), (proj_t, wh, h0, c0, outs, cs)


def _vjp_bwd(res, grads):
    proj_t, wh, h0, c0, outs, cs = res
    douts, (dhT, dcT) = grads
    T, B, H = cs.shape
    # h_T IS outs[-1], so its cotangent folds into dout[-1]; the c_T
    # cotangent seeds the backward kernel's cell-grad carry at step T-1.
    douts = douts.astype(jnp.float32).at[-1].add(dhT.astype(jnp.float32))
    hprev = jnp.concatenate([h0.astype(outs.dtype)[None], outs[:-1]], axis=0)
    cprev = jnp.concatenate([c0.astype(jnp.float32)[None], cs[:-1]], axis=0)
    dz, dh0, dc0 = _lstm_bwd_call(
        douts, proj_t, hprev, cprev, cs, wh, dcT.astype(jnp.float32),
        interpret=_interpret(),
    )
    dproj = dz.astype(proj_t.dtype)
    # weight grad as ONE big MXU matmul: (H, T*B) x (T*B, 4H)
    dwh = jnp.dot(
        hprev.reshape(T * B, H).astype(jnp.float32).T, dz.reshape(T * B, 4 * H),
        preferred_element_type=jnp.float32,
    ).astype(wh.dtype)
    return dproj, dwh, dh0.astype(h0.dtype), dc0.astype(c0.dtype)


lstm_unroll.defvjp(_vjp_fwd, _vjp_bwd)


# --------------------------------------------------------------------------
# fused SEQUENCE op: burn-in + train segment in one launch, stop-gradient
# seam handled inside the backward kernel
# --------------------------------------------------------------------------
#
# R2D2 replays (burn-in ‖ learning ‖ forward) windows as ONE T-step sequence
# and stops gradients at the burn-in/train seam: burn-in steps refresh the
# recurrent state from stale-policy data but must not train the core.
#
# The seam position is PER ROW, not static: collect.py packs overlapping
# windows where window 0 of a block gets burn_in=0 and later windows get the
# full Bn, so a (B,) vector of seam indices rides along with every batch.
# That rules out splitting the launch at the seam; instead the forward runs
# the whole sequence as the one fused launch above (bit-identical to
# lstm_unroll — stop_gradient is the identity on values) and the backward
# kernel walks the full T-step reverse grid applying two per-row masks:
#
#   keep       = t >= burn   zeroes the pre-activation grad dz for burn-in
#                            steps (their outputs carry no cotangent),
#   carry_keep = t >  burn   cuts the (dh, dc) carry crossing the seam, so
#                            nothing flows from the train segment into
#                            burn-in steps.
#
# Rows below their seam therefore contribute exact zeros to dproj and to the
# big dWh matmul outside the kernel, and d h0 / d c0 are STRUCTURALLY zero
# for every row (the carry is cut at t == burn >= 0 before it can reach the
# initial state), so the VJP returns zeros without reading kernel outputs.
# Burn-in steps do no gate-recompute work that survives: their lanes are
# masked to zero and the only residual read the seam needs is h/c at the
# seam row itself (already part of the forward outputs; no extra residuals
# are saved for the burn-in segment).


def _seq_bwd_kernel(
    dout_ref, proj_ref, hprev_ref, cprev_ref, cs_ref, wh_ref, dcT_ref, burn_ref,
    dz_ref, dh_s, dc_s,
):
    H = dh_s.shape[-1]
    t = pl.program_id(0)
    # the grid streams blocks in reverse time order; recover the real index
    t_real = pl.num_programs(0) - 1 - t

    @pl.when(t == 0)
    def _():
        dh_s[:] = jnp.zeros_like(dh_s)
        dc_s[:] = dcT_ref[:]

    burn = burn_ref[:]  # (B, 1) int32 per-row seam
    keep = t_real >= burn
    carry_keep = t_real > burn

    wh = wh_ref[:]
    z = proj_ref[0].astype(jnp.float32) + jnp.dot(
        hprev_ref[0].astype(wh.dtype), wh, preferred_element_type=jnp.float32
    )
    i, f, g, o = _split_gates(z, H)
    tanh_c = jnp.tanh(cs_ref[0])

    dh = jnp.where(keep, dout_ref[0].astype(jnp.float32), 0.0) + dh_s[:]
    do = dh * tanh_c
    dc = dh * o * (1.0 - tanh_c * tanh_c) + dc_s[:]
    di = dc * g
    df = dc * cprev_ref[0]
    dg = dc * i
    dz = jnp.concatenate(
        [
            di * i * (1.0 - i),
            df * f * (1.0 - f),
            dg * (1.0 - g * g),
            do * o * (1.0 - o),
        ],
        axis=-1,
    )
    dz_ref[0] = dz
    # carry to step t_real-1, cut at the seam (and already-zero below it)
    dh_s[:] = jnp.where(
        carry_keep,
        jnp.dot(dz.astype(wh.dtype), wh.T, preferred_element_type=jnp.float32),
        0.0,
    )
    dc_s[:] = jnp.where(carry_keep, dc * f, 0.0)


def _seq_bwd_vmem_spec(fused_dwh: bool, B, H, dout_dtype, proj_dtype,
                       h_dtype, wh_dtype):
    """(blocks, scratch) of the per-step-grid sequence backward calls —
    their BlockSpecs and scratch_shapes as (shape, dtype) for the VMEM
    estimate. The fused-dWh arm emits dz in the proj dtype and adds the
    pinned f32 dWh output plus its f32 accumulator scratch."""
    f32 = jnp.float32
    blocks = [
        ((1, B, H), dout_dtype), ((1, B, 4 * H), proj_dtype),
        ((1, B, H), h_dtype), ((1, B, H), f32), ((1, B, H), f32),
        ((H, 4 * H), wh_dtype), ((B, H), f32), ((B, 128), jnp.int32),
        ((1, B, 4 * H), proj_dtype if fused_dwh else f32),
    ]
    scratch = [((B, H), f32)] * 2
    if fused_dwh:
        blocks.append(((H, 4 * H), f32))
        scratch.append(((H, 4 * H), f32))
    return blocks, scratch


@functools.partial(jax.jit, static_argnames=("interpret",))
def _lstm_seq_bwd_call(dout, proj_t, hprev, cprev, cs, wh, dcT, burn, *, interpret: bool):
    T, B, H = cs.shape
    rev3 = lambda t: (T - 1 - t, 0, 0)
    pinned = lambda t: (0, 0)
    params = _compiler_params(
        "lstm sequence backward kernel (default arm)",
        *_seq_bwd_vmem_spec(False, B, H, dout.dtype, proj_t.dtype, hprev.dtype,
                            wh.dtype),
        B, H, wh.dtype, interpret,
    )
    (dz,) = pl.pallas_call(
        _seq_bwd_kernel,
        name="_lstm_seq_bwd_call",
        grid=(T,),
        compiler_params=params,
        in_specs=[
            pl.BlockSpec((1, B, H), rev3, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, B, 4 * H), rev3, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, B, H), rev3, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, B, H), rev3, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, B, H), rev3, memory_space=pltpu.VMEM),
            pl.BlockSpec((H, 4 * H), pinned, memory_space=pltpu.VMEM),
            pl.BlockSpec((B, H), pinned, memory_space=pltpu.VMEM),
            pl.BlockSpec((B, 1), pinned, memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, B, 4 * H), rev3, memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, 4 * H), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((B, H), jnp.float32),
            pltpu.VMEM((B, H), jnp.float32),
        ],
        interpret=interpret,
    )(dout, proj_t, hprev, cprev, cs, wh, dcT, burn)
    return dz


@jax.custom_vjp
def lstm_seq_unroll(
    proj_t: jnp.ndarray,   # (T, B, 4H) time-major input projections x@Wi+b
    wh: jnp.ndarray,       # (H, 4H) recurrent weights
    h0: jnp.ndarray,       # (B, H)
    c0: jnp.ndarray,       # (B, H)
    burn_in: jnp.ndarray,  # (B,) int32 per-row stop-gradient seam position
) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Fused burn-in + train sequence unroll with a stop-gradient seam.

    Forward values are bit-identical to :func:`lstm_unroll` (one launch,
    carry pinned in VMEM scratch for all T steps). The VJP implements the
    R2D2 seam: gradients do not flow into steps t < burn_in[b] of row b,
    and d h0 / d c0 are exact zeros.

    Contract: 0 <= burn_in[b] < T. The replay pipeline guarantees this
    (burn_in + learning + forward == T with learning >= 1); a seam at or
    past T would mean "no train segment", which the masks above do not
    define (every collect/learner caller satisfies the contract by
    construction).
    """
    outs, cs = _lstm_fwd_call(proj_t, wh, h0, c0, interpret=_interpret())
    return outs, (outs[-1].astype(jnp.float32), cs[-1])


def _seq_vjp_fwd(proj_t, wh, h0, c0, burn_in):
    outs, cs = _lstm_fwd_call(proj_t, wh, h0, c0, interpret=_interpret())
    out = (outs, (outs[-1].astype(jnp.float32), cs[-1]))
    return out, (proj_t, wh, h0, c0, burn_in, outs, cs)


def _seq_vjp_bwd(res, grads):
    proj_t, wh, h0, c0, burn_in, outs, cs = res
    douts, (dhT, dcT) = grads
    T, B, H = cs.shape
    douts = douts.astype(jnp.float32).at[-1].add(dhT.astype(jnp.float32))
    hprev = jnp.concatenate([h0.astype(outs.dtype)[None], outs[:-1]], axis=0)
    cprev = jnp.concatenate([c0.astype(jnp.float32)[None], cs[:-1]], axis=0)
    burn = burn_in.astype(jnp.int32).reshape(B, 1)
    dz = _lstm_seq_bwd_call(
        douts, proj_t, hprev, cprev, cs, wh, dcT.astype(jnp.float32), burn,
        interpret=_interpret(),
    )
    dproj = dz.astype(proj_t.dtype)
    # dz is exactly zero for burn-in steps, so they drop out of dWh too
    dwh = jnp.dot(
        hprev.reshape(T * B, H).astype(jnp.float32).T, dz.reshape(T * B, 4 * H),
        preferred_element_type=jnp.float32,
    ).astype(wh.dtype)
    # the seam cut makes initial-state grads structurally zero; the int32
    # seam vector is non-differentiable (float0 cotangent)
    dburn = np.zeros(burn_in.shape, dtype=jax.dtypes.float0)
    return dproj, dwh, jnp.zeros_like(h0), jnp.zeros_like(c0), dburn


lstm_seq_unroll.defvjp(_seq_vjp_fwd, _seq_vjp_bwd)


# --------------------------------------------------------------------------
# backward arm (a): fused dWh — the recurrent-weight gradient accumulates in
# a VMEM scratch inside the reversed-T grid instead of the separate
# (T*B, H)^T @ (T*B, 4H) matmul outside the kernel
# --------------------------------------------------------------------------
#
# Every reversed-T step already holds h_{t-1} (hprev block) and the freshly
# computed dz in VMEM, so the per-step rank-B update
#
#     dWh += h_{t-1}^T @ dz        ((H, B) x (B, 4H) on the MXU)
#
# costs one extra matmul per step and removes BOTH backward-side HBM
# sweeps the outside matmul needed (re-reading hprev and dz at (T, B, *)).
# With dWh fused, dz leaves the kernel only as dproj, so the output is
# emitted directly in the compute dtype — under bf16 the full-size f32 dz
# array disappears from the backward entirely.
#
# Parity note: the fused accumulation sums T per-step f32 partial products
# where the outside matmul contracts T*B in one dot — same math, different
# summation order, so dWh agrees to f32 tolerance (dproj is bit-identical;
# tests/test_pallas_lstm.py pins both).


def _seq_bwd_fused_kernel(
    dout_ref, proj_ref, hprev_ref, cprev_ref, cs_ref, wh_ref, dcT_ref, burn_ref,
    dz_ref, dwh_ref, dh_s, dc_s, dwh_s,
):
    H = dh_s.shape[-1]
    t = pl.program_id(0)
    t_real = pl.num_programs(0) - 1 - t

    @pl.when(t == 0)
    def _():
        dh_s[:] = jnp.zeros_like(dh_s)
        dc_s[:] = dcT_ref[:]
        dwh_s[:] = jnp.zeros_like(dwh_s)

    burn = burn_ref[:]  # (B, 1) int32 per-row seam
    keep = t_real >= burn
    carry_keep = t_real > burn

    wh = wh_ref[:]
    z = proj_ref[0].astype(jnp.float32) + jnp.dot(
        hprev_ref[0].astype(wh.dtype), wh, preferred_element_type=jnp.float32
    )
    i, f, g, o = _split_gates(z, H)
    tanh_c = jnp.tanh(cs_ref[0])

    dh = jnp.where(keep, dout_ref[0].astype(jnp.float32), 0.0) + dh_s[:]
    do = dh * tanh_c
    dc = dh * o * (1.0 - tanh_c * tanh_c) + dc_s[:]
    di = dc * g
    df = dc * cprev_ref[0]
    dg = dc * i
    dz = jnp.concatenate(
        [
            di * i * (1.0 - i),
            df * f * (1.0 - f),
            dg * (1.0 - g * g),
            do * o * (1.0 - o),
        ],
        axis=-1,
    )
    dz_ref[0] = dz.astype(dz_ref.dtype)
    # dz is exactly zero below the seam, so burn-in steps add nothing here
    dwh_s[:] += jnp.dot(
        hprev_ref[0].astype(jnp.float32).T, dz, preferred_element_type=jnp.float32
    )
    dwh_ref[:] = dwh_s[:]
    dh_s[:] = jnp.where(
        carry_keep,
        jnp.dot(dz.astype(wh.dtype), wh.T, preferred_element_type=jnp.float32),
        0.0,
    )
    dc_s[:] = jnp.where(carry_keep, dc * f, 0.0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _lstm_seq_bwd_fused_call(
    dout, proj_t, hprev, cprev, cs, wh, dcT, burn, *, interpret: bool
):
    T, B, H = cs.shape
    rev3 = lambda t: (T - 1 - t, 0, 0)
    pinned = lambda t: (0, 0)
    params = _compiler_params(
        "lstm sequence backward kernel (fused_dwh arm)",
        *_seq_bwd_vmem_spec(True, B, H, dout.dtype, proj_t.dtype, hprev.dtype,
                            wh.dtype),
        B, H, wh.dtype, interpret,
    )
    dz, dwh = pl.pallas_call(
        _seq_bwd_fused_kernel,
        name="_lstm_seq_bwd_fused_call",
        grid=(T,),
        compiler_params=params,
        in_specs=[
            pl.BlockSpec((1, B, H), rev3, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, B, 4 * H), rev3, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, B, H), rev3, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, B, H), rev3, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, B, H), rev3, memory_space=pltpu.VMEM),
            pl.BlockSpec((H, 4 * H), pinned, memory_space=pltpu.VMEM),
            pl.BlockSpec((B, H), pinned, memory_space=pltpu.VMEM),
            pl.BlockSpec((B, 1), pinned, memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, B, 4 * H), rev3, memory_space=pltpu.VMEM),
            pl.BlockSpec((H, 4 * H), pinned, memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, 4 * H), proj_t.dtype),
            jax.ShapeDtypeStruct((H, 4 * H), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((B, H), jnp.float32),
            pltpu.VMEM((B, H), jnp.float32),
            pltpu.VMEM((H, 4 * H), jnp.float32),
        ],
        interpret=interpret,
    )(dout, proj_t, hprev, cprev, cs, wh, dcT, burn)
    return dz, dwh


@jax.custom_vjp
def lstm_seq_unroll_fused_dwh(
    proj_t: jnp.ndarray,   # (T, B, 4H) time-major input projections x@Wi+b
    wh: jnp.ndarray,       # (H, 4H) recurrent weights
    h0: jnp.ndarray,       # (B, H)
    c0: jnp.ndarray,       # (B, H)
    burn_in: jnp.ndarray,  # (B,) int32 per-row stop-gradient seam position
) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """:func:`lstm_seq_unroll` with the fused-dWh backward arm
    (config.seq_fused_dwh). Forward values and residuals are identical to
    the default arm; only the backward kernel differs."""
    outs, cs = _lstm_fwd_call(proj_t, wh, h0, c0, interpret=_interpret())
    return outs, (outs[-1].astype(jnp.float32), cs[-1])


def _seq_fused_vjp_fwd(proj_t, wh, h0, c0, burn_in):
    outs, cs = _lstm_fwd_call(proj_t, wh, h0, c0, interpret=_interpret())
    out = (outs, (outs[-1].astype(jnp.float32), cs[-1]))
    return out, (proj_t, wh, h0, c0, burn_in, outs, cs)


def _seq_fused_vjp_bwd(res, grads):
    proj_t, wh, h0, c0, burn_in, outs, cs = res
    douts, (dhT, dcT) = grads
    T, B, H = cs.shape
    douts = douts.astype(jnp.float32).at[-1].add(dhT.astype(jnp.float32))
    hprev = jnp.concatenate([h0.astype(outs.dtype)[None], outs[:-1]], axis=0)
    cprev = jnp.concatenate([c0.astype(jnp.float32)[None], cs[:-1]], axis=0)
    burn = burn_in.astype(jnp.int32).reshape(B, 1)
    dz, dwh = _lstm_seq_bwd_fused_call(
        douts, proj_t, hprev, cprev, cs, wh, dcT.astype(jnp.float32), burn,
        interpret=_interpret(),
    )
    dburn = np.zeros(burn_in.shape, dtype=jax.dtypes.float0)
    return (
        dz.astype(proj_t.dtype),
        dwh.astype(wh.dtype),
        jnp.zeros_like(h0),
        jnp.zeros_like(c0),
        dburn,
    )


lstm_seq_unroll_fused_dwh.defvjp(_seq_fused_vjp_fwd, _seq_fused_vjp_bwd)


# --------------------------------------------------------------------------
# backward arm (b): gradient-checkpointed backward — residuals shrink from
# O(T*B*H) to O((T/S)*B*H); the kernel recomputes each S-segment's gates
# from its checkpointed (h, c) carry before walking it in reverse
# --------------------------------------------------------------------------
#
# The VJP saves only the (h, c) carries ENTERING every S-step segment
# (N = T/S checkpoints each (B, H)) plus the op inputs. The backward kernel
# runs one grid step per segment, newest segment first:
#
#   1. forward-recompute the segment's h/c sequence into VMEM scratch from
#      the checkpoint (S gate matmuls),
#   2. walk the segment in reverse exactly like the default backward kernel
#      — same seam masks on the real timestep index, so a seam landing
#      INSIDE a recomputed segment behaves identically to the default arm —
#      accumulating dWh in scratch (the h sequence never exists in HBM for
#      an outside matmul to read, so this arm fuses dWh by construction),
#   3. carry (dh, dc) in scratch across segment boundaries.
#
# fp32 parity is bitwise for dproj (the recompute replays the forward's own
# f32 ops), and summation-order tolerance for dWh. Under bf16 the recompute
# matches the default arm's rounding: h is stored f32 in scratch but every
# consumer casts through the compute dtype, exactly the round-trip the
# default arm's bf16 `outs` residual applies.


def _seq_bwd_ckpt_kernel(
    dout_ref, proj_ref, hin_ref, cin_ref, wh_ref, dcT_ref, burn_ref,
    dz_ref, dwh_ref, hs_s, cs_s, dh_s, dc_s, dwh_s, *, S: int,
):
    H = dh_s.shape[-1]
    k = pl.program_id(0)
    seg_real = pl.num_programs(0) - 1 - k  # real segment index (oldest = 0)
    base = seg_real * S                    # real t of the segment's step 0

    @pl.when(k == 0)
    def _():
        dh_s[:] = jnp.zeros_like(dh_s)
        dc_s[:] = dcT_ref[:]
        dwh_s[:] = jnp.zeros_like(dwh_s)

    burn = burn_ref[:]  # (B, 1) int32 per-row seam
    wh = wh_ref[:]

    # ---- 1. forward recompute from the segment checkpoint
    hs_s[0] = hin_ref[0].astype(jnp.float32)
    cs_s[0] = cin_ref[0]

    def fwd_body(s, _):
        h_lo = hs_s[s].astype(wh.dtype)
        z = proj_ref[s].astype(jnp.float32) + jnp.dot(
            h_lo, wh, preferred_element_type=jnp.float32
        )
        i, f, g, o = _split_gates(z, H)
        c_new = f * cs_s[s] + i * g
        hs_s[s + 1] = o * jnp.tanh(c_new)
        cs_s[s + 1] = c_new
        return 0

    jax.lax.fori_loop(0, S, fwd_body, 0)

    # ---- 2. reverse walk with the seam masks on the REAL timestep
    def bwd_body(s_rev, _):
        s = S - 1 - s_rev
        t_real = base + s
        keep = t_real >= burn
        carry_keep = t_real > burn
        h_lo = hs_s[s].astype(wh.dtype)
        z = proj_ref[s].astype(jnp.float32) + jnp.dot(
            h_lo, wh, preferred_element_type=jnp.float32
        )
        i, f, g, o = _split_gates(z, H)
        tanh_c = jnp.tanh(cs_s[s + 1])
        dh = jnp.where(keep, dout_ref[s].astype(jnp.float32), 0.0) + dh_s[:]
        do = dh * tanh_c
        dc = dh * o * (1.0 - tanh_c * tanh_c) + dc_s[:]
        di = dc * g
        df = dc * cs_s[s]
        dg = dc * i
        dz = jnp.concatenate(
            [
                di * i * (1.0 - i),
                df * f * (1.0 - f),
                dg * (1.0 - g * g),
                do * o * (1.0 - o),
            ],
            axis=-1,
        )
        dz_ref[s] = dz.astype(dz_ref.dtype)
        dwh_s[:] += jnp.dot(
            h_lo.astype(jnp.float32).T, dz, preferred_element_type=jnp.float32
        )
        dh_s[:] = jnp.where(
            carry_keep,
            jnp.dot(dz.astype(wh.dtype), wh.T, preferred_element_type=jnp.float32),
            0.0,
        )
        dc_s[:] = jnp.where(carry_keep, dc * f, 0.0)
        return 0

    jax.lax.fori_loop(0, S, bwd_body, 0)
    dwh_ref[:] = dwh_s[:]


def _ckpt_bwd_vmem_spec(S, B, H, dout_dtype, proj_dtype, h_dtype, wh_dtype):
    """(blocks, scratch) of the checkpointed backward call for segment
    length S — whole S-step segments are VMEM blocks, so this is the arm
    whose need grows with the stride (choose_backward_arm reads it)."""
    f32 = jnp.float32
    blocks = [
        ((S, B, H), dout_dtype), ((S, B, 4 * H), proj_dtype),
        ((1, B, H), h_dtype), ((1, B, H), f32),
        ((H, 4 * H), wh_dtype), ((B, H), f32), ((B, 128), jnp.int32),
        ((S, B, 4 * H), proj_dtype), ((H, 4 * H), f32),
    ]
    scratch = [((S + 1, B, H), f32)] * 2 + [((B, H), f32)] * 2 + [((H, 4 * H), f32)]
    return blocks, scratch


@functools.partial(jax.jit, static_argnames=("S", "interpret"))
def _lstm_seq_bwd_ckpt_call(
    dout, proj_t, h_ckpt, c_ckpt, wh, dcT, burn, *, S: int, interpret: bool
):
    T, B, H = dout.shape
    N = T // S
    revseg3 = lambda k: (N - 1 - k, 0, 0)
    pinned = lambda k: (0, 0)
    params = _compiler_params(
        f"lstm sequence backward kernel (ckpt arm, segment {S})",
        *_ckpt_bwd_vmem_spec(S, B, H, dout.dtype, proj_t.dtype, h_ckpt.dtype,
                             wh.dtype),
        B, H, wh.dtype, interpret,
    )
    dz, dwh = pl.pallas_call(
        functools.partial(_seq_bwd_ckpt_kernel, S=S),
        name="_lstm_seq_bwd_ckpt_call",
        grid=(N,),
        compiler_params=params,
        in_specs=[
            pl.BlockSpec((S, B, H), revseg3, memory_space=pltpu.VMEM),
            pl.BlockSpec((S, B, 4 * H), revseg3, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, B, H), revseg3, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, B, H), revseg3, memory_space=pltpu.VMEM),
            pl.BlockSpec((H, 4 * H), pinned, memory_space=pltpu.VMEM),
            pl.BlockSpec((B, H), pinned, memory_space=pltpu.VMEM),
            pl.BlockSpec((B, 1), pinned, memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((S, B, 4 * H), revseg3, memory_space=pltpu.VMEM),
            pl.BlockSpec((H, 4 * H), pinned, memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, 4 * H), proj_t.dtype),
            jax.ShapeDtypeStruct((H, 4 * H), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((S + 1, B, H), jnp.float32),
            pltpu.VMEM((S + 1, B, H), jnp.float32),
            pltpu.VMEM((B, H), jnp.float32),
            pltpu.VMEM((B, H), jnp.float32),
            pltpu.VMEM((H, 4 * H), jnp.float32),
        ],
        interpret=interpret,
    )(dout, proj_t, h_ckpt, c_ckpt, wh, dcT, burn)
    return dz, dwh


@functools.lru_cache(maxsize=None)
def lstm_seq_unroll_ckpt(S: int):
    """Build the checkpointed-backward sequence op for segment length S.

    Returns a custom-vjp function with :func:`lstm_seq_unroll`'s signature
    and forward values (same fused forward launch), whose VJP saves only
    the N = T/S segment-boundary (h, c) carries as residuals. Requires
    T % S == 0 (config.validate enforces seq_len % seq_grad_checkpoint).
    The factory is cached so every trace of a given S reuses one function
    object (stable jit keys)."""
    if S < 1:
        raise ValueError(f"seq_grad_checkpoint segment length must be >= 1, got {S}")

    @jax.custom_vjp
    def seq_unroll_ckpt(proj_t, wh, h0, c0, burn_in):
        outs, cs = _lstm_fwd_call(proj_t, wh, h0, c0, interpret=_interpret())
        return outs, (outs[-1].astype(jnp.float32), cs[-1])

    def vjp_fwd(proj_t, wh, h0, c0, burn_in):
        T = proj_t.shape[0]
        if T % S != 0:
            raise ValueError(
                f"seq len {T} not divisible by checkpoint segment {S}"
            )
        outs, cs = _lstm_fwd_call(proj_t, wh, h0, c0, interpret=_interpret())
        out = (outs, (outs[-1].astype(jnp.float32), cs[-1]))
        # carries ENTERING segments 1..N-1 are the step-(kS-1) outputs;
        # segment 0 starts from (h0, c0). The full outs/cs arrays are NOT
        # residuals — that is the whole point of this arm.
        h_ckpt = jnp.concatenate(
            [h0.astype(outs.dtype)[None], outs[S - 1 : T - 1 : S]], axis=0
        )
        c_ckpt = jnp.concatenate(
            [c0.astype(jnp.float32)[None], cs[S - 1 : T - 1 : S]], axis=0
        )
        return out, (proj_t, wh, h0, c0, burn_in, h_ckpt, c_ckpt)

    def vjp_bwd(res, grads):
        proj_t, wh, h0, c0, burn_in, h_ckpt, c_ckpt = res
        douts, (dhT, dcT) = grads
        T, B, fourH = proj_t.shape
        douts = douts.astype(jnp.float32).at[-1].add(dhT.astype(jnp.float32))
        burn = burn_in.astype(jnp.int32).reshape(B, 1)
        dz, dwh = _lstm_seq_bwd_ckpt_call(
            douts, proj_t, h_ckpt, c_ckpt, wh, dcT.astype(jnp.float32), burn,
            S=S, interpret=_interpret(),
        )
        dburn = np.zeros(burn_in.shape, dtype=jax.dtypes.float0)
        return (
            dz.astype(proj_t.dtype),
            dwh.astype(wh.dtype),
            jnp.zeros_like(h0),
            jnp.zeros_like(c0),
            dburn,
        )

    seq_unroll_ckpt.defvjp(vjp_fwd, vjp_bwd)
    return seq_unroll_ckpt


def seq_backward_residual_bytes(T: int, B: int, H: int, proj_dtype,
                                ckpt_every: int = 0) -> dict:
    """Carry-residual HBM footprint of each backward arm, in bytes.

    The accounting the bench's `peak_residual_bytes` row reports: what the
    VJP saves ACROSS the forward/backward boundary beyond the op's own
    inputs (proj_t/wh/burn ride along under every arm — autodiff would pin
    them regardless). Default and fused-dWh arms save the full h sequence
    (outs, proj dtype) and c sequence (f32); the checkpointed arm saves
    N = T/ckpt_every boundary carries of each.
    """
    itemsize = jnp.dtype(proj_dtype).itemsize
    if ckpt_every:
        n = T // ckpt_every
        return {
            "h_residual_bytes": n * B * H * itemsize,
            "c_residual_bytes": n * B * H * 4,
            "carry_residual_bytes": n * B * H * (itemsize + 4),
        }
    return {
        "h_residual_bytes": T * B * H * itemsize,
        "c_residual_bytes": T * B * H * 4,
        "carry_residual_bytes": T * B * H * (itemsize + 4),
    }


def choose_backward_arm(
    T: int, B: int, H: int, proj_dtype, budget_bytes: int, mode: str = "auto",
    vmem_bytes: Optional[int] = None,
) -> Tuple[str, int]:
    """Pick the sequence backward arm from a peak-residual-bytes budget.

    Returns (arm, ckpt_stride) with arm in {"default", "fused_dwh",
    "ckpt"} and ckpt_stride the checkpoint segment length S (0 unless
    arm == "ckpt"). Peak = the carry residuals above + the dz
    pre-activation-grad array the backward materializes: full float32
    (T, B, 4H) under the default arm (dz feeds the outside dWh matmul in
    f32), proj-dtype under the fused/ckpt arms (dz only feeds dproj once
    dWh is accumulated in-kernel). This is exactly the accounting
    bench.py's `backward_arms` rows report as peak_residual_bytes.

    mode="auto" walks the arms cheapest-recompute-first: default, then
    fused_dwh, then ckpt with the SMALLEST divisor stride S >= 2 of T
    whose peak fits (least recompute within budget; larger S means fewer
    checkpoints but whole-segment gate recompute). When no stride fits,
    the largest divisor (minimum possible residual) is used — the budget
    is a selection dial, not a hard allocator. mode="fused_dwh"/"ckpt"/
    "default" force that arm (ckpt still auto-picks S).

    `vmem_bytes` (the device's VMEM, config.resolve_backward_arm passes
    it on a TPU) is the hard side: the HBM budget alone once walked B=256
    fp32 to ("ckpt", 85) — one segment, the whole sequence as VMEM
    blocks. With it, a ckpt stride whose blocks cannot fit is never a
    candidate, `auto` steps past an arm that cannot fit, and when nothing
    fits the error says so here rather than inside the compiler. None
    (the interpreter: no VMEM) skips the check."""
    itemsize = jnp.dtype(proj_dtype).itemsize
    f32 = jnp.float32
    dz_f32 = T * B * 4 * H * 4
    dz_proj = T * B * 4 * H * itemsize
    carry_full = seq_backward_residual_bytes(T, B, H, proj_dtype)[
        "carry_residual_bytes"
    ]
    # the calls' own block lists at this shape (dout arrives f32; proj, h
    # and wh share the compute dtype)
    dtypes = (f32, proj_dtype, proj_dtype, proj_dtype)

    def fits(blocks, scratch) -> bool:
        return vmem_bytes is None or (
            kernel_vmem_bytes(blocks, scratch, B, H, proj_dtype) <= vmem_bytes
        )

    def ckpt_stride() -> int:
        divisors = [
            s for s in range(2, T + 1)
            if T % s == 0
            and fits(*_ckpt_bwd_vmem_spec(s, B, H, *dtypes))
        ]
        if not divisors and T > 1:
            raise ValueError(
                f"no checkpoint segment of T={T} fits {vmem_bytes >> 20} MiB "
                f"of VMEM at B={B}, H={H}: shard the batch (dp)"
            )
        for s in divisors:
            peak = (
                seq_backward_residual_bytes(T, B, H, proj_dtype, s)[
                    "carry_residual_bytes"
                ]
                + dz_proj
            )
            if peak <= budget_bytes:
                return s
        return divisors[-1] if divisors else T

    if mode == "default":
        return ("default", 0)
    if mode == "fused_dwh":
        return ("fused_dwh", 0)
    if mode == "ckpt":
        return ("ckpt", ckpt_stride())
    if mode != "auto":
        raise ValueError(f"unknown backward-arm mode {mode!r}")
    if carry_full + dz_f32 <= budget_bytes and fits(
        *_seq_bwd_vmem_spec(False, B, H, *dtypes)
    ):
        return ("default", 0)
    if carry_full + dz_proj <= budget_bytes and fits(
        *_seq_bwd_vmem_spec(True, B, H, *dtypes)
    ):
        return ("fused_dwh", 0)
    return ("ckpt", ckpt_stride())
