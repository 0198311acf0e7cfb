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
v5e refused the fp32 backward at B=64, H=512 ("Scoped allocation with
size 21.41M and limit 16.00M"). Every call therefore passes
`vmem_limit_bytes` sized from its own blocks (`_compiler_params`), and a
call whose blocks cannot fit the device's VMEM at all raises here, by
name, instead of inside Mosaic; a training shape that cannot fit is
refused before that, where the core is built (`require_seq_backward_fits`).
"""

from __future__ import annotations

import functools
from typing import Tuple

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
    lists. Checked against the v5e compiler's own total for the sequence
    backward at T=85, B=64, H=512 fp32: it asked 21.4 MiB where this
    gives 31."""
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
            f"but the device has {cap >> 20} MiB — shard the batch (dp)"
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


def _seq_bwd_vmem_spec(B, H, dout_dtype, proj_dtype, h_dtype, wh_dtype):
    """(blocks, scratch) of the sequence backward call — its BlockSpecs and
    scratch_shapes as (shape, dtype) for the VMEM estimate."""
    f32 = jnp.float32
    blocks = [
        ((1, B, H), dout_dtype), ((1, B, 4 * H), proj_dtype),
        ((1, B, H), h_dtype), ((1, B, H), f32), ((1, B, H), f32),
        ((H, 4 * H), wh_dtype), ((B, H), f32), ((B, 128), jnp.int32),
        ((1, B, 4 * H), f32),
    ]
    return blocks, [((B, H), f32)] * 2


def require_seq_backward_fits(T: int, B: int, H: int, dtype, vmem_bytes: int) -> None:
    """Raise, naming the shape, unless the sequence backward call (the
    larger of the unroll's two calls) fits `vmem_bytes` of VMEM at (T, B, H)
    in compute dtype `dtype`. LSTM.from_config asks it where the core is
    resolved on a TPU, so a shape the kernel cannot hold (H = 2,048 at
    B = 64: ~196 MiB of a v5e's 128) is refused there and not at the first
    trace or inside Mosaic. The blocks are per step, so T is named for the
    reader only; what the backward keeps in HBM (float32 dz of (T, B, 4H)
    beside the h and c sequences) is not budgeted here."""
    f32 = jnp.float32
    need = kernel_vmem_bytes(
        *_seq_bwd_vmem_spec(B, H, f32, dtype, dtype, dtype), B, H, dtype
    )
    if need > vmem_bytes:
        raise ValueError(
            f"the LSTM sequence kernel's backward needs ~{need >> 20} MiB of "
            f"VMEM at T={T}, B={B}, H={H} ({jnp.dtype(dtype).name}) and the "
            f"device has {vmem_bytes >> 20} MiB: shard the batch (dp) or run "
            "lstm_backend='scan'"
        )


@functools.partial(jax.jit, static_argnames=("interpret",))
def _lstm_seq_bwd_call(dout, proj_t, hprev, cprev, cs, wh, dcT, burn, *, interpret: bool):
    T, B, H = cs.shape
    rev3 = lambda t: (T - 1 - t, 0, 0)
    pinned = lambda t: (0, 0)
    params = _compiler_params(
        "lstm sequence backward kernel",
        *_seq_bwd_vmem_spec(B, H, dout.dtype, proj_t.dtype, hprev.dtype, wh.dtype),
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
