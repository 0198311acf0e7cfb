"""Pallas LRU recurrence — one sequential pass over time, in either direction.

The LRU core's recurrence (models/lru.py) is diagonal and linear,

    h_t = lambda * h_{t-1} + u_t            (elementwise in C^H, f32 pairs)

so what it has to move is one read of `u` and one write of `h`, and its
arithmetic is four multiplies and four adds per element. As a
`jax.lax.associative_scan` over T = 581 the chip's compiler made ~3,000
small slice / pad / multiply / concatenate instructions of it, each level
re-reading and re-writing four f32 (B, T, H) arrays: a quarter of the
long-window cell's device time (PERF.md finding 34). This kernel walks time
ONCE instead:

- grid (H / 128, T / chunk): the lane blocks are independent, the time
  chunks of one lane block run in order ("arbitrary"), with the (B, 128) x 2
  carry in VMEM scratch across them (TPU grid iterations execute
  sequentially, scratch persists);
- per chunk a loop over its steps, the carry in registers: nothing touches
  HBM except `u` streaming in and `h` streaming out, both time-major
  (T, B, H) so that one step is whole (8, 128) tiles;
- `reverse=True` walks the same grid from the last step to the first. That
  IS the backward pass: with g_t the cotangent of h_t,
  delta_t = g_t + conj(lambda) delta_{t+1} is this recurrence over reversed
  time with (lam_re, -lam_im) and a zero initial carry (`lru_scan`'s VJP).

T need not divide: the wrapper picks a chunk that divides T where one is
near, and otherwise pads with zero inputs on the side that is processed
LAST (after the last kept step in either direction, so padding never
reaches a kept state).

Numerics: float32 throughout, no complex dtype (the module's contract); the
summation order is the sequential one, which is `LRU.step`'s.

Off the chip the kernel runs under the Pallas interpreter, as the LSTM's
do (`pallas_lstm._interpret`): how the CPU tests pin it against `LRU.step`
and the associative scan.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from r2d2_tpu.ops import pallas_lstm

LANES = 128    # the kernel's block of H: one vreg's lanes
SUBLANES = 8   # f32 rows per vreg: B is whole tiles
# one pipelined (chunk, B, 128) f32 block; two inputs and two outputs, each
# double-buffered, make 8 of them: 12 MiB beside the carry. (Wider blocks of
# H, 256 and 512 lanes, and chunks of 7 and 83 steps all ran at the pace of
# a plain copy of the same bytes on the v5e: PERF.md finding 34.)
_BLOCK_BYTES = 3 << 19
_UNROLL = 8    # steps per loop iteration: the scheduler overlaps their loads


def kernel_fits(rows: int, hidden: int) -> bool:
    """The kernel's own shape test: whole (8, 128) f32 tiles per step. Rows
    are the rows one device sees (the caller's local batch)."""
    return rows > 0 and rows % SUBLANES == 0 and hidden > 0 and hidden % LANES == 0


def chunk_len(T: int, B: int) -> int:
    """Steps per time chunk at B rows: the fewest chunks whose block stays
    under _BLOCK_BYTES, or up to four times as many where that count divides
    T (581 = 7 x 83, 85 = 5 x 17); else the fewest, with T padded up to a
    multiple."""
    cap = max(_BLOCK_BYTES // (B * LANES * 4), 1)
    fewest = -(-T // cap)
    for n in range(fewest, min(4 * fewest, T) + 1):
        if T % n == 0:
            return T // n
    return -(-T // fewest)


def _kernel(lam_re_ref, lam_im_ref, u_re_ref, u_im_ref, h0_re_ref, h0_im_ref,
            h_re_ref, h_im_ref, c_re, c_im, *, reverse: bool):
    chunk, B, _ = u_re_ref.shape

    @pl.when(pl.program_id(1) == 0)
    def _():
        c_re[:] = h0_re_ref[:]
        c_im[:] = h0_im_ref[:]

    lam_re = jnp.broadcast_to(lam_re_ref[:], (B, LANES))
    lam_im = jnp.broadcast_to(lam_im_ref[:], (B, LANES))

    def step(i, carry):
        t = chunk - 1 - i if reverse else i
        h_re, h_im = carry
        new_re = lam_re * h_re - lam_im * h_im + u_re_ref[t]
        new_im = lam_re * h_im + lam_im * h_re + u_im_ref[t]
        h_re_ref[t] = new_re
        h_im_ref[t] = new_im
        return new_re, new_im

    def group(g, carry):
        for j in range(_UNROLL):
            carry = step(g * _UNROLL + j, carry)
        return carry

    carry = jax.lax.fori_loop(0, chunk // _UNROLL, group, (c_re[:], c_im[:]))
    for i in range(chunk - chunk % _UNROLL, chunk):
        carry = step(i, carry)
    c_re[:], c_im[:] = carry


def _lru_call(name: str, reverse: bool, lam_re, lam_im, u_re, u_im, h0_re, h0_im,
              chunk: int, interpret: bool):
    T, B, H = u_re.shape
    if not kernel_fits(B, H):
        raise ValueError(f"{name}: B={B}, H={H} are not whole (8, 128) tiles")
    f32 = jnp.float32
    pad = -T % chunk
    if pad:  # zero inputs on the side that is processed last
        widths = ((pad, 0) if reverse else (0, pad), (0, 0), (0, 0))
        u_re, u_im = jnp.pad(u_re, widths), jnp.pad(u_im, widths)
    n = (T + pad) // chunk
    params = None
    if not interpret:
        block = pallas_lstm._nbytes((chunk, B, LANES), f32)
        need = 8 * block + 8 * pallas_lstm._nbytes((B, LANES), f32) + (2 << 20)
        cap = pallas_lstm.vmem_capacity_bytes()
        if need > cap:
            raise ValueError(
                f"{name}: blocks need ~{need >> 20} MiB of VMEM at B={B}, "
                f"chunk={chunk} but the device has {cap >> 20} MiB"
            )
        params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=need
        )
    along = (lambda h, t: (n - 1 - t, 0, h)) if reverse else (lambda h, t: (t, 0, h))
    seq = pl.BlockSpec((chunk, B, LANES), along, memory_space=pltpu.VMEM)
    row = pl.BlockSpec((B, LANES), lambda h, t: (0, h), memory_space=pltpu.VMEM)
    lam = pl.BlockSpec((1, LANES), lambda h, t: (0, h), memory_space=pltpu.VMEM)
    h_re, h_im = pl.pallas_call(
        functools.partial(_kernel, reverse=reverse),
        name=name,
        grid=(H // LANES, n),
        compiler_params=params,
        in_specs=[lam, lam, seq, seq, row, row],
        out_specs=[seq, seq],
        out_shape=[jax.ShapeDtypeStruct((T + pad, B, H), f32)] * 2,
        scratch_shapes=[pltpu.VMEM((B, LANES), f32)] * 2,
        interpret=interpret,
    )(
        lam_re.astype(f32).reshape(1, H), lam_im.astype(f32).reshape(1, H),
        u_re.astype(f32), u_im.astype(f32), h0_re.astype(f32), h0_im.astype(f32),
    )
    if pad:
        keep = slice(pad, None) if reverse else slice(0, T)
        h_re, h_im = h_re[keep], h_im[keep]
    return h_re, h_im


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _lru_fwd_call(lam_re, lam_im, u_re, u_im, h0_re, h0_im, *, chunk: int, interpret: bool):
    return _lru_call("_lru_fwd_call", False, lam_re, lam_im, u_re, u_im, h0_re, h0_im,
                     chunk, interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _lru_rev_call(lam_re, lam_im, u_re, u_im, h0_re, h0_im, *, chunk: int, interpret: bool):
    return _lru_call("_lru_rev_call", True, lam_re, lam_im, u_re, u_im, h0_re, h0_im,
                     chunk, interpret)


def lru_states(lam_re, lam_im, u_re, u_im, h0_re, h0_im, reverse: bool = False):
    """All T states of h_t = lambda h_{t-1} + u_t from h0, time-major:
    lam (H,), u (T, B, H), h0 (B, H) -> h (T, B, H), float32 pairs. With
    `reverse` time runs from the last step to the first (h_t from h_{t+1}).
    No gradient of its own: `lru_scan` is the differentiable op."""
    call = _lru_rev_call if reverse else _lru_fwd_call
    T, B, _ = u_re.shape
    return call(lam_re, lam_im, u_re, u_im, h0_re, h0_im,
                chunk=chunk_len(T, B), interpret=pallas_lstm._interpret())


@jax.custom_vjp
def lru_scan(lam_re, lam_im, u_re, u_im, h0_re, h0_im) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """`lru_states` forward in time, differentiable in every argument. The
    final carry is the caller's `h[-1]`: sliced outside, so its cotangent
    arrives folded into the states' by ordinary autodiff."""
    return lru_states(lam_re, lam_im, u_re, u_im, h0_re, h0_im)


def _vjp_fwd(lam_re, lam_im, u_re, u_im, h0_re, h0_im):
    h_re, h_im = lru_states(lam_re, lam_im, u_re, u_im, h0_re, h0_im)
    return (h_re, h_im), (lam_re, lam_im, h0_re, h0_im, h_re, h_im)


def _vjp_bwd(res, grads):
    lam_re, lam_im, h0_re, h0_im, h_re, h_im = res
    f32 = jnp.float32
    g_re, g_im = (g.astype(f32) for g in grads)
    zero = jnp.zeros(h_re.shape[1:], f32)
    # delta_t = g_t + conj(lambda) delta_{t+1}: the same kernel, reversed
    d_re, d_im = lru_states(lam_re, -lam_im, g_re, g_im, zero, zero, reverse=True)
    # dL/dh0 = conj(lambda) delta_1
    dh0_re = lam_re * d_re[0] + lam_im * d_im[0]
    dh0_im = lam_re * d_im[0] - lam_im * d_re[0]
    # dL/dlambda = sum over t, b of delta_t conj(h_{t-1}), with h_0 = h0: one
    # reduction over the saved states (the shift is two slices, no copy)
    p_re, p_im = h0_re.astype(f32), h0_im.astype(f32)
    dlam_re = (
        jnp.sum(d_re[1:] * h_re[:-1] + d_im[1:] * h_im[:-1], axis=(0, 1))
        + jnp.sum(d_re[0] * p_re + d_im[0] * p_im, axis=0)
    )
    dlam_im = (
        jnp.sum(d_im[1:] * h_re[:-1] - d_re[1:] * h_im[:-1], axis=(0, 1))
        + jnp.sum(d_im[0] * p_re - d_re[0] * p_im, axis=0)
    )
    return (
        dlam_re.astype(lam_re.dtype), dlam_im.astype(lam_im.dtype),
        d_re, d_im, dh0_re.astype(h0_re.dtype), dh0_im.astype(h0_im.dtype),
    )


lru_scan.defvjp(_vjp_fwd, _vjp_bwd)
