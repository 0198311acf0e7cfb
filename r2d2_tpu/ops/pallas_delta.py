"""Pallas chunk solve of the gated delta rule — the triangles in the lanes.

A chunk of the gated delta rule (models/hybrid_stack.py,
`delta_rule_chunked`) solves `(I + L) X = rhs` for a strictly lower-triangular
`L (Q, Q)` a value head and chunk: 2,560 independent triangles of 64 x 64 a
layer and pass at the qwen3-next cell's shape. XLA's `solve_triangular` made
one `InvertDiagBlocksLowerTriangular` call of 12.9 ms of them, ~60x over its
bytes: 5 us a triangle, each dependent row step a trip through HBM on arrays
whose minor axis of 64 pads to 128 lanes (PERF.md finding 57). What is
sequential is small, the inverse `T = (I + L)^-1` (16 KB a triangle, 42 MB a
pass); what is large, `T rhs` over 256 columns, is one batched matmul. So
this kernel computes `T` and nothing else:

- the triangles are the MINOR axis: `L` arrives as `(Q, Q, N)` and a program
  holds a `(Q, Q, 128)` block in VMEM, one triangle a lane, all 128 advancing
  together. Row i of every triangle is `T[i] = e_i - sum_{k < i} L[i, k]
  T[k]`: `T[k]` is whole `(8, 128)` vregs, `L[i, k]` one sublane broadcast
  over them, and the rows already solved never leave VMEM. No lane is
  wasted on a triangle's zeros beyond its own row block (`T[i, j] = 0` for j
  > i: row i reads and writes the first `i // 8 + 1` vregs of a row), and no
  array with a minor axis of 64 crosses the kernel's edge;
- grid `(N / 128,)`, "parallel": no carry between programs; at Q = 64 a
  block is 1 MiB in and 1 MiB out, the pipeline's four buffers 4 MiB;
- the arithmetic is forward substitution itself, float32 multiply and
  subtract on the VPU in row order: what `solve_triangular` does, safe on an
  agent's keys (`k_i . k_j` near 1, L near `beta` times all ones), where the
  series `(I - L)(I + L^2)(I + L^4)...` loses every digit (finding 56.3).

`unit_lower_solve(L, rhs)` is the differentiable op: `T` from the kernel,
`X = T rhs` at "highest", and in the backward pass `rhs_bar = T^T X_bar`,
`L_bar = -strict(rhs_bar X^T)` from the SAVED `T` (42 MB a layer, alive
only inside the layer's rematerialised backward): the backward pass runs no
kernel and no solve at all.

Off the chip the kernel runs under the Pallas interpreter
(`pallas_lstm._interpret`): how the CPU tests pin it against
`solve_triangular`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from r2d2_tpu.ops import pallas_lstm

LANES = 128    # triangles a program: one a lane
SUBLANES = 8   # f32 rows a vreg: a row of T is Q / 8 of them
MAX_Q = 128    # the pipeline's four (Q, Q, 128) f32 blocks are 32 MiB there: a quarter of the v5e's VMEM
F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


def kernel_fits(triangles: int, Q: int) -> bool:
    """The kernel's own shape test: whole lanes of triangles, whole vregs a
    row, blocks that fit VMEM. `triangles` is every axis of L before its last
    two, multiplied."""
    return triangles > 0 and triangles % LANES == 0 and 0 < Q <= MAX_Q and Q % SUBLANES == 0


def _kernel(l_ref, t_ref):
    Q = l_ref.shape[0]
    for block in range(Q // SUBLANES):                 # static: row i's width is its block's
        width = SUBLANES * (block + 1)
        column = jax.lax.broadcasted_iota(jnp.int32, (width, LANES), 0)

        def row(i, _):
            def minus(k, acc):
                return acc - l_ref[i, pl.ds(k, 1), :] * t_ref[k, :width, :]

            t_ref[i, :width, :] = jax.lax.fori_loop(0, i, minus, (column == i).astype(F32))
            if width < Q:
                t_ref[i, width:, :] = jnp.zeros((Q - width, LANES), F32)
            return 0

        jax.lax.fori_loop(SUBLANES * block, width, row, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gdn_inverse_call(lt, *, interpret: bool):
    Q, _, N = lt.shape
    if not kernel_fits(N, Q):
        raise ValueError(f"_gdn_inverse_call: N={N} triangles of Q={Q} do not fit the kernel (kernel_fits)")
    block = pl.BlockSpec((Q, Q, LANES), lambda t: (0, 0, t), memory_space=pltpu.VMEM)
    # in and out, each double-buffered; the default scoped limit (16 MiB) holds Q = 64's 4 MiB
    vmem = max(4 * pallas_lstm._nbytes((Q, Q, LANES), F32) + (4 << 20), 16 << 20)
    return pl.pallas_call(
        _kernel,
        name="_gdn_inverse_call",
        grid=(N // LANES,),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=vmem),
        in_specs=[block],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((Q, Q, N), F32),
        interpret=interpret,
    )(lt.astype(F32))


def unit_lower_inverse(L):
    """`(I + L)^-1` for L (.., Q, Q) strictly lower triangular (what lies on
    and above the diagonal is not read), float32, by the kernel. No gradient
    of its own: `unit_lower_solve` is the differentiable op."""
    Q = L.shape[-1]
    lt = jnp.moveaxis(L.reshape(-1, Q, Q), 0, 2)                      # (Q, Q, N): triangles minor
    return jnp.moveaxis(_gdn_inverse_call(lt, interpret=pallas_lstm._interpret()), 2, 0).reshape(L.shape)


def _highest(a, b):
    return jnp.einsum("...ij,...jk->...ik", a, b, precision=_HIGHEST, preferred_element_type=F32)


@jax.custom_vjp
def unit_lower_solve(L, rhs):
    """`(I + L)^-1 rhs` for L (.., Q, Q) strictly lower triangular and rhs
    (.., Q, m), float32, differentiable in both: `hybrid_stack.unit_lower_solve`
    with the inverse by the kernel."""
    return _vjp_fwd(L, rhs)[0]


def _vjp_fwd(L, rhs):
    T = unit_lower_inverse(L)
    X = _highest(T, rhs)
    return X, (T, X)


def _vjp_bwd(res, x_bar):
    T, X = res
    rhs_bar = _highest(jnp.swapaxes(T, -1, -2), x_bar.astype(F32))
    i = jnp.arange(T.shape[-1])
    L_bar = jnp.where(i[:, None] > i[None, :], -_highest(rhs_bar, jnp.swapaxes(X, -1, -2)), 0.0)
    return L_bar, rhs_bar


unit_lower_solve.defvjp(_vjp_fwd, _vjp_bwd)
