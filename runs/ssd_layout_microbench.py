"""The Mamba-2 mixer's SEQUENCE form alone, on the chip, as one update of the
nemotron cell runs one of its three `M` layers (ISSUE 55's step 0):

    python runs/ssd_layout_microbench.py                       # every form, ~6 min
    python runs/ssd_layout_microbench.py --forms parent committed --hlo-dir chiprun_out/ssd_hlo

One layer at published widths (B 8, T 581, hidden 2688, 64 heads x 64, state
128, 8 groups, chunk 128, bf16 matmuls), from the residual stream `(B, T,
2688)`, the stored state `(B, 64, 64, 128)` and the convolution's tail to a
scalar of the layer's three outputs and that scalar's gradient w.r.t. the
parameters and the input, under `jax.checkpoint` (the layer is recomputed in
the backward pass, as `nn.remat` does in the stack). K updates to a call under
`lax.scan`, every update with inputs of its own; `--reps` calls in flight.

A form names, part by part, WHERE each axis of the intermediate arrays lives;
the operations, dtypes and chunk size are the same in all of them:

  split   split     `jnp.split` of the projection and of the convolution's output
          slice     static lane-aligned slices (offsets 0, 4096, 10240; 4096, 5120)
          split_slice / slice_split  one of each: (the projection's, the convolution's)
  norm    reshape   the gated RMSNorm's 8 groups by a `(.., 8, 512)` view
          slices    eight lane-aligned slices reduced and concatenated
          indicator a `(4096, 8)` membership matmul at HIGHEST and its transpose
  ssd     heads     the chunked scan with heads and head_dim minor: `(B, n, Q,
                    G, R, P)` operands, `(B, n, Q, G, R)` scalars (the parent)
          scalars   operands as `heads`; dt, its running sum and every exp of
                    it time-minor `(B, n, H, Q)`, the decay `(B, n, H, Qi, Qj)`
          tminor    scalars time-minor AND heads a batch axis of the chunk
                    einsums with time in the lanes: `x dt` as `(B, n, H, P, Q)`
                    by one 2-D transposition of `(Q, 4096)` blocks, B and C as
                    `(B, n, G, Q, N)`, y back by one transposition
          tminor_qp as `tminor` with the operands `(B, n, H, Q, P)`: heads a
                    batch axis, head_dim (64, half a lane tile) in the lanes
  conv    parts     the convolution and silu over x's channels and over B and C's
                    apart (the default: over all of xBC, x, B and C sliced after)
  cum     tri       the running sum over Q as a product with a `(Q, Q)`
                    triangle at HIGHEST
          cumsum    `jnp.cumsum` over the lanes
  pad     operands  the chunk operands padded to whole chunks one by one
          act       ONE `jnp.pad`, of the convolution's output before x, B and C
                    are sliced from it
          conv      the convolution's `concatenate([tail, xBC])` takes the
                    padding rows too, so x, B and C come out whole chunks long
                    (dt is padded with 0: decay 1, no input)

`committed` is `hybrid_stack.Mamba2Mixer` itself on the same parameters (what
the cell runs); `parent` is PR 54's mixer, kept here so that the ranking can be
re-read. One JSON line per form: host clock around `--reps` calls in flight,
per UPDATE (a call is K of them), median of 5 rounds (never one blocking call:
PERF.md finding 34.2), and the form's largest distance from `parent` in the
three outputs and in the gradient, over their scales. A microbenchmark, not a
cell: it ranks the forms. Exits 3 without a TPU.

READINGS (TPU v5 lite, my chip runs, PR 55, 2026-10-04, calls 1, 2 and 4, which
repeat each other to 0.02 ms; ms per update of ONE layer = forward + recompute
+ backward; in brackets against `parent`):

  parent                       32.75
  one part changed:
    split_slice                33.15  (+0.41: alone the slices lose; with the
                                       other parts they win, see below)
    norm_slices                30.25  (-2.50)
    norm_indicator             29.88  (-2.86)
    ssd_scalars                30.29  (-2.45)
    ssd_tminor                 26.42  (-6.32)
    ssd_tminor_cumsum          26.99  (-5.76: the triangle product wins by 0.57)
    ssd_tminor_qp              28.95  (-3.79: head_dim in the lanes loses 2.5)
    ssd_tminor_fence_s         27.55  (-5.19: a barrier that makes S an array
    ssd_tminor_fence_sx        27.69  (-5.06   of its own loses; so for x dt)
    ssd_tminor_padconv         27.02  (-5.73: padding at the convolution loses)
  every part:
    all_slices                 24.78  (-7.96, padded at the convolution)
    all_indicator              25.11  (-7.64, padded at the convolution)
    all_slices_padops          24.36  (-8.38)
    all_indicator_padact       23.94  (-8.81: one pad of the conv's output)
    all_indicator_padops_split 23.73  (-9.02: `jnp.split` loses 0.8 here ...
    all_indicator_padops_split_slice 23.66  ... and it is the projection's)
    all_indicator_padops_slice_split 22.99  (the convolution's is a wash)
    all_indicator_padops_convparts 22.92  (-9.83: nothing)
    all_indicator_padops_fence_sx 25.21 (-7.54)
    all_indicator_padops       22.92 / 22.94  (-9.80)   <- the committed form
    committed                  22.93

What decided, besides the ranking: merged into H between an elementwise pass
and its broadcast operand, (G, R) left `broadcast f32[8,5,8,8,128,128]` (168
MB) and five `broadcast f32[8,5,64,64,128]` a layer as passes of their own
(the described compile, before any chip call): the big arrays keep G and R
apart. Each tap of the convolution reading xBC shifted in time (no
`concatenate([tail, xBC])`) compiled to three MORE bare slices (42 against 39,
20.16 GB against 18.54) and was taken out unrun. What is left un-fused in the
committed form, a layer and update: six `copy f32[8,5,4096,128]` (the two
transpositions, forward, recompute, backward), three `pad f32[8,640,4096]`
and their `slice f32[8,581,4096]`.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# the nemotron cell's mixer (benchmark/configs/nemotron-twotower-30b-a3b-ep16.json)
WIDTHS = dict(hidden_size=2688, mamba_num_heads=64, mamba_head_dim=64, ssm_state_size=128, n_groups=8,
              conv_kernel=4, chunk_size=128)
SHAPE = dict(B=8, T=581, K=4)
TINY_WIDTHS = dict(hidden_size=64, mamba_num_heads=4, mamba_head_dim=16, ssm_state_size=16, n_groups=2,
                   conv_kernel=4, chunk_size=8)
TINY_SHAPE = dict(B=2, T=21, K=2)
PARENT = dict(split="split", norm="reshape", ssd="heads", cum="cumsum", pad="operands")
FORMS = {
    "parent": PARENT,
    "split_slice": dict(PARENT, split="slice"),
    "norm_slices": dict(PARENT, norm="slices"),
    "norm_indicator": dict(PARENT, norm="indicator"),
    "ssd_scalars": dict(PARENT, ssd="scalars", cum="tri"),
    "ssd_tminor": dict(PARENT, ssd="tminor", cum="tri"),
    "ssd_tminor_cumsum": dict(PARENT, ssd="tminor", cum="cumsum"),
    "ssd_tminor_qp": dict(PARENT, ssd="tminor_qp", cum="tri"),
    "ssd_tminor_fence_s": dict(PARENT, ssd="tminor", cum="tri", fence="s"),
    "ssd_tminor_fence_sx": dict(PARENT, ssd="tminor", cum="tri", fence="sx"),
    "ssd_tminor_padconv": dict(PARENT, ssd="tminor", cum="tri", pad="conv"),
    "all_slices": dict(split="slice", norm="slices", ssd="tminor", cum="tri", pad="conv"),
    "all_indicator": dict(split="slice", norm="indicator", ssd="tminor", cum="tri", pad="conv"),
    "all_indicator_padops": dict(split="slice", norm="indicator", ssd="tminor", cum="tri", pad="operands"),
    "all_indicator_padops_split": dict(split="split", norm="indicator", ssd="tminor", cum="tri", pad="operands"),
    "all_indicator_padops_split_slice": dict(split="split_slice", norm="indicator", ssd="tminor", cum="tri",
                                             pad="operands"),
    "all_indicator_padops_slice_split": dict(split="slice_split", norm="indicator", ssd="tminor", cum="tri",
                                             pad="operands"),
    "all_indicator_padact": dict(split="slice", norm="indicator", ssd="tminor", cum="tri", pad="act"),
    "all_indicator_padops_convparts": dict(split="slice", norm="indicator", ssd="tminor", cum="tri", pad="operands",
                                           conv="parts"),
    "all_slices_padops": dict(split="slice", norm="slices", ssd="tminor", cum="tri", pad="operands"),
    "all_indicator_padops_fence_sx": dict(split="slice", norm="indicator", ssd="tminor", cum="tri", pad="operands",
                                          fence="sx"),
    "committed": None,
}


def build(tiny: bool):
    """-> (programs(name) -> (K updates under scan, one update with its outputs),
    make(normal) -> (the parameters' initialiser, the K updates' inputs), (B, T,
    K)) at the cell's widths, or at tiny ones in float32."""
    import jax
    import jax.numpy as jnp

    from r2d2_tpu.models import hybrid_stack as hs

    F32, HIGHEST = jnp.float32, jax.lax.Precision.HIGHEST
    dtype = F32 if tiny else jnp.bfloat16
    w = TINY_WIDTHS if tiny else WIDTHS
    B, T, K = (TINY_SHAPE if tiny else SHAPE).values()
    D, H, P, N, G = (w[k] for k in ("hidden_size", "mamba_num_heads", "mamba_head_dim", "ssm_state_size", "n_groups"))
    R, d_inner, taps, chunk, eps = H // G, H * P, w["conv_kernel"], w["chunk_size"], 1e-5
    conv_dim = d_inner + 2 * G * N

    def mm(x, weight):
        return jnp.dot(x.astype(dtype), weight.astype(dtype), preferred_element_type=F32)

    def norm(x, weight, groups, form):
        x = x.astype(F32)
        width = x.shape[-1] // groups
        if form == "reshape" or groups == 1:
            parts = x.reshape(*x.shape[:-1], groups, width)
            parts = parts * jax.lax.rsqrt(jnp.mean(parts * parts, axis=-1, keepdims=True) + eps)
            return parts.reshape(x.shape) * weight
        if form == "slices":
            parts = [x[..., g * width:(g + 1) * width] for g in range(groups)]
            return jnp.concatenate(
                [v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps) for v in parts], axis=-1) * weight
        member = (jnp.arange(x.shape[-1])[:, None] // width == jnp.arange(groups)[None, :]).astype(F32)
        mean = jnp.dot(x * x, member, precision=HIGHEST) / width
        return x * jnp.dot(jax.lax.rsqrt(mean + eps), member.T, precision=HIGHEST) * weight

    def running_sum(a, form):
        if form == "cumsum":
            return jnp.cumsum(a, axis=-1)
        q = jnp.arange(a.shape[-1])
        return jnp.dot(a, (q[:, None] <= q[None, :]).astype(F32), precision=HIGHEST)

    def across_chunks(own, whole, h0):
        def across(h, inp):
            own_n, whole_n = inp
            return whole_n * h + own_n, h

        return jax.lax.scan(across, h0, (own, whole))

    def ssd_heads(x, dt, a_log, b, c, h0):
        """PR 54's `ssd_chunked`: x (B, T, H, P), dt (B, T, H), b and c (B, T, G, N)."""
        Q = min(chunk, x.shape[1])
        pad = (-x.shape[1]) % Q
        if pad:
            x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2)) for v in (x, dt, b, c))
        n = x.shape[1] // Q
        x = (x * dt[..., None]).reshape(B, n, Q, G, R, P)
        cum = jnp.cumsum((-jnp.exp(a_log) * dt).reshape(B, n, Q, G, R), axis=2)
        b, c = b.reshape(B, n, Q, G, N).astype(dtype), c.reshape(B, n, Q, G, N).astype(dtype)
        i = jnp.arange(Q)
        lower = (i[:, None] >= i[None, :])[None, None, :, :, None, None]
        seg = cum[:, :, :, None] - cum[:, :, None, :]
        decay = jnp.where(lower, jnp.exp(jnp.where(lower, seg, 0.0)), 0.0)
        cb = jnp.einsum("bnigs,bnjgs->bnijg", c, b, preferred_element_type=F32)
        y = jnp.einsum("bnijgr,bnjgrp->bnigrp", (cb[..., None] * decay).astype(dtype), x.astype(dtype),
                       preferred_element_type=F32)
        to_end = jnp.exp(cum[:, :, -1:] - cum)
        own = jnp.einsum("bnjgs,bnjgrp->bngrps", b, (x * to_end[..., None]).astype(dtype), preferred_element_type=F32)
        whole = jnp.exp(cum[:, :, -1])
        h_last, h_in = across_chunks(jnp.moveaxis(own, 1, 0), jnp.moveaxis(whole, 1, 0)[..., None, None],
                                     h0.reshape(B, G, R, P, N).astype(F32))
        y = y + jnp.einsum("bnigs,nbgrps->bnigrp", c, h_in.astype(dtype),
                           preferred_element_type=F32) * jnp.exp(cum)[..., None]
        return y.reshape(B, n * Q, H, P)[:, :n * Q - pad], h_last.reshape(B, H, P, N)

    def ssd_time_minor(x, dt, a_log, b, c, h0, form, cum_form, fences=""):
        """x (B, T, H P), dt (B, T, H), b and c (B, T, G N): per-channel arrays in, per-channel y out."""
        T_ = x.shape[1]
        Q = min(chunk, T_)
        pad = (-T_) % Q
        n = (T_ + pad) // Q

        def chunks(v):
            v = jnp.pad(v, ((0, 0), (0, pad), (0, 0))) if pad else v
            return v.reshape(B, n, Q, v.shape[-1])

        dt_t = jnp.swapaxes(chunks(dt), 2, 3)                                     # (B, n, H, Q)
        # the big arrays keep G and R apart from here on: a reshape of (G, R) into H between an
        # elementwise pass and its broadcast operand leaves the broadcast a pass of its own
        cum = running_sum(-jnp.exp(a_log)[:, None] * dt_t, cum_form).reshape(B, n, G, R, Q)
        dt_t = dt_t.reshape(B, n, G, R, 1, Q)
        i = jnp.arange(Q)
        lower = i[:, None] >= i[None, :]
        decay = jnp.where(lower, jnp.exp(jnp.where(lower, cum[..., :, None] - cum[..., None, :], 0.0)), 0.0)
        to_end, from_start = jnp.exp(cum[..., -1:] - cum)[..., None, :], jnp.exp(cum)[..., None, :]
        whole = jnp.moveaxis(jnp.exp(cum[..., -1]), 1, 0)[..., None, None]          # (n, B, G, R, 1, 1)
        b_g, c_g = (jnp.swapaxes(chunks(v.astype(dtype)).reshape(B, n, Q, G, N), 2, 3) for v in (b, c))
        cb = jnp.einsum("bngis,bngjs->bngij", c_g, b_g, preferred_element_type=F32)
        fence = lambda v, which: jax.lax.optimization_barrier(v) if which in fences else v
        s = fence((cb[:, :, :, None] * decay).astype(dtype), "s")                    # (B, n, G, R, Qi, Qj)
        h0 = h0.reshape(B, G, R, P, N).astype(F32)
        if form == "scalars":     # operands as the parent has them, heads and head_dim minor
            back = lambda v: jnp.moveaxis(v[..., 0, :], -1, 2)[..., None]           # (B, n, Q, G, R, 1)
            xq = chunks(x).reshape(B, n, Q, G, R, P) * back(dt_t)
            b_q, c_q = (chunks(v.astype(dtype)).reshape(B, n, Q, G, N) for v in (b, c))
            y = jnp.einsum("bngrij,bnjgrp->bnigrp", s, xq.astype(dtype), preferred_element_type=F32)
            own = jnp.einsum("bnjgs,bnjgrp->nbgrps", b_q, (xq * back(to_end)).astype(dtype),
                             preferred_element_type=F32)
            h_last, h_in = across_chunks(own, whole, h0)
            y = y + jnp.einsum("bnigs,nbgrps->bnigrp", c_q, h_in.astype(dtype),
                               preferred_element_type=F32) * back(from_start)
            return y.reshape(B, n * Q, H * P)[:, :T_], h_last.reshape(B, H, P, N)
        if form == "tminor":      # (P, Q): time in the lanes
            xq = jnp.swapaxes(chunks(x), 2, 3).reshape(B, n, G, R, P, Q) * dt_t
            y = jnp.einsum("bngrpj,bngrij->bngrpi", fence(xq.astype(dtype), "x"), s, preferred_element_type=F32)
            own = jnp.einsum("bngrpj,bngjs->nbgrps", fence((xq * to_end).astype(dtype), "x"), b_g,
                             preferred_element_type=F32)
            h_last, h_in = across_chunks(own, whole, h0)
            y = y + jnp.einsum("nbgrps,bngis->bngrpi", h_in.astype(dtype), c_g,
                               preferred_element_type=F32) * from_start
            y = jnp.swapaxes(y.reshape(B, n, H * P, Q), 2, 3)
        else:                     # tminor_qp, (Q, P): head_dim in the lanes
            lanes = lambda v: jnp.swapaxes(v, -1, -2)                                # (.., 1, Q) -> (.., Q, 1)
            xq = jnp.swapaxes(chunks(x).reshape(B, n, Q, H, P), 2, 3).reshape(B, n, G, R, Q, P) * lanes(dt_t)
            y = jnp.einsum("bngrij,bngrjp->bngrip", s, xq.astype(dtype), preferred_element_type=F32)
            own = jnp.einsum("bngrjp,bngjs->nbgrps", (xq * lanes(to_end)).astype(dtype), b_g,
                             preferred_element_type=F32)
            h_last, h_in = across_chunks(own, whole, h0)
            y = y + jnp.einsum("nbgrps,bngis->bngrip", h_in.astype(dtype), c_g,
                               preferred_element_type=F32) * lanes(from_start)
            y = jnp.swapaxes(y.reshape(B, n, H, Q, P), 2, 3)                         # (B, n, Q, H, P)
        return y.reshape(B, n * Q, H * P)[:, :T_], h_last.reshape(B, H, P, N)

    def mixer(form, p, x, ssm, tail):
        zxbcdt = mm(norm(x, p["pre_norm"], 1, "reshape"), p["in_proj"])
        cuts = [d_inner, d_inner + conv_dim]
        if form["split"] in ("split", "split_slice"):
            z, xbc, dt = jnp.split(zxbcdt, cuts, axis=-1)
        else:
            z, xbc, dt = zxbcdt[..., :cuts[0]], zxbcdt[..., cuts[0]:cuts[1]], zxbcdt[..., cuts[1]:]
        dt = jax.nn.softplus(dt + p["dt_bias"])
        T_ = x.shape[1]
        whole_chunks = (-T_) % min(chunk, T_)
        pad = whole_chunks if form["pad"] == "conv" else 0
        seq = jnp.concatenate([tail, xbc] + ([jnp.zeros((B, pad, conv_dim), F32)] if pad else []), axis=1)

        def conv_of(lo, hi):
            return jax.nn.silu(sum(p["conv_weight"][k, lo:hi] * seq[:, k:k + T_ + pad, lo:hi] for k in range(taps))
                               + p["conv_bias"][lo:hi])

        cuts = [d_inner, d_inner + G * N]
        if form.get("conv") == "parts":   # x's channels and B C's convolved apart: no slice of the output
            xs, bc = conv_of(0, d_inner), conv_of(d_inner, conv_dim)
            b, c = bc[..., :G * N], bc[..., G * N:]
        else:
            act = conv_of(0, conv_dim)
            if form["pad"] == "act":   # ONE pad, of the convolution's output; x, B and C are slices of it
                pad = whole_chunks
                act = jnp.pad(act, ((0, 0), (0, pad), (0, 0)))
            if form["split"] in ("split", "slice_split"):
                xs, b, c = jnp.split(act, cuts, axis=-1)
            else:
                xs, b, c = act[..., :cuts[0]], act[..., cuts[0]:cuts[1]], act[..., cuts[1]:]
        if pad:
            dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        if form["ssd"] == "heads":
            lead = xs.shape[:-1]
            y, ssm = ssd_heads(xs.reshape(*lead, H, P), dt, p["A_log"], b.reshape(*lead, G, N),
                               c.reshape(*lead, G, N), ssm)
            y = (y + p["D"][:, None] * xs.reshape(*lead, H, P)).reshape(*lead, d_inner)
        else:
            y, ssm = ssd_time_minor(xs, dt, p["A_log"], b, c, ssm, form["ssd"], form["cum"], form.get("fence", ""))
            y = y + jnp.repeat(p["D"], P) * xs
        y = norm(y[:, :T_] * jax.nn.silu(z), p["norm"], G, form["norm"])
        return x + mm(y, p["out_proj"]), ssm, seq[:, T_:T_ + taps - 1]

    spec = hs.StackSpec(
        hybrid_override_pattern="M", num_attention_heads=1, num_key_value_heads=1, head_dim=1, n_routed_experts=1,
        num_experts_per_tok=1, moe_intermediate_size=1, moe_shared_expert_intermediate_size=1,
        routed_scaling_factor=1.0, norm_eps=eps, time_step_min=0.001, time_step_max=0.1, time_step_floor=1e-4, **w)
    module = hs.Mamba2Mixer(spec, dtype)

    def layer(name):
        if FORMS[name] is None:
            return lambda p, *a: module.apply({"params": p}, *a)
        return lambda p, *a: mixer(FORMS[name], p, *a)

    def scalar(name, p, x, ssm, tail):
        out, state, last = jax.checkpoint(layer(name))(p, x, ssm, tail)
        return jnp.mean(jnp.square(out)) + jnp.mean(jnp.square(state)) + jnp.mean(last)

    def programs(name):
        @jax.jit
        def updates(p, inputs):
            def one(carry, inp):
                value, (dp, dx) = jax.value_and_grad(
                    lambda p, x: scalar(name, p, x, *inp[1:]), argnums=(0, 1))(p, inp[0])
                return (carry[0] + value, jax.tree.map(jnp.add, carry[1], dp), carry[2] + dx), None

            zero = (jnp.zeros((), F32), jax.tree.map(jnp.zeros_like, p), jnp.zeros(inputs[0].shape[1:], F32))
            return jax.lax.scan(one, zero, inputs)[0]

        @jax.jit
        def one_update(p, inp):
            grads = jax.grad(lambda p, x: scalar(name, p, x, *inp[1:]), argnums=(0, 1))(p, inp[0])
            return layer(name)(p, *inp), grads

        return updates, one_update

    def make(normal):
        """`normal(*shape)` -> an array or its shape: (parameters, the K updates' inputs)."""
        init = lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((B, T, D)), jnp.zeros((B, H, P, N)),
                                   jnp.zeros((B, taps - 1, conv_dim)))["params"]
        # every update has inputs of its own, as it has a batch of its own
        return init, (normal(K, B, T, D), normal(K, B, H, P, N), normal(K, B, taps - 1, conv_dim))

    return programs, make, (B, T, K)


def describe(args) -> int:
    """Each form compiled at full size for a described (not attached) v5e: no
    time, only what the compiler wrote."""
    import re

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    chip = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    programs, make, (B, T, K) = build(tiny=False)
    init, inputs = make(lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=chip))
    params = jax.tree.map(lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=chip), jax.eval_shape(init))
    sizes = {"f32": 4, "bf16": 2, "s32": 4, "pred": 1}
    relayout = re.compile(
        r"\s*(?:ROOT )?%[\w.\-]+ = (\w+)\[([\d,]+)\]\S* (copy|reshape|pad|slice|broadcast|transpose)\(")
    for name in args.forms:
        t = time.time()
        compiled = programs(name)[0].lower(params, inputs).compile()
        text = compiled.as_text()
        if args.hlo_dir:
            os.makedirs(args.hlo_dir, exist_ok=True)
            with open(os.path.join(args.hlo_dir, f"{name}.txt"), "w") as fh:
                fh.write(text)
        fused = set(re.findall(r"calls=(%[\w.\-]+)", text))
        bare, written, inside = {}, 0, None
        for line in text.splitlines():
            head = re.match(r"\s*(?:ENTRY )?(%[\w.\-]+) \(.*\{\s*$", line)
            if head:
                inside = head.group(1)
            m = relayout.match(line)
            if m and inside not in fused and m.group(1) in sizes:
                size = sizes[m.group(1)] * math.prod(int(d) for d in m.group(2).split(","))
                if size >= 1e6:
                    key = f"{m.group(3)} {m.group(1)}[{m.group(2)}]"
                    bare[key] = bare.get(key, 0) + 1
                    written += size
        cost, memory = compiled.cost_analysis(), compiled.memory_analysis()
        print(json.dumps({
            "described": "v5e", "form": name, "parts": FORMS[name], "compile_s": round(time.time() - t, 1),
            "bare_relayouts_1mb": sum(bare.values()), "their_results_gb": round(written / 1e9, 2),
            "bytes_accessed_gb_per_update": round(cost.get("bytes accessed", 0.0) / 1e9, 2) if cost else None,
            "temp_gb": round(memory.temp_size_in_bytes / 1e9, 2), "bare": dict(sorted(bare.items())),
        }), flush=True)
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--forms", nargs="*", default=list(FORMS), choices=list(FORMS))
    p.add_argument("--reps", type=int, default=5)
    p.add_argument("--hlo-dir", default=None, help="write each form's compiled text here")
    p.add_argument("--allow-cpu", action="store_true", help="run tiny on the CPU (a smoke test, no reading)")
    p.add_argument("--describe-v5e", action="store_true",
                   help="no chip: compile each form at full size for a DESCRIBED v5e and count its bare "
                        "re-layouts of 1 MB or more and the compiler's bytes accessed (ranks nothing)")
    args = p.parse_args(argv)
    if args.describe_v5e:
        return describe(args)

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu" and not args.allow_cpu:
        print("no TPU: a microbenchmark of the chip's compiler has nothing to say here", file=sys.stderr)
        return 3
    device = jax.devices()[0].device_kind
    programs, make, (B, T, K) = build(tiny=args.allow_cpu)

    def timed(fn, *a):
        jax.block_until_ready(fn(*a))  # warm
        rounds = []
        for _ in range(5):
            t = time.perf_counter()
            for _ in range(args.reps):
                out = fn(*a)
            jax.block_until_ready(out)
            rounds.append((time.perf_counter() - t) / args.reps)
        return statistics.median(rounds) * 1e3

    def distance(got, want):
        """The largest difference over the scale (the largest entry) of each leaf, the worst leaf."""
        pairs = zip(jax.tree.leaves(got), jax.tree.leaves(want))
        return max(float(jnp.max(jnp.abs(g - v)) / jnp.maximum(jnp.max(jnp.abs(v)), 1e-30)) for g, v in pairs)

    rng = np.random.default_rng(0)
    init, inputs = make(lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32))
    inputs = (inputs[0], 0.5 * inputs[1], inputs[2])
    params = jax.jit(init)()
    # norm weights, biases and skips away from their initial 1 / 0, so that a form that forgot one is caught
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 16))
    params = jax.tree.map(lambda v: v + 0.1 * jax.random.normal(next(keys), v.shape), params)
    want, read, worst = None, {}, 0.0
    for name in args.forms:
        updates, one_update = programs(name)
        try:
            got = one_update(params, tuple(a[0] for a in inputs))
            compiled = updates.lower(params, inputs).compile()
        except Exception as e:  # a form the chip's compiler refuses is a reading too
            print(json.dumps({"device": device, "form": name, "refused": repr(e)[:300]}), flush=True)
            continue
        want = got if want is None else want
        off = [distance(g, v) for g, v in zip(got, want)]
        worst = max(worst, *off)
        if args.hlo_dir:
            os.makedirs(args.hlo_dir, exist_ok=True)
            with open(os.path.join(args.hlo_dir, f"{name}.txt"), "w") as fh:
                fh.write(compiled.as_text())
        read[name] = timed(compiled, params, inputs) / K
        memory = compiled.memory_analysis()
        print(json.dumps({
            "device": device, "form": name, "parts": FORMS[name], "rows": B, "T": T, "K": K,
            "update_ms": read[name], "temp_mb": round(memory.temp_size_in_bytes / 1e6, 1) if memory else None,
            "outputs_diff_over_scale_from_first_form": off[0], "grads_diff_over_scale_from_first_form": off[1],
        }), flush=True)
    if "parent" in read:
        print(json.dumps({"device": device, "faster_than_parent_ms": {
            f: read["parent"] - ms for f, ms in read.items() if f != "parent"}}), flush=True)
    # float32 on the CPU: the forms differ by rounding alone; bf16 matmuls on the chip by a few of their ulps
    return 0 if worst < (1e-4 if args.allow_cpu else 5e-2) else 1


if __name__ == "__main__":
    sys.exit(main())
