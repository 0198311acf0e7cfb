"""A core that is a pattern of residual layers: state-space mixers, routed
expert mixtures held as one chip's share, and attention over the episode.

`recurrent_core="hybrid_stack"` puts a stack of pre-norm residual layers in
the core slot, `x <- x + mixer(RMSNorm(x))`, one mixer for each letter of
`core_config["hybrid_override_pattern"]`, between an input projection
`(latent + A + 1) -> hidden` (it stands where a language model has its token
embedding) and a final RMSNorm. The widths come from `config.core_config`
under the names a published `nemotron_h` config gives them (`StackSpec`):

- `M`, a Mamba-2 mixer (Dao & Gu 2024, "Transformers are SSMs"): `[z | xBC |
  dt] = in_proj(u)`; a causal depthwise convolution and silu over `xBC`, whose
  last `conv_kernel - 1` inputs are state; per head the recurrence `h_t = a_t
  h_{t-1} + dt_t x_t (x) B_t`, `a_t = exp(-exp(A_log) dt_t)`, `y_t = h_t C_t +
  D x_t`; a grouped RMSNorm of `y silu(z)`; `out_proj`. `unroll` runs the
  recurrence in its chunked form (matmuls inside a chunk of `chunk_size`
  steps, a scan over the chunks' states, the sequence padded to whole chunks
  with `dt = 0`, which leaves the state as it is); `step` is the recurrence.
- `E`, a routed mixture held as a SHARE: sigmoid scores over all
  `n_routed_experts`, the top `num_experts_per_tok` of score + correction
  bias, weights normalised and scaled, experts `W_down relu(W_up x)^2`, and a
  shared expert for every token. This chip holds experts
  `[first_expert_held, first_expert_held + num_experts_held)`: it routes over
  all of them, computes its own, and leaves out what the others would add (no
  code stands in for the absent chips or their exchange). The device work is
  STATIC: each held expert computes exactly `C = capacity(tokens)` rows, an
  assignment beyond an expert's `C` in flattened `(b, t)` order is dropped
  and counted, and no shape, loop bound or branch depends on the routed load.
- `*`, grouped-query attention without a positional encoding (position comes
  from the mixers). The carry holds the keys and values, after projection, of
  the last `config.max_episode_steps` positions as a ring, and a count;
  `unroll`'s T queries see the valid part of that memory and their own
  sequence causally. No episode is longer than the ring, so every position
  attends to its whole episode and nothing is ever truncated.

THE CARRY is one flat float32 vector a row, `state_shape(cfg) = (1, S)`
(models/core.py: the rule's `n = 1`): every `M` layer's state and convolution
tail, every `*` layer's keys and values, and the count as two numbers below
256 (so a bfloat16 store holds it exactly), padded to whole 128-lanes. Zero
is the episode start. The class splits and joins it; stores, accumulator,
gather and `batch["hidden"]` see an array like any other. Its statements for
the seam: `cuts_at_burn_in = False` (as the LRU: burn-in is backpropagated
through), `keeps_window_starts = True` (a row's state is megabytes: the
collector keeps the carry at a block's static window starts alone,
collect.py), and `open_carry` / `close_carry` / `step_open`: the OPENED form
is the tuple of the `segments()` parts, float32 (`split_state` opens,
`join_state` closes), and the layers are written once, over parts (`_layers`).
`step` and `unroll` split the flat row, run the layers and join; the
collector's scan carries the parts from one env step to the next, each a
buffer of its own that its layer updates in place, and joins where a state is
stored. Only that scan sees the opened form. What the mixtures count in an `unroll` is sown
(`counts_of` reads it); the loss hands it on with its metrics and the fused runners
publish it with a readback they already make.

Matmuls run in the compute dtype with float32 accumulation; the residual
stream, the recurrence, the norms, the softmax and the router are float32.
Each layer of `unroll` is rematerialised in the backward pass.

WHERE THE AXES LIVE in the mixer's sequence form (the chip's tiles are 8
sublanes x 128 lanes, so an axis of 8 or 64 in a big array's minor place
costs a pass to re-tile it, PERF.md finding 55): per-channel arrays stay `(B,
T, C)` with C a multiple of 128 from `in_proj`'s output to `out_proj`'s input
(z, xBC, dt and x, B, C are lane-aligned slices of it, and the grouped norm
takes its groups' statistics by a membership matmul, not by a `(.., 8, 512)`
view); inside `ssd_chunked` a chunk's 128 steps are the minor axis of every
per-head scalar and of `x dt` and y, with heads a batch axis of the einsums,
reached by one transposition in and one out. `step` (one row of 16 a call)
keeps heads and head_dim as axes: its arrays are a tile or two.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from r2d2_tpu.models.core import Carry

F32 = jnp.float32
LANES = 128
QUERY_BLOCK = 128  # queries whose scores are held at once
# what a mixture counts in one call, in the order `ExpertMixture.routed` gives them
COUNTS = ("rows_offered", "rows_dropped", "load_max", "load_mean")


@dataclasses.dataclass(frozen=True)
class StackSpec:
    """`config.core_config`, checked: the published keys by their published
    names, and below them what is this repo's own (ARCHITECTURE.md)."""

    hidden_size: int
    hybrid_override_pattern: str
    mamba_num_heads: int
    mamba_head_dim: int
    ssm_state_size: int
    n_groups: int
    conv_kernel: int
    chunk_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    n_routed_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    moe_shared_expert_intermediate_size: int
    routed_scaling_factor: float
    norm_eps: float
    time_step_min: float
    time_step_max: float
    time_step_floor: float
    # this repo's own: the share held here and the static capacity
    num_experts_held: int = 0
    first_expert_held: int = 0
    capacity_factor: float = 2.0
    # no key of core_config: the config's own, the length of the attention's memory
    max_episode_steps: int = 0

    @classmethod
    def of(cls, cfg) -> "StackSpec":
        given = dict(cfg.core_config)
        names = {f.name for f in dataclasses.fields(cls)} - {"max_episode_steps"}
        required = {f.name for f in dataclasses.fields(cls) if f.default is dataclasses.MISSING}
        if set(given) - names or required - set(given):
            raise ValueError(
                f"core_config: unknown keys {sorted(set(given) - names)}, "
                f"missing keys {sorted(required - set(given))}"
            )
        spec = cls(**given, max_episode_steps=cfg.max_episode_steps)
        spec = dataclasses.replace(spec, num_experts_held=spec.num_experts_held or spec.n_routed_experts)
        if spec.hidden_size != cfg.hidden_dim:
            raise ValueError(f"core_config hidden_size {spec.hidden_size} is not hidden_dim {cfg.hidden_dim}")
        if not spec.hybrid_override_pattern or set(spec.hybrid_override_pattern) - set("ME*"):
            raise ValueError(f"pattern {spec.hybrid_override_pattern!r}: letters M, E and *")
        if spec.mamba_num_heads % spec.n_groups or spec.num_attention_heads % spec.num_key_value_heads:
            raise ValueError("heads must divide into their groups")
        if not 0 <= spec.first_expert_held <= spec.n_routed_experts - spec.num_experts_held:
            raise ValueError("the held experts lie outside the routed ones")
        return spec

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    def capacity(self, tokens: int) -> int:
        """Rows each held expert computes for `tokens` tokens: the balanced
        share times `capacity_factor`, up to whole 128-row tiles."""
        share = self.capacity_factor * tokens * self.num_experts_per_tok / self.n_routed_experts
        return LANES * max(math.ceil(share / LANES), 1)

    def segments(self):
        """[(layer index, name, shape)] of one row's carry, in order."""
        out = []
        kv = (self.max_episode_steps, self.num_key_value_heads, self.head_dim)
        for i, kind in enumerate(self.hybrid_override_pattern):
            if kind == "M":
                out.append((i, "ssm", (self.mamba_num_heads, self.mamba_head_dim, self.ssm_state_size)))
                out.append((i, "conv", (self.conv_kernel - 1, self.conv_dim)))
            elif kind == "*":
                out += [(i, "keys", kv), (i, "values", kv)]
        return out + [(-1, "count", (2,))]

    @property
    def state_size(self) -> int:
        n = sum(math.prod(shape) for _, _, shape in self.segments())
        return LANES * math.ceil(n / LANES)


def split_state(spec: StackSpec, flat):
    """(B, S) -> {(layer, name): (B, *shape)} float32."""
    out, at = {}, 0
    for i, name, shape in spec.segments():
        n = math.prod(shape)
        out[(i, name)] = flat[:, at:at + n].reshape(flat.shape[0], *shape).astype(F32)
        at += n
    return out


def join_state(spec: StackSpec, parts):
    flat = jnp.concatenate(
        [parts[(i, name)].reshape(parts[(i, name)].shape[0], -1).astype(F32) for i, name, _ in spec.segments()],
        axis=1,
    )
    return jnp.pad(flat, ((0, 0), (0, spec.state_size - flat.shape[1])))


def _count_of(pair):
    return (pair[:, 0] * LANES + pair[:, 1]).astype(jnp.int32)


def _count_pair(count):
    return jnp.stack([count // LANES, count % LANES], axis=1).astype(F32)


def _mm(x, w, dtype):
    """x @ w in the compute dtype, accumulated and returned in float32."""
    return jnp.dot(x.astype(dtype), w.astype(dtype), preferred_element_type=F32)


def rms_norm(x, weight, eps, groups: int = 1):
    """x * rsqrt(mean(x^2) + eps) * weight over the last axis, in `groups`
    equal parts of it, float32. The groups' statistics are taken on x as it
    lies, `(.., C)`: a `(.., groups, C / groups)` view would put the groups
    where the chip's tiles have the time axis, a pass over x for every reshape."""
    x = x.astype(F32)
    if groups > 1:
        width = x.shape[-1] // groups
        member = (jnp.arange(x.shape[-1])[:, None] // width == jnp.arange(groups)[None, :]).astype(F32)
        mean = jnp.dot(x * x, member, precision=jax.lax.Precision.HIGHEST) / width       # (.., groups)
        return x * jnp.dot(jax.lax.rsqrt(mean + eps), member.T, precision=jax.lax.Precision.HIGHEST) * weight
    parts = x.reshape(*x.shape[:-1], groups, x.shape[-1] // groups)
    parts = parts * jax.lax.rsqrt(jnp.mean(parts * parts, axis=-1, keepdims=True) + eps)
    return parts.reshape(x.shape) * weight


def _dt_bias_init(lo, hi, floor):
    def init(key, shape, dtype=F32):
        dt = jnp.exp(jax.random.uniform(key, shape, dtype) * (math.log(hi) - math.log(lo)) + math.log(lo))
        dt = jnp.maximum(dt, floor)
        return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1

    return init


def _a_log_init(key, shape, dtype=F32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


# normal with variance 1 / fan-in; not truncated: at these widths the un-jitted
# initialisation spent 32 s compiling truncated normals (PERF.md finding 53)
_matrix = nn.initializers.variance_scaling(1.0, "fan_in", "normal")
_expert_matrix = nn.initializers.variance_scaling(1.0, "fan_in", "normal", in_axis=-2, out_axis=-1, batch_axis=(0,))


def running_sum(a):
    """The running sum over the last axis (a chunk's steps, in the lanes) as a
    product with a triangle, float32."""
    q = jnp.arange(a.shape[-1])
    return jnp.dot(a, (q[:, None] <= q[None, :]).astype(F32), precision=jax.lax.Precision.HIGHEST)


def ssd_chunked(x, dt, a_log, b, c, h0, chunk: int, dtype):
    """The Mamba-2 recurrence over a sequence, in chunks.

    x (B, T, H P), dt (B, T, H) after softplus, a_log (H,), b and c (B, T, G
    N), h0 (B, H, P, N), all float32 and per channel as the projection and
    the convolution leave them -> (y (B, T, H P) without the `D x` term, h_T).
    Inside a chunk of Q steps the outputs are matmuls: `y_i = sum_{j<=i} (C_i .
    B_j) exp(cum_i - cum_j) dt_j x_j + C_i . h_in exp(cum_i)` with `cum` the
    running sum of `-exp(a_log) dt`; the chunks' states follow one from the
    other by a scan. Padding has dt = 0: decay 1, no input.

    Where the axes live: a chunk's steps are the MINOR axis of every per-head
    scalar (`dt`, `cum` and each exp of it, `(B, n, G, R, Q)`) and of `x dt`
    and y (`(B, n, G, R, P, Q)`), heads are a batch axis of the einsums, and
    B and C keep their state axis minor (`(B, n, G, Q, N)`): x goes there by
    one transposition of `(Q, H P)` blocks and y comes back by one. G and R
    stay apart on the big arrays: merged into H between an elementwise pass
    and its broadcast operand they leave the broadcast a pass of its own."""
    B, T, _ = x.shape
    H, N = dt.shape[-1], h0.shape[-1]
    G, P = b.shape[-1] // N, x.shape[-1] // H
    R = H // G  # heads a group
    Q = min(chunk, T)
    pad = (-T) % Q
    n = (T + pad) // Q

    def chunks(v):
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0))) if pad else v
        return v.reshape(B, n, Q, v.shape[-1])

    dt = jnp.swapaxes(chunks(dt), 2, 3)                              # (B, n, H, Q)
    cum = running_sum(-jnp.exp(a_log)[:, None] * dt).reshape(B, n, G, R, Q)
    x = jnp.swapaxes(chunks(x), 2, 3).reshape(B, n, G, R, P, Q) * dt.reshape(B, n, G, R, 1, Q)
    b, c = (jnp.swapaxes(chunks(v.astype(dtype)).reshape(B, n, Q, G, N), 2, 3) for v in (b, c))
    # inside each chunk
    i = jnp.arange(Q)
    lower = i[:, None] >= i[None, :]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, cum[..., :, None] - cum[..., None, :], 0.0)), 0.0)
    cb = jnp.einsum("bngis,bngjs->bngij", c, b, preferred_element_type=F32)
    y = jnp.einsum("bngrpj,bngrij->bngrpi", x.astype(dtype), (cb[:, :, :, None] * decay).astype(dtype),
                   preferred_element_type=F32)
    # each chunk's own contribution to the state at its end, then the scan
    to_end = jnp.exp(cum[..., -1:] - cum)[..., None, :]              # (B, n, G, R, 1, Q)
    own = jnp.einsum("bngrpj,bngjs->nbgrps", (x * to_end).astype(dtype), b, preferred_element_type=F32)
    whole = jnp.moveaxis(jnp.exp(cum[..., -1]), 1, 0)                # (n, B, G, R)

    def across(h, inp):
        own_n, whole_n = inp
        return whole_n[..., None, None] * h + own_n, h

    h_last, h_in = jax.lax.scan(across, h0.reshape(B, G, R, P, N).astype(F32), (own, whole))
    y = y + jnp.einsum("nbgrps,bngis->bngrpi", h_in.astype(dtype), c,
                       preferred_element_type=F32) * jnp.exp(cum)[..., None, :]
    y = jnp.swapaxes(y.reshape(B, n, H * P, Q), 2, 3).reshape(B, n * Q, H * P)
    return y[:, :T], h_last.reshape(B, H, P, N)


class Mamba2Mixer(nn.Module):
    spec: StackSpec
    dtype: jnp.dtype

    def setup(self):
        s = self.spec
        D, H = s.hidden_size, s.mamba_num_heads
        self.pre_norm = self.param("pre_norm", nn.initializers.ones, (D,))
        self.in_proj = self.param("in_proj", _matrix, (D, 2 * s.d_inner + 2 * s.n_groups * s.ssm_state_size + H))
        self.conv_weight = self.param("conv_weight", _matrix, (s.conv_kernel, s.conv_dim))
        self.conv_bias = self.param("conv_bias", nn.initializers.zeros, (s.conv_dim,))
        self.a_log = self.param("A_log", _a_log_init, (H,))
        self.d_skip = self.param("D", nn.initializers.ones, (H,))
        self.dt_bias = self.param("dt_bias", _dt_bias_init(s.time_step_min, s.time_step_max, s.time_step_floor), (H,))
        self.norm = self.param("norm", nn.initializers.ones, (s.d_inner,))
        self.out_proj = self.param("out_proj", _matrix, (s.d_inner, D))

    def _project(self, x):
        s = self.spec
        zxbcdt = _mm(rms_norm(x, self.pre_norm, s.norm_eps), self.in_proj, self.dtype)
        # static slices, read by the passes that use them (no copies of the parts)
        cut = s.d_inner + s.conv_dim
        z, xbc, dt = zxbcdt[..., :s.d_inner], zxbcdt[..., s.d_inner:cut], zxbcdt[..., cut:]
        # time_step_limit (0, inf) of the published config clamps nothing
        return z, xbc, jax.nn.softplus(dt + self.dt_bias)

    def _heads(self, xbc):
        """(.., conv_dim) after the convolution -> x (.., H P), B and C (.., G N): per channel."""
        s = self.spec
        bc = s.d_inner + s.n_groups * s.ssm_state_size
        return xbc[..., :s.d_inner], xbc[..., s.d_inner:bc], xbc[..., bc:]

    def _out(self, y, xs, z):
        """y and xs (.., H P) -> out_proj of the gated grouped norm of y + D x."""
        s = self.spec
        y = (y + jnp.repeat(self.d_skip, s.mamba_head_dim) * xs) * jax.nn.silu(z)
        return _mm(rms_norm(y, self.norm, s.norm_eps, groups=s.n_groups), self.out_proj, self.dtype)

    def __call__(self, x, ssm, tail):
        """x (B, T, D), ssm (B, H, P, N), tail (B, K-1, conv_dim) -> the same three."""
        s, T = self.spec, x.shape[1]
        z, xbc, dt = self._project(x)
        seq = jnp.concatenate([tail, xbc], axis=1)
        conv = sum(self.conv_weight[k] * seq[:, k:k + T] for k in range(s.conv_kernel)) + self.conv_bias
        xs, b, c = self._heads(jax.nn.silu(conv))
        y, ssm = ssd_chunked(xs, dt, self.a_log, b, c, ssm, s.chunk_size, self.dtype)
        return x + self._out(y, xs, z), ssm, seq[:, T:]

    def step(self, x, ssm, tail):
        """One step of the recurrence itself: x (B, D)."""
        s = self.spec
        z, xbc, dt = self._project(x)
        seq = jnp.concatenate([tail, xbc[:, None]], axis=1)          # (B, K, conv_dim)
        xs, b, c = self._heads(jax.nn.silu(jnp.sum(self.conv_weight * seq, axis=1) + self.conv_bias))
        H, R = s.mamba_num_heads, s.mamba_num_heads // s.n_groups
        heads = xs.reshape(-1, H, s.mamba_head_dim)
        b, c = (jnp.repeat(v.reshape(-1, s.n_groups, s.ssm_state_size), R, axis=1) for v in (b, c))  # (B, H, N)
        a = jnp.exp(-jnp.exp(self.a_log) * dt)                       # (B, H)
        ssm = a[..., None, None] * ssm + (dt[..., None] * heads)[..., None] * b[:, :, None, :]
        y = jnp.einsum("bhpn,bhn->bhp", ssm, c).reshape(xs.shape)
        return x + self._out(y, xs, z), ssm, seq[:, 1:]


class Experts(nn.Module):
    """The held experts' two matmuls, batched over the experts."""

    spec: StackSpec
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, rows):
        s = self.spec
        up = self.param("up", _expert_matrix, (s.num_experts_held, s.hidden_size, s.moe_intermediate_size))
        down = self.param("down", _expert_matrix, (s.num_experts_held, s.moe_intermediate_size, s.hidden_size))
        h = jnp.einsum("ecd,edf->ecf", rows.astype(self.dtype), up.astype(self.dtype), preferred_element_type=F32)
        h = jnp.square(jax.nn.relu(h))
        return jnp.einsum("ecf,efd->ecd", h.astype(self.dtype), down.astype(self.dtype), preferred_element_type=F32)


class ExpertMixture(nn.Module):
    spec: StackSpec
    dtype: jnp.dtype

    def setup(self):
        s = self.spec
        D = s.hidden_size
        self.pre_norm = self.param("pre_norm", nn.initializers.ones, (D,))
        self.router = self.param("router", _matrix, (D, s.n_routed_experts))
        self.correction_bias = self.param("e_score_correction_bias", nn.initializers.zeros, (s.n_routed_experts,))
        self.experts = Experts(s, self.dtype, name="experts")
        self.shared_up = self.param("shared_up", _matrix, (D, s.moe_shared_expert_intermediate_size))
        self.shared_down = self.param("shared_down", _matrix, (s.moe_shared_expert_intermediate_size, D))

    def scores(self, x):
        """x (N, D) normalised tokens -> (sigmoid scores over ALL routed
        experts (N, E) in float32, the top `num_experts_per_tok` of score +
        correction bias (N, K))."""
        scores = jax.nn.sigmoid(jnp.dot(x, self.router, precision=jax.lax.Precision.HIGHEST))
        return scores, jax.lax.top_k(scores + self.correction_bias, self.spec.num_experts_per_tok)[1]

    def routed(self, x):
        """x (N, D) normalised tokens in (b, t) order -> (what the held
        experts add (N, D), counts (4,) in COUNTS' order)."""
        s = self.spec
        N, D = x.shape
        E, K, Eh, C = s.n_routed_experts, s.num_experts_per_tok, s.num_experts_held, s.capacity(N)
        scores, chosen = self.scores(x)                                                # (N, E), (N, K)
        weight = jnp.take_along_axis(scores, chosen, axis=1)
        weight = weight / jnp.sum(weight, axis=1, keepdims=True) * s.routed_scaling_factor
        # a token's place in each held expert's queue, in (b, t) order
        local = chosen - s.first_expert_held
        mine = local[..., None] == jnp.arange(Eh)                                      # (N, K, Eh)
        queue = jnp.cumsum(jnp.any(mine, axis=1).astype(jnp.int32), axis=0) - 1        # (N, Eh)
        place = jnp.sum(jnp.where(mine, queue[:, None, :], 0), axis=-1)                # (N, K)
        held = jnp.any(mine, axis=-1)
        kept = held & (place < C)
        slot = jnp.where(kept, local * C + place, Eh * C).reshape(-1)  # beyond the table: dropped
        token = jnp.broadcast_to(jnp.arange(N, dtype=jnp.int32)[:, None], (N, K)).reshape(-1)
        slot_token = jnp.full((Eh * C,), N, jnp.int32).at[slot].set(token, mode="drop")
        slot_weight = jnp.zeros((Eh * C,), F32).at[slot].set(weight.reshape(-1), mode="drop")
        rows = jnp.take(jnp.pad(x, ((0, 1), (0, 0))), slot_token, axis=0).reshape(Eh, C, D)
        out = self.experts(rows).reshape(Eh * C, D) * slot_weight[:, None]
        y = jnp.zeros((N + 1, D), F32).at[slot_token].add(out)[:N]
        load = jnp.sum((chosen[..., None] == jnp.arange(E)).astype(F32), axis=(0, 1))  # (E,)
        counts = jnp.stack([jnp.sum(held).astype(F32), jnp.sum(held & ~kept).astype(F32),
                            jnp.max(load), jnp.mean(load)])
        return y, jax.lax.stop_gradient(counts)

    def shared(self, x):
        h = jnp.square(jax.nn.relu(_mm(x, self.shared_up, self.dtype)))
        return _mm(h, self.shared_down, self.dtype)

    def __call__(self, x):
        """x (..., D) -> (x + held experts' part + shared expert, counts)."""
        flat = rms_norm(x, self.pre_norm, self.spec.norm_eps).reshape(-1, x.shape[-1])
        routed, counts = self.routed(flat)
        return x + (routed + self.shared(flat)).reshape(x.shape), counts


def _ring_write(memory, new, count):
    """memory (B, W, ...), new (B, T, ...): step t of `new` goes to slot
    `(count + t) % W`; a slot hit twice keeps the later step."""
    W, T = memory.shape[1], new.shape[1]
    first = (jnp.arange(W)[None, :] - count[:, None]) % W           # the first t that hits slot j
    last = first + W * ((T - 1 - first) // W)
    tail = (1,) * (memory.ndim - 2)
    taken = jnp.take_along_axis(new, jnp.clip(last, 0, T - 1).reshape(*last.shape, *tail), axis=1)
    return jnp.where((first < T).reshape(*first.shape, *tail), taken, memory)


class EpisodeAttention(nn.Module):
    spec: StackSpec
    dtype: jnp.dtype

    def setup(self):
        s = self.spec
        D = s.hidden_size
        self.pre_norm = self.param("pre_norm", nn.initializers.ones, (D,))
        self.q_proj = self.param("q_proj", _matrix, (D, s.num_attention_heads * s.head_dim))
        self.k_proj = self.param("k_proj", _matrix, (D, s.num_key_value_heads * s.head_dim))
        self.v_proj = self.param("v_proj", _matrix, (D, s.num_key_value_heads * s.head_dim))
        self.o_proj = self.param("o_proj", _matrix, (s.num_attention_heads * s.head_dim, D))

    def __call__(self, x, keys, values, count):
        """x (B, T, D); keys, values (B, W, KV, Dh) the ring; count (B,) int
        positions seen so far -> (x', keys', values')."""
        s = self.spec
        B, T, _ = x.shape
        KV, Dh, W = s.num_key_value_heads, s.head_dim, s.max_episode_steps
        R = s.num_attention_heads // KV
        h = rms_norm(x, self.pre_norm, s.norm_eps)
        q = _mm(h, self.q_proj, self.dtype).reshape(B, T, KV, R, Dh)
        k = _mm(h, self.k_proj, self.dtype).reshape(B, T, KV, Dh)
        v = _mm(h, self.v_proj, self.dtype).reshape(B, T, KV, Dh)
        all_k = jnp.concatenate([keys, k], axis=1).astype(self.dtype)             # (B, W + T, KV, Dh)
        all_v = jnp.concatenate([values, v], axis=1).astype(self.dtype)
        remembered = jnp.arange(W)[None, :] < jnp.minimum(count, W)[:, None]      # (B, W)
        Q = min(QUERY_BLOCK, T)
        pad = (-T) % Q
        blocks = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0))).reshape(B, -1, Q, KV, R, Dh)

        @jax.checkpoint
        def attend(args):
            q_block, t0 = args                                                    # (B, Q, KV, R, Dh)
            scores = jnp.einsum("bqgrd,bsgd->bgrqs", q_block.astype(self.dtype), all_k,
                                preferred_element_type=F32) / math.sqrt(Dh)
            causal = jnp.arange(T)[None, :] <= (t0 + jnp.arange(Q))[:, None]      # (Q, T)
            seen = jnp.concatenate([jnp.broadcast_to(remembered[:, None, :], (B, Q, W)),
                                    jnp.broadcast_to(causal[None], (B, Q, T))], axis=-1)
            probs = jax.nn.softmax(jnp.where(seen[:, None, None], scores, -1e30), axis=-1)
            return jnp.einsum("bgrqs,bsgd->bqgrd", probs.astype(self.dtype), all_v, preferred_element_type=F32)

        out = jax.lax.map(attend, (jnp.moveaxis(blocks, 1, 0), jnp.arange(blocks.shape[1]) * Q))
        out = jnp.moveaxis(out, 0, 1).reshape(B, T + pad, KV * R * Dh)[:, :T]
        return x + _mm(out, self.o_proj, self.dtype), _ring_write(keys, k, count), _ring_write(values, v, count)


KINDS = {"M": ("ssm", Mamba2Mixer), "E": ("moe", ExpertMixture), "*": ("attention", EpisodeAttention)}
STATE_NAMES = {"M": ("ssm", "conv"), "E": (), "*": ("keys", "values")}


def _layer(spec, dtype, kind: str, index: int):
    """One layer of `kind`, named by its kind and place; its `__call__` (a
    sequence) is recomputed in the backward pass, its `step` is not."""
    name, cls = KINDS[kind]
    return nn.remat(cls)(spec, dtype, name=f"{name}_{index}")


def _run_layer(kind: str, layer, x, state, count):
    """x (B, T, D), or (B, D) for one step; `state` the layer's parts of the
    carry -> (x', state', counts)."""
    step = x.ndim == 2
    nothing = jnp.zeros((len(COUNTS),), F32)
    if kind == "M":
        x, ssm, tail = (layer.step if step else layer)(x, *state)
        return x, (ssm, tail), nothing
    if kind == "E":
        x, counts = layer(x)
        return x, (), counts
    seq, keys, values = layer(x[:, None] if step else x, *state, count)
    return (seq[:, 0] if step else seq), (keys, values), nothing


class HybridStack(nn.Module):
    spec: StackSpec
    in_dim: int
    dtype: jnp.dtype = F32

    # the seam's statements (models/core.py)
    cuts_at_burn_in = False
    keeps_window_starts = True

    @staticmethod
    def state_shape(cfg):
        return (1, StackSpec.of(cfg).state_size)

    @classmethod
    def from_config(cls, cfg, in_dim: int, tp_size: int = 1) -> "HybridStack":
        if tp_size > 1:
            raise ValueError("the hybrid_stack core has no tensor-parallel form")
        return cls(StackSpec.of(cfg), in_dim=in_dim, dtype=jnp.dtype(cfg.resolved_compute_dtype))

    def setup(self):
        s = self.spec
        self.embed = self.param("in_proj", _matrix, (self.in_dim, s.hidden_size))
        self.layers = [_layer(s, self.dtype, kind, i) for i, kind in enumerate(s.hybrid_override_pattern)]
        self.final_norm = self.param("final_norm", nn.initializers.ones, (s.hidden_size,))

    def _layers(self, x, parts):
        """x (B, T, in_dim), or (B, in_dim) for one step; `parts` the carry as
        `split_state` gives it -> (out, parts', counts)."""
        s = self.spec
        parts = dict(parts)
        count = _count_of(parts[(-1, "count")])
        counts = jnp.zeros((len(COUNTS),), F32)
        x = _mm(x, self.embed, self.dtype)
        for i, (kind, layer) in enumerate(zip(s.hybrid_override_pattern, self.layers)):
            state = tuple(parts[(i, name)] for name in STATE_NAMES[kind])
            x, state, c = _run_layer(kind, layer, x, state, count)
            parts.update({(i, name): value for name, value in zip(STATE_NAMES[kind], state)})
            counts = counts + c
        parts[(-1, "count")] = _count_pair(count + (1 if x.ndim == 2 else x.shape[1]))
        return rms_norm(x, self.final_norm, s.norm_eps), parts, counts

    def _run(self, x, carry):
        """`_layers` on the stored form: split, run, join."""
        s = self.spec
        # the row as the gather (or the scan) hands it over, whole: left free,
        # the chip's compiler slices the STORE into the layers' parts ahead of
        # the gather and copies all of it, every update (PERF.md finding 53)
        out, parts, counts = self._layers(x, split_state(s, jax.lax.optimization_barrier(carry[0])))
        return out, (join_state(s, parts),), counts

    def __call__(self, xs, carry: Carry, burn_in=None) -> Tuple[jnp.ndarray, Carry]:
        """Unroll over (B, T, D) from carry -> ((B, T, hidden), final carry).
        `burn_in` is ignored (cuts_at_burn_in = False). The mixtures' counts
        of this call are sown as `intermediates/.../moe_counts`."""
        if self.is_initializing():
            # un-jitted initialisation wants the parameters alone: one step
            # makes them all, op by op, at a row each
            out, carry = self.step(xs[:, 0], carry)
            return jnp.broadcast_to(out[:, None], (*xs.shape[:2], out.shape[-1])), carry
        outs, carry, counts = self._run(xs, carry)
        self.sow("intermediates", "moe_counts", counts)
        return outs, carry

    def step(self, x, carry: Carry) -> Tuple[jnp.ndarray, Carry]:
        """One acting step on (B, D): the recurrence itself, a ring write."""
        out, carry, _ = self._run(x, carry)
        return out, carry

    # the opened form (models/core.py): the parts themselves, in `segments()` order

    @nn.nowrap
    def open_carry(self, carry: Carry):
        return tuple(split_state(self.spec, carry[0]).values())

    @nn.nowrap
    def close_carry(self, opened) -> Carry:
        return (join_state(self.spec, self._parts(opened)),)

    @nn.nowrap
    def _parts(self, opened):
        return {(i, name): part for (i, name, _), part in zip(self.spec.segments(), opened)}

    def step_open(self, x, opened):
        """`step` between two joins: close_carry(step_open(x, open_carry(c))[1])
        is step(x, c)[1], bit for bit."""
        out, parts, _ = self._layers(x, self._parts(opened))
        return out, tuple(parts.values())

    @staticmethod
    def counts_of(intermediates) -> dict:
        """{name of profiling.SPANS: value} from what one `unroll` sowed, with
        the two ratios a reader wants beside them; learner.make_loss_fn asks
        for it with the online unroll and hands it on with the metrics."""
        leaves = jax.tree.leaves(intermediates)
        if not leaves:
            return {}
        offered, dropped, load_max, load_mean = leaves[0]
        return {
            "moe.rows_offered": offered, "moe.rows_dropped": dropped,
            "moe.dropped_share": 100.0 * dropped / jnp.maximum(offered, 1.0),
            "moe.load_max_over_mean": load_max / jnp.maximum(load_mean, 1e-9),
        }
