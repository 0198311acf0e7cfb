"""A core that is a stack of residual blocks: state-space or delta-rule mixers,
routed expert mixtures held as one chip's share or a dense MLP, and attention
over the episode's stored keys and values or its stored latents.

`recurrent_core="hybrid_stack"` puts a stack of pre-norm residual blocks in
the core slot, `x <- x + block(norm(x))`, between an input projection `(latent
+ A + 1) -> hidden` (it stands where a language model has its token
embedding) and a final norm. THREE FAMILIES of published models are read from
`config.core_config`, each under the names its own `config.json` gives its
widths; `core_config["model_type"]` says which (`spec_of`, the one place that
asks for a family by name; absent: `nemotron_h`):

- `nemotron_h` (`StackSpec`): one block for each letter of
  `hybrid_override_pattern`, `M` a Mamba-2 mixer, `E` a mixture, `*` attention.
- `qwen3_next` (`Qwen3NextSpec`): a decoder layer is TWO blocks, its mixer then
  its mixture; layer `i` of `num_hidden_layers` mixes by attention where `(i +
  1) % full_attention_interval == 0` and by the gated delta rule otherwise. Its
  norms scale by `1 + weight`.
- `kimi_linear` (`KimiLinearSpec`): a decoder layer is TWO blocks, its mixer
  then its MLP, both read from published lists: `linear_attn_config` names
  the layers (counted from 1) that mix by Kimi Delta Attention and those that
  mix by latent attention, and the first `first_k_dense_replace` layers' MLP
  is dense where the later ones' is the mixture.

What `HybridStack` asks of a spec is the same for both: `hidden_size`, `eps`
and `norm_offset`, `blocks` (the residual blocks in order, `(kind, layer
index)`), `sizes(kind)` (that block's own sizes, handed to its class: each
class reads the sizes IT uses, not a union of the families' keys), and what
`_Stack` makes of those (`segments()`, `state_size`, `capacity()`). A further
family is a spec class with those, an entry in `FAMILIES`, and a block class in
`KINDS` for whatever mechanism the seven kinds lack. The kinds:

- `M`, a Mamba-2 mixer (Dao & Gu 2024, "Transformers are SSMs"): `[z | xBC |
  dt] = in_proj(u)`; a causal depthwise convolution and silu over `xBC`, whose
  last `conv_kernel - 1` inputs are state; per head the recurrence `h_t = a_t
  h_{t-1} + dt_t x_t (x) B_t`, `a_t = exp(-exp(A_log) dt_t)`, `y_t = h_t C_t +
  D x_t`; a grouped RMSNorm of `y silu(z)`; `out_proj`. `unroll` runs the
  recurrence in its chunked form (matmuls inside a chunk of `chunk_size`
  steps, a scan over the chunks' states, the sequence padded to whole chunks
  with `dt = 0`, which leaves the state as it is); `step` is the recurrence.
- `D`, a Gated DeltaNet mixer (Yang et al. 2024, "Gated Delta Networks"): `[q |
  k | v | z] = in_proj_qkvz(u)`, `[b | a] = in_proj_ba(u)`; the convolution and
  silu over `q | k | v`; per value head a MATRIX state updated by the delta
  rule, `S <- exp(g_t) S; S <- S + k_t (beta_t (v_t - S^T k_t))^T; o_t = S^T
  q_t`: not diagonal and not a sum of outer products of the inputs, so its
  chunked form (`delta_rule_chunked`) solves a unit lower-triangular system a
  chunk and head before it scans over the chunks; `step` is the recurrence.
- `K`, a Kimi Delta Attention mixer (Kimi Team 2025, "Kimi Linear"): the delta
  rule again, with separate q, k, v projections and convolutions, two
  low-rank gates, and a forget gate PER KEY CHANNEL: `S <- Diag(exp(g_t)) S`
  scales each row of the state by its own decay. The chunk's pair terms are
  then `sum_d k_id k_jd exp(G_id - G_jd)`, which no single `k k^T` gives
  without exponentiating something positive, so `kda_chunked` (the third
  chunked matrix recurrence, beside `ssd_chunked` and `delta_rule_chunked`)
  builds them between sub-chunks by matmuls of operands decayed towards a
  shared row and inside a sub-chunk column by column; the triangular solve
  and the scan over chunks are `D`'s.
- `E`, a routed mixture held as a SHARE: scores over ALL routed experts
  (sigmoid + correction bias, scaled | a softmax, renormalised), the top k,
  experts of two matrices (`W_down relu(W_up x)^2`) or three (`W_down (silu(
  W_gate x) * W_up x)`), and a shared expert for every token (as it is | times
  the sigmoid of a learned scalar gate). This chip holds experts
  `[first_expert_held, first_expert_held + num_experts_held)`: it routes over
  all of them, computes its own, and leaves out what the others would add (no
  code stands in for the absent chips or their exchange). The device work is
  STATIC: each held expert computes exactly `C = capacity(tokens)` rows, an
  assignment beyond an expert's `C` in flattened `(b, t)` order is dropped
  and counted, and no shape, loop bound or branch depends on the routed load.
  Where the tokens are no more than `C` (an acting step's 16 rows) nothing can
  be dropped and there is no queue: each held expert computes the tokens
  themselves, and a token takes its weighted sum of them (`unqueued`).
  `kimi_linear` takes the first family's router (sigmoid + correction bias,
  scaled) with the second's experts (three matrices) and an ungated shared
  expert: options of `MixtureSizes`, no line of the class.
- `F`, a dense gated MLP, `down(silu(gate x) * up x)`, for a layer whose
  neighbours' MLPs are mixtures. No state, no counts.
- `*`, grouped-query attention over the episode. The carry holds the keys and
  values, after projection, of the last `config.max_episode_steps` positions as
  a ring, and a count; `unroll`'s T queries see the valid part of that memory
  and their own sequence causally. No episode is longer than the ring, so
  every position attends to its whole episode and nothing is ever truncated.
  `nemotron_h` has no positional encoding (position comes from the mixers);
  `qwen3_next` norms each head's query and key, rotates the first `rotary_dim`
  dimensions of both to the position the count gives (`count + t`; the ring
  holds keys already rotated), and gates the heads' outputs by a sigmoid taken
  from the second half of the query projection. With the three off the layer
  is the first family's, op for op.
- `L`, multi-head latent attention without positional encoding. The carry
  holds NOT keys and values but the latent they are projected from, `[norm(c)
  | k_pe]`, 576 numbers a position where 32 heads' keys and values would be
  10,240, as a ring of the last `config.max_episode_steps` positions. So
  `unroll` and `step` are two forms against one up-projection: a sequence
  up-projects the ring and itself to keys and values once and attends as `*`
  does; an acting step ABSORBS the key half of the up-projection into its
  query, scores and sums the stored latents themselves, and applies the
  value half after the sum: no per-head key or value over the ring.

THE CARRY is one flat float32 vector a row, `state_shape(cfg) = (1, S)`
(models/core.py: the rule's `n = 1`): every mixer's state and convolution
tail, every `*` block's keys and values, every `L` block's latents, and the count as two numbers below
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
stream, the recurrences (the delta rule's triangular inverse at "highest"),
the norms, the softmax, the router and the rotary embedding are float32.
Each block of `unroll` is rematerialised in the backward pass.

WHERE THE AXES LIVE in a mixer's sequence form (the chip's tiles are 8
sublanes x 128 lanes, so an axis of 8 or 64 in a big array's minor place
costs a pass to re-tile it, PERF.md finding 55): per-channel arrays stay `(B,
T, C)` with C a multiple of 128 from the input projection's output to
`out_proj`'s input (z, xBC, dt and x, B, C, or q, k, v, z, are lane-aligned
slices of it, and a grouped norm takes its groups' statistics by a membership
matmul, not by a `(.., 8, 512)` view); inside `ssd_chunked` a chunk's 128
steps are the minor axis of every per-head scalar and of `x dt` and y, with
heads a batch axis of the einsums, reached by one transposition in and one
out; `delta_rule_chunked` keeps its heads' 128 dimensions minor and puts a
chunk's steps minor on the per-head scalars alone, and so does `kda_chunked`,
whose gate sums are per channel and lie as the keys do. `step` (one row of 16
a call) keeps heads and head_dim as axes: its arrays are a tile or two.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from r2d2_tpu.models.core import Carry
from r2d2_tpu.ops import pallas_delta

F32 = jnp.float32
LANES = 128
QUERY_BLOCK = 128  # queries whose scores are held at once
# what a mixture counts in one call, in the order `ExpertMixture.routed` gives them
COUNTS = ("rows_offered", "rows_dropped", "load_max", "load_mean")


@dataclasses.dataclass(frozen=True)
class AttentionSizes:
    """What `EpisodeAttention` uses, whatever the family. The three options
    are off in `nemotron_h`: no norm on a head's query and key, no rotary
    embedding, no output gate."""

    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    max_episode_steps: int
    eps: float
    norm_offset: float = 0.0      # a norm scales by `norm_offset + weight`
    qk_norm: bool = False
    rotary_dim: int = 0
    rope_theta: float = 0.0
    output_gate: bool = False


@dataclasses.dataclass(frozen=True)
class MixtureSizes:
    """What `ExpertMixture` uses, whatever the family: `softmax` is the
    router (off: sigmoid + correction bias), `gated` the form of an expert
    (three matrices, `down(silu(gate x) * up x)`; off: two, `down relu(up
    x)^2`), `shared_gate` the sigmoid of a learned scalar on the shared expert."""

    hidden_size: int
    experts: int
    top_k: int
    expert_width: int
    shared_width: int
    held: int
    first_held: int
    capacity_factor: float
    eps: float
    norm_offset: float = 0.0
    scale: float = 1.0
    softmax: bool = False
    gated: bool = False
    shared_gate: bool = False

    def capacity(self, tokens: int) -> int:
        """Rows each held expert computes for `tokens` tokens: the balanced
        share times `capacity_factor`, up to whole 128-row tiles."""
        share = self.capacity_factor * tokens * self.top_k / self.experts
        return LANES * max(math.ceil(share / LANES), 1)


@dataclasses.dataclass(frozen=True)
class DeltaSizes:
    """What `GatedDeltaNet` uses."""

    hidden_size: int
    key_heads: int
    key_dim: int
    value_heads: int
    value_dim: int
    conv_kernel: int
    eps: float
    norm_offset: float
    chunk: int

    @property
    def conv_dim(self) -> int:
        return 2 * self.key_heads * self.key_dim + self.value_heads * self.value_dim


@dataclasses.dataclass(frozen=True)
class KdaSizes:
    """What `KimiDeltaAttention` uses: `heads` heads whose keys and values
    are both `head_dim` wide, the two low-rank gates through `gate_rank`."""

    hidden_size: int
    heads: int
    head_dim: int
    conv_kernel: int
    gate_rank: int
    eps: float
    chunk: int

    @property
    def width(self) -> int:
        return self.heads * self.head_dim


@dataclasses.dataclass(frozen=True)
class LatentSizes:
    """What `LatentAttention` uses: a position is remembered as `latent +
    rope_dim` numbers, whatever the heads."""

    hidden_size: int
    heads: int
    latent: int
    nope_dim: int
    rope_dim: int
    value_dim: int
    max_episode_steps: int
    eps: float

    @property
    def stored(self) -> int:
        return self.latent + self.rope_dim


@dataclasses.dataclass(frozen=True)
class MlpSizes:
    """What `DenseMlp` uses."""

    hidden_size: int
    width: int
    eps: float


class _Stack:
    """What `HybridStack` reads of a family's spec beyond `hidden_size`,
    `blocks` (the residual blocks in order, `(kind, layer index)`) and
    `sizes(kind)` (that block's own sizes), made from those two."""

    def capacity(self, tokens: int) -> int:
        return self.sizes("E").capacity(tokens)

    def segments(self):
        """[(layer index, name, shape)] of one row's carry, in order."""
        out = [(i, name, shape) for kind, i in self.blocks
               for name, shape in zip(STATE_NAMES[kind], KINDS[kind][1].state_shapes(self.sizes(kind)))]
        return out + [(-1, "count", (2,))]

    @property
    def state_size(self) -> int:
        n = sum(math.prod(shape) for _, _, shape in self.segments())
        return LANES * math.ceil(n / LANES)


def _read(cls, cfg, experts: str):
    """`cfg.core_config` as a spec of `cls`, refused if it has a key `cls`
    does not name or lacks one `cls` requires; then what every family checks:
    the width against the config's, and the held experts (default: all of
    the `experts` the family counts under that key) against the routed ones."""
    given = dict(cfg.core_config)
    names = {f.name for f in dataclasses.fields(cls)} - {"max_episode_steps"}
    required = {f.name for f in dataclasses.fields(cls) if f.default is dataclasses.MISSING}
    if set(given) - names or required - set(given):
        raise ValueError(
            f"core_config: unknown keys {sorted(set(given) - names)}, "
            f"missing keys {sorted(required - set(given))}"
        )
    spec = cls(**given, max_episode_steps=cfg.max_episode_steps)
    spec = dataclasses.replace(spec, num_experts_held=spec.num_experts_held or getattr(spec, experts))
    if spec.hidden_size != cfg.hidden_dim:
        raise ValueError(f"core_config hidden_size {spec.hidden_size} is not hidden_dim {cfg.hidden_dim}")
    if not 0 <= spec.first_expert_held <= getattr(spec, experts) - spec.num_experts_held:
        raise ValueError("the held experts lie outside the routed ones")
    return spec


@dataclasses.dataclass(frozen=True)
class StackSpec(_Stack):
    """`config.core_config` of a `nemotron_h` stack, checked: the published
    keys by their published names, and below them what is this repo's own
    (ARCHITECTURE.md)."""

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
    model_type: str = "nemotron_h"
    # no key of core_config: the config's own, the length of the attention's memory
    max_episode_steps: int = 0

    @classmethod
    def of(cls, cfg) -> "StackSpec":
        spec = _read(cls, cfg, "n_routed_experts")
        if not spec.hybrid_override_pattern or set(spec.hybrid_override_pattern) - set("ME*"):
            raise ValueError(f"pattern {spec.hybrid_override_pattern!r}: letters M, E and *")
        if spec.mamba_num_heads % spec.n_groups or spec.num_attention_heads % spec.num_key_value_heads:
            raise ValueError("heads must divide into their groups")
        return spec

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    # what the stack asks of a family's spec (`_Stack` makes the rest of these)

    norm_offset = 0.0  # a norm scales by its weight

    @property
    def eps(self) -> float:
        return self.norm_eps

    @property
    def blocks(self):
        """One residual block for each letter, at its place in the pattern."""
        return tuple((kind, i) for i, kind in enumerate(self.hybrid_override_pattern))

    def sizes(self, kind: str):
        if kind == "M":
            return self  # the mixer is this family's own: its sizes are these keys
        if kind == "*":
            return AttentionSizes(self.hidden_size, self.num_attention_heads, self.num_key_value_heads, self.head_dim,
                                  self.max_episode_steps, self.norm_eps)
        return MixtureSizes(self.hidden_size, self.n_routed_experts, self.num_experts_per_tok,
                            self.moe_intermediate_size, self.moe_shared_expert_intermediate_size,
                            self.num_experts_held, self.first_expert_held, self.capacity_factor, self.norm_eps,
                            scale=self.routed_scaling_factor)


DELTA_CHUNK = 64  # steps of the delta rule that `GatedDeltaNet` takes as one chunk: the program's own


@dataclasses.dataclass(frozen=True)
class Qwen3NextSpec(_Stack):
    """`config.core_config` of a `qwen3_next` stack, checked: that family's
    keys by their published names, and this repo's own three. A decoder layer
    is two residual blocks, the mixer then the mixture; layer `i` mixes by
    attention where `(i + 1) % full_attention_interval == 0` and by the gated
    delta rule otherwise (the family publishes no pattern string)."""

    model_type: str
    hidden_size: int
    num_hidden_layers: int
    full_attention_interval: int
    linear_num_key_heads: int
    linear_key_head_dim: int
    linear_num_value_heads: int
    linear_value_head_dim: int
    linear_conv_kernel_dim: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    partial_rotary_factor: float
    rope_theta: float
    num_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    shared_expert_intermediate_size: int
    norm_topk_prob: bool
    rms_norm_eps: float
    num_experts_held: int = 0
    first_expert_held: int = 0
    capacity_factor: float = 2.0
    max_episode_steps: int = 0

    @classmethod
    def of(cls, cfg) -> "Qwen3NextSpec":
        spec = _read(cls, cfg, "num_experts")
        if spec.num_hidden_layers < 1 or spec.full_attention_interval < 1:
            raise ValueError("num_hidden_layers and full_attention_interval: 1 or more")
        if (spec.linear_num_value_heads % spec.linear_num_key_heads
                or spec.num_attention_heads % spec.num_key_value_heads):
            raise ValueError("heads must divide into their groups")
        if int(spec.head_dim * spec.partial_rotary_factor) % 2:
            raise ValueError("the rotary part of a head is pairs of dimensions")
        if spec.norm_topk_prob is not True:
            raise ValueError("norm_topk_prob: true (the mixture divides the chosen weights by their sum)")
        return spec

    norm_offset = 1.0  # a norm scales by 1 + weight

    @property
    def eps(self) -> float:
        return self.rms_norm_eps

    @property
    def blocks(self):
        mixer = lambda i: "*" if (i + 1) % self.full_attention_interval == 0 else "D"
        return tuple(block for i in range(self.num_hidden_layers) for block in ((mixer(i), i), ("E", i)))

    def sizes(self, kind: str):
        if kind == "D":
            return DeltaSizes(self.hidden_size, self.linear_num_key_heads, self.linear_key_head_dim,
                              self.linear_num_value_heads, self.linear_value_head_dim, self.linear_conv_kernel_dim,
                              self.rms_norm_eps, self.norm_offset, DELTA_CHUNK)
        if kind == "*":
            return AttentionSizes(self.hidden_size, self.num_attention_heads, self.num_key_value_heads, self.head_dim,
                                  self.max_episode_steps, self.rms_norm_eps, norm_offset=self.norm_offset, qk_norm=True,
                                  rotary_dim=int(self.head_dim * self.partial_rotary_factor),
                                  rope_theta=self.rope_theta, output_gate=True)
        return MixtureSizes(self.hidden_size, self.num_experts, self.num_experts_per_tok, self.moe_intermediate_size,
                            self.shared_expert_intermediate_size, self.num_experts_held, self.first_expert_held,
                            self.capacity_factor, self.rms_norm_eps, norm_offset=self.norm_offset, softmax=True,
                            gated=True, shared_gate=True)


@dataclasses.dataclass(frozen=True)
class KimiLinearSpec(_Stack):
    """`config.core_config` of a `kimi_linear` stack, checked: that family's
    keys by their published names (`linear_attn_config` the published group,
    whole: its two lists count layers from 1 and may name more than
    `num_hidden_layers`), and this repo's own three. A decoder layer is two
    residual blocks: its mixer, Kimi Delta Attention where `kda_layers` lists
    it and latent attention where `full_attn_layers` does, then a dense MLP
    in the first `first_k_dense_replace` layers and the mixture after them."""

    model_type: str
    hidden_size: int
    num_hidden_layers: int
    linear_attn_config: Tuple
    num_attention_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    first_k_dense_replace: int
    intermediate_size: int
    num_experts: int
    num_experts_per_token: int
    moe_intermediate_size: int
    num_shared_experts: int
    routed_scaling_factor: float
    moe_renormalize: bool
    rms_norm_eps: float
    num_experts_held: int = 0
    first_expert_held: int = 0
    capacity_factor: float = 2.0
    max_episode_steps: int = 0

    @classmethod
    def of(cls, cfg) -> "KimiLinearSpec":
        spec = _read(cls, cfg, "num_experts")
        linear = spec.linear
        wanted = {"kda_layers", "full_attn_layers", "num_heads", "head_dim", "short_conv_kernel_size"}
        if set(linear) != wanted:
            raise ValueError(f"linear_attn_config: keys {sorted(linear)}, not {sorted(wanted)}")
        layers = range(1, spec.num_hidden_layers + 1)
        kda, full = set(linear["kda_layers"]), set(linear["full_attn_layers"])
        if spec.num_hidden_layers < 1 or kda & full or not set(layers) <= kda | full:
            raise ValueError("every layer from 1 to num_hidden_layers is in kda_layers or in full_attn_layers, not both")
        if spec.moe_renormalize is not True:
            raise ValueError("moe_renormalize: true (the mixture divides the chosen weights by their sum)")
        if not 0 <= spec.first_k_dense_replace <= spec.num_hidden_layers or spec.num_shared_experts < 1:
            raise ValueError("first_k_dense_replace: 0 to num_hidden_layers; num_shared_experts: 1 or more")
        return spec

    norm_offset = 0.0  # a norm scales by its weight

    @property
    def linear(self) -> dict:
        """`linear_attn_config`, which the config holds as pairs, as the group of keys it is."""
        return dict(self.linear_attn_config)

    @property
    def eps(self) -> float:
        return self.rms_norm_eps

    @property
    def blocks(self):
        full = set(self.linear["full_attn_layers"])
        mixer = lambda i: "L" if i + 1 in full else "K"
        mlp = lambda i: "F" if i < self.first_k_dense_replace else "E"
        return tuple(block for i in range(self.num_hidden_layers) for block in ((mixer(i), i), (mlp(i), i)))

    def sizes(self, kind: str):
        linear = self.linear
        if kind == "K":
            return KdaSizes(self.hidden_size, linear["num_heads"], linear["head_dim"], linear["short_conv_kernel_size"],
                            linear["head_dim"], self.rms_norm_eps, DELTA_CHUNK)
        if kind == "L":
            return LatentSizes(self.hidden_size, self.num_attention_heads, self.kv_lora_rank, self.qk_nope_head_dim,
                               self.qk_rope_head_dim, self.v_head_dim, self.max_episode_steps, self.rms_norm_eps)
        if kind == "F":
            return MlpSizes(self.hidden_size, self.intermediate_size, self.rms_norm_eps)
        return MixtureSizes(self.hidden_size, self.num_experts, self.num_experts_per_token, self.moe_intermediate_size,
                            self.moe_intermediate_size * self.num_shared_experts, self.num_experts_held,
                            self.first_expert_held, self.capacity_factor, self.rms_norm_eps,
                            scale=self.routed_scaling_factor, gated=True)


FAMILIES = {"nemotron_h": StackSpec, "qwen3_next": Qwen3NextSpec, "kimi_linear": KimiLinearSpec}


def spec_of(cfg):
    """The spec of the family `core_config["model_type"]` names (absent:
    `nemotron_h`): the one place that asks for a family by name."""
    family = dict(cfg.core_config).get("model_type", "nemotron_h")
    if family not in FAMILIES:
        raise ValueError(f"core_config model_type {family!r}: one of {sorted(FAMILIES)}")
    return FAMILIES[family].of(cfg)


def split_state(spec, flat):
    """(B, S) -> {(layer, name): (B, *shape)} float32."""
    out, at = {}, 0
    for i, name, shape in spec.segments():
        n = math.prod(shape)
        out[(i, name)] = flat[:, at:at + n].reshape(flat.shape[0], *shape).astype(F32)
        at += n
    return out


def join_state(spec, parts):
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


def _norm_weight(module, name: str, width: int, offset: float):
    """A norm's scale, `offset + weight` with the weight initialised so that
    the scale starts at one (`nemotron_h`: the weight itself; `qwen3_next`:
    `1 + weight` from zero)."""
    if not offset:
        return module.param(name, nn.initializers.ones, (width,))
    return offset + module.param(name, nn.initializers.zeros, (width,))


def _sizes(spec, kind: str):
    """A block's own sizes: a family's spec says them, sizes are themselves."""
    return spec.sizes(kind) if hasattr(spec, "sizes") else spec


def _dt_bias_init(lo, hi, floor):
    def init(key, shape, dtype=F32):
        dt = jnp.exp(jax.random.uniform(key, shape, dtype) * (math.log(hi) - math.log(lo)) + math.log(lo))
        dt = jnp.maximum(dt, floor)
        return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1

    return init


def _a_log_init(key, shape, dtype=F32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _a_log_init_from_zero(key, shape, dtype=F32):
    """log U(0, 16) as `qwen3_next` has it, floored away from log 0."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1e-3, 16.0))


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

    @staticmethod
    def state_shapes(s):
        return (s.mamba_num_heads, s.mamba_head_dim, s.ssm_state_size), (s.conv_kernel - 1, s.conv_dim)

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
    """The held experts' matmuls, batched over the experts: `down relu(up
    x)^2`, or gated, `down(silu(gate x) * up x)`."""

    spec: MixtureSizes
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, rows):
        s = self.spec
        into = lambda name: self.param(name, _expert_matrix, (s.held, s.hidden_size, s.expert_width)).astype(self.dtype)
        rows = rows.astype(self.dtype)
        if s.gated:
            gate = jnp.einsum("ecd,edf->ecf", rows, into("gate"), preferred_element_type=F32)
        up = into("up")
        down = self.param("down", _expert_matrix, (s.held, s.expert_width, s.hidden_size))
        h = jnp.einsum("ecd,edf->ecf", rows, up, preferred_element_type=F32)
        h = jax.nn.silu(gate) * h if s.gated else jnp.square(jax.nn.relu(h))
        return jnp.einsum("ecf,efd->ecd", h.astype(self.dtype), down.astype(self.dtype), preferred_element_type=F32)


class ExpertMixture(nn.Module):
    spec: MixtureSizes
    dtype: jnp.dtype

    @staticmethod
    def state_shapes(s):
        return ()

    def setup(self):
        s = self.sizes = _sizes(self.spec, "E")
        D = s.hidden_size
        self.pre_norm = _norm_weight(self, "pre_norm", D, s.norm_offset)
        self.router = self.param("router", _matrix, (D, s.experts))
        if not s.softmax:
            self.correction_bias = self.param("e_score_correction_bias", nn.initializers.zeros, (s.experts,))
        self.experts = Experts(s, self.dtype, name="experts")
        if s.gated:
            self.shared_gate = self.param("shared_gate", _matrix, (D, s.shared_width))
        self.shared_up = self.param("shared_up", _matrix, (D, s.shared_width))
        self.shared_down = self.param("shared_down", _matrix, (s.shared_width, D))
        if s.shared_gate:
            self.shared_expert_gate = self.param("shared_expert_gate", _matrix, (D, 1))

    def scores(self, x):
        """x (N, D) normalised tokens -> (scores over ALL routed experts (N,
        E) in float32, the top `top_k` (N, K)): a softmax and its top, or
        sigmoids and the top of score + correction bias."""
        logits = jnp.dot(x, self.router, precision=jax.lax.Precision.HIGHEST)
        if self.sizes.softmax:
            scores = jax.nn.softmax(logits, axis=-1)
            return scores, jax.lax.top_k(scores, self.sizes.top_k)[1]
        scores = jax.nn.sigmoid(logits)
        return scores, jax.lax.top_k(scores + self.correction_bias, self.sizes.top_k)[1]

    @nn.nowrap
    def routing(self, x):
        """x (N, D) normalised tokens -> (the chosen experts' weights (N, K),
        normalised to sum to `scale`; the chosen experts (N, K))."""
        s = self.sizes
        scores, chosen = self.scores(x)                                                # (N, E), (N, K)
        weight = jnp.take_along_axis(scores, chosen, axis=1)
        weight = weight / jnp.sum(weight, axis=1, keepdims=True)
        if s.scale != 1.0:
            weight = weight * s.scale
        return weight, chosen

    @nn.nowrap
    def queued(self, x, weight, chosen):
        """Any N: each held expert computes the first `capacity(N)` tokens
        that chose it, in (b, t) order, and the rest are dropped -> (what the
        held experts add (N, D), which choices are held (N, K), which kept)."""
        s = self.sizes
        N, D = x.shape
        K, Eh, C = s.top_k, s.held, s.capacity(N)
        # a token's place in each held expert's queue, in (b, t) order
        local = chosen - s.first_held
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
        return jnp.zeros((N + 1, D), F32).at[slot_token].add(out)[:N], held, kept

    @nn.nowrap
    def unqueued(self, x, weight, chosen):
        """N <= capacity(N): no held expert can be offered more rows than it
        computes, so a place in a queue means nothing. The held experts run on
        the N tokens themselves and token n takes `sum_e w[n, e] out[e, n]`,
        `w[n, e]` the weight it gave held expert e (0 where it did not choose
        it), the product and the sum in float32 as `queued` has them."""
        s = self.sizes
        mine = (chosen - s.first_held)[..., None] == jnp.arange(s.held)                # (N, K, Eh)
        each = jnp.sum(jnp.where(mine, weight[..., None], 0.0), axis=1)                # (N, Eh)
        out = self.experts(jnp.broadcast_to(x, (s.held, *x.shape)))                    # (Eh, N, D)
        held = jnp.any(mine, axis=-1)
        return jnp.sum(out * each.T[..., None], axis=0), held, held

    def routed(self, x):
        """x (N, D) normalised tokens in (b, t) order -> (what the held
        experts add (N, D), counts (4,) in COUNTS' order). Which form combines
        them is read off the shape: the queue only where a token can be dropped."""
        s = self.sizes
        weight, chosen = self.routing(x)
        y, held, kept = (self.unqueued if x.shape[0] <= s.capacity(x.shape[0]) else self.queued)(x, weight, chosen)
        load = jnp.sum((chosen[..., None] == jnp.arange(s.experts)).astype(F32), axis=(0, 1))  # (E,)
        counts = jnp.stack([jnp.sum(held).astype(F32), jnp.sum(held & ~kept).astype(F32),
                            jnp.max(load), jnp.mean(load)])
        return y, jax.lax.stop_gradient(counts)

    def shared(self, x):
        h = _mm(x, self.shared_up, self.dtype)
        h = jax.nn.silu(_mm(x, self.shared_gate, self.dtype)) * h if self.sizes.gated else jnp.square(jax.nn.relu(h))
        y = _mm(h, self.shared_down, self.dtype)
        if self.sizes.shared_gate:
            y = y * jax.nn.sigmoid(jnp.dot(x, self.shared_expert_gate, precision=jax.lax.Precision.HIGHEST))
        return y

    def __call__(self, x):
        """x (..., D) -> (x + held experts' part + shared expert, counts)."""
        flat = rms_norm(x, self.pre_norm, self.sizes.eps).reshape(-1, x.shape[-1])
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


def rotary(x, positions, rotary_dim: int, theta: float):
    """x (B, T, ..., Dh) with its first `rotary_dim` dimensions rotated to
    `positions` (B, T), rotate-half convention (dimension i pairs with i +
    rotary_dim / 2, `inv_freq_i = theta^(-2 i / rotary_dim)`), float32."""
    half = rotary_dim // 2
    angle = positions.astype(F32)[..., None] * theta ** (-jnp.arange(half, dtype=F32) / half)   # (B, T, half)
    angle = angle.reshape(*angle.shape[:2], *(1,) * (x.ndim - 3), half)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


class EpisodeAttention(nn.Module):
    spec: AttentionSizes
    dtype: jnp.dtype

    @staticmethod
    def state_shapes(s):
        return ((s.max_episode_steps, s.num_key_value_heads, s.head_dim),) * 2

    def setup(self):
        s = self.sizes = _sizes(self.spec, "*")
        D = s.hidden_size
        self.pre_norm = _norm_weight(self, "pre_norm", D, s.norm_offset)
        # with an output gate a head's projection is [query | gate]
        self.q_proj = self.param("q_proj", _matrix, (D, s.num_attention_heads * s.head_dim * (2 if s.output_gate else 1)))
        self.k_proj = self.param("k_proj", _matrix, (D, s.num_key_value_heads * s.head_dim))
        self.v_proj = self.param("v_proj", _matrix, (D, s.num_key_value_heads * s.head_dim))
        if s.qk_norm:
            self.q_norm = _norm_weight(self, "q_norm", s.head_dim, s.norm_offset)
            self.k_norm = _norm_weight(self, "k_norm", s.head_dim, s.norm_offset)
        self.o_proj = self.param("o_proj", _matrix, (s.num_attention_heads * s.head_dim, D))

    def __call__(self, x, keys, values, count):
        """x (B, T, D); keys, values (B, W, KV, Dh) the ring; count (B,) int
        positions seen so far -> (x', keys', values'). Where the family has
        them: a norm on each head's query and key, then the rotary embedding
        at positions `count + t` (so the ring holds keys already rotated),
        and a sigmoid gate on the heads' outputs."""
        s = self.sizes
        B, T, _ = x.shape
        KV, Dh, W = s.num_key_value_heads, s.head_dim, s.max_episode_steps
        R = s.num_attention_heads // KV
        h = rms_norm(x, self.pre_norm, s.eps)
        q = _mm(h, self.q_proj, self.dtype).reshape(B, T, KV, R, -1)
        k = _mm(h, self.k_proj, self.dtype).reshape(B, T, KV, Dh)
        v = _mm(h, self.v_proj, self.dtype).reshape(B, T, KV, Dh)
        if s.output_gate:
            q, gate = q[..., :Dh], q[..., Dh:].reshape(B, T, KV * R * Dh)
        if s.qk_norm:
            q, k = rms_norm(q, self.q_norm, s.eps), rms_norm(k, self.k_norm, s.eps)
        if s.rotary_dim:
            positions = count[:, None] + jnp.arange(T)
            q, k = (rotary(a, positions, s.rotary_dim, s.rope_theta) for a in (q, k))
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
        if s.output_gate:
            out = out * jax.nn.sigmoid(gate)
        return x + _mm(out, self.o_proj, self.dtype), _ring_write(keys, k, count), _ring_write(values, v, count)


def unit_lower_solve(L, rhs):
    """`(I + L)^-1 rhs` for L (.., Q, Q) strictly lower triangular and rhs
    (.., Q, m), float32: forward substitution (`solve_triangular`:
    differentiable, static in shape)."""
    return jax.scipy.linalg.solve_triangular(jnp.eye(L.shape[-1], dtype=F32) + L, rhs, lower=True, unit_diagonal=True)


def delta_rule_chunked(q, k, v, g, beta, s0, chunk: int, dtype):
    """The gated delta rule over a sequence, in chunks.

    q and k (B, T, Hk dk), q scaled and both L2-normalised per head; v (B, T,
    Hv dv); g <= 0 and beta in (0, 1) (B, T, Hv); s0 (B, Hv, dk, dv): per
    value head, served by key head `h // (Hv / Hk)`, the recurrence `S <-
    exp(g_t) S; S <- S + k_t (beta_t (v_t - S^T k_t))^T; o_t = S^T q_t` ->
    (o (B, T, Hv dv), S_T), float32.

    The state is a matrix and its update is not a sum of outer products of
    the inputs (each step's `v_t - S^T k_t` reads the state the steps before
    it wrote), so a chunk of Q steps first solves for what its steps write
    GIVEN the state it starts from: with `G` the running sum of g inside the
    chunk and `L_ij = beta_i (k_i . k_j) exp(G_i - G_j)` for j < i, the
    written rows are `v' = T (beta v) - T (beta k exp(G)) S` with `T = (I +
    L)^-1`, a unit lower-triangular system solved by forward substitution in
    float32: where the triangles fill whole lanes (`pallas_delta.kernel_fits`)
    by the kernel of ops/pallas_delta.py, a triangle a lane and the rows in
    VMEM, then one matmul at "highest" (PERF.md finding 57: XLA's own
    inversion was a quarter of the qwen3-next cell's step); otherwise by
    `unit_lower_solve` (`solve_triangular`). NOT by the series `(I
    - L)(I + L^2)(I + L^4) ...`, exact in exact arithmetic after log2(Q)
    squarings and as fast on the chip: an agent's consecutive frames give keys
    with `k_i . k_j` near 1, L is then near `beta` times all ones, the series'
    terms reach binomials of Q (1e11 and more at Q = 64) while their sum stays
    of order one, and float32 loses it all: a training run's loss went to NaN
    (PERF.md finding 56). Then, chunk after
    chunk (a scan), `o = (q exp(G)) S + tril(q k^T exp(G_i - G_j)) v'` and `S
    <- exp(G_Q) S + (k exp(G_Q - G))^T v'`. Every exponent is <= 0. Padding
    has g = 0 and beta = 0: decay 1, nothing written.

    Where the axes live (PERF.md finding 55): q, k, v go from `(B, T, H d)`
    to `(B, n, H, Q, d)` by one transposition of `(Q, H)` blocks (d = 128 is
    the lane width and stays minor) and o comes back by one; heads are a
    batch axis of the einsums; a chunk's steps are the minor axis of every
    per-head scalar (`g`, `G`, `beta`: `(B, n, Hk, R, Q)`)."""
    B, T, _ = q.shape
    Hv, dk, dv = s0.shape[1:]
    Hk = q.shape[-1] // dk
    R = Hv // Hk  # value heads a key head
    Q = min(chunk, T)
    pad = (-T) % Q
    n = (T + pad) // Q
    highest = jax.lax.Precision.HIGHEST

    def chunks(a, *heads):
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0))) if pad else a
        return a.reshape(B, n, Q, *heads, -1)

    q, k = (jnp.moveaxis(chunks(a, Hk), 2, 3) for a in (q, k))             # (B, n, Hk, Q, dk)
    v = jnp.moveaxis(chunks(v, Hk, R), 2, 4)                               # (B, n, Hk, R, Q, dv)
    g, beta = (jnp.moveaxis(chunks(a, Hk), 2, 4) for a in (g, beta))       # (B, n, Hk, R, Q)
    G = running_sum(g)
    i = jnp.arange(Q)
    lower, strict = i[:, None] >= i[None, :], i[:, None] > i[None, :]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, G[..., :, None] - G[..., None, :], 0.0)), 0.0)
    # what the chunk's steps write, given the state it starts from
    kk = jnp.einsum("bnkid,bnkjd->bnkij", k, k, precision=highest)
    L = jnp.where(strict, beta[..., :, None] * kk[:, :, :, None] * decay, 0.0)   # (B, n, Hk, R, Q, Q)
    k_r = k[:, :, :, None]                                                       # a key head's R value heads
    solve = pallas_delta.unit_lower_solve if pallas_delta.kernel_fits(B * n * Hv, Q) else unit_lower_solve
    solved = solve(L, jnp.concatenate([(beta * jnp.exp(G))[..., None] * k_r, beta[..., None] * v], axis=-1))
    w, u = solved[..., :dk], solved[..., dk:]
    # what each step reads of its own chunk, and what the chunk leaves in the state
    qk = jnp.einsum("bnkid,bnkjd->bnkij", q.astype(dtype), k.astype(dtype), preferred_element_type=F32)
    inside = (qk[:, :, :, None] * decay).astype(dtype)
    q_in = (jnp.exp(G)[..., None] * q[:, :, :, None]).astype(dtype)              # reads the incoming state
    k_out = (jnp.exp(G[..., -1:] - G)[..., None] * k_r).astype(dtype)            # writes the outgoing one
    whole = jnp.exp(G[..., -1])                                                  # (B, n, Hk, R)

    def across(S, chunk_n):
        w_n, u_n, q_n, k_n, inside_n, whole_n = chunk_n
        S_low = S.astype(dtype)
        written = u_n - jnp.einsum("bkrid,bkrde->bkrie", w_n.astype(dtype), S_low, preferred_element_type=F32)
        o = (jnp.einsum("bkrid,bkrde->bkrie", q_n, S_low, preferred_element_type=F32)
             + jnp.einsum("bkrij,bkrje->bkrie", inside_n, written.astype(dtype), preferred_element_type=F32))
        S = whole_n[..., None, None] * S + jnp.einsum("bkrid,bkrie->bkrde", k_n, written.astype(dtype),
                                                      preferred_element_type=F32)
        return S, o

    per_chunk = tuple(jnp.moveaxis(a, 1, 0) for a in (w, u, q_in, k_out, inside, whole))
    S, o = jax.lax.scan(across, s0.reshape(B, Hk, R, dk, dv).astype(F32), per_chunk)   # o (n, B, Hk, R, Q, dv)
    o = jnp.transpose(o, (1, 0, 4, 2, 3, 5)).reshape(B, n * Q, Hv * dv)
    return o[:, :T], S.reshape(B, Hv, dk, dv)


class GatedDeltaNet(nn.Module):
    """The `qwen3_next` linear-attention mixer (Yang et al. 2024, "Gated Delta
    Networks"): `[q | k | v | z] = in_proj_qkvz(u)`, `[b | a] = in_proj_ba(u)`;
    a causal depthwise convolution and silu over `q | k | v`, whose last
    `conv_kernel - 1` inputs are state; q and k L2-normalised per head, q
    scaled; `beta = sigmoid(b)`, `g = -exp(A_log) softplus(a + dt_bias)`; the
    delta rule; `out_proj(norm(o) silu(z))` with the norm over each head."""

    spec: DeltaSizes
    dtype: jnp.dtype

    @staticmethod
    def state_shapes(s):
        return (s.value_heads, s.key_dim, s.value_dim), (s.conv_kernel - 1, s.conv_dim)

    def setup(self):
        s = self.spec
        D, Hv = s.hidden_size, s.value_heads
        self.pre_norm = _norm_weight(self, "pre_norm", D, s.norm_offset)
        self.in_proj_qkvz = self.param("in_proj_qkvz", _matrix, (D, s.conv_dim + Hv * s.value_dim))
        self.in_proj_ba = self.param("in_proj_ba", _matrix, (D, 2 * Hv))
        self.conv_weight = self.param("conv_weight", _matrix, (s.conv_kernel, s.conv_dim))
        self.a_log = self.param("A_log", _a_log_init_from_zero, (Hv,))
        self.dt_bias = self.param("dt_bias", nn.initializers.ones, (Hv,))
        self.norm = self.param("norm", nn.initializers.ones, (s.value_dim,))
        self.out_proj = self.param("out_proj", _matrix, (Hv * s.value_dim, D))

    def _project(self, x):
        """x (.., D) -> qkv (.., conv_dim) before the convolution, z, beta, g."""
        s = self.spec
        h = rms_norm(x, self.pre_norm, s.eps)
        qkvz, ba = _mm(h, self.in_proj_qkvz, self.dtype), _mm(h, self.in_proj_ba, self.dtype)
        b, a = ba[..., :s.value_heads], ba[..., s.value_heads:]
        g = -jnp.exp(self.a_log) * jax.nn.softplus(a + self.dt_bias)
        return qkvz[..., :s.conv_dim], qkvz[..., s.conv_dim:], jax.nn.sigmoid(b), g

    def _heads(self, qkv):
        """(.., conv_dim) after the convolution -> q scaled, k, v: per channel,
        q and k L2-normalised per head (`x rsqrt(sum(x^2) + 1e-6)`, which is a
        grouped RMS norm with the weight `dk^-1/2`)."""
        s = self.spec
        cut = s.key_heads * s.key_dim
        unit = lambda a, scale: rms_norm(a, scale * s.key_dim ** -0.5, 1e-6 / s.key_dim, groups=s.key_heads)
        return unit(qkv[..., :cut], s.key_dim ** -0.5), unit(qkv[..., cut:2 * cut], 1.0), qkv[..., 2 * cut:]

    def _out(self, o, z):
        s = self.spec
        o = rms_norm(o, jnp.tile(self.norm, s.value_heads), s.eps, groups=s.value_heads) * jax.nn.silu(z)
        return _mm(o, self.out_proj, self.dtype)

    def __call__(self, x, delta, tail):
        """x (B, T, D), delta (B, Hv, dk, dv), tail (B, K-1, conv_dim) -> the same three."""
        s, T = self.spec, x.shape[1]
        qkv, z, beta, g = self._project(x)
        seq = jnp.concatenate([tail, qkv], axis=1)
        conv = sum(self.conv_weight[j] * seq[:, j:j + T] for j in range(s.conv_kernel))
        q, k, v = self._heads(jax.nn.silu(conv))
        o, delta = self.recurrence(q, k, v, g, beta, delta)
        return x + self._out(o, z), delta, seq[:, T:]

    def recurrence(self, q, k, v, g, beta, delta):
        """The chunked delta rule under a name of its own (`gdn_<i>.recurrence`)."""
        return delta_rule_chunked(q, k, v, g, beta, delta, self.spec.chunk, self.dtype)

    def step(self, x, delta, tail):
        """One step of the recurrence itself: x (B, D). Sums, not matmuls: the
        state is float32 and a row's is 2 MB."""
        s = self.spec
        qkv, z, beta, g = self._project(x)
        seq = jnp.concatenate([tail, qkv[:, None]], axis=1)                      # (B, K, conv_dim)
        q, k, v = self._heads(jax.nn.silu(jnp.sum(self.conv_weight * seq, axis=1)))
        R = s.value_heads // s.key_heads
        q, k = (jnp.repeat(a.reshape(-1, s.key_heads, s.key_dim), R, axis=1) for a in (q, k))   # (B, Hv, dk)
        v = v.reshape(-1, s.value_heads, s.value_dim)
        delta = jnp.exp(g)[..., None, None] * delta
        written = beta[..., None] * (v - jnp.sum(delta * k[..., None], axis=2))
        delta = delta + k[..., None] * written[:, :, None, :]
        o = jnp.sum(delta * q[..., None], axis=2).reshape(x.shape[0], -1)
        return x + self._out(o, z), delta, seq[:, 1:]


KDA_SUB = 16  # steps of a chunk whose pair terms `kda_chunked` takes column by column


def _kda_pairs(q, k, G, sub: int, dtype):
    """The two pair matrices of a chunk of the delta rule whose decay is per
    key channel: q, k and G (.., Q, dk), G the running sum of g <= 0 inside
    the chunk -> (`A_ij = sum_d k_id k_jd exp(G_id - G_jd)` for j < i, float32
    at "highest"; `P_ij = sum_d q_id k_jd exp(G_id - G_jd)` for j <= i, from
    operands in `dtype`), both (.., Q, Q) and zero elsewhere.

    No single matmul gives them: `exp(-G_j)` alone overflows float32 inside a
    chunk. EVERY EXPONENT EVALUATED IS <= 0: between two sub-chunks of `sub`
    steps the decay is split at the later one's first row, `exp(G_i - G_ref)
    exp(G_ref - G_j)` with `G_i <= G_ref <= G_j`, and the block is a matmul of
    `k exp(G - G_ref)` against `k exp(G_ref - G)`; inside a sub-chunk the
    `sub` columns are taken one after the other, elementwise over d and
    reduced, each recomputed in the backward pass, so that no `(.., sub, sub,
    dk)` array outlives its column."""
    Q, dk = q.shape[-2:]
    m = Q // sub
    rows = jnp.arange(sub)
    # inside each sub-chunk, column by column
    inner = lambda a: a.reshape(*a.shape[:-2], m, sub, dk)
    q_in, k_in, G_in = inner(q), inner(k), inner(G)

    @jax.checkpoint
    def column(_, j):
        k_j, G_j = (jax.lax.dynamic_index_in_dim(a, j, axis=-2) for a in (k_in, G_in))      # (.., m, 1, dk)
        after = (rows >= j)[:, None]
        decayed = k_j * jnp.exp(jnp.where(after, G_in - G_j, 0.0))                    # (.., m, sub, dk)
        return None, (jnp.sum(jnp.where(rows[:, None] > j, k_in * decayed, 0.0), axis=-1),
                      jnp.sum(jnp.where(after, q_in * decayed, 0.0), axis=-1))

    _, (a_own, p_own) = jax.lax.scan(column, None, rows)                              # (sub, .., m, sub)
    a_own, p_own = jnp.moveaxis(a_own, 0, -1), jnp.moveaxis(p_own, 0, -1)             # (.., m, sub rows, sub columns)
    a_rows, p_rows = [], []
    for a in range(m):
        # the sub-chunks before this one, the decay split at this one's first row
        at = a * sub
        parts_a, parts_p = [a_own[..., a, :, :]], [p_own[..., a, :, :]]
        if a:
            ref = G[..., at:at + 1, :]
            late = jnp.exp(G[..., at:at + sub, :] - ref)                                # (.., sub, dk), exponents <= 0
            early = k[..., :at, :] * jnp.exp(ref - G[..., :at, :])                      # (.., at, dk), exponents <= 0
            parts_a.insert(0, jnp.einsum("...id,...jd->...ij", k[..., at:at + sub, :] * late, early,
                                         precision=jax.lax.Precision.HIGHEST))
            parts_p.insert(0, jnp.einsum("...id,...jd->...ij", (q[..., at:at + sub, :] * late).astype(dtype),
                                         early.astype(dtype), preferred_element_type=F32))
        if Q - at - sub:
            later = jnp.zeros((*q.shape[:-2], sub, Q - at - sub), F32)
            parts_a.append(later)
            parts_p.append(later)
        a_rows.append(jnp.concatenate(parts_a, axis=-1))
        p_rows.append(jnp.concatenate(parts_p, axis=-1))
    return jnp.concatenate(a_rows, axis=-2), jnp.concatenate(p_rows, axis=-2)


def kda_chunked(q, k, v, g, beta, s0, chunk: int, dtype, sub: int = KDA_SUB):
    """The delta rule with a decay per key channel (Kimi Delta Attention)
    over a sequence, in chunks: the third chunked matrix recurrence here,
    beside `ssd_chunked` and `delta_rule_chunked`.

    q and k (B, T, H dk), q scaled and both L2-normalised per head; v (B, T,
    H dv); g <= 0 (B, T, H dk); beta in (0, 1) (B, T, H); s0 (B, H, dk, dv):
    per head the recurrence `S <- Diag(exp(g_t)) S; S <- S + k_t (beta_t (v_t
    - S^T k_t))^T; o_t = S^T q_t` -> (o (B, T, H dv), S_T), float32.

    As in `delta_rule_chunked` a chunk of Q steps first solves for what its
    steps write given the state it starts from, `v' = T (beta v) - T (beta k
    exp(G)) S` with `T = (I + L)^-1`, but G, the running sum of g inside the
    chunk, is now a vector over the key dimension and `L_ij = beta_i sum_d
    k_id k_jd exp(G_id - G_jd)`: the pair term is no longer one `k k^T` times
    a scalar decay (`_kda_pairs`, which also gives the same form for `q_i,
    k_j`). Then the scan over the chunks: `o = (q exp(G)) S + P v'` and `S <-
    Diag(exp(G_Q)) S + (k exp(G_Q - G))^T v'`, the state's decay a scaling of
    its rows. Every exponent is <= 0; g is not clamped. The triangular system
    is `delta_rule_chunked`'s: the kernel where the triangles fill whole
    lanes, `solve_triangular` elsewhere. Padding has g = 0, beta = 0 and k =
    0: decay 1, nothing written.

    Where the axes live (PERF.md finding 55): q, k, v and g go from `(B, T, H
    d)` to `(B, n, H, Q, d)` by one transposition of `(Q, H)` blocks (d = 128
    stays minor; G lies as k does) and o comes back by one; a chunk's steps
    are the minor axis of `beta` alone, the one per-head scalar left."""
    B, T, _ = q.shape
    H, dk, dv = s0.shape[1:]
    sub = min(sub, chunk)
    Q = min(chunk, sub * math.ceil(T / sub))
    if Q % sub:
        raise ValueError(f"a chunk of {chunk} steps is not whole sub-chunks of {sub}")
    pad = (-T) % Q
    n = (T + pad) // Q
    highest = jax.lax.Precision.HIGHEST

    def chunks(a):
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0))) if pad else a
        return jnp.moveaxis(a.reshape(B, n, Q, H, -1), 2, 3)                     # (B, n, H, Q, d)

    q, k, v, g = chunks(q), chunks(k), chunks(v), chunks(g)
    beta = chunks(beta)[..., 0]                                                  # (B, n, H, Q)
    i = jnp.arange(Q)
    G = jnp.einsum("ij,bnhjd->bnhid", (i[:, None] >= i[None, :]).astype(F32), g, precision=highest)
    A, inside = _kda_pairs(q, k, G, sub, dtype)
    # what the chunk's steps write, given the state it starts from
    L = beta[..., None] * A                                                      # (B, n, H, Q, Q), strictly lower
    solve = pallas_delta.unit_lower_solve if pallas_delta.kernel_fits(B * n * H, Q) else unit_lower_solve
    solved = solve(L, beta[..., None] * jnp.concatenate([jnp.exp(G) * k, v], axis=-1))
    w, u = solved[..., :dk], solved[..., dk:]
    q_in = (jnp.exp(G) * q).astype(dtype)                                        # reads the incoming state
    k_out = (jnp.exp(G[..., -1:, :] - G) * k).astype(dtype)                      # writes the outgoing one
    whole = jnp.exp(G[..., -1, :])                                               # (B, n, H, dk)

    def across(S, chunk_n):
        w_n, u_n, q_n, k_n, inside_n, whole_n = chunk_n
        S_low = S.astype(dtype)
        written = u_n - jnp.einsum("bhid,bhde->bhie", w_n.astype(dtype), S_low, preferred_element_type=F32)
        o = (jnp.einsum("bhid,bhde->bhie", q_n, S_low, preferred_element_type=F32)
             + jnp.einsum("bhij,bhje->bhie", inside_n, written.astype(dtype), preferred_element_type=F32))
        S = whole_n[..., None] * S + jnp.einsum("bhid,bhie->bhde", k_n, written.astype(dtype),
                                                preferred_element_type=F32)
        return S, o

    per_chunk = tuple(jnp.moveaxis(a, 1, 0) for a in (w, u, q_in, k_out, inside.astype(dtype), whole))
    S, o = jax.lax.scan(across, s0.astype(F32), per_chunk)                       # o (n, B, H, Q, dv)
    o = jnp.transpose(o, (1, 0, 3, 2, 4)).reshape(B, n * Q, H * dv)
    return o[:, :T], S


class KimiDeltaAttention(nn.Module):
    """The `kimi_linear` linear-attention mixer (Kimi Team 2025, "Kimi
    Linear", arXiv:2510.26692): `q, k, v = q_proj(u), k_proj(u), v_proj(u)`,
    each through its own causal depthwise convolution and silu, whose last
    `conv_kernel - 1` inputs are state; q and k L2-normalised per head, q
    scaled; `beta = sigmoid(b_proj(u))` a head; the forget gate PER KEY
    CHANNEL, `g = -exp(A_log) softplus(f_b(f_a(u)) + dt_bias)` through rank
    `gate_rank`; the delta rule with `Diag(exp(g))` on the state's rows;
    `o_proj(norm(o) sigmoid(g_b(g_a(u))))` with the norm over each head."""

    spec: KdaSizes
    dtype: jnp.dtype

    @staticmethod
    def state_shapes(s):
        return (s.heads, s.head_dim, s.head_dim), (s.conv_kernel - 1, 3 * s.width)

    def setup(self):
        s = self.spec
        D, C, r = s.hidden_size, s.width, s.gate_rank
        self.pre_norm = self.param("pre_norm", nn.initializers.ones, (D,))
        self.q_proj = self.param("q_proj", _matrix, (D, C))
        self.k_proj = self.param("k_proj", _matrix, (D, C))
        self.v_proj = self.param("v_proj", _matrix, (D, C))
        self.q_conv = self.param("q_conv", _matrix, (s.conv_kernel, C))
        self.k_conv = self.param("k_conv", _matrix, (s.conv_kernel, C))
        self.v_conv = self.param("v_conv", _matrix, (s.conv_kernel, C))
        self.f_a = self.param("f_a", _matrix, (D, r))
        self.f_b = self.param("f_b", _matrix, (r, C))
        self.g_a = self.param("g_a", _matrix, (D, r))
        self.g_b = self.param("g_b", _matrix, (r, C))
        self.b_proj = self.param("b_proj", _matrix, (D, s.heads))
        self.a_log = self.param("A_log", _a_log_init, (s.heads,))
        self.dt_bias = self.param("dt_bias", _dt_bias_init(1e-3, 0.1, 1e-4), (C,))
        self.norm = self.param("norm", nn.initializers.ones, (s.head_dim,))
        self.o_proj = self.param("o_proj", _matrix, (C, D))

    def _project(self, x):
        """x (.., D) -> [q | k | v] (.., 3 C) before the convolutions, the
        output gate (.., C), beta (.., H), g (.., C) <= 0."""
        s = self.spec
        h = rms_norm(x, self.pre_norm, s.eps)
        qkv = jnp.concatenate([_mm(h, w, self.dtype) for w in (self.q_proj, self.k_proj, self.v_proj)], axis=-1)
        gate = _mm(_mm(h, self.g_a, self.dtype), self.g_b, self.dtype)
        forget = _mm(_mm(h, self.f_a, self.dtype), self.f_b, self.dtype)
        g = -jnp.repeat(jnp.exp(self.a_log), s.head_dim) * jax.nn.softplus(forget + self.dt_bias)
        return qkv, gate, jax.nn.sigmoid(_mm(h, self.b_proj, self.dtype)), g

    @property
    def conv_weight(self):
        return jnp.concatenate([self.q_conv, self.k_conv, self.v_conv], axis=-1)         # (K, 3 C)

    def _heads(self, qkv):
        """(.., 3 C) after the convolutions -> q scaled, k, v: per channel, q
        and k L2-normalised per head (`x rsqrt(sum(x^2) + 1e-6)`)."""
        s = self.spec
        C = s.width
        unit = lambda a, scale: rms_norm(a, scale * s.head_dim ** -0.5, 1e-6 / s.head_dim, groups=s.heads)
        return unit(qkv[..., :C], s.head_dim ** -0.5), unit(qkv[..., C:2 * C], 1.0), qkv[..., 2 * C:]

    def _out(self, o, gate):
        s = self.spec
        o = rms_norm(o, jnp.tile(self.norm, s.heads), s.eps, groups=s.heads) * jax.nn.sigmoid(gate)
        return _mm(o, self.o_proj, self.dtype)

    def __call__(self, x, delta, tail):
        """x (B, T, D), delta (B, H, dk, dv), tail (B, K-1, 3 C) -> the same three."""
        s, T = self.spec, x.shape[1]
        qkv, gate, beta, g = self._project(x)
        seq = jnp.concatenate([tail, qkv], axis=1)
        weight = self.conv_weight
        q, k, v = self._heads(jax.nn.silu(sum(weight[j] * seq[:, j:j + T] for j in range(s.conv_kernel))))
        o, delta = self.recurrence(q, k, v, g, beta, delta)
        return x + self._out(o, gate), delta, seq[:, T:]

    def recurrence(self, q, k, v, g, beta, delta):
        """The chunked form under a name of its own (`kda_<i>.recurrence`)."""
        return kda_chunked(q, k, v, g, beta, delta, self.spec.chunk, self.dtype)

    def step(self, x, delta, tail):
        """One step of the recurrence itself: x (B, D). Sums, not matmuls: the
        state is float32 and a row's is 2 MB."""
        s = self.spec
        qkv, gate, beta, g = self._project(x)
        seq = jnp.concatenate([tail, qkv[:, None]], axis=1)                      # (B, K, 3 C)
        q, k, v = (a.reshape(-1, s.heads, s.head_dim)
                   for a in self._heads(jax.nn.silu(jnp.sum(self.conv_weight * seq, axis=1))))
        delta = jnp.exp(g.reshape(-1, s.heads, s.head_dim))[..., None] * delta   # the rows of S, each by its own decay
        written = beta[..., None] * (v - jnp.sum(delta * k[..., None], axis=2))
        delta = delta + k[..., None] * written[:, :, None, :]
        o = jnp.sum(delta * q[..., None], axis=2).reshape(x.shape[0], -1)
        return x + self._out(o, gate), delta, seq[:, 1:]


class LatentAttention(nn.Module):
    """Multi-head latent attention (DeepSeek-V2) as `kimi_linear` has it, with
    no positional encoding (`mla_use_nope`: position comes from the KDA
    layers): `q_proj(u)` is each head's `[q_nope | q_pe]`; `[c | k_pe] =
    kv_a_proj(u)`, `c <- norm(c)`; each head's `[k_nope | v] = kv_b_proj(c)`
    and its key is `[k_nope | k_pe]`, `k_pe` shared by the heads; `softmax(q
    k^T / sqrt(nope_dim + rope_dim)) v`, causal; `o_proj`.

    THE CARRY HOLDS THE LATENT: a ring of the last `max_episode_steps`
    positions' `[c | k_pe]` after the norm (`latent + rope_dim` numbers a
    position, where the heads' keys and values would be `heads x (nope_dim +
    rope_dim + value_dim)`), and the stack's count. That makes two forms
    against ONE parameter `kv_b_proj`: `__call__` (T queries a row)
    up-projects the ring and its own T positions to keys and values once and
    attends in query blocks as `EpisodeAttention` does; `step` (one query a
    row) ABSORBS: `q_c = q_nope W_UK^T` (heads x latent), scores `q_c . c +
    q_pe . k_pe` over the ring, the weighted sum of c, then `W_UV`: no array
    of per-head keys or values over the ring."""

    spec: LatentSizes
    dtype: jnp.dtype

    @staticmethod
    def state_shapes(s):
        return ((s.max_episode_steps, s.stored),)

    def setup(self):
        s = self.spec
        D, H = s.hidden_size, s.heads
        self.pre_norm = self.param("pre_norm", nn.initializers.ones, (D,))
        self.q_proj = self.param("q_proj", _matrix, (D, H * (s.nope_dim + s.rope_dim)))
        self.kv_a_proj = self.param("kv_a_proj", _matrix, (D, s.stored))
        self.kv_a_norm = self.param("kv_a_norm", nn.initializers.ones, (s.latent,))
        self.kv_b_proj = self.param("kv_b_proj", _matrix, (s.latent, H * (s.nope_dim + s.value_dim)))
        self.o_proj = self.param("o_proj", _matrix, (H * s.value_dim, D))

    def _project(self, x):
        """x (.., D) -> (q (.., H, nope + rope), what the ring stores of these
        positions (.., latent + rope): `[norm(c) | k_pe]`)."""
        s = self.spec
        h = rms_norm(x, self.pre_norm, s.eps)
        q = _mm(h, self.q_proj, self.dtype).reshape(*x.shape[:-1], s.heads, s.nope_dim + s.rope_dim)
        stored = _mm(h, self.kv_a_proj, self.dtype)
        return q, jnp.concatenate([rms_norm(stored[..., :s.latent], self.kv_a_norm, s.eps), stored[..., s.latent:]], axis=-1)

    def __call__(self, x, latent, count):
        """x (B, T, D); latent (B, W, latent + rope) the ring; count (B,) int
        positions seen so far -> (x', latent')."""
        s = self.spec
        B, T, _ = x.shape
        H, W = s.heads, s.max_episode_steps
        q, new = self._project(x)
        memory = jnp.concatenate([latent, new], axis=1).astype(self.dtype)        # (B, W + T, latent + rope)
        kv = _mm(memory[..., :s.latent], self.kv_b_proj, self.dtype).reshape(B, W + T, H, s.nope_dim + s.value_dim)
        k_nope, values = kv[..., :s.nope_dim].astype(self.dtype), kv[..., s.nope_dim:].astype(self.dtype)
        k_pe = memory[..., s.latent:]
        remembered = jnp.arange(W)[None, :] < jnp.minimum(count, W)[:, None]      # (B, W)
        Q = min(QUERY_BLOCK, T)
        pad = (-T) % Q
        blocks = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(B, -1, Q, H, s.nope_dim + s.rope_dim)

        @jax.checkpoint
        def attend(args):
            q_block, t0 = args                                                    # (B, Q, H, nope + rope)
            q_block = q_block.astype(self.dtype)
            scores = (jnp.einsum("bqhd,bshd->bhqs", q_block[..., :s.nope_dim], k_nope, preferred_element_type=F32)
                      + jnp.einsum("bqhd,bsd->bhqs", q_block[..., s.nope_dim:], k_pe, preferred_element_type=F32)
                      ) / math.sqrt(s.nope_dim + s.rope_dim)
            causal = jnp.arange(T)[None, :] <= (t0 + jnp.arange(Q))[:, None]      # (Q, T)
            seen = jnp.concatenate([jnp.broadcast_to(remembered[:, None, :], (B, Q, W)),
                                    jnp.broadcast_to(causal[None], (B, Q, T))], axis=-1)
            probs = jax.nn.softmax(jnp.where(seen[:, None], scores, -1e30), axis=-1)
            return jnp.einsum("bhqs,bshd->bqhd", probs.astype(self.dtype), values, preferred_element_type=F32)

        out = jax.lax.map(attend, (jnp.moveaxis(blocks, 1, 0), jnp.arange(blocks.shape[1]) * Q))
        out = jnp.moveaxis(out, 0, 1).reshape(B, T + pad, H * s.value_dim)[:, :T]
        return x + _mm(out, self.o_proj, self.dtype), _ring_write(latent, new, count)

    def step(self, x, latent, count):
        """One position a row, absorbed: x (B, D). What it attends to is what
        `__call__` does at T = 1: the valid part of the ring and itself."""
        s = self.spec
        H, W = s.heads, s.max_episode_steps
        q, new = self._project(x)                                                 # (B, H, nope + rope), (B, latent + rope)
        up = self.kv_b_proj.reshape(s.latent, H, s.nope_dim + s.value_dim).astype(self.dtype)
        q_c = jnp.einsum("bhd,chd->bhc", q[..., :s.nope_dim].astype(self.dtype), up[..., :s.nope_dim],
                         preferred_element_type=F32)                              # (B, H, latent)
        query = jnp.concatenate([q_c, q[..., s.nope_dim:]], axis=-1).astype(self.dtype)     # against [c | k_pe] as stored
        scores = jnp.concatenate([
            jnp.einsum("bhc,bwc->bhw", query, latent.astype(self.dtype), preferred_element_type=F32),
            jnp.einsum("bhc,bc->bh", query, new.astype(self.dtype), preferred_element_type=F32)[..., None]],
            axis=-1) / math.sqrt(s.nope_dim + s.rope_dim)                          # (B, H, W + 1)
        seen = jnp.concatenate([jnp.arange(W)[None, :] < jnp.minimum(count, W)[:, None],
                                jnp.ones((x.shape[0], 1), bool)], axis=-1)
        probs = jax.nn.softmax(jnp.where(seen[:, None], scores, -1e30), axis=-1).astype(self.dtype)
        mixed = (jnp.einsum("bhw,bwc->bhc", probs[..., :W], latent[..., :s.latent].astype(self.dtype),
                            preferred_element_type=F32)
                 + probs[..., W:].astype(F32) * new[:, None, :s.latent].astype(self.dtype))                   # (B, H, latent)
        out = jnp.einsum("bhc,chd->bhd", mixed.astype(self.dtype), up[..., s.nope_dim:], preferred_element_type=F32)
        return (x + _mm(out.reshape(x.shape[0], -1), self.o_proj, self.dtype),
                _ring_write(latent, new[:, None], count))


class DenseMlp(nn.Module):
    """A layer's MLP where it is no mixture (`kimi_linear`'s first
    `first_k_dense_replace` layers): `down(silu(gate x) * up x)`, pre-norm."""

    spec: MlpSizes
    dtype: jnp.dtype

    @staticmethod
    def state_shapes(s):
        return ()

    @nn.compact
    def __call__(self, x):
        s = self.spec
        h = rms_norm(x, self.param("pre_norm", nn.initializers.ones, (s.hidden_size,)), s.eps)
        gate = self.param("gate", _matrix, (s.hidden_size, s.width))
        up = self.param("up", _matrix, (s.hidden_size, s.width))
        down = self.param("down", _matrix, (s.width, s.hidden_size))
        return x + _mm(jax.nn.silu(_mm(h, gate, self.dtype)) * _mm(h, up, self.dtype), down, self.dtype)


KINDS = {"M": ("ssm", Mamba2Mixer), "D": ("gdn", GatedDeltaNet), "E": ("moe", ExpertMixture),
         "*": ("attention", EpisodeAttention), "K": ("kda", KimiDeltaAttention), "L": ("mla", LatentAttention),
         "F": ("mlp", DenseMlp)}
STATE_NAMES = {"M": ("ssm", "conv"), "D": ("delta", "conv"), "E": (), "*": ("keys", "values"),
               "K": ("delta", "conv"), "L": ("latent",), "F": ()}


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
    if kind in "MDK":
        x, matrix, tail = (layer.step if step else layer)(x, *state)
        return x, (matrix, tail), nothing
    if kind == "E":
        x, counts = layer(x)
        return x, (), counts
    if kind == "F":
        return layer(x), (), nothing
    if kind == "L":  # its step is a form of its own (absorbed), not its sequence form at T = 1
        x, latent = (layer.step if step else layer)(x, *state, count)
        return x, (latent,), nothing
    seq, keys, values = layer(x[:, None] if step else x, *state, count)
    return (seq[:, 0] if step else seq), (keys, values), nothing


class HybridStack(nn.Module):
    spec: _Stack
    in_dim: int
    dtype: jnp.dtype = F32

    # the seam's statements (models/core.py)
    cuts_at_burn_in = False
    keeps_window_starts = True

    @staticmethod
    def state_shape(cfg):
        return (1, spec_of(cfg).state_size)

    @classmethod
    def from_config(cls, cfg, in_dim: int, tp_size: int = 1) -> "HybridStack":
        if tp_size > 1:
            raise ValueError("the hybrid_stack core has no tensor-parallel form")
        return cls(spec_of(cfg), in_dim=in_dim, dtype=jnp.dtype(cfg.resolved_compute_dtype))

    def setup(self):
        s = self.spec
        self.embed = self.param("in_proj", _matrix, (self.in_dim, s.hidden_size))
        self.layers = [_layer(s.sizes(kind), self.dtype, kind, i) for kind, i in s.blocks]
        self.final_norm = _norm_weight(self, "final_norm", s.hidden_size, s.norm_offset)

    def _layers(self, x, parts):
        """x (B, T, in_dim), or (B, in_dim) for one step; `parts` the carry as
        `split_state` gives it -> (out, parts', counts)."""
        s = self.spec
        parts = dict(parts)
        count = _count_of(parts[(-1, "count")])
        counts = jnp.zeros((len(COUNTS),), F32)
        x = _mm(x, self.embed, self.dtype)
        for (kind, i), layer in zip(s.blocks, self.layers):
            state = tuple(parts[(i, name)] for name in STATE_NAMES[kind])
            x, state, c = _run_layer(kind, layer, x, state, count)
            parts.update({(i, name): value for name, value in zip(STATE_NAMES[kind], state)})
            counts = counts + c
        parts[(-1, "count")] = _count_pair(count + (1 if x.ndim == 2 else x.shape[1]))
        return rms_norm(x, self.final_norm, s.eps), parts, counts

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
