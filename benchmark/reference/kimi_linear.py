"""Plain float32 reference of a `kimi_linear` layer stack in the agent's core slot.

Written from the equations of the published model (config.json of
moonshotai/Kimi-Linear-48B-A3B-Instruct, `model_type: kimi_linear`; the linear
mixer: Kimi Team 2025, "Kimi Linear: An Expressive, Efficient Attention
Architecture", arXiv:2510.26692; the full layer: multi-head latent attention as
DeepSeek-V2 defines it) and not from the program: straightforward `jax.numpy`,
Kimi Delta Attention as a loop over time (`lax.scan`, one step at a time),
latent attention as ONE masked softmax over keys and values up-projected for
EVERY position (no absorption, no chunk, no query block), the experts one
after the other (a `lax.scan` over them) on every token under a mask. It
shares with the program only the parameter tree's names and the order of one
row's stored state, so that the same seeded weights and the same stored
sequences feed both. What is every cell's (Nature encoder, dueling heads, the
n-step double-Q loss under the value rescaling) is `reference/model.py`'s, and
what is both stacks' (the encoder's convolutions as shifted matmuls, the
checkpointed loop over time, the unit of a pattern that repeats, the static
capacity) is `reference/nemotron_h.py`'s: imported, not repeated.

Layer `i` (counted from 1, as `linear_attn_config` counts) mixes by Kimi Delta
Attention where `kda_layers` lists it and by latent attention where
`full_attn_layers` does; its MLP is dense in the first `first_k_dense_replace`
layers and the mixture after them: `x <- x + mixer(norm(x)); x <- x +
mlp(norm(x))`, between an input projection and a final norm. `norm(x) = x
rsqrt(mean(x^2) + rms_norm_eps) w`.

- Kimi Delta Attention (`kda_<i>`): `q, k = l2norm(silu(conv(q_proj(u)))),
  l2norm(silu(conv(k_proj(u))))`, `v = silu(conv(v_proj(u)))`, causal
  depthwise convolutions over the last `short_conv_kernel_size` inputs, no
  bias, `num_heads` heads of `head_dim` each (`l2norm(x) = x rsqrt(sum(x^2) +
  1e-6)`); `beta = sigmoid(b_proj(u))` a head; the forget gate PER KEY CHANNEL
  `g = -exp(A_log_h) softplus(f_b(f_a(u)) + dt_bias)`; per head, S (dk, dv):
  `S <- Diag(exp(g_t)) S; r = S^T k_t; S <- S + k_t (beta_t (v_t - r))^T;
  o_t = S^T (q_t / sqrt(dk))`; `o_proj(norm_w(o_t) * sigmoid(g_b(g_a(u))))`,
  the norm over each head's dv.
- latent attention (`mla_<i>`): `q_proj(u)` is each head's `[q_nope | q_pe]`;
  `[c | k_pe] = kv_a_proj(u)`, `c <- norm(c)`; each head's `[k_nope | v] =
  kv_b_proj(c)`, its key `[k_nope | k_pe]` with `k_pe` shared by the heads; NO
  rotation of `q_pe` or `k_pe` (`mla_use_nope`); `softmax(q k^T /
  sqrt(qk_nope_head_dim + qk_rope_head_dim)) v`, causal; `o_proj`.
- dense MLP (`mlp_<i>`): `down(silu(gate x) * up x)`.
- mixture (`moe_<i>`): `s = sigmoid(x W_r)`; the top `num_experts_per_token` of
  `s + e_score_correction_bias`; weights `s / sum(s chosen) x
  routed_scaling_factor`; expert `W_down (silu(W_gate x) * W_up x)`; plus the
  shared expert, as it is.

Every caller wraps these in `jax.default_matmul_precision("highest")`.

Departures from the published model, each because the configuration states
it (`assumed` / `reduced` in benchmark/configs/) and the program does the
same:
- the low-rank gates. `config.json` gives neither their rank nor their biases:
  rank `head_dim` (128) for both, as the family's own code has it, and no bias
  on either up-projection (that code gives the output gate's one; a bias is a
  constant added under a sigmoid and no mechanism).
- initialisers: `A_log = log U(1, 16)` a head; `dt_bias` the inverse softplus of
  a step drawn log-uniformly from [1e-3, 0.1], a key channel; matrices
  lecun-normal; norms one. They decide only where seeded weights put the gates.
- column order. Each head's `[q_nope | q_pe]` in `q_proj`, `[c | k_pe]` in
  `kv_a_proj`, each head's `[k_nope | v]` in `kv_b_proj`: a permutation of a
  random matrix's columns and no equation.
- no rotation. `mla_use_nope` is true: `rope_theta` 10,000 and `rope_scaling`
  are read by nothing (a test applies the rotation as a control that must
  fail). Unused likewise: `num_key_value_heads` (latent attention has no
  grouped keys), the top-level `head_dim` 72, `num_expert_group` / `topk_group`
  1 and `use_grouped_topk` (one group is no grouping), `moe_layer_freq` 1,
  `num_nextn_predict_layers` 0, `q_lora_rank` null (the query is projected
  directly), `model_max_length`.
- `e_score_correction_bias` is zero and no gradient reaches it (it enters the
  choice alone): the published training moves it by a load-balancing rule that
  `config.json` does not describe.
- the attention memory. A row's stored state holds `[norm(c) | k_pe]` of its
  last `max_episode_steps` positions (a ring: softmax does not ask in which
  order) and how many positions it has seen; a sequence's queries see the
  valid part of it and the sequence causally. The memory is as long as an
  episode, so that is full causal attention over the episode.
- the share. This chip holds experts `[first_expert_held, + num_experts_held)`
  of `num_experts`: the router scores all, and what the others would add is
  left out, here as there.
- the capacity. Each held expert takes at most `C` assignments a call
  (`nemotron_h.capacity`, factor 2.0); a token's assignment beyond it, in
  flattened (b, t) order, is dropped. The published model drops nothing;
  static shapes on a TPU are the reason.
- the input projection `(latent + A + 1) -> hidden` stands for the token
  embedding, and the dueling heads for the vocabulary's.
- burn-in is backpropagated through (no seam), as for the LRU core.
- memory and compile time, not mathematics: as `nemotron_h.py` (the loop over
  time and each layer checkpointed; a unit of blocks that repeats after the
  dense layer runs as one `lax.scan`).

`kernel_checks`, at the end, is the one place that calls the program: it
imports the program's layers to hold them, one kind at a time, to the layers
above. Nothing above it knows the program.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import correct, flops
from benchmark.reference import model as base
from benchmark.reference import nemotron_h as shared

F32 = jnp.float32
Sizes = shared.Sizes
NAMES = {"K": "kda", "L": "mla", "F": "mlp", "E": "moe"}
ROPE_THETA = 10000.0  # the published `rope_theta`: read by the layer checks' control alone (`mla_use_nope`)


def stack_of(cfg) -> Dict:
    s = dict(cfg.core_config)
    s["linear_attn_config"] = dict(s["linear_attn_config"])
    s.setdefault("num_experts_held", s["num_experts"])
    s.setdefault("first_expert_held", 0)
    s.setdefault("capacity_factor", 2.0)
    s["max_episode_steps"] = cfg.max_episode_steps  # the length of the attention's memory
    # under nemotron_h's names too, for its `capacity`
    s["n_routed_experts"], s["num_experts_per_tok"] = s["num_experts"], s["num_experts_per_token"]
    return s


def sizes_of(cfg) -> Sizes:
    return Sizes(encoder=cfg.encoder, hidden=cfg.hidden_dim, action_dim=cfg.action_dim,
                 learning=cfg.learning_steps, forward=cfg.forward_steps, eps=cfg.value_rescale_eps,
                 stack=stack_of(cfg))


def blocks(s: Dict):
    """[(kind, layer)] of the residual blocks in order, layers counted from 0
    (the published lists count from 1): a layer's mixer (`K` Kimi Delta
    Attention, `L` latent attention), then its MLP (`F` dense, `E` the mixture)."""
    full = set(s["linear_attn_config"]["full_attn_layers"])
    return [block for i in range(s["num_hidden_layers"])
            for block in (("L" if i + 1 in full else "K", i), ("F" if i < s["first_k_dense_replace"] else "E", i))]


# ----------------------------------------------------------------- operations


def layer_flops_per_token(s: Dict, seq_len: int) -> Dict[str, float]:
    """Multiply-accumulates (counted twice) that one token requires of one
    block of each kind. `K`: the three projections, the two low-rank gates,
    beta, the output projection, and what the recurrence itself needs,
    chunk-free: `S^T k`, the outer product written and `S^T q`, (H x dk x dv)
    each; not what a chunked form spends on its pair matrices and triangles.
    `L`: the query's and the latent's projections, the up-projection of the
    token's OWN latent to keys and values (once a position, as a sequence
    does it), the output projection, and causal scores and values over the
    sequence's own positions, (T + 1) / 2 keys a query on average (the
    remembered positions of a row's earlier windows are left out: a lower
    bound). `F`: three matrices. `E`: the router, the shared expert, and the
    routed experts at the BALANCED share of the experts held here (tokens x k
    x held / experts rows a layer), never the padded capacity."""
    D = s["hidden_size"]
    linear = s["linear_attn_config"]
    H, d = linear["num_heads"], linear["head_dim"]
    kda = 2 * D * 3 * H * d + 2 * 2 * (D * d + d * H * d) + 2 * D * H + 2 * H * d * D + 3 * 2 * H * d * d
    heads, nope, rope, value = (s[k] for k in ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    mla = (2 * D * heads * (nope + rope) + 2 * D * (s["kv_lora_rank"] + rope)
           + 2 * s["kv_lora_rank"] * heads * (nope + value) + 2 * heads * value * D
           + 2 * heads * (nope + rope + value) * (seq_len + 1) / 2)
    mlp = 3 * 2 * D * s["intermediate_size"]
    rows = s["num_experts_per_token"] * s["num_experts_held"] / s["num_experts"]
    moe = (2 * D * s["num_experts"] + 3 * 2 * D * s["moe_intermediate_size"] * s["num_shared_experts"]
           + rows * 3 * 2 * D * s["moe_intermediate_size"])
    return {"K": kda, "L": mla, "F": mlp, "E": moe}


def update_flops(cfg) -> int:
    """Operations one learner update requires, as flops.update_flops counts
    them for the other cores: the online net forward over T and backward (2 x)
    over the L learning frames, the target net forward over T; heads at 5 L
    positions. Recomputed layers are not counted."""
    s = stack_of(cfg)
    T, L = cfg.seq_len, cfg.learning_steps
    per_kind = layer_flops_per_token(s, T)
    stack = sum(per_kind[kind] for kind, _ in blocks(s))
    embed = 2 * (cfg.hidden_dim + cfg.action_dim + 1) * s["hidden_size"]
    trunk = flops.encoder_flops_per_frame(cfg.encoder, cfg.obs_shape, cfg.hidden_dim) + embed + stack
    heads = flops.heads_flops_per_step(cfg.hidden_dim, cfg.action_dim)
    return int(cfg.batch_size * (trunk * (T + 2 * L + T) + heads * 5 * L))


# ---------------------------------------------------------------- the layers


def norm(x, weight, eps):
    """`x rsqrt(mean(x^2) + eps) w` over the last axis."""
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


def unit(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


def kda_layer(p, x, state, tail, s: Dict, scalar_gate: bool = False):
    """x (B, T, D); state (B, H, dk, dv); tail (B, K - 1, 3 H d), oldest
    first, the inputs of the q, k and v convolutions side by side -> (the
    block's output, (state, tail) after the last step). `scalar_gate` is for
    the layer checks' control: what the output is when a head forgets all its
    key channels alike (the channel mean of g, a Gated DeltaNet's gate)."""
    linear = s["linear_attn_config"]
    H, d = linear["num_heads"], linear["head_dim"]
    u = norm(x, p["pre_norm"], s["rms_norm_eps"])
    qkv = jnp.concatenate([u @ p["q_proj"], u @ p["k_proj"], u @ p["v_proj"]], axis=-1)
    taps = jnp.concatenate([p["q_conv"], p["k_conv"], p["v_conv"]], axis=-1)             # (K, 3 H d)
    beta = jax.nn.sigmoid(u @ p["b_proj"])                                              # (B, T, H)
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus((u @ p["f_a"]) @ p["f_b"] + p["dt_bias"]).reshape(*x.shape[:2], H, d)
    if scalar_gate:
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    gate = jax.nn.sigmoid((u @ p["g_a"]) @ p["g_b"])

    def step(carry, inp):
        S, window = carry
        qkv_t, beta_t, g_t = inp                                       # (B, 3 H d), (B, H), (B, H, d)
        window = jnp.concatenate([window, qkv_t[:, None]], axis=1)     # the last K inputs
        conv = jax.nn.silu(jnp.sum(window * taps, axis=1)).reshape(-1, 3, H, d)
        q_t, k_t, v_t = unit(conv[:, 0]) * d ** -0.5, unit(conv[:, 1]), conv[:, 2]
        S = jnp.exp(g_t)[..., None] * S                                # each row of S by its own channel's decay
        r = jnp.sum(S * k_t[..., None], axis=2)                        # S^T k
        S = S + k_t[..., None] * (beta_t[..., None] * (v_t - r))[:, :, None, :]
        return (S, window[:, 1:]), jnp.sum(S * q_t[..., None], axis=2)  # S^T q (B, H, dv)

    over_time = lambda a: jnp.swapaxes(a, 0, 1)
    last, o = shared._loop_over_time(step, (state, tail), (over_time(qkv), over_time(beta), over_time(g)))
    o = norm(over_time(o), p["norm"], s["rms_norm_eps"])               # (B, T, H, dv), the norm over each head
    return x + (o.reshape(gate.shape) * gate) @ p["o_proj"], last


def rotate(x, positions, theta: float):
    """x (B, T, ..., dims) rotated to `positions` (B, T), rotate-half: what
    the published model does NOT do (`mla_use_nope`); the layer checks'
    control applies it."""
    dims = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, dims, 2) / dims)
    angle = (positions[..., None] * inv_freq).reshape(*positions.shape, *(1,) * (x.ndim - 3), dims // 2)
    cos, sin = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1), jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)
    half = jnp.concatenate([-x[..., dims // 2:], x[..., :dims // 2]], axis=-1)
    return x * cos + half * sin


def mla_layer(p, x, latent, seen, s: Dict, rotated: bool = False, ring_k_pe: bool = True):
    """x (B, T, D); latent (B, W, kv_lora_rank + rope) remembered, `[norm(c) |
    k_pe]` a position; seen (B,) how many positions the row has been through
    (the first min(seen, W) slots hold them). Keys and values are up-projected
    for every position, remembered and own, and one masked softmax attends.
    `rotated` and `ring_k_pe` are for the layer checks' controls: what the
    output is with a rotary embedding on `q_pe` and the sequence's `k_pe`, and
    without the remembered positions' `k_pe`. -> (output, what the sequence's
    own positions add to the memory (B, T, kv_lora_rank + rope))."""
    B, T, _ = x.shape
    heads, rank, W = s["num_attention_heads"], s["kv_lora_rank"], s["max_episode_steps"]
    nope, rope, value = s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    h = norm(x, p["pre_norm"], s["rms_norm_eps"])
    q = (h @ p["q_proj"]).reshape(B, T, heads, nope + rope)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    own = h @ p["kv_a_proj"]
    c, k_pe = norm(own[..., :rank], p["kv_a_norm"], s["rms_norm_eps"]), own[..., rank:]
    if rotated:
        positions = seen[:, None] + jnp.arange(T)
        q_pe, k_pe = rotate(q_pe, positions, ROPE_THETA), rotate(k_pe, positions, ROPE_THETA)
    new = jnp.concatenate([c, k_pe], axis=-1)
    memory = jnp.concatenate([latent if ring_k_pe else latent.at[..., rank:].set(0.0), new], axis=1)   # (B, W + T, rank + rope)
    kv = (memory[..., :rank] @ p["kv_b_proj"]).reshape(B, W + T, heads, nope + value)
    keys = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(memory[:, :, None, rank:], (B, W + T, heads, rope))], axis=-1)
    scores = jnp.einsum("bthd,bshd->bhts", jnp.concatenate([q_nope, q_pe], axis=-1), keys) / math.sqrt(nope + rope)
    remembered = jnp.arange(W)[None, None, :] < jnp.minimum(seen, W)[:, None, None]       # (B, 1, W)
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]                              # (T, T)
    allowed = jnp.concatenate([jnp.broadcast_to(remembered, (B, T, W)), jnp.broadcast_to(causal, (B, T, T))], axis=-1)
    probs = jax.nn.softmax(jnp.where(allowed[:, None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", probs, kv[..., nope:])
    return x + out.reshape(B, T, heads * value) @ p["o_proj"], new


def gated_mlp(tokens, gate, up, down):
    return (jax.nn.silu(tokens @ gate) * (tokens @ up)) @ down


def mlp_layer(p, x, s: Dict):
    return x + gated_mlp(norm(x, p["pre_norm"], s["rms_norm_eps"]), p["gate"], p["up"], p["down"])


def router(p, tokens, s: Dict):
    """tokens (n, D), normalised -> (sigmoid scores over all experts (n,
    experts), the top `num_experts_per_token` of score + bias (n, k))."""
    scores = jax.nn.sigmoid(tokens @ p["router"])
    return scores, jnp.argsort(-(scores + p["e_score_correction_bias"]), axis=-1)[:, :s["num_experts_per_token"]]


def moe_layer(p, x, s: Dict, drop: bool = True, chosen=None):
    """x (B, T, D). The held experts run one after the other on every token,
    under the mask of the assignments each one keeps. `chosen` (n, k), where
    given, is the choice of experts to follow in place of the router's own
    (the layer checks hand over the program's, so that a choice that rounding
    flipped does not stand between two outputs that are compared)."""
    shape = x.shape
    tokens = norm(x, p["pre_norm"], s["rms_norm_eps"]).reshape(-1, shape[-1])   # (b, t) order
    n = tokens.shape[0]
    scores, own = router(p, tokens, s)
    chosen = own if chosen is None else chosen
    member = jnp.any(chosen[:, :, None] == jnp.arange(s["num_experts"]), axis=1)   # (n, experts)
    # moe_renormalize, then routed_scaling_factor
    weights = scores / jnp.sum(jnp.where(member, scores, 0.0), axis=-1, keepdims=True) * s["routed_scaling_factor"]
    room = shared.capacity(s, n) if drop else n
    out = gated_mlp(tokens, p["shared_gate"], p["shared_up"], p["shared_down"])
    held = slice(s["first_expert_held"], s["first_expert_held"] + s["num_experts_held"])

    def one_expert(out, expert):
        gate, up, down, asks, weight = expert
        keeps = asks & (jnp.cumsum(asks) <= room)
        return out + jnp.where(keeps, weight, 0.0).astype(out.dtype)[:, None] * gated_mlp(tokens, gate, up, down), None

    experts = p["experts"]
    out, _ = jax.lax.scan(one_expert, out, (experts["gate"], experts["up"], experts["down"],
                                            member[:, held].T, weights[:, held].T))
    return x + out.reshape(shape)


def state_shapes(s: Dict):
    """For each block, the shapes of what one row stores for it, in the stored
    vector's order: a `K` block's state (H, dk, dv) then its last K - 1 conv
    inputs (q's, k's and v's side by side); an `L` block's latent ring (W,
    kv_lora_rank + rope); nothing for an MLP of either kind."""
    linear = s["linear_attn_config"]
    H, d = linear["num_heads"], linear["head_dim"]
    shapes = {"K": [(H, d, d), (linear["short_conv_kernel_size"] - 1, 3 * H * d)],
              "L": [(s["max_episode_steps"], s["kv_lora_rank"] + s["qk_rope_head_dim"])], "F": [], "E": []}
    return [shapes[kind] for kind, _ in blocks(s)]


def stored_state(hidden, s: Dict):
    """One row's stored vector -> what each block starts from, and how many
    positions the row has seen: the two numbers after the blocks' parts,
    (count // 128, count % 128)."""
    flat = hidden.reshape(hidden.shape[0], -1).astype(F32)
    out, at = [], 0
    for shapes in state_shapes(s):
        parts = []
        for shape in shapes:
            parts.append(flat[:, at:at + math.prod(shape)].reshape(-1, *shape))
            at += math.prod(shape)
        out.append(parts)
    return out, (flat[:, at] * 128 + flat[:, at + 1]).astype(jnp.int32)


def one_block(kind: str, p, x, start, seen, s: Dict, drop: bool):
    if kind == "K":
        return jax.checkpoint(lambda p, x, a, b: kda_layer(p, x, a, b, s)[0])(p, x, *start)
    if kind == "L":
        return jax.checkpoint(lambda p, x, a: mla_layer(p, x, a, seen, s)[0])(p, x, *start)
    if kind == "F":
        return jax.checkpoint(lambda p, x: mlp_layer(p, x, s))(p, x)
    return jax.checkpoint(lambda p, x: moe_layer(p, x, s, drop))(p, x)


def stack_outputs(pc, x, hidden, s: Dict, drop: bool = True):
    """pc: the core's parameters, each block under its kind and its layer
    (`kda_0`, `mlp_0`, `kda_1`, `moe_1`, ..., `mla_3`, `moe_3`, ...); x (B, T,
    latent + A + 1); hidden (B, 1, S) as the replay holds it -> the stack's
    outputs (B, T, hidden), block after block. Compile time, not mathematics:
    where the blocks after the dense layers start with a unit that repeats,
    those run as a `lax.scan` over the repetitions (`nemotron_h.repeats`)."""
    order = blocks(s)
    starts, seen = stored_state(hidden, s)
    params = [pc[f"{NAMES[kind]}_{i}"] for kind, i in order]
    x = x @ pc["in_proj"]
    first = 2 * s["first_k_dense_replace"]
    unit_, times = shared.repeats("".join(kind for kind, _ in order[first:]))
    u = len(unit_)
    for j in range(first):
        x = one_block(order[j][0], params[j], x, starts[j], seen, s, drop)
    if times:
        stacked = lambda per: jax.tree.map(lambda *v: jnp.stack(v),
                                           *[per[first + r * u:first + (r + 1) * u] for r in range(times)])

        def one_unit(x, params_and_starts):
            for kind, p, start in zip(unit_, *params_and_starts):
                x = one_block(kind, p, x, start, seen, s, drop)
            return x, None

        x, _ = jax.lax.scan(one_unit, x, (stacked(params), stacked(starts)))
    for j in range(first + u * times, len(order)):
        x = one_block(order[j][0], params[j], x, starts[j], seen, s, drop)
    return norm(x, pc["final_norm"], s["rms_norm_eps"])


# ------------------------------------------------------ the agent around them


def q_views(p, batch, sz: Sizes, drop: bool = True):
    """(q_learn, q_boot, mask), as reference/model.q_views gives them."""
    L, F = sz.learning, sz.forward
    obs = batch["obs"]
    B, T = obs.shape[:2]
    burn, learn, fwd = batch["burn_in"], batch["learning"], batch["forward"]
    x = shared.core_input(p, obs.reshape(B * T, *obs.shape[2:]), batch["last_action"].reshape(-1),
                          batch["last_reward"].reshape(-1), sz).reshape(B, T, -1)
    outs = stack_outputs(p["core"], x, batch["hidden"], sz.stack, drop)
    t = jnp.arange(L)
    learn_idx = jnp.clip(burn[:, None] + t[None], 0, T - 1)
    end = (burn + learn + fwd)[:, None] - 1
    boot_idx = jnp.clip(jnp.minimum(burn[:, None] + F + t[None], end), 0, T - 1)
    take = lambda idx: jnp.take_along_axis(outs, idx[:, :, None], axis=1)
    mask = (t[None] < learn[:, None]).astype(F32)
    return base.dueling(p, take(learn_idx)), base.dueling(p, take(boot_idx)), mask


loss_from_q = base.loss_from_q


def loss_and_q(params, target_params, batch, sz: Sizes):
    q_learn, q_boot, mask = q_views(params, batch, sz)
    _, q_boot_target, _ = q_views(target_params, batch, sz)
    batch = dict(batch, is_weights=batch["is_weights"].astype(F32))
    return loss_from_q(q_learn, q_boot, q_boot_target, mask, batch, sz), q_learn


def loss_q_gradnorm(params, target_params, batch, sz: Sizes):
    """-> (loss, q_learn (B, L, A), global gradient norm), all float32."""
    params, target_params = shared._f32(params), shared._f32(target_params)
    (loss, q_learn), grads = jax.value_and_grad(loss_and_q, has_aux=True)(params, target_params, batch, sz)
    return loss, q_learn, jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))


def island_inputs(params, target_params, batch, sz: Sizes) -> Dict:
    params, target_params = shared._f32(params), shared._f32(target_params)
    q_learn, q_boot, mask = q_views(params, batch, sz)
    return {"q_learn": q_learn, "q_boot": q_boot, "mask": mask,
            "q_boot_target": q_views(target_params, batch, sz)[1]}


def act_unroll(params, obs, last_action, last_reward, sz: Sizes) -> jnp.ndarray:
    """Acting from the zero state: (S, T, ...) -> Q (S, T, A). One step at a
    time an expert is never offered more than its capacity, so nothing drops."""
    S, T = obs.shape[:2]
    n = sum(math.prod(shape) for shapes in state_shapes(sz.stack) for shape in shapes) + 2
    params = shared._f32(params)
    x = shared.core_input(params, obs.reshape(S * T, *obs.shape[2:]), last_action.reshape(-1),
                          last_reward.reshape(-1), sz).reshape(S, T, -1)
    return base.dueling(params, stack_outputs(params["core"], x, jnp.zeros((S, 1, n), F32), sz.stack, drop=False))


# ------------------------------------------------- the layers, one by one

# What `correct`'s whole-program numbers cannot tell (a top-k choice that
# rounding flipped owns the largest Q error, whatever the precision: the
# configuration's `limits_why`), these tell: the program's blocks against this
# file's, one kind at a time, with the program's choice of experts handed
# over. compute dtype -> limits; the readings behind the bfloat16 row are in
# the configuration's `layer_limits_why` and PERF.md finding 60.
# Three regimes of the `K` block: its own initialisation on independent draws
# (`kda_*`); every head's decay slowed to exp(SLOW_A_LOG) on an agent's
# inputs (one row a sequence plus a twentieth of noise a step: consecutive
# keys hardly differ, the chunk's triangle is near `beta` times all ones, and
# a state that cannot forget keeps every rounding: `kda_slow_*`, where the
# all-bfloat16 control is told); and gates as negative as the initialiser
# allows (every head's `A_log` at log 16 and every channel's `dt_bias` at its
# largest, a decay of e^-10 and beyond a step wherever the input's projection
# is large: `kda_fast_*`, where a chunk form that exponentiated anything
# positive would overflow).
# `*_step_*` is the acting step iterated over the first STEPS positions from
# the same stored state, against this file's SEQUENCE form: for `L` that is
# the absorbed step against keys and values up-projected for every position.
LAYER_LIMITS = {
    "bfloat16": {"router_score_err": 3e-5, "kda_out_err_over_scale": 8e-3, "kda_state_err_over_scale": 1.8e-2,
                 "kda_slow_out_err_over_scale": 9e-3, "kda_slow_state_err_over_scale": 1.2e-2,
                 "kda_fast_out_err_over_scale": 6e-3, "kda_fast_state_err_over_scale": 2.2e-2,
                 "kda_step_out_err_over_scale": 7e-3, "mlp_out_err_over_scale": 1e-2,
                 "moe_out_err_over_scale": 1.3e-2, "mla_out_err_over_scale": 1.2e-2,
                 "mla_latent_err_over_scale": 1.5e-2, "mla_step_out_err_over_scale": 1.3e-2},
    "float32": {"router_score_err": 1e-5, "kda_out_err_over_scale": 1e-4, "kda_state_err_over_scale": 1e-4,
                "kda_slow_out_err_over_scale": 1e-4, "kda_slow_state_err_over_scale": 1e-4,
                "kda_fast_out_err_over_scale": 1e-4, "kda_fast_state_err_over_scale": 1e-4,
                "kda_step_out_err_over_scale": 1e-4, "mlp_out_err_over_scale": 1e-4,
                "moe_out_err_over_scale": 1e-4, "mla_out_err_over_scale": 1e-4,
                "mla_latent_err_over_scale": 1e-4, "mla_step_out_err_over_scale": 1e-4},
}
SLOW_A_LOG = -6.0
FAST_A_LOG = math.log(16.0)
FAST_DT_BIAS = math.log(math.expm1(0.1))   # the inverse softplus of the largest step the initialiser draws
CHECK_ROWS = 2
STEPS = 16
COMPARED = ("kda_out", "kda_state", "kda_slow_out", "kda_slow_state", "kda_fast_out", "kda_fast_state",
            "kda_step_out", "mlp_out", "moe_out", "mla_out", "mla_latent", "mla_step_out")


def _slow(p):
    """A `K` block's parameters with every head's decay rate at exp(SLOW_A_LOG)."""
    return dict(p, A_log=jnp.full_like(p["A_log"], SLOW_A_LOG))


def _fast(p):
    """A `K` block's parameters with the gates as negative as the initialiser allows."""
    return dict(p, A_log=jnp.full_like(p["A_log"], FAST_A_LOG), dt_bias=jnp.full_like(p["dt_bias"], FAST_DT_BIAS))


def _reference_blocks(params, tokens, chosen, x, slow_x, delta, tail, latent, seen, s):
    """This file's four blocks, one after the other as a stack has them (`K`,
    `F`, `E`, `L`), on whatever precision the arguments come in; the `K` block
    twice more, with heads that hardly forget on an agent's inputs (`slow_x`)
    and with gates as negative as they come; and the controls that leave a
    mechanism out or put one in."""
    p = {kind: tree["params"] for kind, tree in params.items()}
    after_k, (state, _) = kda_layer(p["K"], x, delta, tail, s)
    slow_out, (slow_state, _) = kda_layer(_slow(p["K"]), slow_x, delta, tail, s)
    fast_out, (fast_state, _) = kda_layer(_fast(p["K"]), x, delta, tail, s)
    after_f = mlp_layer(p["F"], after_k, s)
    own = router(p["E"], norm(after_f, p["E"]["pre_norm"], s["rms_norm_eps"]).reshape(tokens.shape), s)[1]
    after_e = moe_layer(p["E"], after_f, s, chosen=chosen)
    after_l, new = mla_layer(p["L"], after_e, latent, seen, s)
    return {"kda_out": after_k, "kda_state": state, "kda_slow_out": slow_out, "kda_slow_state": slow_state,
            "kda_fast_out": fast_out, "kda_fast_state": fast_state, "kda_step_out": after_k[:, :STEPS],
            "mlp_out": after_f, "scores": router(p["E"], tokens, s)[0], "chosen": own, "moe_out": after_e,
            "mla_out": after_l, "mla_latent": new, "mla_step_out": after_l[:, :STEPS],
            "kda_scalar_gate": kda_layer(p["K"], x, delta, tail, s, scalar_gate=True)[0],
            "mla_without_ring_k_pe": mla_layer(p["L"], after_e, latent, seen, s, ring_k_pe=False)[0],
            "mla_rotated": mla_layer(p["L"], after_e, latent, seen, s, rotated=True)[0]}


def kernel_checks(cfg, seed: int, batch: int) -> Dict:
    """The program's four block kinds (models/hybrid_stack.py, imported here
    and nowhere else in this file) against this file's, at the
    configuration's widths and sequence length on seeded weights, inputs and
    stored state, CHECK_ROWS rows, one after the other as a stack has them:
    `K`, then `F`, `E` and `L`, each on what its side's block before it gave.
    Compared: the `K` block's output and its state after the last step (the
    chunked form against the loop over time), in the three regimes the table
    above LAYER_LIMITS names; the router's scores on the SAME tokens (the
    program's); each later block's output, this file's `E` following the
    program's choice of experts; what the `L` block writes to its ring; and
    both mixers' acting `step`, iterated over the first STEPS positions from
    the same stored state, against this file's sequence form (the absorbed
    step against keys and values up-projected for every position).
    `router_flip_share` is recorded, not judged. THE CONTROLS run beside it,
    on every seed, and each has to be told: `control_bfloat16`, this file's
    blocks fed bfloat16 weights, inputs and state at the default precision
    (the reference one precision down), must read over the router's limit and
    over the slow state's; a `K` block whose heads forget all their key
    channels alike (the channel mean of g: a scalar gate) must read over its
    output's limit; and an `L` block without the remembered positions' `k_pe`,
    and one with a rotary embedding applied, over that output's."""
    from r2d2_tpu.models import hybrid_stack as hs

    s, spec = stack_of(cfg), hs.spec_of(cfg)
    dtype = jnp.dtype(cfg.resolved_compute_dtype)
    B, T, D, W = min(batch, CHECK_ROWS), cfg.seq_len, s["hidden_size"], s["max_episode_steps"]
    one_of_each = dict(s, num_hidden_layers=2, first_k_dense_replace=1,
                       linear_attn_config=dict(s["linear_attn_config"], kda_layers=[1], full_attn_layers=[2]))
    (delta_shape, tail_shape), _, (latent_shape,), _ = state_shapes(one_of_each)       # blocks K, F, L, E
    rng = np.random.default_rng(seed)
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape).astype(np.float32))
    x, delta, tail = normal(B, T, D), 0.5 * normal(B, *delta_shape), normal(B, *tail_shape)
    slow_x = normal(B, 1, D) + 0.05 * normal(B, T, D)   # an agent's: one frame after another, hardly different
    latent = normal(B, *latent_shape)
    seen = jnp.asarray(rng.integers(0, max(W - T, 0) + 1, size=B), jnp.int32)  # positions the rows have been through
    layers = {kind: hs.KINDS[kind][1](spec.sizes(kind), dtype) for kind in "KFEL"}
    args = {"K": (x, delta, tail), "F": (x,), "E": (x,), "L": (x, latent, seen)}
    params = {kind: jax.jit(layers[kind].init)(jax.random.PRNGKey(seed + i), *args[kind])
              for i, kind in enumerate("KFEL")}
    # norm weights away from the one they start at, so that a forgotten weight shows
    params = jax.tree.map(lambda v: v + 0.1 * jnp.asarray(rng.normal(size=v.shape), F32) if v.ndim == 1 else v, params)

    def steps(layer, p, xs, *state, count=None):
        """`layer.step` over the leading positions of xs (B, STEPS, D) -> its outputs (B, STEPS, D)."""
        def one(carry, inp):
            x_t, t = inp
            out, *carry = layer.apply(p, x_t, *carry, *(() if count is None else (count + t,)), method="step")
            return tuple(carry), out

        return jnp.swapaxes(jax.lax.scan(one, state, (jnp.swapaxes(xs, 0, 1), jnp.arange(xs.shape[1])))[1], 0, 1)

    def program(params, x, slow_x, delta, tail, latent, seen):
        after_k, state, _ = layers["K"].apply(params["K"], x, delta, tail)
        slow_out, slow_state, _ = layers["K"].apply({"params": _slow(params["K"]["params"])}, slow_x, delta, tail)
        fast_out, fast_state, _ = layers["K"].apply({"params": _fast(params["K"]["params"])}, x, delta, tail)
        after_f = layers["F"].apply(params["F"], after_k)
        tokens = hs.rms_norm(after_f, params["E"]["params"]["pre_norm"], s["rms_norm_eps"]).reshape(-1, D)
        scores, chosen = layers["E"].apply(params["E"], tokens, method="scores")
        after_e, _ = layers["E"].apply(params["E"], after_f)
        after_l, ring = layers["L"].apply(params["L"], after_e, latent, seen)
        own = jax.vmap(lambda row, at: jnp.take(row, (at + jnp.arange(T)) % W, axis=0))(ring, seen)
        return {"kda_out": after_k, "kda_state": state, "kda_slow_out": slow_out, "kda_slow_state": slow_state,
                "kda_fast_out": fast_out, "kda_fast_state": fast_state,
                "kda_step_out": steps(layers["K"], params["K"], x[:, :STEPS], delta, tail),
                "mlp_out": after_f, "tokens": tokens, "scores": scores, "chosen": chosen, "moe_out": after_e,
                "mla_out": after_l, "mla_latent": own,
                "mla_step_out": steps(layers["L"], params["L"], after_e[:, :STEPS], latent, count=seen)}

    inputs = (x, slow_x, delta, tail, latent, seen)
    got = jax.jit(program)(params, *inputs)
    reference = jax.jit(lambda *a: _reference_blocks(*a, s))
    with jax.default_matmul_precision("highest"):
        want = reference(params, got["tokens"], got["chosen"], *inputs)
    low = lambda tree: jax.tree.map(lambda v: v.astype(jnp.bfloat16) if v.dtype == F32 else v, tree)
    control = reference(low(params), low(got["tokens"]), got["chosen"], *low(inputs))
    got, want, control = jax.device_get((got, want, jax.tree.map(lambda v: v.astype(F32) if v.dtype == jnp.bfloat16 else v,
                                                                 control)))

    def readings(side):
        out = {name + "_err_over_scale": correct.scale_err(side[name], want[name]) for name in COMPARED}
        out["router_score_err"] = float(np.max(np.abs(side["scores"] - want["scores"])))
        return out

    out = readings(got)
    out["router_flip_share"] = float(np.mean(np.any(np.sort(got["chosen"], 1) != np.sort(want["chosen"], 1), axis=1)))
    out["limits"] = limits = dict(LAYER_LIMITS[dtype.name])
    out["rows_steps"] = [B, T]
    out["control_bfloat16"] = readings(control)
    out["control_scalar_gate_err_over_scale"] = correct.scale_err(want["kda_scalar_gate"], want["kda_out"])
    out["control_ring_k_pe_dropped_err_over_scale"] = correct.scale_err(want["mla_without_ring_k_pe"], want["mla_out"])
    out["control_rotated_err_over_scale"] = correct.scale_err(want["mla_rotated"], want["mla_out"])
    over = lambda value, name: not (np.isfinite(value) and value <= limits[name])
    out["controls_told"] = bool(over(out["control_bfloat16"]["router_score_err"], "router_score_err")
                                and over(out["control_bfloat16"]["kda_slow_state_err_over_scale"], "kda_slow_state_err_over_scale")
                                and over(out["control_scalar_gate_err_over_scale"], "kda_out_err_over_scale")
                                and over(out["control_ring_k_pe_dropped_err_over_scale"], "mla_out_err_over_scale")
                                and over(out["control_rotated_err_over_scale"], "mla_out_err_over_scale"))
    out["ok"] = bool(not any(over(out[k], k) for k in limits) and out["controls_told"])
    return out
