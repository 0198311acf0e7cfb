"""Plain float32 reference of a `qwen3_next` layer stack in the agent's core slot.

Written from the equations of the published model (config.json of
Qwen/Qwen3-Next-80B-A3B-Instruct, `model_type: qwen3_next`; the linear mixer:
Yang, Kautz & Hatamizadeh 2024, "Gated Delta Networks", equation 10's
recurrence) and not from the program: straightforward `jax.numpy`, the delta
rule as a loop over time (`lax.scan`, one step at a time), the experts one
after the other (a `lax.scan` over them) on every token under a mask,
attention as one masked softmax with the rotary embedding at each token's
absolute position in its episode. It shares with the program only the
parameter tree's names and the order of one row's stored state, so that the
same seeded weights and the same stored sequences feed both. What is every
cell's (Nature encoder, dueling heads, the n-step double-Q loss under the
value rescaling) is `reference/model.py`'s, and what is both stacks' (the
encoder's convolutions as shifted matmuls, the checkpointed loop over time,
the unit of a pattern that repeats, the static capacity) is
`reference/nemotron_h.py`'s: imported, not repeated.

Layer `i` of `num_hidden_layers` mixes by attention where `(i + 1) %
full_attention_interval == 0` and by the gated delta rule otherwise, and every
layer's MLP is the mixture: `x <- x + mixer(norm(x)); x <- x + mixture(norm(
x))`, between an input projection and a final norm. `norm(x) = x rsqrt(mean(
x^2) + rms_norm_eps) (1 + w)`.

- gated delta rule (`gdn_<i>`): `[q | k | v | z] = in_proj_qkvz(u)`, `[b | a] =
  in_proj_ba(u)`; `[q | k | v] <- silu(conv([q | k | v]))`, a causal depthwise
  convolution over the last `linear_conv_kernel_dim` inputs, no bias; `beta =
  sigmoid(b)`, `g = -exp(A_log) softplus(a + dt_bias)` per value head; q and k
  L2-normalised per head (`x rsqrt(sum(x^2) + 1e-6)`), each key head serving
  `value heads / key heads` value heads in a row, q scaled by `dk^-1/2`; per
  value head, S (dk, dv): `S <- exp(g_t) S; r = S^T k_t; S <- S + k_t (beta_t
  (v_t - r))^T; o_t = S^T q_t`; `out_proj(norm_w(o_t) silu(z_t))`, the norm
  over each head's dv, scaled by `w`.
- gated attention (`attention_<i>`): `q_proj(u)` is each head's `[query |
  gate]`; query and key normed per head, then their first `partial_rotary_factor
  x head_dim` dimensions rotated (rotate-half, `inv_freq_i = rope_theta^(-2 i /
  rotary_dim)`) at the token's position; `softmax(q k^T / sqrt(head_dim)) v`,
  causal; times `sigmoid(gate)`; `o_proj`.
- mixture (`moe_<i>`): `p = softmax(x W_r)` over all experts; the top
  `num_experts_per_tok` of p, their weights divided by their sum; expert
  `W_down (silu(W_gate x) * W_up x)`; plus `sigmoid(x w_s) shared(x)`.

Every caller wraps these in `jax.default_matmul_precision("highest")`.

Departures from the published model, each because the configuration states
it (`assumed` / `reduced` in benchmark/configs/) and the program does the
same:
- column order. The checkpoint interleaves `in_proj_qkvz`'s and
  `in_proj_ba`'s columns by key head; that is a permutation of a random
  matrix's columns and no equation: here they are `[q | k | v | z]`, `[b | a]`.
- no multi-token-prediction head: `config.json` does not describe it.
- the attention memory. A row's stored state holds the keys (after the norm
  and the rotation, each at its own position) and values of its last
  `max_episode_steps` positions (a ring: softmax does not ask in which order)
  and how many positions it has seen; a sequence's queries see the valid part
  of it and the sequence causally, and the count is where the sequence's
  positions start. The memory is as long as an episode, so that is full
  causal attention over the episode.
- the share. This chip holds experts `[first_expert_held, + num_experts_held)`
  of `num_experts`: the router scores all, and what the others would add is
  left out, here as there.
- the capacity. Each held expert takes at most `C` assignments a call
  (`nemotron_h.capacity`); a token's assignment beyond it, in flattened (b, t)
  order, is dropped. The published model drops nothing; static shapes on a
  TPU are the reason.
- the input projection `(latent + A + 1) -> hidden` stands for the token
  embedding, and the dueling heads for the vocabulary's.
- burn-in is backpropagated through (no seam), as for the LRU core.
- memory and compile time, not mathematics: as `nemotron_h.py` (the loop over
  time and each layer checkpointed; the three `(gdn, moe)` pairs one
  `lax.scan`).

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
NAMES = {"D": "gdn", "E": "moe", "*": "attention"}


def stack_of(cfg) -> Dict:
    s = dict(cfg.core_config)
    s.setdefault("num_experts_held", s["num_experts"])
    s.setdefault("first_expert_held", 0)
    s.setdefault("capacity_factor", 2.0)
    s["max_episode_steps"] = cfg.max_episode_steps  # the length of the attention's memory
    # under nemotron_h's names too, for its `capacity`
    s["n_routed_experts"] = s["num_experts"]
    return s


def sizes_of(cfg) -> Sizes:
    return Sizes(encoder=cfg.encoder, hidden=cfg.hidden_dim, action_dim=cfg.action_dim,
                 learning=cfg.learning_steps, forward=cfg.forward_steps, eps=cfg.value_rescale_eps,
                 stack=stack_of(cfg))


def blocks(s: Dict):
    """[(kind, layer)] of the residual blocks in order: a layer's mixer (`*`
    attention, `D` the delta rule), then its mixture `E`."""
    mixer = lambda i: "*" if (i + 1) % s["full_attention_interval"] == 0 else "D"
    return [block for i in range(s["num_hidden_layers"]) for block in ((mixer(i), i), ("E", i))]


# ----------------------------------------------------------------- operations


def layer_flops_per_token(s: Dict, seq_len: int) -> Dict[str, float]:
    """Multiply-accumulates (counted twice) that one token requires of one
    block of each kind. `D`: the three projections and what the recurrence
    itself needs, chunk-free: `S^T k`, the outer product written and `S^T q`,
    (Hv x dk x dv) each; not what a chunked form spends on its triangles.
    `E`: the router, the shared expert and its gate, and the routed experts at
    the BALANCED share of the experts held here (tokens x k x held / experts
    rows a layer), never the padded capacity. `*`: the four projections (the
    query's with its gate) and causal scores and values over the sequence's
    own positions, (T + 1) / 2 keys a query on average (the remembered keys
    of a row's earlier windows are left out: a lower bound)."""
    D = s["hidden_size"]
    keys = s["linear_num_key_heads"] * s["linear_key_head_dim"]
    values = s["linear_num_value_heads"] * s["linear_value_head_dim"]
    delta = (2 * D * (2 * keys + 2 * values + 2 * s["linear_num_value_heads"]) + 2 * values * D
             + 3 * 2 * values * s["linear_key_head_dim"])
    rows = s["num_experts_per_tok"] * s["num_experts_held"] / s["num_experts"]
    moe = (2 * D * s["num_experts"] + 3 * 2 * D * s["shared_expert_intermediate_size"] + 2 * D
           + rows * 3 * 2 * D * s["moe_intermediate_size"])
    q_width = s["num_attention_heads"] * s["head_dim"]
    kv_width = s["num_key_value_heads"] * s["head_dim"]
    attention = 2 * D * (3 * q_width + 2 * kv_width) + 2 * 2 * q_width * (seq_len + 1) / 2
    return {"D": delta, "E": moe, "*": attention}


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
    """`x rsqrt(mean(x^2) + eps) (1 + w)` over the last axis."""
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * (1.0 + weight)


def unit(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


def delta_layer(p, x, state, tail, s: Dict):
    """x (B, T, D); state (B, Hv, dk, dv); tail (B, K - 1, conv_dim), oldest
    first -> (the block's output, (state, tail) after the last step)."""
    Hk, dk = s["linear_num_key_heads"], s["linear_key_head_dim"]
    Hv, dv = s["linear_num_value_heads"], s["linear_value_head_dim"]
    u = norm(x, p["pre_norm"], s["rms_norm_eps"])
    qkvz, ba = u @ p["in_proj_qkvz"], u @ p["in_proj_ba"]
    qkv, z = qkvz[..., :2 * Hk * dk + Hv * dv], qkvz[..., 2 * Hk * dk + Hv * dv:]
    rate = jnp.exp(p["A_log"])

    def step(carry, inp):
        S, window = carry
        qkv_t, ba_t = inp                                              # (B, conv_dim), (B, 2 Hv)
        window = jnp.concatenate([window, qkv_t[:, None]], axis=1)     # the last K inputs
        conv = jax.nn.silu(jnp.sum(window * p["conv_weight"], axis=1))
        q_t = jnp.repeat(unit(conv[:, :Hk * dk].reshape(-1, Hk, dk)) * dk ** -0.5, Hv // Hk, axis=1)
        k_t = jnp.repeat(unit(conv[:, Hk * dk:2 * Hk * dk].reshape(-1, Hk, dk)), Hv // Hk, axis=1)
        v_t = conv[:, 2 * Hk * dk:].reshape(-1, Hv, dv)
        beta = jax.nn.sigmoid(ba_t[:, :Hv])
        g = -rate * jax.nn.softplus(ba_t[:, Hv:] + p["dt_bias"])
        S = jnp.exp(g)[:, :, None, None] * S
        r = jnp.sum(S * k_t[..., None], axis=2)                        # S^T k
        S = S + k_t[..., None] * (beta[..., None] * (v_t - r))[:, :, None, :]
        return (S, window[:, 1:]), jnp.sum(S * q_t[..., None], axis=2)  # S^T q (B, Hv, dv)

    last, o = shared._loop_over_time(step, (state, tail), (jnp.swapaxes(qkv, 0, 1), jnp.swapaxes(ba, 0, 1)))
    o = jnp.swapaxes(o, 0, 1)                                          # (B, T, Hv, dv)
    o = o / jnp.sqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + s["rms_norm_eps"]) * p["norm"]
    return x + (o.reshape(z.shape) * jax.nn.silu(z)) @ p["out_proj"], last


def router(p, tokens, s: Dict):
    """tokens (n, D), normalised -> (softmax over all experts (n, experts),
    the top `num_experts_per_tok` of it (n, k))."""
    scores = jax.nn.softmax(tokens @ p["router"], axis=-1)
    return scores, jnp.argsort(-scores, axis=-1)[:, :s["num_experts_per_tok"]]


def gated_mlp(tokens, gate, up, down):
    return (jax.nn.silu(tokens @ gate) * (tokens @ up)) @ down


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
    weights = scores / jnp.sum(jnp.where(member, scores, 0.0), axis=-1, keepdims=True)   # norm_topk_prob
    room = shared.capacity(s, n) if drop else n
    out = jax.nn.sigmoid(tokens @ p["shared_expert_gate"]) * gated_mlp(
        tokens, p["shared_gate"], p["shared_up"], p["shared_down"])
    held = slice(s["first_expert_held"], s["first_expert_held"] + s["num_experts_held"])

    def one_expert(out, expert):
        gate, up, down, asks, weight = expert
        keeps = asks & (jnp.cumsum(asks) <= room)
        return out + jnp.where(keeps, weight, 0.0).astype(out.dtype)[:, None] * gated_mlp(tokens, gate, up, down), None

    experts = p["experts"]
    out, _ = jax.lax.scan(one_expert, out, (experts["gate"], experts["up"], experts["down"],
                                            member[:, held].T, weights[:, held].T))
    return x + out.reshape(shape)


def rotate(x, positions, s: Dict):
    """x (B, T, heads, Dh): its first `partial_rotary_factor x Dh` dimensions
    rotated to `positions` (B, T), rotate-half."""
    dims = int(s["head_dim"] * s["partial_rotary_factor"])
    inv_freq = 1.0 / s["rope_theta"] ** (jnp.arange(0, dims, 2) / dims)
    angle = (positions[..., None] * inv_freq).astype(x.dtype)[:, :, None, :]   # (B, T, 1, dims / 2)
    cos, sin = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1), jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)
    part, rest = x[..., :dims], x[..., dims:]
    half = jnp.concatenate([-part[..., dims // 2:], part[..., :dims // 2]], axis=-1)
    return jnp.concatenate([part * cos + half * sin, rest], axis=-1)


def attention_layer(p, x, keys, values, seen, s: Dict, gated: bool = True, rotated: bool = True):
    """x (B, T, D); keys (normed and rotated), values (B, W, KV, Dh)
    remembered; seen (B,) how many positions the row has been through (the
    first min(seen, W) slots hold them; the sequence's own are at `seen + t`).
    `gated` and `rotated` are for the layer checks' controls: what the output
    is without the gate or the rotation. -> (output, the sequence's own keys)."""
    B, T, _ = x.shape
    heads, kv, dh, W = s["num_attention_heads"], s["num_key_value_heads"], s["head_dim"], s["max_episode_steps"]
    h = norm(x, p["pre_norm"], s["rms_norm_eps"])
    q, gate = jnp.split((h @ p["q_proj"]).reshape(B, T, heads, 2 * dh), 2, axis=-1)
    q = norm(q, p["q_norm"], s["rms_norm_eps"])
    k = norm((h @ p["k_proj"]).reshape(B, T, kv, dh), p["k_norm"], s["rms_norm_eps"])
    if rotated:
        positions = seen[:, None] + jnp.arange(T)
        q, k = rotate(q, positions, s), rotate(k, positions, s)
    all_k = jnp.concatenate([keys, k], axis=1)
    all_v = jnp.concatenate([values, (h @ p["v_proj"]).reshape(B, T, kv, dh)], axis=1)
    all_k, all_v = jnp.repeat(all_k, heads // kv, axis=2), jnp.repeat(all_v, heads // kv, axis=2)
    scores = jnp.einsum("bthd,bshd->bhts", q, all_k) / math.sqrt(dh)
    remembered = jnp.arange(W)[None, None, :] < jnp.minimum(seen, W)[:, None, None]       # (B, 1, W)
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]                              # (T, T)
    allowed = jnp.concatenate([jnp.broadcast_to(remembered, (B, T, W)), jnp.broadcast_to(causal, (B, T, T))], axis=-1)
    probs = jax.nn.softmax(jnp.where(allowed[:, None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", probs, all_v)
    if gated:
        out = out * jax.nn.sigmoid(gate)
    return x + out.reshape(B, T, heads * dh) @ p["o_proj"], k


def state_shapes(s: Dict):
    """For each block, the shapes of what one row stores for it, in the stored
    vector's order: a `D` block's state (Hv, dk, dv) then its last K - 1 conv
    inputs; a `*` block's keys then its values (W, KV, Dh)."""
    conv_dim = (2 * s["linear_num_key_heads"] * s["linear_key_head_dim"]
                + s["linear_num_value_heads"] * s["linear_value_head_dim"])
    shapes = {"D": [(s["linear_num_value_heads"], s["linear_key_head_dim"], s["linear_value_head_dim"]),
                    (s["linear_conv_kernel_dim"] - 1, conv_dim)],
              "E": [], "*": [(s["max_episode_steps"], s["num_key_value_heads"], s["head_dim"])] * 2}
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
    if kind == "D":
        return jax.checkpoint(lambda p, x, a, b: delta_layer(p, x, a, b, s)[0])(p, x, *start)
    if kind == "E":
        return jax.checkpoint(lambda p, x: moe_layer(p, x, s, drop))(p, x)
    return jax.checkpoint(lambda p, x, a, b: attention_layer(p, x, a, b, seen, s)[0])(p, x, *start)


def stack_outputs(pc, x, hidden, s: Dict, drop: bool = True):
    """pc: the core's parameters, each block under its kind and its layer
    (`gdn_0`, `moe_0`, ..., `attention_3`, `moe_3`); x (B, T, latent + A + 1);
    hidden (B, 1, S) as the replay holds it -> the stack's outputs (B, T,
    hidden), block after block. Compile time, not mathematics: where the
    blocks start with a unit that repeats, those run as a `lax.scan` over the
    repetitions (`nemotron_h.repeats`)."""
    order = blocks(s)
    pattern = "".join(kind for kind, _ in order)
    starts, seen = stored_state(hidden, s)
    params = [pc[f"{NAMES[kind]}_{i}"] for kind, i in order]
    x = x @ pc["in_proj"]
    unit_, times = shared.repeats(pattern)
    u = len(unit_)
    if times:
        stacked = lambda per: jax.tree.map(lambda *v: jnp.stack(v), *[per[r * u:(r + 1) * u] for r in range(times)])

        def one_unit(x, params_and_starts):
            for kind, p, start in zip(unit_, *params_and_starts):
                x = one_block(kind, p, x, start, seen, s, drop)
            return x, None

        x, _ = jax.lax.scan(one_unit, x, (stacked(params), stacked(starts)))
    for j in range(u * times, len(order)):
        x = one_block(pattern[j], params[j], x, starts[j], seen, s, drop)
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
    batch = {"obs": obs, "last_action": last_action, "last_reward": last_reward, "hidden": jnp.zeros((S, 1, n), F32)}
    params = shared._f32(params)
    x = shared.core_input(params, obs.reshape(S * T, *obs.shape[2:]), last_action.reshape(-1),
                          last_reward.reshape(-1), sz).reshape(S, T, -1)
    return base.dueling(params, stack_outputs(params["core"], x, batch["hidden"], sz.stack, drop=False))


# ------------------------------------------------- the layers, one by one

# What `correct`'s whole-program numbers cannot tell (a top-k choice that
# rounding flipped owns the largest Q error, whatever the precision: the
# configuration's `limits_why`), these tell: the program's blocks against this
# file's, one kind at a time, with the program's choice of experts handed
# over. compute dtype -> limits. bfloat16, from readings on the v5e at
# published widths (my chip runs, PR 56, calls 155, 156 and 159: 32 seeds, the
# last 22 with the forward substitution; PERF.md finding 56.6): the sound
# program's largest | the smallest of the control, this
# file's blocks computed in bfloat16 throughout (weights, inputs, state, every
# norm, softmax, the router, the rotation's angles), which `kernel_checks`
# runs beside the program on every seed:
#   router_score_err               0.0     | 0.86e-3
#   gdn_state_err_over_scale       7.8e-3  | 6.2e-3
#   gdn_out_err_over_scale         7.6e-3  | 6.6e-3
#   moe_out_err_over_scale         7.2e-3  | 7.4e-3
#   attention_out_err_over_scale   7.3e-3  | 8.4e-3; the same block without its output gate reads 3.05e-2 at least
#   attention_keys_err_over_scale  8.5e-3  | 8.4e-3; the keys without their rotation read 1.3 of their scale at least
#   gdn_slow_state_err_over_scale  3.4e-3  | 2.06e-1 (ten seeds, call 159)
#   gdn_slow_out_err_over_scale    4.5e-3  | 9.95e-2 (ten seeds, call 159)
# The control has to come out over the router's limit AND over the slow
# state's; the limits of rows 2 to 6 are tripwires at 2 to 3 times the
# program's largest, which tell wrong mathematics (a gate, a rotation, a
# norm's `1 +`) and not precision. The first six rows read the `D` block on independent draws with
# the decay rates it is initialised with (`exp(A_log)` from U(0, 16): most
# heads forget within a few steps), and there a recurrence in bfloat16 is NOT
# told from the sound program (rows 2 and 3 overlap): the delta rule contracts
# (`I - beta k k^T`, times `exp(g)`) and forgets rounding as it forgets state.
# The last two rows read the regime in which it cannot forget: every head's
# `A_log` set to SLOW_A_LOG (a decay of `exp(-0.0025 softplus(.))` a step, the
# long memory the layer is there for) on an agent's inputs (one row a sequence
# plus a twentieth of noise a step, so consecutive keys hardly differ and the
# chunk's triangle is near `beta` times all ones). A float32 state fed
# bfloat16 operands reads a few thousandths there; a bfloat16 state, which
# rounds 581 times what it cannot forget, a tenth of its scale or more.
# float32 (no cell; the CPU tests at tiny widths read under 1e-5 sound).
LAYER_LIMITS = {
    "bfloat16": {"router_score_err": 3e-5, "gdn_state_err_over_scale": 2e-2, "gdn_out_err_over_scale": 2e-2,
                 "moe_out_err_over_scale": 2e-2, "attention_out_err_over_scale": 1.6e-2,
                 "attention_keys_err_over_scale": 2.5e-2,
                 "gdn_slow_state_err_over_scale": 2e-2, "gdn_slow_out_err_over_scale": 2e-2},
    "float32": {"router_score_err": 1e-5, "gdn_state_err_over_scale": 1e-4, "gdn_out_err_over_scale": 1e-4,
                "moe_out_err_over_scale": 1e-4, "attention_out_err_over_scale": 1e-4,
                "attention_keys_err_over_scale": 1e-4,
                "gdn_slow_state_err_over_scale": 1e-4, "gdn_slow_out_err_over_scale": 1e-4},
}
SLOW_A_LOG = -6.0
CHECK_ROWS = 2
COMPARED = ("gdn_out", "gdn_state", "moe_out", "attention_out", "attention_keys", "gdn_slow_out", "gdn_slow_state")


def _slow(p):
    """A `D` block's parameters with every head's decay rate at exp(SLOW_A_LOG)."""
    return dict(p, A_log=jnp.full_like(p["A_log"], SLOW_A_LOG))


def _reference_blocks(params, tokens, chosen, x, slow_x, delta, tail, keys, values, seen, s):
    """This file's three blocks, one after the other as a stack has them, on
    whatever precision the arguments come in; and the `D` block once more
    with heads that hardly forget, on an agent's inputs (`slow_x`)."""
    p = {kind: tree["params"] for kind, tree in params.items()}
    after_d, (state, _) = delta_layer(p["D"], x, delta, tail, s)
    slow_out, (slow_state, _) = delta_layer(_slow(p["D"]), slow_x, delta, tail, s)
    own = router(p["E"], norm(after_d, p["E"]["pre_norm"], s["rms_norm_eps"]).reshape(tokens.shape), s)[1]
    after_e = moe_layer(p["E"], after_d, s, chosen=chosen)
    after_a, new_keys = attention_layer(p["*"], after_e, keys, values, seen, s)
    return {"gdn_out": after_d, "gdn_state": state, "gdn_slow_out": slow_out, "gdn_slow_state": slow_state,
            "scores": router(p["E"], tokens, s)[0], "chosen": own,
            "moe_out": after_e, "attention_out": after_a, "attention_keys": new_keys,
            # the controls that leave a mechanism out: the block's output without its gate, its keys without their rotation
            "attention_ungated": attention_layer(p["*"], after_e, keys, values, seen, s, gated=False)[0],
            "keys_unrotated": attention_layer(p["*"], after_e, keys, values, seen, s, rotated=False)[1]}


def kernel_checks(cfg, seed: int, batch: int) -> Dict:
    """The program's three block kinds (models/hybrid_stack.py, imported here
    and nowhere else in this file) against this file's, at the
    configuration's widths and sequence length on seeded weights, inputs and
    stored state, CHECK_ROWS rows, one after the other as a stack has them:
    `D`, then `E` on what each side's `D` gave, then `*`. Compared: the `D`
    block's output and its state after the last step (the chunked form
    against the loop over time); the router's scores on the SAME tokens (the
    program's); each later block's output, this file's `E` following the
    program's choice of experts; the keys the attention writes to its ring.
    And the `D` block once more where rounding cannot be forgotten: every
    head's decay slowed to exp(SLOW_A_LOG) on an agent's inputs (`gdn_slow_*`;
    the table above LAYER_LIMITS says why). `router_flip_share` is recorded,
    not judged. THE CONTROLS run beside it, on every seed, and each has to be
    told: `control_bfloat16`, this file's blocks fed bfloat16 weights, inputs
    and state at the default precision (the reference one precision down),
    must read over the router's limit and over the slow state's; the attention
    block's output without its gate must read over the output's limit, and the
    keys it writes without their rotation over the keys'."""
    from r2d2_tpu.models import hybrid_stack as hs

    s, spec = stack_of(cfg), hs.spec_of(cfg)
    dtype = jnp.dtype(cfg.resolved_compute_dtype)
    B, T, D, W = min(batch, CHECK_ROWS), cfg.seq_len, s["hidden_size"], s["max_episode_steps"]
    # one layer of each mixer: blocks D, E, *, E
    (delta_shape, tail_shape), _, (kv_shape, _), _ = state_shapes(dict(s, num_hidden_layers=2, full_attention_interval=2))
    rng = np.random.default_rng(seed)
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape).astype(np.float32))
    x, delta, tail = normal(B, T, D), 0.5 * normal(B, *delta_shape), normal(B, *tail_shape)
    slow_x = normal(B, 1, D) + 0.05 * normal(B, T, D)   # an agent's: one frame after another, hardly different
    keys, values = normal(B, *kv_shape), normal(B, *kv_shape)
    seen = jnp.asarray(rng.integers(0, max(W - T, 0) + 1, size=B), jnp.int32)  # positions the rows have been through
    classes = {"D": hs.GatedDeltaNet, "E": hs.ExpertMixture, "*": hs.EpisodeAttention}
    layers = {kind: cls(spec.sizes(kind), dtype) for kind, cls in classes.items()}
    args = {"D": (x, delta, tail), "E": (x,), "*": (x, keys, values, seen)}
    params = {kind: jax.jit(layers[kind].init)(jax.random.PRNGKey(seed + i), *args[kind])
              for i, kind in enumerate("DE*")}
    # norm weights away from the zero they start at, so that a forgotten `1 +` shows
    params = jax.tree.map(lambda v: v + 0.1 * jnp.asarray(rng.normal(size=v.shape), F32) if v.ndim == 1 else v, params)

    def program(params, x, slow_x, delta, tail, keys, values, seen):
        after_d, state, _ = layers["D"].apply(params["D"], x, delta, tail)
        slow_out, slow_state, _ = layers["D"].apply({"params": _slow(params["D"]["params"])}, slow_x, delta, tail)
        tokens = hs.rms_norm(after_d, 1.0 + params["E"]["params"]["pre_norm"], s["rms_norm_eps"]).reshape(-1, D)
        scores, chosen = layers["E"].apply(params["E"], tokens, method="scores")
        after_e, _ = layers["E"].apply(params["E"], after_d)
        after_a, ring, _ = layers["*"].apply(params["*"], after_e, keys, values, seen)
        own_keys = jax.vmap(lambda row, at: jnp.take(row, (at + jnp.arange(T)) % W, axis=0))(ring, seen)
        return {"gdn_out": after_d, "gdn_state": state, "gdn_slow_out": slow_out, "gdn_slow_state": slow_state,
                "tokens": tokens, "scores": scores, "chosen": chosen,
                "moe_out": after_e, "attention_out": after_a, "attention_keys": own_keys}

    inputs = (x, slow_x, delta, tail, keys, values, seen)
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
    out["control_ungated_err_over_scale"] = correct.scale_err(want["attention_ungated"], want["attention_out"])
    out["control_unrotated_err_over_scale"] = correct.scale_err(want["keys_unrotated"], want["attention_keys"])
    over = lambda value, name: not (np.isfinite(value) and value <= limits[name])
    out["controls_told"] = bool(over(out["control_bfloat16"]["router_score_err"], "router_score_err")
                                and over(out["control_bfloat16"]["gdn_slow_state_err_over_scale"], "gdn_slow_state_err_over_scale")
                                and over(out["control_ungated_err_over_scale"], "attention_out_err_over_scale")
                                and over(out["control_unrotated_err_over_scale"], "attention_keys_err_over_scale"))
    out["ok"] = bool(not any(over(out[k], k) for k in limits) and out["controls_told"])
    return out
