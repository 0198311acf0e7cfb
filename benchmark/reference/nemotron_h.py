"""Plain float32 reference of a `nemotron_h` layer stack in the agent's core slot.

Written from the equations of the published model (config.json of
nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16, `model_type: nemotron_h`;
Mamba-2: Dao & Gu 2024, "Transformers are SSMs", section 3's recurrence) and
not from the program: straightforward `jax.numpy`, the state-space recurrence
as a loop over time (`lax.scan`, one step at a time), the experts one after
the other (a `lax.scan` over them) on every token under a mask, attention as
one masked softmax. It
shares with the program only the parameter tree's names and the order of one
row's stored state, so that the same seeded weights and the same stored
sequences feed both. What is the same as every other cell's (Nature encoder,
dueling heads, the n-step double-Q loss under the value rescaling) is
`reference/model.py`'s, imported and not repeated.

A layer is `x <- x + mixer(RMSNorm(x))`; the stack is an input projection,
the layers of `hybrid_override_pattern`, a final RMSNorm:

- `M`: `[z | xBC | dt] = in_proj(u)`; `xBC <- silu(conv(xBC) + bias)`, a
  causal depthwise convolution over the last `conv_kernel` inputs; `[x | B |
  C] = xBC`; `dt <- softplus(dt + dt_bias)`; per head `h_t = exp(-exp(A_log)
  dt_t) h_{t-1} + dt_t x_t (x) B_t`, `y_t = h_t C_t + D x_t`; `out_proj(
  RMSNorm_grouped(y silu(z)))`.
- `E`: `s = sigmoid(x W_r)`; the top `num_experts_per_tok` of `s + bias`;
  weights `s / sum(s chosen) * routed_scaling_factor`; expert `W_down relu(
  W_up x)^2`; a shared expert for every token.
- `*`: grouped-query attention, `softmax(q k^T / sqrt(head_dim)) v`, causal.

Every caller wraps these in `jax.default_matmul_precision("highest")`.

Departures from the published model, each because the configuration states
it (`assumed` / `reduced` in benchmark/configs/) and the program does the
same:
- the causal tower only. The denoiser tower's adaLN modulation and its
  conditioning on the other tower are published by name, not by equation.
- no rotary embedding: the `nemotron_h` attention applies none.
- the attention memory. A row's stored state holds the keys and values of its
  last `max_episode_steps` positions (a ring: softmax does not ask in which
  order) and how many positions it has seen; a sequence's queries see the
  valid part of it and the sequence causally. The memory is as long as an
  episode, so that is full causal attention over the episode.
- the share. This chip holds experts `[first_expert_held, + num_experts_held)`
  of `n_routed_experts`: the router scores all, and what the others would add
  is left out, here as there.
- the capacity. Each held expert takes at most `C` assignments a call, `C =
  capacity_factor x tokens x num_experts_per_tok / n_routed_experts` up to a
  multiple of 128; a token's assignment beyond it, in flattened (b, t) order,
  is dropped. The published model drops nothing; static shapes on a TPU are
  the reason (GShard / Switch capacity).
- the input projection `(latent + A + 1) -> hidden` stands for the token
  embedding, and the dueling heads for the vocabulary's.
- burn-in is backpropagated through (no seam), as for the LRU core.
- memory, not mathematics: the loop over time is checkpointed in blocks of
  `TIME_BLOCK` steps and each layer is checkpointed, so that 8 sequences of
  581 steps at published widths fit beside their gradient.
- compile time, not mathematics: the Nature trunk's convolutions are written
  as the sums of shifted matmuls they are (`_conv_valid`; the chip's compiler
  took 350 s for the float32 convolutions' gradient), and where the pattern
  starts with a unit that repeats, its repetitions run as one `lax.scan`
  (`repeats`).

`kernel_checks`, at the end, is the one place that calls the program: it
imports the program's layers to hold them, one kind at a time, to the layers
above (as reference/model.py imports its kernel to check it). Nothing above
it knows the program.
"""

from __future__ import annotations

import math
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import correct, flops
from benchmark.reference import model as base

F32 = jnp.float32
TIME_BLOCK = 32


class Sizes(NamedTuple):
    encoder: str
    hidden: int
    action_dim: int
    learning: int
    forward: int
    eps: float
    stack: Dict  # the configuration's core_config, with the defaults filled in


def stack_of(cfg) -> Dict:
    s = dict(cfg.core_config)
    s.setdefault("num_experts_held", s["n_routed_experts"])
    s.setdefault("first_expert_held", 0)
    s.setdefault("capacity_factor", 2.0)
    s["max_episode_steps"] = cfg.max_episode_steps  # the length of the attention's memory
    return s


def sizes_of(cfg) -> Sizes:
    return Sizes(encoder=cfg.encoder, hidden=cfg.hidden_dim, action_dim=cfg.action_dim,
                 learning=cfg.learning_steps, forward=cfg.forward_steps, eps=cfg.value_rescale_eps,
                 stack=stack_of(cfg))


# ----------------------------------------------------------------- operations


def layer_flops_per_token(s: Dict, seq_len: int) -> Dict[str, float]:
    """Multiply-accumulates (counted twice) that one token requires of one
    layer of each kind. `M`: the two projections and the recurrence's own
    update and read-out (H x P x N each). `E`: the router, the shared expert,
    and the routed experts at the BALANCED share of the experts held here
    (tokens x k x held / routed rows a layer), never the padded capacity.
    `*`: the four projections and causal scores and values over the sequence's
    own positions, (T + 1) / 2 keys a query on average (the remembered keys of
    a row's earlier windows are left out: a lower bound)."""
    D = s["hidden_size"]
    d_inner = s["mamba_num_heads"] * s["mamba_head_dim"]
    proj_in = 2 * d_inner + 2 * s["n_groups"] * s["ssm_state_size"] + s["mamba_num_heads"]
    mamba = 2 * D * proj_in + 2 * d_inner * D + 2 * 2 * d_inner * s["ssm_state_size"]
    rows = s["num_experts_per_tok"] * s["num_experts_held"] / s["n_routed_experts"]
    moe = (2 * D * s["n_routed_experts"] + 2 * 2 * D * s["moe_shared_expert_intermediate_size"]
           + rows * 2 * 2 * D * s["moe_intermediate_size"])
    q_width = s["num_attention_heads"] * s["head_dim"]
    kv_width = s["num_key_value_heads"] * s["head_dim"]
    attention = 2 * D * (2 * q_width + 2 * kv_width) + 2 * 2 * q_width * (seq_len + 1) / 2
    return {"M": mamba, "E": moe, "*": attention}


def update_flops(cfg) -> int:
    """Operations one learner update requires, as flops.update_flops counts
    them for the other cores: the online net forward over T and backward (2 x)
    over the L learning frames, the target net forward over T; heads at 5 L
    positions. Recomputed layers are not counted."""
    s = stack_of(cfg)
    T, L = cfg.seq_len, cfg.learning_steps
    per_kind = layer_flops_per_token(s, T)
    stack = sum(per_kind[k] for k in s["hybrid_override_pattern"])
    embed = 2 * (cfg.hidden_dim + cfg.action_dim + 1) * s["hidden_size"]
    trunk = flops.encoder_flops_per_frame(cfg.encoder, cfg.obs_shape, cfg.hidden_dim) + embed + stack
    heads = flops.heads_flops_per_step(cfg.hidden_dim, cfg.action_dim)
    return int(cfg.batch_size * (trunk * (T + 2 * L + T) + heads * 5 * L))


# ---------------------------------------------------------------- the layers


def rms_norm(x, weight, eps, groups: int = 1):
    parts = x.reshape(*x.shape[:-1], groups, -1)
    parts = parts / jnp.sqrt(jnp.mean(jnp.square(parts), axis=-1, keepdims=True) + eps)
    return parts.reshape(x.shape) * weight


def _loop_over_time(step, carry, xs):
    """`lax.scan(step, carry, xs)` over the leading (time) axis, in
    checkpointed blocks of TIME_BLOCK steps -> (the carry after the last
    step, the outputs). The tail is padded: a padded step leaves the carry as
    it is and its output is dropped."""
    T = jax.tree.leaves(xs)[0].shape[0]
    pad = (-T) % TIME_BLOCK
    blocks = jax.tree.map(
        lambda v: jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1)).reshape(-1, TIME_BLOCK, *v.shape[1:]),
        (jnp.ones((T,), bool), xs))

    def real_step(c, inp):
        real, x = inp
        new, y = step(c, x)
        return jax.tree.map(lambda a, b: jnp.where(real, a, b), new, c), y

    carry, ys = jax.lax.scan(jax.checkpoint(lambda c, b: jax.lax.scan(real_step, c, b)), carry, blocks)
    return carry, jax.tree.map(lambda v: v.reshape(-1, *v.shape[2:])[:T], ys)


def mamba_layer(p, x, state, tail, s: Dict):
    """x (B, T, D); state (B, H, P, N); tail (B, K - 1, conv_dim), oldest
    first -> (the layer's output, (state, tail) after the last step)."""
    H, P, N, G = s["mamba_num_heads"], s["mamba_head_dim"], s["ssm_state_size"], s["n_groups"]
    d_inner = H * P
    u = rms_norm(x, p["pre_norm"], s["norm_eps"]) @ p["in_proj"]
    z, xbc, dt = u[..., :d_inner], u[..., d_inner:-H], u[..., -H:]
    a_rate = jnp.exp(p["A_log"])

    def step(carry, inp):
        h, window = carry
        xbc_t, dt_t = inp                                             # (B, conv_dim), (B, H)
        window = jnp.concatenate([window, xbc_t[:, None]], axis=1)    # the last K inputs
        conv = jax.nn.silu(jnp.sum(window * p["conv_weight"], axis=1) + p["conv_bias"])
        x_t = conv[:, :d_inner].reshape(-1, H, P)
        b_t = jnp.repeat(conv[:, d_inner:d_inner + G * N].reshape(-1, G, N), H // G, axis=1)
        c_t = jnp.repeat(conv[:, d_inner + G * N:].reshape(-1, G, N), H // G, axis=1)
        dt_t = jax.nn.softplus(dt_t + p["dt_bias"])
        decay = jnp.exp(-a_rate * dt_t)
        h = decay[:, :, None, None] * h + (dt_t[:, :, None] * x_t)[..., None] * b_t[:, :, None, :]
        y_t = jnp.sum(h * c_t[:, :, None, :], axis=-1) + p["D"][:, None] * x_t
        return (h, window[:, 1:]), y_t.reshape(-1, d_inner)

    last, y = _loop_over_time(step, (state, tail), (jnp.swapaxes(xbc, 0, 1), jnp.swapaxes(dt, 0, 1)))
    y = jnp.swapaxes(y, 0, 1) * jax.nn.silu(z)
    return x + rms_norm(y, p["norm"], s["norm_eps"], groups=G) @ p["out_proj"], last


def capacity(s: Dict, tokens: int) -> int:
    share = s["capacity_factor"] * tokens * s["num_experts_per_tok"] / s["n_routed_experts"]
    return 128 * max(math.ceil(share / 128), 1)


def router(p, tokens, s: Dict):
    """tokens (n, D), normalised -> (sigmoid scores over all routed experts
    (n, routed), the top `num_experts_per_tok` of score + bias (n, k))."""
    scores = jax.nn.sigmoid(tokens @ p["router"])
    return scores, jnp.argsort(-(scores + p["e_score_correction_bias"]), axis=-1)[:, :s["num_experts_per_tok"]]


def moe_layer(p, x, s: Dict, drop: bool = True, chosen=None):
    """x (B, T, D). The held experts run one after the other on every token,
    under the mask of the assignments each one keeps. `chosen` (n, k), where
    given, is the choice of experts to follow in place of the router's own
    (the layer checks hand over the program's, so that a choice that rounding
    flipped does not stand between two outputs that are compared)."""
    shape = x.shape
    tokens = rms_norm(x, p["pre_norm"], s["norm_eps"]).reshape(-1, shape[-1])   # (b, t) order
    n = tokens.shape[0]
    scores, own = router(p, tokens, s)
    chosen = own if chosen is None else chosen
    member = jnp.any(chosen[:, :, None] == jnp.arange(s["n_routed_experts"]), axis=1)   # (n, routed)
    weights = scores / jnp.sum(jnp.where(member, scores, 0.0), axis=-1, keepdims=True) * s["routed_scaling_factor"]
    room = capacity(s, n) if drop else n
    shared = jnp.square(jax.nn.relu(tokens @ p["shared_up"])) @ p["shared_down"]
    held = slice(s["first_expert_held"], s["first_expert_held"] + s["num_experts_held"])

    def one_expert(out, expert):
        up, down, asks, weight = expert
        keeps = asks & (jnp.cumsum(asks) <= room)
        return out + jnp.where(keeps, weight, 0.0)[:, None] * (jnp.square(jax.nn.relu(tokens @ up)) @ down), None

    out, _ = jax.lax.scan(one_expert, shared, (p["experts"]["up"], p["experts"]["down"],
                                               member[:, held].T, weights[:, held].T))
    return x + out.reshape(shape)


def attention_layer(p, x, keys, values, seen, s: Dict):
    """x (B, T, D); keys, values (B, W, KV, Dh) remembered; seen (B,) how
    many positions the row has been through (the first min(seen, W) slots hold them)."""
    B, T, _ = x.shape
    heads, kv, dh, W = s["num_attention_heads"], s["num_key_value_heads"], s["head_dim"], s["max_episode_steps"]
    h = rms_norm(x, p["pre_norm"], s["norm_eps"])
    q = (h @ p["q_proj"]).reshape(B, T, heads, dh)
    k = jnp.concatenate([keys, (h @ p["k_proj"]).reshape(B, T, kv, dh)], axis=1)
    v = jnp.concatenate([values, (h @ p["v_proj"]).reshape(B, T, kv, dh)], axis=1)
    k, v = jnp.repeat(k, heads // kv, axis=2), jnp.repeat(v, heads // kv, axis=2)
    scores = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(dh)
    remembered = jnp.arange(W)[None, None, :] < jnp.minimum(seen, W)[:, None, None]       # (B, 1, W)
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]                              # (T, T)
    allowed = jnp.concatenate([jnp.broadcast_to(remembered, (B, T, W)), jnp.broadcast_to(causal, (B, T, T))], axis=-1)
    probs = jax.nn.softmax(jnp.where(allowed[:, None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", probs, v).reshape(B, T, heads * dh)
    return x + out @ p["o_proj"]


def state_shapes(s: Dict):
    """For each layer of the pattern, the shapes of what one row stores for
    it, in the stored vector's order: an `M` layer's state (H, P, N) then its
    last K - 1 conv inputs; a `*` layer's keys then its values (W, KV, Dh)."""
    conv_dim = s["mamba_num_heads"] * s["mamba_head_dim"] + 2 * s["n_groups"] * s["ssm_state_size"]
    shapes = {"M": [(s["mamba_num_heads"], s["mamba_head_dim"], s["ssm_state_size"]), (s["conv_kernel"] - 1, conv_dim)],
              "E": [], "*": [(s["max_episode_steps"], s["num_key_value_heads"], s["head_dim"])] * 2}
    return [shapes[kind] for kind in s["hybrid_override_pattern"]]


def stored_state(hidden, s: Dict):
    """One row's stored vector -> what each layer starts from, and how many
    positions the row has seen: the two numbers after the layers' parts,
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


NAMES = {"M": "ssm", "E": "moe", "*": "attention"}


def one_layer(kind: str, p, x, start, seen, s: Dict, drop: bool):
    if kind == "M":
        return jax.checkpoint(lambda p, x, a, b: mamba_layer(p, x, a, b, s)[0])(p, x, *start)
    if kind == "E":
        return jax.checkpoint(lambda p, x: moe_layer(p, x, s, drop))(p, x)
    return jax.checkpoint(lambda p, x, a, b: attention_layer(p, x, a, b, seen, s))(p, x, *start)


def repeats(pattern: str):
    """(unit, times): the pattern starts with `times` repetitions of `unit`,
    the choice that covers most of it (the shortest unit among equals);
    ("", 0) where nothing repeats. `EMEMEM*` -> (`EM`, 3)."""
    best = ("", 0)
    for u in range(1, len(pattern) // 2 + 1):
        k = 1
        while pattern[k * u:(k + 1) * u] == pattern[:u]:
            k += 1
        if k > 1 and u * k > len(best[0]) * best[1]:
            best = (pattern[:u], k)
    return best


def stack_outputs(pc, x, hidden, s: Dict, drop: bool = True):
    """pc: the core's parameters, each layer under its kind and its place in
    the pattern (`moe_0`, `ssm_1`, ..., `attention_6`); x (B, T, latent + A +
    1); hidden (B, 1, S) as the replay holds it -> the stack's outputs (B, T,
    hidden), layer after layer. Compile time, not mathematics: where the
    pattern starts with a unit that repeats, those layers run as a `lax.scan`
    over the repetitions, so that the chip's compiler meets each kind once."""
    pattern = s["hybrid_override_pattern"]
    starts, seen = stored_state(hidden, s)
    layers = [pc[f"{NAMES[kind]}_{i}"] for i, kind in enumerate(pattern)]
    x = x @ pc["in_proj"]
    unit, times = repeats(pattern)
    u = len(unit)
    if times:
        stacked = lambda per_layer: jax.tree.map(lambda *v: jnp.stack(v), *[per_layer[r * u:(r + 1) * u] for r in range(times)])

        def one_unit(x, layers_and_starts):
            for kind, p, start in zip(unit, *layers_and_starts):
                x = one_layer(kind, p, x, start, seen, s, drop)
            return x, None

        x, _ = jax.lax.scan(one_unit, x, (stacked(layers), stacked(starts)))
    for i in range(u * times, len(pattern)):
        x = one_layer(pattern[i], layers[i], x, starts[i], seen, s, drop)
    return rms_norm(x, pc["final_norm"], s["norm_eps"])


# ------------------------------------------------------ the agent around them


def _conv_valid(x, kernel, stride: int):
    """A VALID convolution as the sum it is: x (N, H, W, C), kernel (k, k, C,
    O), k a multiple of the stride. The stride goes by space-to-depth (x and
    kernel alike), then every kernel position is one matmul over a shifted
    view. The same sums as `lax.conv_general_dilated` (tests/benchmark holds it
    to reference/model.encode); the chip's compiler takes 350 s for that
    operation's float32 gradient over 8 x 581 frames and 40 s for this."""
    n, h, w, c = x.shape
    k, q = kernel.shape[0], kernel.shape[0] // stride
    x = x.reshape(n, h // stride, stride, w // stride, stride, c).transpose(0, 1, 3, 2, 4, 5)
    x = x.reshape(n, h // stride, w // stride, stride * stride * c)
    kernel = kernel.reshape(q, stride, q, stride, c, -1).transpose(0, 2, 1, 3, 4, 5).reshape(q, q, stride * stride * c, -1)
    oh, ow = x.shape[1] - q + 1, x.shape[2] - q + 1
    return sum(x[:, a:a + oh, b:b + ow] @ kernel[a, b] for a in range(q) for b in range(q))


def encode(p_enc: Dict, obs, encoder: str):
    """reference/model.encode, the Nature trunk's convolutions by `_conv_valid`."""
    if encoder != "nature":
        return base.encode(p_enc, obs, encoder)
    x = obs.astype(F32) / 255.0
    for i, stride in enumerate((4, 2, 1)):
        x = jnp.maximum(_conv_valid(x, p_enc[f"Conv_{i}"]["kernel"], stride) + p_enc[f"Conv_{i}"]["bias"], 0.0)
    x = x.reshape(x.shape[0], -1)
    return jnp.maximum(x @ p_enc["Dense_0"]["kernel"] + p_enc["Dense_0"]["bias"], 0.0)


def core_input(p, obs, last_action, last_reward, sz: Sizes):
    onehot = jax.nn.one_hot(last_action, sz.action_dim, dtype=F32)
    return jnp.concatenate([encode(p["enc"], obs, sz.encoder), onehot, last_reward.astype(F32)[:, None]], axis=-1)



def q_views(p, batch, sz: Sizes):
    """(q_learn, q_boot, mask), as reference/model.q_views gives them."""
    L, F = sz.learning, sz.forward
    obs = batch["obs"]
    B, T = obs.shape[:2]
    burn, learn, fwd = batch["burn_in"], batch["learning"], batch["forward"]
    x = core_input(p, obs.reshape(B * T, *obs.shape[2:]), batch["last_action"].reshape(-1),
                        batch["last_reward"].reshape(-1), sz).reshape(B, T, -1)
    outs = stack_outputs(p["core"], x, batch["hidden"], sz.stack)
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


def _f32(tree):
    return jax.tree.map(lambda v: jnp.asarray(v, F32), tree)


def loss_q_gradnorm(params, target_params, batch, sz: Sizes):
    """-> (loss, q_learn (B, L, A), global gradient norm), all float32."""
    params, target_params = _f32(params), _f32(target_params)
    (loss, q_learn), grads = jax.value_and_grad(loss_and_q, has_aux=True)(params, target_params, batch, sz)
    return loss, q_learn, jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))


def island_inputs(params, target_params, batch, sz: Sizes) -> Dict:
    params, target_params = _f32(params), _f32(target_params)
    q_learn, q_boot, mask = q_views(params, batch, sz)
    return {"q_learn": q_learn, "q_boot": q_boot, "mask": mask,
            "q_boot_target": q_views(target_params, batch, sz)[1]}


def act_unroll(params, obs, last_action, last_reward, sz: Sizes) -> jnp.ndarray:
    """Acting from the zero state: (S, T, ...) -> Q (S, T, A). One step at a
    time an expert is never offered more than its capacity, so nothing drops."""
    params = _f32(params)
    S, T = obs.shape[:2]
    x = core_input(params, obs.reshape(S * T, *obs.shape[2:]), last_action.reshape(-1),
                        last_reward.reshape(-1), sz).reshape(S, T, -1)
    n = sum(math.prod(shape) for shapes in state_shapes(sz.stack) for shape in shapes) + 2
    outs = stack_outputs(params["core"], x, jnp.zeros((S, 1, n), F32), sz.stack, drop=False)
    return base.dueling(params, outs)


# ------------------------------------------------- the layers, one by one

# What `correct`'s whole-program numbers cannot tell (a top-k choice that
# rounding flipped owns the largest Q error, whatever the precision: the
# configuration's `limits_why`), these tell: the program's layers against this
# file's, one kind at a time, with the program's choice of experts handed
# over. compute dtype -> limits. bfloat16: between two sets of readings on
# the v5e at published widths (my chip runs, PR 53, calls 130-131; PERF.md
# finding 53.7), at least 3.3 x above the sound program's largest over 31
# seeds | and under the smallest over 18 seeds of the control, the program
# with every float32 island of the core (router, recurrence, norms, softmax,
# residual stream) in bfloat16, which fails every one on every seed but one
# reading of the state:
#   router_score_err              1.2e-6 | 2.4e-3
#   ssm_state_err_over_scale      9.1e-3 | 3.9e-2
#   ssm_out_err_over_scale        3.9e-3 | 4.4e-2
#   moe_out_err_over_scale        4.7e-3 | 5.5e-2
#   attention_out_err_over_scale  4.8e-3 | 5.7e-2
# float32 (no cell; the CPU tests at tiny widths read under 1e-6 sound and
# 9e-4 or more with one island in bfloat16).
LAYER_LIMITS = {
    "bfloat16": {"router_score_err": 3e-5, "ssm_state_err_over_scale": 3e-2, "ssm_out_err_over_scale": 1.3e-2,
                 "moe_out_err_over_scale": 1.6e-2, "attention_out_err_over_scale": 1.6e-2},
    "float32": {"router_score_err": 1e-5, "ssm_state_err_over_scale": 1e-4, "ssm_out_err_over_scale": 1e-4,
                "moe_out_err_over_scale": 1e-4, "attention_out_err_over_scale": 1e-4},
}
CHECK_ROWS = 2


def kernel_checks(cfg, seed: int, batch: int) -> Dict:
    """The program's three layer kinds (models/hybrid_stack.py, imported here
    and nowhere else in this file, as reference/model.py imports its kernel)
    against this file's, at the configuration's widths and sequence length on
    seeded weights, inputs and stored state, CHECK_ROWS rows, one after the
    other as a stack has them: `M`, then `E` on what each side's `M` gave,
    then `*`. Compared: the `M` layer's output and its state after the last
    step (the chunked form against the loop over time); the router's scores
    on the SAME tokens (the program's); each later layer's output, this file's
    `E` following the program's choice of experts. `router_flip_share` is
    recorded, not judged: the share of tokens whose top-k set, chosen by each
    side from its own tokens one bfloat16 layer downstream of equal inputs,
    differs."""
    from r2d2_tpu.models import hybrid_stack as hs

    s, spec = stack_of(cfg), hs.StackSpec.of(cfg)
    dtype = jnp.dtype(cfg.resolved_compute_dtype)
    B, T, D, W = min(batch, CHECK_ROWS), cfg.seq_len, s["hidden_size"], s["max_episode_steps"]
    (ssm_shape, tail_shape), (kv_shape, _) = state_shapes(dict(s, hybrid_override_pattern="M*"))
    rng = np.random.default_rng(seed)
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape).astype(np.float32))
    x, ssm, tail = normal(B, T, D), 0.5 * normal(B, *ssm_shape), normal(B, *tail_shape)
    keys, values = normal(B, *kv_shape), normal(B, *kv_shape)
    seen = jnp.asarray(rng.integers(0, max(W - T, 0) + 1, size=B), jnp.int32)  # positions the rows have been through
    layers = {"M": hs.Mamba2Mixer(spec, dtype), "E": hs.ExpertMixture(spec, dtype), "*": hs.EpisodeAttention(spec, dtype)}
    args = {"M": (x, ssm, tail), "E": (x,), "*": (x, keys, values, seen)}
    params = {kind: jax.jit(layers[kind].init)(jax.random.PRNGKey(seed + i), *args[kind])
              for i, kind in enumerate("ME*")}

    def program(params, x, ssm, tail, keys, values, seen):
        after_m, state, _ = layers["M"].apply(params["M"], x, ssm, tail)
        tokens = hs.rms_norm(after_m, params["E"]["params"]["pre_norm"], spec.norm_eps).reshape(-1, D)
        scores, chosen = layers["E"].apply(params["E"], tokens, method="scores")
        after_e, _ = layers["E"].apply(params["E"], after_m)
        after_a, _, _ = layers["*"].apply(params["*"], after_e, keys, values, seen)
        return {"ssm_out": after_m, "ssm_state": state, "tokens": tokens, "scores": scores, "chosen": chosen,
                "moe_out": after_e, "attention_out": after_a}

    def reference(params, tokens, chosen, x, ssm, tail, keys, values, seen):
        p = {kind: tree["params"] for kind, tree in params.items()}
        after_m, (state, _) = mamba_layer(p["M"], x, ssm, tail, s)
        own = router(p["E"], rms_norm(after_m, p["E"]["pre_norm"], s["norm_eps"]).reshape(-1, D), s)[1]
        after_e = moe_layer(p["E"], after_m, s, chosen=chosen)
        return {"ssm_out": after_m, "ssm_state": state, "scores": router(p["E"], tokens, s)[0], "chosen": own,
                "moe_out": after_e, "attention_out": attention_layer(p["*"], after_e, keys, values, seen, s)}

    inputs = (x, ssm, tail, keys, values, seen)
    got = jax.jit(program)(params, *inputs)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(reference)(params, got["tokens"], got["chosen"], *inputs)
    got, want = jax.device_get(got), jax.device_get(want)
    out = {name + "_err_over_scale": correct.scale_err(got[name], want[name])
           for name in ("ssm_out", "ssm_state", "moe_out", "attention_out")}
    out["router_score_err"] = float(np.max(np.abs(got["scores"] - want["scores"])))
    out["router_flip_share"] = float(np.mean(np.any(np.sort(got["chosen"], 1) != np.sort(want["chosen"], 1), axis=1)))
    out["limits"] = dict(LAYER_LIMITS[dtype.name])
    out["rows_steps"] = [B, T]
    out["ok"] = bool(all(np.isfinite(out[k]) and out[k] <= limit for k, limit in out["limits"].items()))
    return out
