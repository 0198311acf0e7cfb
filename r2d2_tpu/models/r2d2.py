"""R2D2Network — recurrent dueling double-DQN trunk (L2).

Capability parity with the reference Network (reference model.py:35-188):

- conv/mlp encoder -> LSTM over concat(latent, one-hot last action, last
  reward) -> dueling heads, Q = V + A - mean(A) (model.py:59,80,94).
- `act`: batched single-step acting forward (model.py:73-97, vectorized
  over envs instead of the reference's one-env unbatched call).
- `unroll`: the fixed-shape replacement for BOTH `calculate_q_`
  (model.py:99-158) and `calculate_q` (model.py:161-188). One lax.scan LSTM
  pass over the padded burn_in+learning+forward window, then two clamped
  index views of its outputs (read as one window of each row, never as
  indices: `_dueling_window`):

    learning view   idx(t) = burn_in + t                     (model.py:182)
    bootstrap view  idx(t) = min(burn_in + F_max + t,
                               burn_in + learning + forward - 1)

  The min() reproduces `calculate_q_`'s edge-repeat padding exactly: the
  reference slices [burn_in+F_max : seq_end) and repeats the last output
  min(F_max - forward, learning) times (model.py:141-150); clamping the
  gather index at seq_end-1 is the same function, with no ragged Python
  loop. A (B, L) validity mask replaces `pack_padded_sequence`.

Both Q views come from ONE LSTM pass per network, so a learner update costs
2 conv + 2 LSTM evaluations (online, target) instead of the reference's
3 + 3 (worker.py:404-415).

Obs enter as uint8 and are normalized exactly once, here (SURVEY.md
quirk 15). Head math runs in float32 regardless of compute dtype.
"""

from __future__ import annotations

from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from r2d2_tpu.config import R2D2Config
from r2d2_tpu.models.core import Carry, core_class, state_spec, step_open, unpack_state
from r2d2_tpu.models.encoders import make_encoder


class RowDense(nn.Module):
    """Row-parallel Dense for the manual-tp dueling head outs: the kernel
    holds this shard's contiguous (in/tp, out) ROW slice, the partial
    products all-reduce over `tp_axis`, and the REPLICATED bias is added
    once AFTER the psum (a per-shard bias would count tp times). Param
    names ("kernel"/"bias") and initializers match nn.Dense, so the
    sharding table's `*.adv_out.kernel*` row rules and existing global
    checkpoints line up slice-for-slice. Used only inside
    learner.make_manual_train_step's shard_map (tp_size > 1); the tp=1
    golden path keeps plain nn.Dense modules bit-exactly."""

    features: int
    tp_axis: str = "tp"

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        kernel = self.param(
            "kernel",
            nn.initializers.lecun_normal(),
            (x.shape[-1], self.features),
        )
        bias = self.param("bias", nn.initializers.zeros_init(), (self.features,))
        return jax.lax.psum(x @ kernel, self.tp_axis) + bias


def _moved_by(band, subscripts, x):
    """`x`'s entries moved by a selection matmul: `band` (bool) holds at most
    one True for each output position, so each output is one 1.0 times an
    entry plus zeros, exact in any dtype ("highest" costs bf16 operands
    nothing and keeps f32 ones whole), with no index; the transpose places
    the cotangent with the same band, so nothing is scattered."""
    return jnp.einsum(
        subscripts, band.astype(x.dtype), x,
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    ).astype(x.dtype)


def _time_order(window, others, start):
    """window (B, W, D): steps [start[b], start[b] + W) of each row; others
    (B, T - W, D): the row's remaining steps in order -> (B, T, D) in time
    order, with no index. Step t of a row is `others[t]` below the window and
    `others[t - W]` after it: two static slices of `others` (padded by W
    behind, and by W in front) and a select on `t < start[b]`. Inside, it is
    `window[t - start[b]]`: one run a row, moved as
    `R2D2Network._dueling_window` moves its window: `band[b, t, j]` is 1 where
    t is the window's j-th position and 0 elsewhere (`_moved_by`). Indexed as
    B x T rows a v5e pays 20-30 ns a row and more for each row of the
    transpose's scatter-add (PERF.md finding 49). `start` (B,) int32 is read
    as it is given: any start in [0, T - W] gives the indexed formula's
    values and gradients."""
    W, T = window.shape[1], window.shape[1] + others.shape[1]
    t = jnp.arange(T, dtype=jnp.int32)[None, :, None]
    start = start[:, None, None]
    band = t == start + jnp.arange(W, dtype=jnp.int32)[None, None, :]  # (B, T, W)
    placed = _moved_by(band, "btj,bjd->btd", window)
    below = jnp.pad(others, ((0, 0), (0, W), (0, 0)))
    after = jnp.pad(others, ((0, 0), (W, 0), (0, 0)))
    return jnp.where(t < start, below, jnp.where(t < start + W, placed, after))


class R2D2Network(nn.Module):
    action_dim: int
    # the recurrent core, built by its registered class (models/core.py);
    # the network asks it and never names it. Parameter path: `core`.
    core: nn.Module
    hidden_dim: int = 512
    learning_steps: int = 40
    forward_steps: int = 5
    encoder: str = "nature"
    compute_dtype: str = "float32"
    impala_channels: Tuple[int, ...] = (16, 32, 32)
    # multi-task head conditioning (config.num_tasks): > 1 widens the
    # dueling-head input by a one-hot task embedding and (with
    # task_action_dims set) masks each task's invalid action tail out of
    # the union action space. 1 = the single-task golden path, bit-exact.
    num_tasks: int = 1
    task_action_dims: Tuple[int, ...] = ()
    # extra replicated Dense(latent)+relu encoder layers
    # (config.encoder_depth / MODEL_PRESETS "deep*")
    encoder_depth: int = 0
    # manual tensor parallelism: > 1 builds the SHARD-LOCAL network for
    # learner.make_manual_train_step's shard_map body — every param is
    # declared at its per-device shard shape from the sharding_map
    # table's layout (column-parallel latent/gate/hidden kernels,
    # row-parallel head outs via RowDense, convs/biases-of-row-outs
    # replicated), with explicit all-gather/psum seams in the module
    # math. Only meaningful inside a shard_map manual over "tp"; 1 keeps
    # the historical global modules bit-exactly.
    tp_size: int = 1
    # a canonical frame's shape (config.obs_shape): with it the encoder
    # also takes frames in the device stores' block order
    # (models/encoders.frame_block), told apart by their trailing shape
    obs_shape: Tuple[int, ...] = ()

    @classmethod
    def from_config(cls, cfg: R2D2Config, manual_tp: int = 1) -> "R2D2Network":
        return cls(
            action_dim=cfg.action_dim,
            # core input = concat(latent, one-hot action, reward) (model.py:59)
            core=core_class(cfg).from_config(
                cfg, in_dim=cfg.hidden_dim + cfg.action_dim + 1, tp_size=manual_tp
            ),
            hidden_dim=cfg.hidden_dim,
            learning_steps=cfg.learning_steps,
            forward_steps=cfg.forward_steps,
            encoder=cfg.encoder,
            # precision="bf16" forces bfloat16 compute; fp32 precision
            # defers to the legacy compute_dtype knob (config.py)
            compute_dtype=cfg.resolved_compute_dtype,
            impala_channels=tuple(cfg.impala_channels),
            num_tasks=cfg.num_tasks,
            task_action_dims=tuple(cfg.task_action_dims),
            encoder_depth=cfg.encoder_depth,
            tp_size=manual_tp,
            obs_shape=tuple(cfg.obs_shape),
        )

    def setup(self):
        dtype = jnp.dtype(self.compute_dtype)
        tp = self.tp_size
        self.enc = make_encoder(
            self.encoder, self.hidden_dim, dtype, self.impala_channels,
            depth=self.encoder_depth, tp_size=tp, obs_shape=self.obs_shape,
        )
        if tp > 1:
            # Megatron column/row pair per head: the hidden's column
            # slice feeds this shard's relu'd activations straight into
            # the out's row slice; one psum per head (inside RowDense)
            # closes the seam. Matches the table's *.adv/val_* rules.
            self.adv_hidden = nn.Dense(self.hidden_dim // tp)
            self.adv_out = RowDense(self.action_dim)
            self.val_hidden = nn.Dense(self.hidden_dim // tp)
            self.val_out = RowDense(1)
        else:
            self.adv_hidden = nn.Dense(self.hidden_dim)
            self.adv_out = nn.Dense(self.action_dim)
            self.val_hidden = nn.Dense(self.hidden_dim)
            self.val_out = nn.Dense(1)

    # ----------------------------------------------------------------- util

    def _core_input(self, obs, last_action, last_reward, burn_in=None):
        """(N, *obs) uint8, (N,) int, (N,) float -> (N, latent+A+1).

        With `burn_in` (B,) the inputs are (B, T, ...) sequences whose core
        cuts the gradient at each row's burn-in seam, and the result is
        (B, T, latent+A+1) in time order. Behind the seam a frame can
        receive a cotangent only inside `burn_in[b] + [0, L + F)`: below it
        the core's backward gives exactly zero, and `unroll` reads no
        output after it (an output's cotangent never moves forward in
        time). `burn_in` is per row, so the compiler cannot see that; the
        encoder therefore runs as two sub-batches, each row's L + F frames
        from its seam with gradient and its other T - L - F frames
        without. Per frame the forward is the one call's, and the gradient
        is the same sum without its zero terms, whatever the loss.

        The two encoded parts go back to time order with no index
        (`_time_order`); the one-hot of `last_action` and `last_reward`
        arrive in time order, carry no gradient and never leave it."""
        dtype = jnp.dtype(self.compute_dtype)

        def encode(obs):
            return self.enc(obs.astype(dtype) / 255.0)

        def beside(latent):
            onehot = jax.nn.one_hot(last_action, self.action_dim, dtype=dtype)
            reward = last_reward.astype(dtype)[..., None]
            return jnp.concatenate([latent, onehot, reward], axis=-1)

        if burn_in is None:
            return beside(encode(obs))

        B, T = obs.shape[:2]
        W = self.learning_steps + self.forward_steps
        # a window that would run past T starts earlier: it still covers
        # every frame that can receive a gradient
        start = jnp.clip(burn_in, 0, T - W).astype(jnp.int32)[:, None]  # (B, 1)
        window = start + jnp.arange(W, dtype=jnp.int32)[None, :]         # (B, W)
        c = jnp.arange(T - W, dtype=jnp.int32)[None, :]
        others = jnp.where(c < start, c, c + W)                          # (B, T-W)
        row0 = jnp.arange(B, dtype=jnp.int32)[:, None] * T
        # each frame as one row of bytes: the gather moves whole rows, and
        # the chip's compiler then re-lays each part out for the first conv
        # as it does the one call's batch (gathered as (84, 84, 1) frames,
        # that re-layout lands inside the conv and doubles its time)
        frames = obs.reshape(B * T, -1)

        def frames_at(idx):
            # ONE flattened index, as learner.make_store_gather: the
            # two-index gather of uint8 frames halts the v5e's core
            flat = (row0 + idx).reshape(-1)
            taken = jnp.take(frames, flat, axis=0, mode="clip")
            return taken.reshape(-1, *obs.shape[2:])

        def encoded(part, steps):
            return encode(part).reshape(B, steps, -1)

        # conv1 is handed each part as bytes in frame shape behind a barrier,
        # which keeps `encode`'s convert behind the reshape: the chip's
        # compiler re-lays the part out at one byte an entry and computes the
        # convert inside each conv fusion that reads it. Left free, it hoists
        # the convert onto the flat rows, once for both nets, and every conv
        # reads a bf16 copy of the part from memory. The others' bytes are
        # released together with the window's encoding, so the two parts are
        # encoded one after the other: with both released at once the
        # encoder's arrays of both parts take the fast memory from the core's
        # and the update gains nothing (PERF.md finding 50.2: core +0.084 ms
        # against -0.047 in this order, on an encoder 0.09-0.10 faster)
        mine = encoded(jax.lax.optimization_barrier(frames_at(window)), W)
        rest, mine = jax.lax.optimization_barrier((frames_at(others), mine))
        x = beside(_time_order(
            mine, jax.lax.stop_gradient(encoded(rest, T - W)), start[:, 0]
        ))
        # made once and kept, as the gather's result was: left to fuse, the
        # chip's compiler makes x again for the core's backward, and with
        # the longer lives of its parts the core's own arrays lose their
        # place in the fast memory (PERF.md finding 49.2: the update slower
        # by 3.4 % where this form is faster by 8 %)
        return jax.lax.optimization_barrier(x)

    def _task_mask(self, task: jnp.ndarray | None) -> jnp.ndarray | None:
        """(B, A) bool valid-action mask for each row's task, or None when
        every task spans the full union action space."""
        if task is None or self.num_tasks <= 1 or not self.task_action_dims:
            return None
        dims = jnp.asarray(self.task_action_dims, jnp.int32)
        return jnp.arange(self.action_dim)[None, :] < dims[task][:, None]

    def _dueling(self, h: jnp.ndarray, task: jnp.ndarray | None = None) -> jnp.ndarray:
        """Dueling Q in float32: Q = V + A - mean_a A (model.py:94).

        Multi-task (num_tasks > 1, task a (B,) int32): the head input is
        widened with the one-hot task embedding, the advantage mean runs
        over each task's VALID actions only (the identifiability constant
        must not drift with the number of masked slots), and invalid
        actions are pinned to a -1e9 floor so neither the acting argmax
        nor the learner's bootstrap max can select them."""
        h = h.astype(jnp.float32)
        mask = self._task_mask(task)
        if task is not None and self.num_tasks > 1:
            onehot = jax.nn.one_hot(task, self.num_tasks, dtype=jnp.float32)
            if h.ndim == 3:  # (B, L, H): per-sequence task, broadcast over L
                onehot = jnp.broadcast_to(
                    onehot[:, None, :], (*h.shape[:2], self.num_tasks)
                )
            h = jnp.concatenate([h, onehot], axis=-1)
        adv = self.adv_out(nn.relu(self.adv_hidden(h)))
        val = self.val_out(nn.relu(self.val_hidden(h)))
        if mask is None:
            return val + adv - adv.mean(axis=-1, keepdims=True)
        if adv.ndim == 3:  # (B, L, A): broadcast the (B, A) mask over L
            mask = mask[:, None, :]
        valid = mask.astype(jnp.float32)
        adv_mean = (adv * valid).sum(axis=-1, keepdims=True) / valid.sum(
            axis=-1, keepdims=True
        )
        q = val + adv - adv_mean
        return jnp.where(mask, q, -1e9)

    # ------------------------------------------------------------------ act

    def act(
        self,
        obs: jnp.ndarray,          # (B, *obs_shape) uint8
        last_action: jnp.ndarray,  # (B,) int32
        last_reward: jnp.ndarray,  # (B,) float32
        carry: Carry,              # models/core.py
        task: jnp.ndarray | None = None,  # (B,) int32 (multi-task only)
        opened: bool = False,      # carry is the core's OPENED form (models/core.py)
    ) -> Tuple[jnp.ndarray, Carry]:
        x = self._core_input(obs, last_action, last_reward)
        h, carry = step_open(self.core, x, carry) if opened else self.core.step(x, carry)
        return self._dueling(h, task), carry

    def act_select(
        self,
        obs: jnp.ndarray,             # (B, *obs_shape) uint8
        last_action: jnp.ndarray,     # (B,) int32
        last_reward: jnp.ndarray,     # (B,) float32
        carry: Carry,                 # models/core.py
        explore: jnp.ndarray,         # (B,) bool ε-coin per row
        random_actions: jnp.ndarray,  # (B,) int random draws in [0, A)
        task: jnp.ndarray | None = None,  # (B,) int32 (multi-task only)
        opened: bool = False,         # as `act`
    ) -> Tuple[jnp.ndarray, jnp.ndarray, Carry]:
        """Fused act tail: core step + dueling + ε-greedy select in one op.

        Returns (q (B, A) f32, action (B,) int32, carry). The ε coin and
        the uniform random actions are inputs (not a key) so host-loop
        callers keep their numpy RNG stream — see ops/act_tail.py. In the
        multi-task case callers draw random_actions within each row's
        NATIVE action count (the masked q floor keeps the greedy branch
        valid; random draws are the caller's contract).
        """
        from r2d2_tpu.ops.act_tail import epsilon_greedy_actions

        q, carry = self.act(obs, last_action, last_reward, carry, task, opened)
        return q, epsilon_greedy_actions(q, explore, random_actions), carry

    # --------------------------------------------------------------- unroll

    def unroll(
        self,
        obs: jnp.ndarray,           # (B, T, *obs_shape) uint8
        last_action: jnp.ndarray,   # (B, T) int32
        last_reward: jnp.ndarray,   # (B, T) float32
        hidden: jnp.ndarray,        # (B, *state_shape) stored state (models/core.py)
        burn_in: jnp.ndarray,       # (B,) int32
        learning: jnp.ndarray,      # (B,) int32
        forward: jnp.ndarray,       # (B,) int32
        task: jnp.ndarray | None = None,  # (B,) int32 (multi-task only)
    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """Returns (q_learn (B,L,A), q_boot (B,L,A), mask (B,L) f32)."""
        B, T = obs.shape[:2]
        L, F = self.learning_steps, self.forward_steps

        # a core that cuts at burn-in: burn-in steps refresh state only; the
        # stop-gradient seam lives inside the core's backward pass, so the
        # encoder differentiates each row's L + F frames from the seam only
        # (a sequence no longer than that is all window; un-jitted
        # initialisation wants the parameters alone, not the index work,
        # which would compile op by op in every process)
        if self.core.cuts_at_burn_in and T > L + F and not self.is_initializing():
            x = self._core_input(obs, last_action, last_reward, burn_in)
        else:
            x = self._core_input(
                obs.reshape(B * T, *obs.shape[2:]),
                last_action.reshape(B * T),
                last_reward.reshape(B * T),
            ).reshape(B, T, -1)

        outs, _ = self.core(x, unpack_state(hidden), burn_in=burn_in)  # (B, T, H)

        q_learn, q_boot = self._dueling_window(outs, burn_in, learning, forward, task)
        mask = (jnp.arange(L, dtype=jnp.int32)[None, :] < learning[:, None]).astype(jnp.float32)
        return q_learn, q_boot, mask

    def _dueling_window(self, outs, burn_in, learning, forward, task=None):
        """outs (B, T, H) -> (q_learn, q_boot), each (B, L, A) f32: Q at

            learning view   clip(burn_in + l, 0, T - 1)
            bootstrap view  clip(min(burn_in + F + l,
                                     burn_in + learning + forward - 1), 0, T - 1)

        for l in [0, L): the module docstring's two index views, with no
        index. Both lie in ONE window of W = L + F steps that starts at
        `burn_in[b]` and share L - F of its positions. Gathered as B x L rows
        of H each, twice, a v5e pays 8 ns a row and 17-70 ns for each row of
        the transpose's scatter-add (PERF.md finding 46). So the window is
        moved once, the heads run once over its W rows, and the views are a
        static slice of the window's Q and its slice from F on with the tail
        held at the row's last valid step.

        The window is moved by a selection matmul (`_moved_by`): `band[b, j,
        t]` is 1 where t is the window's j-th position and 0 elsewhere, so
        each output is one 1.0 times an entry plus zeros, exact in any dtype,
        and the transpose places the cotangent with the same band. The clip sits in
        the band, so the answer is the indexed formula's for ANY `burn_in`,
        as the store gather's windows keep it (`learner._windows`). (A row
        with learning + forward = 0 has no valid step and an all-zero mask;
        its bootstrap view reads the window's first position.)"""
        B, T = outs.shape[:2]
        L, F = self.learning_steps, self.forward_steps
        if self.is_initializing():
            # un-jitted initialisation wants the heads' parameters alone, not
            # the window's work, which would compile op by op in every process
            q = jnp.broadcast_to(self._dueling(outs[:, :1], task), (B, L, self.action_dim))
            return q, q
        j = jnp.arange(L + F, dtype=jnp.int32)
        at = jnp.clip(burn_in[:, None] + j[None, :], 0, T - 1)  # (B, W)
        band = at[:, :, None] == jnp.arange(T, dtype=jnp.int32)  # (B, W, T)
        window = _moved_by(band, "bjt,bth->bjh", outs)
        q = self._dueling(window, task)  # (B, W, A) f32
        last = jnp.maximum(learning + forward - 1, 0)[:, None, None]  # (B, 1, 1)
        held = jnp.sum(jnp.where(j[None, :, None] == last, q, 0), axis=1, keepdims=True)
        q_boot = jnp.where(j[None, F:, None] <= last, q[:, F:], held)
        return q[:, :L], q_boot

    def __call__(
        self, obs, last_action, last_reward, hidden, burn_in, learning, forward,
        task=None,
    ):
        return self.unroll(
            obs, last_action, last_reward, hidden, burn_in, learning, forward, task
        )


def init_params(rng: jax.Array, cfg: R2D2Config):
    """Initialize parameters with dummy fixed-shape unroll inputs."""
    net = R2D2Network.from_config(cfg)
    B, T = 2, cfg.seq_len
    obs = jnp.zeros((B, T, *cfg.obs_shape), jnp.uint8)
    la = jnp.zeros((B, T), jnp.int32)
    lr = jnp.zeros((B, T), jnp.float32)
    hid = jnp.zeros((B, *state_spec(cfg)[0]), jnp.float32)
    ones = jnp.ones((B,), jnp.int32)
    # the task input widens the head's Dense inputs, so multi-task init
    # must trace with it for the params to take the wider shape
    task = jnp.zeros((B,), jnp.int32) if cfg.num_tasks > 1 else None
    params = net.init(
        rng, obs, la, lr, hid, ones * cfg.burn_in_steps, ones * cfg.learning_steps,
        ones * cfg.forward_steps, task,
    )
    return net, params
