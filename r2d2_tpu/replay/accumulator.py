"""SequenceAccumulator — actor-side episode accumulator producing Blocks.

Capability parity with the reference LocalBuffer (reference
worker.py:466-652): accumulate one env's transitions, and every
`block_length` steps (or at episode end) pack a Block with n-step returns,
terminal-as-gamma-0 encoding, per-sequence step counts, stored recurrent
states, actor-computed initial priorities, and a burn-in tail carried across
block boundaries for LSTM continuity.

Deliberate behavioral fixes vs the reference (SURVEY.md section 2.5):

- quirk 1: the stored recurrent state for sequence i is taken at the TRUE
  replay-window start `curr_burn_in + i*L - burn_in_i`, not at `i*L`
  (reference worker.py:574) — those differ on every first block of an
  episode.
- quirks 6/7: actor-side initial TDs are computed in the same rescaled
  space as the learner's: |h(R_n + gamma_n * h^-1(max_a q_{t+n})) - q_t(a)|,
  so initial and updated priorities share one scale.
- quirk 13: no hidden global-RNG dependence; this class is deterministic.
"""

from __future__ import annotations

import math
from typing import List, Optional, Tuple

import numpy as np

from r2d2_tpu.config import R2D2Config
from r2d2_tpu.models.core import zero_state
from r2d2_tpu.ops.priority import mixed_td_priorities_np
from r2d2_tpu.ops.returns import n_step_gammas, n_step_returns
from r2d2_tpu.ops.value_rescale import inverse_value_rescale_np, value_rescale_np
from r2d2_tpu.replay.block import Block


class SequenceAccumulator:
    def __init__(self, cfg: R2D2Config, task_id: int = 0, gamma: Optional[float] = None):
        self.cfg = cfg
        self.L = cfg.learning_steps
        self.B = cfg.burn_in_steps
        self.n = cfg.forward_steps
        # per-task gamma (Agent57-style ladder, ops/epsilon.py): the n-step
        # returns and bootstrap discounts are computed HERE at collect time
        # and stored, so a per-task override needs no learner change
        self.gamma = cfg.gamma if gamma is None else float(gamma)
        # stamped into every Block this accumulator packs (multi-task
        # replay stratification; 0 on the single-task golden path)
        self.task_id = int(task_id)
        self.curr_burn_in = 0
        self.size = 0

    def __len__(self) -> int:
        return self.size

    def reset(self, init_obs: np.ndarray) -> None:
        """Seed the episode: NOOP last-action, zero reward, zero hidden
        (reference worker.py:488-509). Observations are COPIED: callers may
        hand in views of buffers they mutate in place later."""
        self.obs_buf: List[np.ndarray] = [np.array(init_obs)]
        self.last_action_buf: List[int] = [0]
        self.last_reward_buf: List[float] = [0.0]
        self.hidden_buf: List[np.ndarray] = [zero_state(self.cfg)]
        self.action_buf: List[int] = []
        self.reward_buf: List[float] = []
        self.qval_buf: List[np.ndarray] = []
        self.curr_burn_in = 0
        self.size = 0
        self.sum_reward = 0.0
        self.done = False

    def carry_state(self) -> dict:
        """The accumulator's full mutable state as flat numpy arrays (for
        the preemption carry in the replay snapshot — npz-safe, no pickle).
        Ragged per-step lists are stacked; counts recover the split."""
        d = {
            "obs": np.stack(self.obs_buf),
            "last_action": np.asarray(self.last_action_buf, np.int64),
            "last_reward": np.asarray(self.last_reward_buf, np.float64),
            "hidden": np.stack(self.hidden_buf),
            "action": np.asarray(self.action_buf, np.int64),
            "reward": np.asarray(self.reward_buf, np.float64),
            "meta": np.asarray(
                [self.curr_burn_in, self.size, int(self.done)], np.int64
            ),
            "sum_reward": np.asarray(self.sum_reward, np.float64),
        }
        if self.qval_buf:
            d["qval"] = np.stack(self.qval_buf)
        else:
            d["qval"] = np.zeros((0, self.cfg.action_dim), np.float32)
        return d

    def restore_carry(self, d: dict) -> None:
        self.obs_buf = list(np.asarray(d["obs"]))
        self.last_action_buf = [int(a) for a in d["last_action"]]
        self.last_reward_buf = [float(r) for r in d["last_reward"]]
        self.hidden_buf = [np.asarray(h, np.float32) for h in d["hidden"]]
        self.action_buf = [int(a) for a in d["action"]]
        self.reward_buf = [float(r) for r in d["reward"]]
        self.qval_buf = [np.asarray(q, np.float32) for q in d["qval"]]
        meta = np.asarray(d["meta"])
        self.curr_burn_in = int(meta[0])
        self.size = int(meta[1])
        self.done = bool(meta[2])
        self.sum_reward = float(np.asarray(d["sum_reward"])[()])

    def add(
        self,
        action: int,
        reward: float,
        next_obs: np.ndarray,
        q_value: np.ndarray,
        hidden: np.ndarray,
    ) -> None:
        """Append one transition. `hidden` is the core's stored state
        (models/core.py: one row of pack_state, float32) AFTER
        consuming the pre-step observation, i.e. the state to use when the
        network next consumes `next_obs` (reference worker.py:511-527)."""
        self.action_buf.append(int(action))
        self.reward_buf.append(float(reward))
        self.hidden_buf.append(np.asarray(hidden, dtype=np.float32))
        self.obs_buf.append(np.array(next_obs))  # copy: see reset()
        self.last_action_buf.append(int(action))
        self.last_reward_buf.append(float(reward))
        self.qval_buf.append(np.asarray(q_value, dtype=np.float32))
        self.sum_reward += float(reward)
        self.size += 1

    def finish(
        self, last_qval: Optional[np.ndarray] = None
    ) -> Tuple[Block, np.ndarray, Optional[float]]:
        """Pack the accumulated steps into a Block.

        last_qval=None means the episode terminated (bootstrap is zeroed via
        gamma_n = 0); otherwise it is Q(s_{T}) used to bootstrap a
        mid-episode cut (reference worker.py:529-554).

        Returns (block, priorities padded to seqs_per_block, episode_reward
        or None if the episode is still running).
        """
        assert 0 < self.size <= self.cfg.block_length
        L, B, n = self.L, self.B, self.n
        size = self.size
        num_seq = math.ceil(size / L)
        max_fwd = min(size, n)
        self.done = last_qval is None

        gamma_n = n_step_gammas(size, self.gamma, n, done=self.done)
        qvals = self.qval_buf + [
            np.zeros_like(self.qval_buf[0]) if self.done else np.asarray(last_qval, dtype=np.float32)
        ]
        qval_arr = np.stack(qvals)  # (size + 1, A)

        n_step_reward = n_step_returns(
            np.asarray(self.reward_buf, dtype=np.float64), self.gamma, n
        )

        obs = np.stack(self.obs_buf)
        last_action = np.asarray(self.last_action_buf, dtype=np.uint8)
        last_reward = np.asarray(self.last_reward_buf, dtype=np.float32)
        actions = np.asarray(self.action_buf, dtype=np.uint8)

        seq_ids = np.arange(num_seq)
        burn_in = np.minimum(seq_ids * L + self.curr_burn_in, B).astype(np.int32)
        learning = np.minimum(L, size - seq_ids * L).astype(np.int32)
        cum_learning = np.cumsum(learning)
        forward = np.minimum(n, size + 1 - cum_learning).astype(np.int32)
        assert forward[-1] == 1 and burn_in[0] == self.curr_burn_in

        # TRUE window starts, in buffer coordinates (quirk-1 fix)
        window_start = self.curr_burn_in + seq_ids * L - burn_in
        hiddens = np.stack([self.hidden_buf[int(w)] for w in window_start])

        # actor-side initial priorities, in rescaled space (quirk-6/7 fix)
        max_q = np.max(qval_arr[max_fwd : size + 1], axis=1)
        max_q = np.pad(max_q, (0, max_fwd - 1), "edge")[:size]
        taken_q = qval_arr[np.arange(size), actions]
        target = value_rescale_np(
            n_step_reward + gamma_n * inverse_value_rescale_np(max_q, self.cfg.value_rescale_eps),
            self.cfg.value_rescale_eps,
        )
        abs_td = np.abs(target - taken_q).astype(np.float32)

        # ragged per-sequence spans -> fixed (num_seq, L) + mask
        td_padded = np.zeros((num_seq, L), dtype=np.float32)
        mask = np.zeros((num_seq, L), dtype=np.float32)
        for i in range(num_seq):
            steps = int(learning[i])
            td_padded[i, :steps] = abs_td[i * L : i * L + steps]
            mask[i, :steps] = 1.0
        priorities = np.zeros(self.cfg.seqs_per_block, dtype=np.float32)
        priorities[:num_seq] = mixed_td_priorities_np(td_padded, mask, self.cfg.td_mix_eta)

        block = Block(
            obs=obs,
            last_action=last_action,
            last_reward=last_reward,
            action=actions,
            n_step_reward=n_step_reward,
            gamma=gamma_n,
            hidden=hiddens,
            num_sequences=num_seq,
            burn_in_steps=burn_in,
            learning_steps=learning,
            forward_steps=forward,
            task=self.task_id,
        )

        episode_reward = self.sum_reward if self.done else None

        if not self.done:
            # carry the last B+1 aligned entries so the next block's early
            # sequences can burn in across the boundary (worker.py:640-647)
            self.obs_buf = self.obs_buf[-B - 1 :]
            self.last_action_buf = self.last_action_buf[-B - 1 :]
            self.last_reward_buf = self.last_reward_buf[-B - 1 :]
            self.hidden_buf = self.hidden_buf[-B - 1 :]
            self.curr_burn_in = len(self.obs_buf) - 1
            self.action_buf.clear()
            self.reward_buf.clear()
            self.qval_buf.clear()
            self.size = 0

        return block, priorities, episode_reward
