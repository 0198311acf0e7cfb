"""MultiTaskTrainer — one task-conditioned learner over per-task fleets.

Topology: T per-task actor fleets (VectorizedActor over each task's vec
env, task_id stamped into every Block) feed T per-task host replay
buffers; ONE train step consumes task-STRATIFIED batches (an equal slice
drawn from every task's buffer, concatenated, with the per-sequence task
vector conditioning the dueling head) and one priority write-back is
split back to each task's sum tree. The learner, parameter store, and
publish cadence are shared — the whole point: one set of weights serves
the family (Agent57's shared-trunk regime, PAPERS.md).

Stratified (not proportional) sampling is deliberate: a dense-reward
task fills its buffer ~10x faster than a sparse one, and priority-
proportional sampling ACROSS tasks would starve the slow task's
gradient signal exactly when it needs it most. Within a task, sampling
stays priority-proportional as ever.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from r2d2_tpu.actor import ParamStore, VectorizedActor
from r2d2_tpu.config import R2D2Config
from r2d2_tpu.learner import DeviceBatch, init_train_state, make_train_step
from r2d2_tpu.models.core import zero_carry
from r2d2_tpu.models.r2d2 import R2D2Network
from r2d2_tpu.multitask.registry import TaskSpec, build_registry
from r2d2_tpu.ops.epsilon import multitask_epsilon_ladders
from r2d2_tpu.replay.replay_buffer import ReplayBuffer
from r2d2_tpu.utils.metrics import MetricsLogger


def _split_even(total: int, parts: int) -> List[int]:
    """total split into `parts` near-equal positive chunks (first chunks
    absorb the remainder)."""
    base, rem = divmod(total, parts)
    out = [base + (1 if i < rem else 0) for i in range(parts)]
    if min(out) < 1:
        raise ValueError(f"cannot split {total} into {parts} positive parts")
    return out


def rollout_returns(
    cfg: R2D2Config,
    net: Optional[R2D2Network],
    params,
    spec: TaskSpec,
    episodes: int = 8,
    horizon: Optional[int] = None,
    seed: int = 0,
    policy: str = "greedy",
) -> np.ndarray:
    """(episodes,) first-episode returns of `policy` on one task.

    policy="greedy": task-conditioned argmax over the shared net (the
    per-task mask floors padded actions, so the argmax stays native).
    policy="random": uniform over the task's NATIVE actions, no net —
    the bench's seeded baseline. Episodes past their first terminal stop
    accruing (the vec env auto-resets underneath; we only score episode
    one per slot). Continuing envs (drift) never terminate, so every
    slot scores the full horizon.
    """
    from r2d2_tpu.train import build_vec_env

    E = episodes
    H = int(horizon or cfg.max_episode_steps)
    cfg_e = cfg.replace(env_name=spec.env_name, num_actors=E)
    env = build_vec_env(cfg_e, seed=seed)
    rng = np.random.default_rng(seed)

    obs = np.array(env.reset_all())
    la = np.zeros(E, np.int32)
    lr = np.zeros(E, np.float32)
    carry = zero_carry(cfg, E)
    task_vec = (
        jnp.full((E,), spec.task_id, jnp.int32) if cfg.num_tasks > 1 else None
    )
    act_fn = None
    if policy == "greedy":
        act_fn = jax.jit(
            lambda p, o, a, r, c: net.apply(
                p, o, a, r, c, task=task_vec, method=net.act
            )
        )
    returns = np.zeros(E, np.float64)
    alive = np.ones(E, bool)
    for _ in range(H):
        if policy == "greedy":
            q, carry = act_fn(params, jnp.asarray(obs), jnp.asarray(la),
                              jnp.asarray(lr), carry)
            actions = np.asarray(jnp.argmax(q, axis=-1), np.int32)
        else:
            actions = rng.integers(0, spec.action_dim, size=E).astype(np.int32)
        term_obs, rewards, dones, next_obs = env.step(actions)
        returns += np.where(alive, np.asarray(rewards, np.float64), 0.0)
        done_now = np.asarray(dones, bool) & alive
        alive &= ~np.asarray(dones, bool)
        obs = np.where(
            done_now.reshape(-1, *([1] * (obs.ndim - 1))), next_obs, term_obs
        )
        la = np.where(alive, actions, 0).astype(np.int32)
        lr = np.where(alive, np.asarray(rewards, np.float32), 0.0).astype(np.float32)
        if not alive.any():
            break
    return returns


class MultiTaskTrainer:
    """One learner, T tasks. Inline alternation (collect then update) —
    the minimal end-to-end multi-task slice, mirroring Trainer's inline
    mode; the threaded planes stay single-task for now."""

    def __init__(
        self,
        cfg: R2D2Config,
        task_names: Sequence[str],
        metrics: Optional[MetricsLogger] = None,
    ):
        cfg, specs = build_registry(cfg, task_names)
        self.cfg = cfg
        self.specs = specs
        T = len(specs)
        bl = cfg.block_length

        self.net, self.state = init_train_state(cfg, jax.random.PRNGKey(cfg.seed))
        self.param_store = ParamStore(self.state.params)
        self.step_fn = make_train_step(cfg, self.net)
        self.sample_rng = np.random.default_rng(cfg.seed + 2)
        self.metrics = metrics

        apt = max(1, cfg.num_actors // T)
        eps = multitask_epsilon_ladders(T, apt, cfg.base_eps, cfg.eps_alpha)
        self.batch_split = _split_even(cfg.batch_size, T)
        # per-task ring: an equal share of capacity, floored to a block
        # multiple (config invariant), never below a handful of blocks
        cap_t = max((cfg.buffer_capacity // T) // bl, 4) * bl
        ls_t = max(cfg.learning_starts // T, max(self.batch_split))

        from r2d2_tpu.train import build_vec_env

        self.replays: List[ReplayBuffer] = []
        self.actors: List[VectorizedActor] = []
        self.task_cfgs: List[R2D2Config] = []
        for spec in specs:
            cfg_t = cfg.replace(
                env_name=spec.env_name,
                num_actors=apt,
                batch_size=self.batch_split[spec.task_id],
                buffer_capacity=cap_t,
                learning_starts=ls_t,
                gamma=spec.gamma,
            )
            self.task_cfgs.append(cfg_t)
            replay = ReplayBuffer(cfg_t)
            env = build_vec_env(cfg_t, seed=cfg.seed + 101 * (spec.task_id + 1))
            actor = VectorizedActor(
                cfg_t,
                self.net,
                self.param_store,
                env,
                eps[spec.task_id],
                replay.add_block,
                seed=cfg.seed + 7 * (spec.task_id + 1),
                task_id=spec.task_id,
                action_dim=spec.action_dim,
                gamma=spec.gamma,
            )
            self.replays.append(replay)
            self.actors.append(actor)
        self._updates = 0
        self._start = time.time()

    # ------------------------------------------------------------- phases

    def warmup(self, max_steps_per_task: int = 1_000_000) -> None:
        """Round-robin collection until EVERY task's buffer opens its
        sampling gate — no task trains on another task's warmup."""
        for t, (actor, replay) in enumerate(zip(self.actors, self.replays)):
            steps = 0
            while not replay.can_sample():
                actor.step()
                steps += actor.steps_per_call
                if steps >= max_steps_per_task:
                    raise RuntimeError(
                        f"task {t} ({self.specs[t].env_name}) warmup exceeded "
                        f"{max_steps_per_task} steps without filling replay"
                    )

    def _sample_stratified(self):
        """One equal-share draw per task, concatenated into a single
        DeviceBatch with the per-sequence task vector; per-task index/
        stamp segments ride along for the split priority write-back."""
        parts = [r.sample_batch(self.sample_rng) for r in self.replays]
        segs = []
        for b in parts:
            segs.append((len(b.idxes), b.idxes, b.old_ptr, b.old_advances))
        cat = lambda xs: np.concatenate(xs, axis=0)
        dev = DeviceBatch(
            obs=jnp.asarray(cat([b.obs for b in parts])),
            last_action=jnp.asarray(cat([b.last_action for b in parts]), jnp.int32),
            last_reward=jnp.asarray(cat([b.last_reward for b in parts])),
            hidden=jnp.asarray(cat([np.asarray(b.hidden) for b in parts])),
            action=jnp.asarray(cat([b.action for b in parts]), jnp.int32),
            n_step_reward=jnp.asarray(cat([b.n_step_reward for b in parts])),
            gamma=jnp.asarray(cat([b.gamma for b in parts])),
            burn_in_steps=jnp.asarray(cat([b.burn_in_steps for b in parts])),
            learning_steps=jnp.asarray(cat([b.learning_steps for b in parts])),
            forward_steps=jnp.asarray(cat([b.forward_steps for b in parts])),
            is_weights=jnp.asarray(cat([b.is_weights for b in parts])),
            task=jnp.asarray(cat([b.task for b in parts]), jnp.int32),
        )
        return dev, segs

    def update(self) -> Dict[str, float]:
        """One stratified train step + split priority write-back."""
        dev, segs = self._sample_stratified()
        self.state, m, priorities = self.step_fn(self.state, dev)
        prios = np.asarray(priorities)
        off = 0
        for replay, (n, idxes, old_ptr, old_adv) in zip(self.replays, segs):
            replay.update_priorities(idxes, prios[off : off + n], old_ptr, old_adv)
            off += n
        self._updates += 1
        if self._updates % self.cfg.publish_interval == 0:
            self.param_store.publish(self.state.params)
        return m

    def train(self, num_updates: int, collect_steps_per_update: int = 1):
        """Inline alternation: every update is preceded by
        collect_steps_per_update env steps on EVERY task's fleet."""
        last_m = None
        for _ in range(num_updates):
            for actor in self.actors:
                for _ in range(collect_steps_per_update):
                    actor.step()
            last_m = self.update()
            if self.metrics is not None and self._updates % 10 == 0:
                self.metrics.log(self._metrics_row(last_m))
        self.param_store.publish(self.state.params)
        return last_m

    # ------------------------------------------------------------ reporting

    def _metrics_row(self, m) -> dict:
        row = {
            "step": self._updates,
            "loss": float(m["loss"]),
            "q_mean": float(m["q_mean"]),
        }
        for t, replay in enumerate(self.replays):
            n_ep, r_sum = replay.pop_episode_stats()
            row[f"task{t}_env_steps"] = replay.env_steps
            row[f"task{t}_episodes"] = n_ep
            row[f"task{t}_mean_return"] = (r_sum / n_ep) if n_ep else None
        return row

    def evaluate(
        self, episodes: int = 8, horizon: Optional[int] = None, seed: int = 1234
    ) -> List[dict]:
        """Per-task greedy eval rows (NOT an average across tasks — the
        acceptance bar is per-task)."""
        params, _ = self.param_store.latest()
        rows = []
        for spec in self.specs:
            rets = rollout_returns(
                self.cfg, self.net, params, spec,
                episodes=episodes, horizon=horizon,
                seed=seed + spec.task_id, policy="greedy",
            )
            rows.append({
                "task": spec.task_id,
                "env": spec.env_name,
                "episodes": episodes,
                "mean_return": float(np.mean(rets)),
                "min_return": float(np.min(rets)),
                "max_return": float(np.max(rets)),
            })
        return rows
