"""Actor service (L4): vectorized ε-greedy experience collection.

Capability parity with the reference Actor (reference worker.py:655-762),
re-architected: instead of one OS process per ε (reference train.py:41-46),
ONE actor object steps E environments with a single jitted, batched policy
call per env-step — the vmap'd acting path that removes the reference's
per-env CPU forward bottleneck (SURVEY.md section 3.2). The Ape-X ε ladder
becomes a per-env vector.

Semantics preserved per env (reference worker.py:685-747):
- ε-greedy on the dueling Q output; per-env LSTM carry held on device.
- every transition goes to that env's SequenceAccumulator with its Q row
  and post-step (h, c) pair.
- block cut at block_length or at max_episode_steps truncation: finished
  with a bootstrap Q for the next obs. The reference re-runs the model
  inline for that Q (worker.py:729-732); here the cut is DEFERRED one step
  so the bootstrap reuses the next iteration's batched policy call — same
  value, no extra forward.
- terminal: finish(None) (gamma_n = 0 path), fresh accumulator seeded with
  the new episode's first obs, carry/last-action/last-reward zeroed
  (worker.py:753-762).
- weight refresh every `actor_update_interval` env steps from the published
  snapshot (worker.py:744-751) — here an atomic reference swap, so a torn
  read of a half-written state_dict (SURVEY.md section 5.2) cannot happen.
"""

from __future__ import annotations

import json
import threading
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from r2d2_tpu.config import R2D2Config
from r2d2_tpu.models.core import pack_state, zero_carry
from r2d2_tpu.models.r2d2 import R2D2Network
from r2d2_tpu.replay.accumulator import SequenceAccumulator
from r2d2_tpu.utils.faults import fault_point


class ParamStore:
    """Published parameter snapshot: learner swaps the reference, actors
    read it — immutable objects make the race benign by construction."""

    def __init__(self, params):
        self._params = jax.tree.map(jnp.copy, params)
        self.version = 0
        self._lock = threading.Lock()

    def publish(self, params) -> None:
        # snapshot: the learner's own buffers may be donated into the next
        # jitted step, so the published tree must be an independent copy
        snap = jax.tree.map(jnp.copy, params)
        with self._lock:
            self._params = snap
            self.version += 1

    def latest(self):
        with self._lock:
            return self._params, self.version


class HostEnvPool:
    """Vec adapter over a list of host-protocol envs (atari/scripted).

    step() returns (terminal-inclusive obs, rewards, dones, next_obs) where
    next_obs differs from obs only on done rows (the fresh episode's first
    frame) — the same contract as CatchVecEnv."""

    def __init__(self, envs: Sequence):
        self.envs = list(envs)
        self.num_envs = len(self.envs)
        self.action_dim = getattr(envs[0], "action_dim", None) or envs[0].action_space.n
        self.obs_shape = envs[0].obs_shape

    def reset_all(self) -> np.ndarray:
        return np.stack([e.reset() for e in self.envs])

    @staticmethod
    def _step_one(env, action) -> tuple:
        """The per-env step + auto-reset contract, defined once for the
        serial and threaded pools: returns (terminal-inclusive obs,
        reward, done, next obs where next differs only on done)."""
        o, r, d, _ = env.step(int(action))
        return o, r, d, (env.reset() if d else o)

    def step(self, actions: np.ndarray):
        obs, rewards, dones, nxt = zip(
            *(self._step_one(e, a) for e, a in zip(self.envs, actions))
        )
        return np.stack(obs), np.asarray(rewards), np.asarray(dones), np.stack(nxt)

    def force_reset(self, i: int) -> np.ndarray:
        """Mid-flight reset of one slot (max_episode_steps truncation)."""
        return self.envs[i].reset()


class ThreadedHostEnvPool(HostEnvPool):
    """HostEnvPool with env stepping fanned across a persistent thread
    pool — the scaling fix for emulator fleets: the reference ran 8 actor
    PROCESSES to step 8 ALEs concurrently (reference worker.py:655-762,
    train.py:44-46); here E≥256 emulator envs on a many-core host step in
    parallel threads under one vectorized policy. Worthwhile because ALE
    (and most C-core emulators) release the GIL inside step(); pure-Python
    envs gain nothing and pure-JAX envs should use their vec adapters
    instead. Same step()/reset_all() contract as HostEnvPool — per-env
    ordering is preserved by mapping over the env list index."""

    def __init__(self, envs: Sequence, workers: Optional[int] = None):
        super().__init__(envs)
        from concurrent.futures import ThreadPoolExecutor

        self._pool = ThreadPoolExecutor(
            max_workers=workers or min(32, self.num_envs),
            thread_name_prefix="envpool",
        )

    def reset_all(self) -> np.ndarray:
        return np.stack(list(self._pool.map(lambda e: e.reset(), self.envs)))

    def step(self, actions: np.ndarray):
        obs, rewards, dones, nxt = zip(
            *self._pool.map(self._step_one, self.envs, actions)
        )
        return np.stack(obs), np.asarray(rewards), np.asarray(dones), np.stack(nxt)

    def close(self) -> None:
        """Release the worker threads; a sweep building one pool per game
        must not accumulate idle executors. Also called on GC."""
        self._pool.shutdown(wait=False)

    def __del__(self):  # best-effort: explicit close() is preferred
        try:
            self._pool.shutdown(wait=False)
        except Exception:
            pass


def _carry_keys(carry) -> List[str]:
    """npz keys of the carry's rows in the preemption carry: the first two
    keep the names every snapshot on disk has."""
    on_disk = ("carry_h", "carry_c")
    return [on_disk[i] if i < 2 else f"carry_{i}" for i in range(len(carry))]


class VectorizedActor:
    def __init__(
        self,
        cfg: R2D2Config,
        net: R2D2Network,
        param_store: ParamStore,
        env,  # vec env protocol: num_envs, reset_all(), step(actions)
        epsilons: np.ndarray,  # (E,) per-env ε (the ladder)
        push_block: Callable,  # (block, priorities, episode_reward) -> None
        seed: int = 0,
        task_id: int = 0,              # stamped into every pushed Block
        action_dim: Optional[int] = None,  # task's NATIVE action count
        gamma: Optional[float] = None,     # per-task discount (Agent57)
    ):
        E = env.num_envs
        assert len(epsilons) == E
        self.cfg = cfg
        self.net = net
        self.param_store = param_store
        self.env = env
        self.epsilons = np.asarray(epsilons, np.float32)
        self.push_block = push_block
        self.rng = np.random.default_rng(seed)
        # random exploration draws stay inside the task's native action
        # range; greedy picks are already confined by the model's task mask
        self.action_dim = cfg.action_dim if action_dim is None else int(action_dim)
        self.task_id = int(task_id)
        self.gamma = gamma

        # fused act tail (ops/act_tail.py): core step + dueling + ε-greedy
        # select run as ONE jitted program; the ε coin and random draws are
        # inputs so the host numpy RNG stream (and host-vs-device action
        # parity) is unchanged.
        task_vec = (
            jnp.full((E,), self.task_id, jnp.int32) if cfg.num_tasks > 1 else None
        )
        self._policy = jax.jit(
            lambda params, obs, la, lr, carry, explore, rand_a: net.apply(
                params, obs, la, lr, carry, explore, rand_a,
                task=task_vec, method=net.act_select,
            )
        )
        self.params, self.param_version = param_store.latest()

        self._reset_state(np.array(env.reset_all()))  # writable copy (vec
        self.total_steps = 0     # envs may hand back read-only device buffers)
        self._steps_since_refresh = 0

    def _reset_state(self, obs: np.ndarray) -> None:
        """Per-episode-stream state: accumulators seeded with `obs`, zeroed
        carry/last-action/last-reward, cleared pending-cut flags. Shared by
        __init__ and resync so restart recovery can never miss a field."""
        cfg = self.cfg
        E = self.env.num_envs
        self.accs: List[SequenceAccumulator] = [
            SequenceAccumulator(cfg, task_id=self.task_id, gamma=self.gamma)
            for _ in range(E)
        ]
        for i in range(E):
            self.accs[i].reset(obs[i])
        self.obs = obs
        self.last_action = np.zeros(E, np.int32)
        self.last_reward = np.zeros(E, np.float32)
        self.carry = zero_carry(cfg, E)
        self.episode_steps = np.zeros(E, np.int64)
        # envs whose accumulator awaits a bootstrap Q from the next policy call
        self._pending_cut = np.zeros(E, bool)
        self._pending_truncate = np.zeros(E, bool)

    # ------------------------------------------------------------------ api

    @property
    def steps_per_call(self) -> int:
        """Env transitions one step() yields (collector duck-type)."""
        return self.env.num_envs

    def run_steps(self, n: int) -> None:
        for _ in range(n):
            self.step()

    def step(self) -> None:
        fault_point("actor.step")
        cfg = self.cfg
        E = self.env.num_envs

        # ε-greedy over the ladder vector (reference worker.py:703-706):
        # coins drawn on host in the pre-fusion stream order, selection
        # fused into the policy program (net.act_select).
        explore = self.rng.random(E) < self.epsilons
        random_a = self.rng.integers(0, self.action_dim, size=E)
        q, device_actions, carry = self._policy(
            self.params,
            jnp.asarray(self.obs),
            jnp.asarray(self.last_action),
            jnp.asarray(self.last_reward),
            self.carry,
            jnp.asarray(explore),
            jnp.asarray(random_a.astype(np.int32)),
        )
        q_np = np.asarray(q, np.float32)

        # Deferred cuts: this call's Q is Q(s) for exactly the obs the cut
        # needs to bootstrap from (block boundary, worker.py:726-732; or
        # max_episode_steps truncation).
        fresh = np.zeros(E, bool)  # slots starting a new episode this tick
        for i in np.nonzero(self._pending_cut | self._pending_truncate)[0]:
            block, prios, ep_reward = self.accs[i].finish(last_qval=q_np[i])
            self.push_block(block, prios, ep_reward)
            if self._pending_truncate[i]:
                # new episode: fresh env state if the env supports mid-flight
                # reset (host pools do; device envs with bounded episodes
                # never truncate), zeroed carry/last-action/last-reward.
                if hasattr(self.env, "force_reset"):
                    self.obs[i] = self.env.force_reset(i)
                self.last_action[i] = 0
                self.last_reward[i] = 0.0
                self.episode_steps[i] = 0
                fresh[i] = True
        self._pending_cut[:] = False
        self._pending_truncate[:] = False

        # Fresh slots take a NOOP: their Q row was computed from the dead
        # episode's obs, so this tick is absorbed as one extra no-op at
        # episode start (same family as the noop-start wrapper) and not
        # recorded; the accumulator is seeded with the post-step obs below.
        actions = np.asarray(device_actions, np.int32).copy()
        actions[fresh] = 0
        term_obs, rewards, dones, next_obs = self.env.step(actions)

        # (E, *state_shape): the stored-state rule, models/core.py
        hidden_np = pack_state(tuple(np.asarray(x) for x in carry))

        keep = np.ones(E, np.float32)
        for i in range(E):
            if fresh[i]:
                seed_obs = next_obs[i] if dones[i] else term_obs[i]
                self.accs[i].reset(seed_obs)
                self.obs[i] = seed_obs
                keep[i] = 0.0
                continue
            self.accs[i].add(int(actions[i]), float(rewards[i]), term_obs[i], q_np[i], hidden_np[i])
            self.episode_steps[i] += 1
            if dones[i]:
                block, prios, ep_reward = self.accs[i].finish(last_qval=None)
                self.push_block(block, prios, ep_reward)
                self.accs[i].reset(next_obs[i])
                self.obs[i] = next_obs[i]
                self.last_action[i] = 0
                self.last_reward[i] = 0.0
                self.episode_steps[i] = 0
                keep[i] = 0.0
            else:
                self.obs[i] = term_obs[i]
                self.last_action[i] = actions[i]
                self.last_reward[i] = rewards[i]
                if self.episode_steps[i] >= cfg.max_episode_steps:
                    self._pending_truncate[i] = True
                elif len(self.accs[i]) == cfg.block_length:
                    self._pending_cut[i] = True

        if not keep.all():
            k = jnp.asarray(keep)[:, None]
            self.carry = tuple(x * k for x in carry)
        else:
            self.carry = carry

        self.total_steps += E
        self._steps_since_refresh += E
        if self._steps_since_refresh >= cfg.actor_update_interval:
            self._steps_since_refresh = 0
            self._maybe_refresh_params()

    def resync(self) -> None:
        """Recover to a consistent state after a mid-step fault (the
        supervisor's restart hook). step() is not re-entrant once env.step
        has run — a crash between env.step and the accumulator writes would
        leave self.obs/carry describing the pre-step world while the env
        has advanced, and re-entering would push misaligned (obs, action,
        hidden) sequences into replay. Instead: discard every in-flight
        accumulator window and start fresh episodes in all slots."""
        self._reset_state(np.array(self.env.reset_all()))

    def carry_state(self) -> dict:
        """Every mutable field step() reads, as flat npz-safe numpy arrays
        (the preemption carry). Restoring this on a fresh actor of the same
        config makes the next step() bit-identical to the one an
        uninterrupted run would have taken — unlike resync(), which
        discards in-flight windows and restarts the episode streams."""
        d = {
            "rng": np.asarray(json.dumps(self.rng.bit_generator.state)),
            "obs": np.asarray(self.obs),
            "last_action": self.last_action.copy(),
            "last_reward": self.last_reward.copy(),
            **{k: np.asarray(x) for k, x in zip(_carry_keys(self.carry), self.carry)},
            "episode_steps": self.episode_steps.copy(),
            "pending_cut": self._pending_cut.copy(),
            "pending_truncate": self._pending_truncate.copy(),
            "counters": np.asarray(
                [self.total_steps, self._steps_since_refresh, self.param_version],
                np.int64,
            ),
        }
        for j, leaf in enumerate(jax.tree.leaves(self.params)):
            d[f"params_{j}"] = np.asarray(leaf)
        for i, acc in enumerate(self.accs):
            for k, v in acc.carry_state().items():
                d[f"acc{i}_{k}"] = v
        return d

    def restore_carry(self, d: dict) -> None:
        self.rng.bit_generator.state = json.loads(str(np.asarray(d["rng"])[()]))
        self.obs = np.array(d["obs"])
        self.last_action = np.asarray(d["last_action"], np.int32)
        self.last_reward = np.asarray(d["last_reward"], np.float32)
        self.carry = tuple(jnp.asarray(d[k]) for k in _carry_keys(self.carry))
        self.episode_steps = np.asarray(d["episode_steps"], np.int64)
        self._pending_cut = np.asarray(d["pending_cut"], bool)
        self._pending_truncate = np.asarray(d["pending_truncate"], bool)
        counters = np.asarray(d["counters"])
        self.total_steps = int(counters[0])
        self._steps_since_refresh = int(counters[1])
        self.param_version = int(counters[2])
        treedef = jax.tree.structure(self.params)
        leaves = [jnp.asarray(d[f"params_{j}"]) for j in range(treedef.num_leaves)]
        self.params = jax.tree.unflatten(treedef, leaves)
        for i, acc in enumerate(self.accs):
            prefix = f"acc{i}_"
            acc.restore_carry({
                k[len(prefix):]: v for k, v in d.items() if k.startswith(prefix)
            })

    # ---------------------------------------------------------------- utils

    def _maybe_refresh_params(self) -> None:
        params, version = self.param_store.latest()
        if version != self.param_version:
            self.params = params
            self.param_version = version
