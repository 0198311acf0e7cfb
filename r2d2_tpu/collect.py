"""Fully on-device experience collection (L4, device data path end to end).

The host VectorizedActor (actor.py) removes the reference's per-env CPU
forward bottleneck (reference worker.py:699-700) by batching the policy,
but every env step is still a host->device round trip and every block a
host->HBM upload. For pure-JAX functional envs (envs/catch.py, and any env
exposing reset/step/render as jit-vmappable functions) the ENTIRE
collection loop runs as one jitted lax.scan chunk on device:

    policy act -> epsilon-greedy over the ladder vector -> env dynamics ->
    render -> block packing (n-step returns, terminal-as-gamma-0 encoding,
    per-sequence counters, true-window-start stored hiddens, rescaled-space
    initial priorities)

and the packed block fields are handed to the HBM replay store
(DeviceReplayBuffer.add_blocks_batch) WITHOUT visiting host memory. Host
work per chunk: sum-tree bookkeeping over a few kilobytes of priorities
and counters.

Chunk semantics == reference actor semantics with max_episode_steps ==
chunk_len: each chunk starts fresh episodes in every slot (zero carry,
NOOP last-action, zero reward — reference worker.py:488-509), steps until
each env's episode terminates (slots that finish early idle out the rest
of the chunk), and slots still running at the chunk end are TRUNCATED with
a bootstrap Q from one final policy evaluation — exactly the host actor's
deferred-cut path (actor.py). Packing reproduces
replay.accumulator.SequenceAccumulator bit-for-bit, including the quirk-1
(stored-state alignment) and quirk-6/7 (rescaled-space initial priority)
fixes; tests/test_collect.py pins equivalence against the host actor path
on identical trajectories.

EPISODES LONGER THAN ONE CHUNK (carry_episodes=True): a slot still alive
at the chunk end is NOT reset — its env state, recurrent state, last
action/reward, and partial episode reward carry into the next chunk,
whose block stores the episode's continuation. The chunk boundary is a
standard truncation-with-bootstrap cut (the same final-Q bootstrap as
above, reward-correct under n-step returns), and the continuation
block's first learning window replays from the CARRIED recurrent state
stored as its window-0 state with ZERO burn-in — the R2D2 paper's pure
stored-state strategy at the seam. This is deliberately SIMPLER than the
host SequenceAccumulator, which also copies the previous block's last
burn_in entries into a continuation block's head so window 0 can refresh
the stale stored state by burn-in replay (accumulator.py:123,170-176,
mirroring reference worker.py:613-616): here only windows 1+ of each
block get burn-in refresh, and the seam window leans on the stored
state alone. Consequence: host-vs-device block equivalence holds
exactly for episode-aligned chunks (the tested contract); for
multi-chunk episodes the device path trades the seam window's burn-in
refresh for a fixed-shape jittable packer. Episode stats (count, total
reward) are reported once per episode, at its true end (or at the
cfg.max_episode_steps cap).
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from r2d2_tpu.config import R2D2Config
from r2d2_tpu.models.core import Carry, close_carry, core_class, open_carry, pack_state, zero_carry
from r2d2_tpu.models.encoders import block_frames, blocked_shape
from r2d2_tpu.models.r2d2 import R2D2Network
from r2d2_tpu.ops.epsilon import epsilon_ladder
from r2d2_tpu.ops.priority import mixed_td_priorities
from r2d2_tpu.ops.value_rescale import inverse_value_rescale, value_rescale
from r2d2_tpu.replay.block import frames_to_rows


def _where_rows(mask: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Row-wise select: mask (E,) broadcast over a/b's trailing dims."""
    return jnp.where(mask.reshape(mask.shape + (1,) * (a.ndim - 1)), a, b)


def default_chunk_len(cfg: R2D2Config) -> int:
    """The chunk rule shared by collection and device-side eval: episodes
    are truncated at block_length (a block holds at most one episode)."""
    return min(cfg.block_length, cfg.max_episode_steps)


class CollectCarry(NamedTuple):
    """Per-slot cross-chunk episode state (carry_episodes=True): the env
    state, post-step recurrent state, last action/reward the policy must
    resume from, and the episode reward/steps accumulated in earlier
    chunks (ep_steps enforces cfg.max_episode_steps for envs whose
    internal horizon is looser than the config cap)."""

    env_state: object
    core: Carry                 # the core's carry, (E, H) f32 each (models/core.py)
    last_action: jnp.ndarray    # (E,) int32
    last_reward: jnp.ndarray    # (E,) f32
    prefix_reward: jnp.ndarray  # (E,) f32
    ep_steps: jnp.ndarray       # (E,) int32


def initial_carry(cfg: R2D2Config, fn_env, num_envs: int, key) -> CollectCarry:
    """Fresh episodes in every slot: reset env states, zero recurrent
    state / NOOP last action / zero reward (reference worker.py:488-509)."""
    E = num_envs
    return CollectCarry(
        env_state=jax.vmap(fn_env.reset)(jax.random.split(key, E)),
        core=zero_carry(cfg, E),
        last_action=jnp.zeros(E, jnp.int32),
        last_reward=jnp.zeros(E, jnp.float32),
        prefix_reward=jnp.zeros(E, jnp.float32),
        ep_steps=jnp.zeros(E, jnp.int32),
    )


def make_collect_fn(
    cfg: R2D2Config, net: R2D2Network, fn_env, num_envs: int, chunk_len: int,
    carry_episodes: bool = False, task_id: int = 0,
    action_dim: Optional[int] = None, gamma: Optional[float] = None,
):
    """Jitted chunk collector (see make_collect_core for the contract)."""
    return jax.jit(
        make_collect_core(
            cfg, net, fn_env, num_envs, chunk_len, carry_episodes,
            task_id=task_id, action_dim=action_dim, gamma=gamma,
        )
    )


def make_collect_core(
    cfg: R2D2Config, net: R2D2Network, fn_env, num_envs: int, chunk_len: int,
    carry_episodes: bool = False, task_id: int = 0,
    action_dim: Optional[int] = None, gamma: Optional[float] = None,
):
    """Build the (un-jitted) chunk collector — jit it directly
    (make_collect_fn) or compose it into a larger dispatch
    (megastep.make_megastep fuses it with K learner updates).

    fn_env protocol (all jit/vmap-safe): reset(key) -> state,
    step(state, action) -> (state', reward, done), render(state) -> uint8
    obs of cfg.obs_shape.

    Returns collect(params, env_state, epsilons, key) ->
      (fields, priorities, num_seq, sizes, dones, ep_rewards,
       fresh_env_state, key')
    where `fields` is a dict of (E, ...) store-slot-shaped device arrays
    keyed exactly like DeviceReplayBuffer.stores.

    carry_episodes=True (episodes longer than one chunk, module
    docstring): the env_state argument and the 7th result are a
    CollectCarry instead of a bare env state — slots alive at the chunk
    end continue their episode next chunk (carried env/recurrent state),
    finished/idle slots restart fresh, and ep_rewards holds FULL episode
    returns (prefix + chunk), meaningful where dones is set.

    Multi-task plane: task_id stamps every packed block's per-sequence
    task field (present only when cfg.num_tasks > 1) and conditions the
    policy; action_dim narrows RANDOM exploration draws to the task's
    native action count (greedy picks stay safe because the task mask in
    models/r2d2.py floors padded actions); gamma overrides cfg.gamma for
    this task's stored n-step returns (Agent57-style per-task discount).
    """
    E, T = num_envs, chunk_len
    L, Bn, n = cfg.learning_steps, cfg.burn_in_steps, cfg.forward_steps
    S, bl, slot = cfg.seqs_per_block, cfg.block_length, cfg.block_slot_len
    A = cfg.action_dim if action_dim is None else int(action_dim)
    gamma = cfg.gamma if gamma is None else float(gamma)
    eps_h = cfg.value_rescale_eps
    # (E,) task conditioning vector for the policy; None on the golden path
    task_vec = (
        jnp.full((E,), int(task_id), jnp.int32) if cfg.num_tasks > 1 else None
    )
    if not (0 < T <= bl):
        raise ValueError(f"chunk_len {T} must be in (0, block_length={bl}]")

    vreset = jax.vmap(fn_env.reset)
    vstep = jax.vmap(fn_env.step)
    vrender = jax.vmap(fn_env.render)
    # a frame in the device stores' byte order (replay/block.py) and its shape
    frame_block = cfg.resolved_frame_block
    stored_shape = blocked_shape(cfg.obs_shape, frame_block)
    stored_order = lambda frames: block_frames(frames, cfg.obs_shape, frame_block)

    t1 = jnp.arange(T + 1)
    tT = jnp.arange(T)
    sid = jnp.arange(S)
    # where each of a block's S windows starts: sid * L less its burn-in,
    # min(sid * L, Bn), which `_pack` gives every window that holds a step.
    # Static, so a core whose state is large (its class says
    # `keeps_window_starts`, models/core.py) has its carry kept there alone:
    # the chunk's scan runs in segments that end at the starts, and the
    # carry between two segments is the state `_pack` stores. Every other
    # core stacks its state at every step, as before.
    window_starts = np.clip(np.arange(S) * L - np.minimum(np.arange(S) * L, Bn), 0, T)
    starts_only = getattr(core_class(cfg), "keeps_window_starts", False)
    segment_ends = sorted({*window_starts.tolist(), T} - {0}) if starts_only else [T]

    def _pack(obs, final_obs, actions, rewards, qs, hiddens, size, done, qf,
              init_la, init_lr, init_hid):
        """Pack ONE env's chunk into store-slot-shaped block fields.

        Mirrors SequenceAccumulator.finish (replay/accumulator.py) with
        fixed shapes + masks: obs (T, R, 128) and final_obs (R, 128), frames
        as the store's lane-aligned rows, actions/rewards (T,) already
        zero-masked past `size`, qs (T, A), hiddens (T, *state_shape)
        post-step states (models/core.py), size scalar int, done scalar
        bool, qf (A,) the final policy eval for the truncation bootstrap.
        init_la/init_lr/init_hid
        are the pre-chunk last action / last reward / recurrent state:
        zeros at an episode start, the carried values on a continuation
        chunk (carry_episodes). Where the collector keeps the carry at the
        window starts alone (`starts_only`), hiddens is (S, *state_shape):
        the state BEFORE each of `window_starts`, init_hid among them."""
        valid_t1 = t1 <= size          # stored entries 0..size
        valid_T = tT < size            # recorded transitions

        stored_obs = jnp.concatenate([obs, final_obs[None]], axis=0)
        stored_obs = jnp.where(
            valid_t1.reshape(-1, *([1] * (obs.ndim - 1))), stored_obs, 0
        )
        stored_la = jnp.where(valid_t1, jnp.concatenate([init_la[None], actions]), 0)
        stored_lr = jnp.where(valid_t1, jnp.concatenate([init_lr[None], rewards]), 0.0)
        pad1 = slot - (T + 1)
        f_obs = jnp.pad(stored_obs, ((0, pad1),) + ((0, 0),) * (obs.ndim - 1))
        f_la = jnp.pad(stored_la, (0, pad1))
        f_lr = jnp.pad(stored_lr, (0, pad1))

        # n-step return R_t = sum_{k<n} gamma^k r_{t+k}, zeros past the end
        # (ops/returns.n_step_returns semantics, reference worker.py:593-595)
        rpad = jnp.concatenate([rewards, jnp.zeros(max(n - 1, 0), jnp.float32)])
        R = jnp.zeros(T, jnp.float32)
        for k in range(n):
            R = R + (gamma**k) * jax.lax.dynamic_slice_in_dim(rpad, k, T)
        R = jnp.where(valid_T, R, 0.0)

        # bootstrap discount gamma_n(t): gamma^n on full windows, shrinking
        # gamma^{size-t} toward a truncation, 0 past a terminal
        # (ops/returns.n_step_gammas semantics, reference worker.py:543-554)
        max_fwd = jnp.minimum(size, n)
        exp_tail = jnp.clip(size - tT, 1, n).astype(jnp.float32)
        g_tail = jnp.where(done, 0.0, jnp.power(jnp.float32(gamma), exp_tail))
        gamma_n = jnp.where(tT < size - max_fwd, jnp.float32(gamma**n), g_tail)
        gamma_n = jnp.where(valid_T, gamma_n, 0.0)

        padT = bl - T
        f_action = jnp.pad(actions, (0, padT))
        f_R = jnp.pad(R, (0, padT))
        f_gamma = jnp.pad(gamma_n, (0, padT))

        # per-sequence counters (reference worker.py:606-610; int32 per
        # SURVEY.md quirk 12). Window 0 always packs with burn_in=0: the
        # chunk is either episode-aligned (its true start) or a
        # carry_episodes continuation whose window 0 replays from the
        # carried stored state without burn-in (module docstring).
        num_seq = (size + L - 1) // L
        valid_seq = sid < num_seq
        burn = jnp.where(valid_seq, jnp.minimum(sid * L, Bn), 0)
        learn = jnp.clip(size - sid * L, 0, L)
        cum = jnp.cumsum(learn)
        fwd = jnp.where(valid_seq, jnp.clip(size + 1 - cum, 0, n), 0)

        # stored recurrent state at the TRUE window start (quirk-1 fix):
        # hidden_buf[t] = state before consuming obs t; index 0 is the
        # episode-start zero state, or the carried state on a
        # continuation chunk (carry_episodes)
        if starts_only:
            hid_seq = jnp.where(valid_seq[:, None, None], hiddens, 0.0)
        else:
            stored_hid = jnp.concatenate([init_hid[None], hiddens], axis=0)
            wstart = jnp.clip(sid * L - burn, 0, T)
            hid_seq = jnp.where(valid_seq[:, None, None], stored_hid[wstart], 0.0)

        # actor-side initial priorities in rescaled space (quirk-6/7 fix):
        # bootstrap value is max_a Q(s_{min(t+max_fwd, size)}), zeroed at a
        # terminal (SequenceAccumulator.finish edge-pad closed form)
        qarr = jnp.concatenate([qs, qf[None].astype(jnp.float32)], axis=0)
        qarr = jnp.where((t1 >= size)[:, None] & done, 0.0, qarr)
        boot_idx = jnp.minimum(tT + max_fwd, size)
        max_q = jnp.max(qarr, axis=1)[boot_idx]
        taken_q = qarr[tT, actions]
        target = value_rescale(R + gamma_n * inverse_value_rescale(max_q, eps_h), eps_h)
        abs_td = jnp.where(valid_T, jnp.abs(target - taken_q), 0.0)
        td_pad = jnp.pad(abs_td, (0, padT)).reshape(S, L)
        m = (jnp.arange(L)[None, :] < learn[:, None]).astype(jnp.float32)
        prios = mixed_td_priorities(td_pad, m, cfg.td_mix_eta)

        fields = {
            "obs": f_obs.astype(jnp.uint8),
            "last_action": f_la.astype(jnp.int32),
            "last_reward": f_lr.astype(jnp.float32),
            "action": f_action.astype(jnp.int32),
            "n_step_reward": f_R,
            "gamma": f_gamma,
            # downcast to the store dtype at pack time (f32 | bf16): the
            # donated slab write into the HBM store requires exact dtype
            # match with store_field_specs
            "hidden": hid_seq.astype(jnp.dtype(cfg.state_dtype)),
            "burn_in": burn.astype(jnp.int32),
            "learning": learn.astype(jnp.int32),
            "forward": fwd.astype(jnp.int32),
        }
        if cfg.num_tasks > 1:
            # per-sequence task ids, lockstep with store_field_specs
            fields["task"] = jnp.full((S,), int(task_id), jnp.int32)
        return fields, prios, num_seq.astype(jnp.int32)

    def collect(params, env_state, epsilons, key):
        if carry_episodes:
            carry0: CollectCarry = env_state
            env_state = carry0.env_state
            core0 = carry0.core
            la0, lr0 = carry0.last_action, carry0.last_reward
        else:
            core0 = zero_carry(cfg, E)
            la0 = jnp.zeros(E, jnp.int32)
            lr0 = jnp.zeros(E, jnp.float32)

        def body(carry, key_t):
            env_state, core, la, lr, active = carry
            # each step's frames are put in the store's block order ONCE:
            # acting's first conv and the store's rows read that one tensor
            obs = stored_order(vrender(env_state))
            ke, ka = jax.random.split(key_t)
            explore = jax.random.uniform(ke, (E,)) < epsilons
            rand_a = jax.random.randint(ka, (E,), 0, A)
            # fused act tail (ops/act_tail.py): same math as the former
            # argmax/where pair, selection fused with the core step
            q, act, core2 = net.apply(
                params, obs, la, lr, core, explore, rand_a,
                task=task_vec, opened=True, method=net.act_select,
            )
            # scan carry stays f32 regardless of compute dtype (bf16->f32
            # is exact, and act re-casts on use — same values as the host
            # actor's bf16 carry)
            core2 = tuple(x.astype(jnp.float32) for x in core2)
            new_env, reward, done = vstep(env_state, act)
            # freeze slots whose episode already ended: their remaining
            # steps are padding (and step `size` renders the terminal obs)
            env_state = jax.tree.map(
                lambda new, old: _where_rows(active, new, old), new_env, env_state
            )
            reward = jnp.where(active, reward.astype(jnp.float32), 0.0)
            act = jnp.where(active, act, 0)
            done = done & active
            rec = {
                # the store's row format from the first write on: the scan
                # stacks (E, R, 128) slabs whose lanes are the frame's own
                # bytes. Stacked as raw frames, the compiler lays the (T, E,
                # 84, 84, 1) buffer out to suit whatever reshapes it later
                # and may put T on the lanes: every env step then rewrites
                # the whole buffer a byte per tile (3 ms a step at T=1024,
                # E=64; PERF.md finding 25.3)
                "obs": frames_to_rows(obs, stored_shape),
                "action": act,
                "reward": reward,
                "q": q.astype(jnp.float32),
                # stacked at every step unless the class keeps the starts alone
                **({} if starts_only else {"hidden": pack_state(close_carry(net.core, core2)).astype(jnp.float32)}),
                "applied": active,
                "done": done,
            }
            la2 = jnp.where(active, act, la)
            lr2 = jnp.where(active, reward, lr)
            return (env_state, core2, la2, lr2, active & ~done), rec

        keys = jax.random.split(key, T + 2)
        # the scans carry the core's state in its OPENED form (models/core.py:
        # a stack's parts, each a loop-carried buffer of its own; the carry
        # itself for a core that states nothing): opened once before the first
        # segment, closed where a state leaves a scan, at each segment's end
        init = (env_state, open_carry(net.core, core0), la0, lr0, jnp.ones(E, bool))
        carry, recs, at_start, begin = init, [], {0: core0}, 0
        for end in segment_ends:
            carry, rec = jax.lax.scan(body, carry, keys[begin:end])
            recs.append(rec)
            at_start[end] = close_carry(net.core, carry[1])
            begin = end
        env_f, _, la_f, lr_f, alive_f = carry
        core_f = at_start[T]
        rec = recs[0] if len(recs) == 1 else jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *recs)
        if starts_only:  # (E, S, *state_shape): the state before each window's first step
            hiddens = jnp.stack(
                [pack_state(at_start[int(w)]) for w in window_starts], axis=1
            ).astype(jnp.float32)

        final_obs = stored_order(vrender(env_f))
        q_final, _ = net.apply(
            params, final_obs, la_f, lr_f, core_f, task=task_vec, method=net.act
        )

        sizes = jnp.sum(rec["applied"].astype(jnp.int32), axis=0)  # (E,)
        dones = jnp.any(rec["done"], axis=0)
        ep_rewards = jnp.sum(rec["reward"], axis=0)

        env_major = lambda x: jnp.swapaxes(x, 0, 1)  # (T, E, ...) -> (E, T, ...)
        fields, priorities, num_seq = jax.vmap(_pack)(
            env_major(rec["obs"]),
            frames_to_rows(final_obs, stored_shape),
            env_major(rec["action"]),
            env_major(rec["reward"]),
            env_major(rec["q"]),
            hiddens if starts_only else env_major(rec["hidden"]),
            sizes,
            dones,
            q_final,
            la0,
            lr0,
            pack_state(core0),
        )
        fresh_env = vreset(jax.random.split(keys[T + 1], E))
        if carry_episodes:
            # slots still alive continue their episode next chunk; done
            # slots restart fresh. alive_f == ~dones here (every slot
            # starts the chunk alive), kept explicit for clarity. A slot
            # whose episode has reached cfg.max_episode_steps is CAPPED:
            # restarted fresh (its last block already carries the
            # truncation bootstrap) and counted as a finished episode in
            # the stats — the reference's Atari-style cap semantics.
            ep_len = carry0.ep_steps + sizes
            capped = alive_f & (ep_len >= cfg.max_episode_steps)
            cont = alive_f & ~capped
            next_env = jax.tree.map(
                lambda o, f: _where_rows(cont, o, f), env_f, fresh_env
            )
            ep_total = carry0.prefix_reward + ep_rewards
            new_carry = CollectCarry(
                env_state=next_env,
                core=tuple(jnp.where(cont[:, None], x, 0.0) for x in core_f),
                last_action=jnp.where(cont, la_f, 0),
                last_reward=jnp.where(cont, lr_f, 0.0),
                prefix_reward=jnp.where(cont, ep_total, 0.0),
                ep_steps=jnp.where(cont, ep_len, 0),
            )
            # dones | capped drives EPISODE STATS only (the in-block
            # gamma encoding already happened per the env's own terminal)
            return (
                fields, priorities, num_seq, sizes, dones | capped, ep_total,
                new_carry, keys[T],
            )
        return fields, priorities, num_seq, sizes, dones, ep_rewards, fresh_env, keys[T]

    return collect


class DeviceCollector:
    """Drives the jitted chunk collector against a DeviceReplayBuffer.

    Duck-type-compatible with VectorizedActor where the Trainer needs it:
    step() advances collection (one CHUNK here, not one env step),
    steps_per_call reports how many env transitions a step() yields at
    most, and resync() restores a consistent state after a supervised
    restart."""

    def __init__(
        self,
        cfg: R2D2Config,
        net: R2D2Network,
        param_store,
        fn_env,
        replay,
        epsilons: Optional[np.ndarray] = None,
        seed: int = 0,
        chunk_len: Optional[int] = None,
        task_id: int = 0,
        action_dim: Optional[int] = None,
        gamma: Optional[float] = None,
    ):
        E = cfg.num_actors
        self.cfg = cfg
        self.E = E
        self.chunk = int(chunk_len or default_chunk_len(cfg))
        # episodes longer than one chunk: carry env + recurrent state
        # across chunks so the episode CONTINUES into its next block
        # (truncation-bootstrap at the seam, stored-state window-0 replay
        # — module docstring) instead of silently never visiting states
        # past the first chunk
        self.carry_episodes = cfg.max_episode_steps > self.chunk
        self.replay = replay
        self.param_store = param_store
        self._fn_env = fn_env
        eps = (
            np.asarray(epsilons, np.float32)
            if epsilons is not None
            else epsilon_ladder(E, cfg.base_eps, cfg.eps_alpha)
        )
        assert len(eps) == E
        self.epsilons = jnp.asarray(eps, jnp.float32)
        self._collect = make_collect_fn(
            cfg, net, fn_env, E, self.chunk, carry_episodes=self.carry_episodes,
            task_id=task_id, action_dim=action_dim, gamma=gamma,
        )
        self.key = jax.random.PRNGKey(seed)
        kr, self.key = jax.random.split(self.key)
        if self.carry_episodes:
            self.env_state = initial_carry(cfg, fn_env, E, kr)
        else:
            self.env_state = jax.vmap(fn_env.reset)(jax.random.split(kr, E))
        self.total_steps = 0

    @property
    def steps_per_call(self) -> int:
        return self.E * self.chunk

    def step(self) -> int:
        """Collect one chunk and push E blocks into replay; returns the
        number of env transitions recorded."""
        params, _ = self.param_store.latest()
        (fields, prios, num_seq, sizes, dones, ep_rewards, self.env_state, self.key) = (
            self._collect(params, self.env_state, self.epsilons, self.key)
        )
        sizes_np = np.asarray(sizes)
        self.replay.add_blocks_batch(
            fields,
            np.asarray(num_seq),
            sizes_np,
            np.asarray(prios),
            np.asarray(ep_rewards),
            np.asarray(dones),
        )
        recorded = int(sizes_np.sum())
        self.total_steps += recorded
        return recorded

    def resync(self) -> None:
        """Supervised-restart hook: fresh episodes in every slot (the
        in-flight chunk, if any, was never pushed — nothing to unwind)."""
        kr, self.key = jax.random.split(self.key)
        if self.carry_episodes:
            self.env_state = initial_carry(self.cfg, self._fn_env, self.E, kr)
        else:
            self.env_state = jax.vmap(self._fn_env.reset)(jax.random.split(kr, self.E))

    def carry_state(self) -> dict:
        """Preemption carry (npz-safe): the PRNG key, step counter, and the
        full env/episode carry as indexed pytree leaves. step() is a pure
        function of (params, env_state, key), so restoring these resumes
        the collection stream exactly."""
        d = {
            "key": np.asarray(self.key),
            "total_steps": np.asarray(self.total_steps, np.int64),
        }
        for j, leaf in enumerate(jax.tree.leaves(self.env_state)):
            # deliberate readback: preemption carry runs once per snapshot,
            # not per env step  # r2d2: disable=host-sync-in-hot-path
            d[f"env_{j}"] = np.asarray(leaf)
        return d

    def restore_carry(self, d: dict) -> None:
        self.key = jnp.asarray(d["key"])
        self.total_steps = int(np.asarray(d["total_steps"])[()])
        treedef = jax.tree.structure(self.env_state)
        leaves = [jnp.asarray(d[f"env_{j}"]) for j in range(treedef.num_leaves)]
        self.env_state = jax.tree.unflatten(treedef, leaves)
