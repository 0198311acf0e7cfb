"""On-device collector (collect.py) — equivalence with the host actor path.

The strongest possible pin: the DeviceCollector's in-jit packing must
reproduce the host VectorizedActor + SequenceAccumulator blocks
field-by-field on identical trajectories. The scripted env's host and
functional twins are deterministic and epsilon=0 makes the policy greedy,
so both paths see the same observations, take the same actions, and must
pack the same blocks (terminal AND truncation paths).
"""

import jax
import numpy as np
import pytest

from r2d2_tpu.actor import HostEnvPool, ParamStore, VectorizedActor
from r2d2_tpu.collect import DeviceCollector, make_collect_fn
from r2d2_tpu.config import tiny_test
from r2d2_tpu.envs.catch import CatchEnv
from r2d2_tpu.envs.fake import ScriptedEnv, ScriptedFnEnv
from r2d2_tpu.learner import init_train_state, make_fused_multi_train_step
from r2d2_tpu.replay.block import frames_to_rows, rows_to_frames
from r2d2_tpu.replay.device_store import DeviceReplayBuffer

E = 3


def _cfg(**kw):
    base = dict(
        block_length=12,
        buffer_capacity=624,
        learning_starts=24,
        num_actors=E,
        max_episode_steps=12,
    )
    base.update(kw)
    return tiny_test().replace(**base)


def _host_blocks(cfg, net, params, episode_len, steps):
    """Collect blocks via the host actor path on the scripted env."""
    store = ParamStore(params)
    pool = HostEnvPool([ScriptedEnv(episode_len=episode_len) for _ in range(cfg.num_actors)])
    pushed = []
    actor = VectorizedActor(
        cfg, net, store, pool, np.zeros(cfg.num_actors, np.float32),
        lambda b, p, r: pushed.append((b, p, r)), seed=7,
    )
    for _ in range(steps):
        actor.step()
    return pushed


def _device_out(cfg, net, params, episode_len, chunk):
    fn_env = ScriptedFnEnv(episode_len=episode_len)
    collect = make_collect_fn(cfg, net, fn_env, cfg.num_actors, chunk)
    key = jax.random.PRNGKey(3)
    env_state = jax.vmap(fn_env.reset)(jax.random.split(key, cfg.num_actors))
    eps = jax.numpy.zeros(cfg.num_actors)
    return collect(params, env_state, eps, jax.random.PRNGKey(11))


def _compare(cfg, fields, prios, num_seq, sizes, i, block, host_prios):
    size = int(sizes[i])
    assert size == len(block.action)
    ns = int(num_seq[i])
    assert ns == block.num_sequences
    # the collector packs obs as the store holds them: lane-aligned rows
    obs_rows = np.asarray(fields["obs"][i])
    np.testing.assert_array_equal(rows_to_frames(obs_rows, cfg.obs_shape)[: size + 1], block.obs)
    # entries past size+1 are zeroed padding, and so is every frame's tail
    assert not obs_rows[size + 1 :].any()
    np.testing.assert_array_equal(obs_rows, frames_to_rows(rows_to_frames(obs_rows, cfg.obs_shape), cfg.obs_shape))
    np.testing.assert_array_equal(
        np.asarray(fields["last_action"][i])[: size + 1], block.last_action.astype(np.int32)
    )
    np.testing.assert_allclose(
        np.asarray(fields["last_reward"][i])[: size + 1], block.last_reward, atol=1e-6
    )
    np.testing.assert_array_equal(
        np.asarray(fields["action"][i])[:size], block.action.astype(np.int32)
    )
    np.testing.assert_allclose(
        np.asarray(fields["n_step_reward"][i])[:size], block.n_step_reward, atol=1e-5
    )
    np.testing.assert_allclose(np.asarray(fields["gamma"][i])[:size], block.gamma, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(fields["burn_in"][i])[:ns], block.burn_in_steps)
    np.testing.assert_array_equal(np.asarray(fields["learning"][i])[:ns], block.learning_steps)
    np.testing.assert_array_equal(np.asarray(fields["forward"][i])[:ns], block.forward_steps)
    np.testing.assert_allclose(np.asarray(fields["hidden"][i])[:ns], block.hidden, atol=1e-5)
    np.testing.assert_allclose(np.asarray(prios[i]), host_prios, atol=1e-4)


def test_terminal_chunk_matches_host_actor():
    """Episodes end inside the chunk: terminal encoding, stored hiddens,
    counters, and initial priorities all match the host path."""
    cfg = _cfg()
    net, state = init_train_state(cfg, jax.random.PRNGKey(0))
    ep_len = 9
    pushed = _host_blocks(cfg, net, state.params, ep_len, steps=ep_len)
    assert len(pushed) == E
    fields, prios, num_seq, sizes, dones, ep_rewards, _, _ = _device_out(
        cfg, net, state.params, ep_len, chunk=cfg.block_length
    )
    assert np.asarray(dones).all()
    script_sum = sum(float(i % 3) for i in range(ep_len))
    np.testing.assert_allclose(np.asarray(ep_rewards), script_sum, atol=1e-6)
    for i in range(E):
        block, host_prios, ep_reward = pushed[i]
        assert ep_reward == pytest.approx(script_sum)
        _compare(cfg, fields, prios, num_seq, sizes, i, block, host_prios)


def test_truncation_chunk_matches_host_actor():
    """Episodes outlive the chunk: the truncation bootstrap (final policy
    eval) and shrinking gamma tail match the host actor's deferred cut."""
    chunk = 7
    cfg = _cfg(max_episode_steps=chunk)
    net, state = init_train_state(cfg, jax.random.PRNGKey(1))
    # host actor needs one extra step to flush the deferred truncation cut
    pushed = _host_blocks(cfg, net, state.params, episode_len=100, steps=chunk + 1)
    assert len(pushed) >= E
    fields, prios, num_seq, sizes, dones, _, _, _ = _device_out(
        cfg, net, state.params, episode_len=100, chunk=chunk
    )
    assert not np.asarray(dones).any()
    assert (np.asarray(sizes) == chunk).all()
    for i in range(E):
        block, host_prios, ep_reward = pushed[i]
        assert ep_reward is None
        _compare(cfg, fields, prios, num_seq, sizes, i, block, host_prios)
    # truncation keeps a live bootstrap: gamma tail is gamma^2, gamma^1
    g = np.asarray(fields["gamma"][0])
    assert g[chunk - 1] == pytest.approx(cfg.gamma)
    assert g[chunk - 2] == pytest.approx(cfg.gamma**2)


def test_collector_feeds_device_replay_end_to_end():
    """DeviceCollector -> HBM store -> fused train step: blocks land in the
    store, sampling opens, and one update returns finite loss/priorities."""
    cfg = _cfg()
    net, state = init_train_state(cfg, jax.random.PRNGKey(2))
    replay = DeviceReplayBuffer(cfg)
    collector = DeviceCollector(
        cfg, net, ParamStore(state.params), ScriptedFnEnv(episode_len=9), replay, seed=5
    )
    while not replay.can_sample():
        collector.step()
    assert collector.total_steps >= cfg.learning_starts
    n_ep, r_sum = replay.pop_episode_stats()
    assert n_ep > 0 and r_sum == pytest.approx(n_ep * sum(i % 3 for i in range(9)))

    si = replay.sample_indices(np.random.default_rng(0))
    step_fn = make_fused_multi_train_step(cfg, net, 1, donate=False)
    state2, metrics, priorities = replay.run_with_stores(
        lambda stores: step_fn(
            state, stores, jax.numpy.asarray(si.b)[None], jax.numpy.asarray(si.s)[None],
            jax.numpy.asarray(si.is_weights)[None],
        )
    )
    priorities = priorities[0]
    assert np.isfinite(float(metrics["loss"]))
    assert np.asarray(priorities).shape == (cfg.batch_size,)
    assert np.isfinite(np.asarray(priorities)).all()
    replay.update_priorities(si.idxes, np.asarray(priorities), si.old_ptr)


def test_collector_on_catch_env():
    """Catch's functional core drives the collector: fixed-length episodes
    terminate inside the chunk and blocks account correctly."""
    env = CatchEnv(height=12, width=12)
    cfg = _cfg(max_episode_steps=12).replace(action_dim=env.NUM_ACTIONS)
    net, state = init_train_state(cfg, jax.random.PRNGKey(4))
    replay = DeviceReplayBuffer(cfg)
    collector = DeviceCollector(
        cfg, net, ParamStore(state.params), env, replay, seed=6
    )
    n = collector.step()
    # catch episodes last exactly height-2 steps
    assert n == E * (cfg.obs_shape[0] - 2)
    assert len(replay) == n
    totals = replay.episode_totals()
    assert totals[0] == E


def test_resync_restores_consistent_state():
    cfg = _cfg()
    net, state = init_train_state(cfg, jax.random.PRNGKey(0))
    replay = DeviceReplayBuffer(cfg)
    collector = DeviceCollector(
        cfg, net, ParamStore(state.params), ScriptedFnEnv(episode_len=9), replay
    )
    collector.step()
    before = collector.total_steps
    collector.resync()
    collector.step()
    assert collector.total_steps == 2 * before


def test_carry_episodes_across_chunks():
    """Episodes longer than one chunk (carry_episodes): the episode
    CONTINUES into the next chunk's block — env state, recurrent state,
    and last action/reward carry across the seam; the continuation
    block's window-0 stored state is the carried state; episode stats
    report once, with the full return."""
    from r2d2_tpu.collect import initial_carry, make_collect_core

    cfg = _cfg(max_episode_steps=24)  # block/chunk 12 -> 2-chunk episodes
    net, state = init_train_state(cfg, jax.random.PRNGKey(0))
    fn_env = ScriptedFnEnv(episode_len=24, action_dim=cfg.action_dim)
    collect = make_collect_fn(cfg, net, fn_env, E, 12, carry_episodes=True)

    carry0 = initial_carry(cfg, fn_env, E, jax.random.PRNGKey(5))
    eps = jax.numpy.zeros(E)
    out1 = collect(state.params, carry0, eps, jax.random.PRNGKey(8))
    f1, _, _, sizes1, dones1, ep1, carry1, _ = out1
    assert not np.asarray(dones1).any()          # mid-episode at the seam
    np.testing.assert_array_equal(np.asarray(sizes1), 12)
    # prefix reward = chunk-1 script sum (0,1,2 repeating over 12 steps)
    np.testing.assert_allclose(np.asarray(carry1.prefix_reward), 12.0)
    # carried env state resumes at t=12, not a fresh episode
    np.testing.assert_array_equal(np.asarray(carry1.env_state.t), 12)

    out2 = collect(state.params, carry1, eps, jax.random.PRNGKey(9))
    f2, _, _, sizes2, dones2, ep2, carry2, _ = out2
    assert np.asarray(dones2).all()              # episode ends in chunk 2
    np.testing.assert_array_equal(np.asarray(sizes2), 12)
    np.testing.assert_allclose(np.asarray(ep2), 24.0)  # FULL return
    np.testing.assert_allclose(np.asarray(carry2.prefix_reward), 0.0)

    # continuation block: first stored obs is the seam obs (t=12), the
    # window-0 stored state is the CARRIED recurrent state, and the first
    # stored last-action/reward are the carried values
    assert np.asarray(f2["obs"])[:, 0].max() == 12
    np.testing.assert_allclose(
        np.asarray(f2["hidden"])[:, 0],
        np.stack([np.asarray(x) for x in carry1.core], axis=1),
        atol=1e-6,
    )
    np.testing.assert_array_equal(
        np.asarray(f2["last_action"])[:, 0], np.asarray(carry1.last_action)
    )
    np.testing.assert_allclose(
        np.asarray(f2["last_reward"])[:, 0], np.asarray(carry1.last_reward)
    )


def test_device_collector_carry_mode_end_to_end():
    """DeviceCollector auto-enables the carry when max_episode_steps
    exceeds the chunk: transitions past the first chunk ARE collected and
    each multi-chunk episode is counted once with its full reward."""
    cfg = _cfg(max_episode_steps=24)
    net, state = init_train_state(cfg, jax.random.PRNGKey(0))
    fn_env = ScriptedFnEnv(episode_len=24, action_dim=cfg.action_dim)
    replay = DeviceReplayBuffer(cfg)
    collector = DeviceCollector(
        cfg, net, ParamStore(state.params), fn_env, replay,
        epsilons=np.zeros(E, np.float32), seed=5,
    )
    assert collector.carry_episodes
    n1 = collector.step()
    assert n1 == E * 12
    n_ep, r_sum = replay.pop_episode_stats()
    assert n_ep == 0  # no episode finished at the seam
    n2 = collector.step()
    assert n2 == E * 12
    n_ep, r_sum = replay.pop_episode_stats()
    assert n_ep == E and r_sum == pytest.approx(24.0 * E)
    assert len(replay) == 2 * E * 12

    # resync restarts fresh episodes (carry rebuilt)
    collector.resync()
    np.testing.assert_array_equal(np.asarray(collector.env_state.env_state.t), 0)
    np.testing.assert_allclose(np.asarray(collector.env_state.prefix_reward), 0.0)
