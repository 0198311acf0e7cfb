"""Multi-task plane (multitask/): the grown env family's core invariants
(keydoor memory demand, drift's no-terminal contract, banditgrid's reward
variance), the registry's union geometry, the per-task ladders, task-id
plumbing through blocks and replay, and the one-learner trainer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from r2d2_tpu.actor import ParamStore
from r2d2_tpu.collect import DeviceCollector
from r2d2_tpu.config import tiny_test
from r2d2_tpu.envs.banditgrid import BanditGridEnv, build_banditgrid_env
from r2d2_tpu.envs.drift import DriftEnv, build_drift_env
from r2d2_tpu.envs.functional import FnVecEnv
from r2d2_tpu.envs.keydoor import KeyDoorEnv, build_keydoor_env, keydoor_params
from r2d2_tpu.learner import init_train_state
from r2d2_tpu.multitask import MultiTaskTrainer, build_registry, resolve_task_names
from r2d2_tpu.ops.epsilon import multitask_epsilon_ladders, multitask_gamma_ladder
from r2d2_tpu.replay.accumulator import SequenceAccumulator
from r2d2_tpu.replay.device_store import DeviceReplayBuffer
from r2d2_tpu.replay.replay_buffer import ReplayBuffer

pytestmark = pytest.mark.multitask

OBS = (12, 12, 1)


# ------------------------------------------------------------------ keydoor


def test_keydoor_cue_visible_then_gone():
    env = KeyDoorEnv(height=12, width=12, length=4, num_colors=2, cue_steps=1)
    s = env.reset(jax.random.PRNGKey(3))
    frame = np.asarray(env.render(s))
    color = int(s.color)
    assert frame[0, color, 0] == 255  # cue row flashes the key color
    s, _, _ = env.step(s, jnp.int32(0))
    frame = np.asarray(env.render(s))
    assert not frame[0].any()  # cue gone after the window
    assert frame[-1, env.length - 1, 0] == 255  # door stays a static landmark


def test_keydoor_recall_decides_the_reward():
    env = KeyDoorEnv(height=12, width=12, length=4, num_colors=2, cue_steps=1)
    for match in (True, False):
        s = env.reset(jax.random.PRNGKey(5))
        for _ in range(env.length - 1):  # walk right to the door
            s, r, d = env.step(s, jnp.int32(2))
            assert float(r) == 0.0 and not bool(d)
        color = int(s.color)
        open_action = 3 + (color if match else (color + 1) % env.colors)
        s, r, d = env.step(s, jnp.int32(open_action))
        assert bool(d)  # any open at the door terminates
        assert float(r) == (1.0 if match else 0.0)


def test_keydoor_open_off_door_is_noop():
    env = KeyDoorEnv(height=12, width=12, length=4, num_colors=2)
    s = env.reset(jax.random.PRNGKey(1))
    s2, r, d = env.step(s, jnp.int32(3))  # open at cell 0: not the door
    assert float(r) == 0.0 and not bool(d)
    assert int(s2.pos) == int(s.pos)


def test_keydoor_name_params_and_validation():
    assert keydoor_params("keydoor:5:3:2") == dict(
        length=5, num_colors=3, cue_steps=2
    )
    env = build_keydoor_env(OBS, max_episode_steps=100, name="keydoor:4:2")
    assert env.NUM_ACTIONS == 5
    with pytest.raises(ValueError):
        keydoor_params("keydoor:1")  # degenerate corridor
    with pytest.raises(ValueError):
        build_keydoor_env((12, 3, 1), 100, "keydoor:6:2")  # canvas too narrow


# -------------------------------------------------------------------- drift


def test_drift_never_terminates():
    """The continuing-env invariant: done is False on EVERY step."""
    env = DriftEnv(height=12, width=12, drift_every=2)
    step = jax.jit(env.step)
    s = env.reset(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    for _ in range(200):
        s, r, d = step(s, jnp.int32(rng.integers(0, 5)))  # incl. out-of-range
        assert not bool(d)
        assert float(r) in (0.0, 1.0)


def test_drift_pays_for_tracking():
    env = DriftEnv(height=12, width=12, drift_every=1_000_000)  # static target
    s = env.reset(jax.random.PRNGKey(2))
    # walk the agent onto the target, then sit: every step pays +1
    while int(s.pos) != int(s.target):
        a = 2 if int(s.pos) < int(s.target) else 1
        s, r, d = env.step(s, jnp.int32(a))
    for _ in range(3):
        s, r, d = env.step(s, jnp.int32(0))
        assert float(r) == 1.0 and not bool(d)


def test_drift_factory_ignores_episode_budget():
    env = build_drift_env(OBS, max_episode_steps=4, name="drift:3")
    assert env.every == 3
    s = env.reset(jax.random.PRNGKey(7))
    for _ in range(16):  # well past the (ignored) episode budget
        s, _, d = env.step(s, jnp.int32(0))
        assert not bool(d)


# --------------------------------------------------------------- banditgrid


def test_banditgrid_reward_variance_dominates():
    """Sitting still on ONE arm still yields noisy rewards whose spread
    rivals the mean surface — the property that stresses priorities."""
    env = BanditGridEnv(height=12, width=12, grid=4, horizon=1_000_000)
    s = env.reset(jax.random.PRNGKey(4))
    rewards = []
    for _ in range(256):
        s, r, _ = env.step(s, jnp.int32(0))  # NOOP: stay on the start arm
        rewards.append(float(r))
    rewards = np.asarray(rewards)
    mu = float(np.asarray(env._means())[0, 0])
    assert abs(rewards.mean() - mu) < 0.15  # unbiased around the arm mean
    assert rewards.std() > 0.3  # variance is the signal's dominant term


def test_banditgrid_mean_surface_rises_to_far_corner():
    env = BanditGridEnv(height=12, width=12, grid=4, horizon=16)
    means = np.asarray(env._means())
    assert means[0, 0] == 0.0 and means[-1, -1] == 1.0
    assert (np.diff(means, axis=0) > 0).all()
    assert (np.diff(means, axis=1) > 0).all()


def test_banditgrid_horizon_terminates():
    env = build_banditgrid_env(OBS, max_episode_steps=100, name="banditgrid:4:6")
    s = env.reset(jax.random.PRNGKey(8))
    for i in range(6):
        s, _, d = env.step(s, jnp.int32(4))
        assert bool(d) == (i == 5)


# ------------------------------------------------- determinism + vec/collect


@pytest.mark.parametrize("make", [
    lambda: KeyDoorEnv(height=12, width=12, length=4, num_colors=2),
    lambda: DriftEnv(height=12, width=12),
    lambda: BanditGridEnv(height=12, width=12, grid=4, horizon=16),
])
def test_env_core_determinism(make):
    """Same key, same actions -> bitwise-identical trajectories (under jit,
    as the collector runs them)."""
    outs = []
    for _ in range(2):
        env = make()
        step = jax.jit(env.step)
        s = env.reset(jax.random.PRNGKey(42))
        traj = []
        for t in range(12):
            s, r, d = step(s, jnp.int32(t % 3))
            traj.append((np.asarray(env.render(s)), float(r), bool(d)))
        outs.append(traj)
    for (f1, r1, d1), (f2, r2, d2) in zip(*outs):
        np.testing.assert_array_equal(f1, f2)
        assert r1 == r2 and d1 == d2


@pytest.mark.parametrize("name", ["keydoor:4:2", "drift", "banditgrid"])
def test_fnvec_adapter_over_family(name):
    """FnVecEnv vmaps each core and auto-resets terminals; the host
    protocol surface (reset_all/step shapes) holds for every family."""
    from r2d2_tpu.train import build_fn_env

    cfg = tiny_test().replace(env_name=name)
    env = FnVecEnv(build_fn_env(cfg), num_envs=3, seed=0)
    obs = env.reset_all()
    assert obs.shape == (3, *OBS) and obs.dtype == np.uint8
    for _ in range(5):
        term_obs, rewards, dones, next_obs = env.step(np.zeros(3, np.int64))
        assert term_obs.shape == (3, *OBS) and next_obs.shape == (3, *OBS)
        assert rewards.shape == (3,) and dones.shape == (3,)
        if name == "drift":
            assert not dones.any()


@pytest.mark.parametrize("name", ["keydoor:4:2", "banditgrid"])
def test_device_collector_over_family(name):
    """The on-device collector jits each new core end-to-end: blocks land
    in the HBM store and sampling opens."""
    from r2d2_tpu.train import build_fn_env

    cfg = tiny_test().replace(
        env_name=name, num_actors=2, block_length=12, buffer_capacity=240,
        learning_starts=24, max_episode_steps=20,
    )
    fn_env = build_fn_env(cfg)
    cfg = cfg.replace(action_dim=fn_env.NUM_ACTIONS)
    net, state = init_train_state(cfg, jax.random.PRNGKey(0))
    replay = DeviceReplayBuffer(cfg)
    collector = DeviceCollector(
        cfg, net, ParamStore(state.params), fn_env, replay, seed=3
    )
    while not replay.can_sample():
        collector.step()
    assert collector.total_steps >= cfg.learning_starts


# --------------------------------------------------- registry + ladders


def test_resolve_task_names_aliases_and_passthrough():
    assert resolve_task_names("maze,drift,bandit") == [
        "keydoor", "drift", "banditgrid"
    ]
    assert resolve_task_names("keydoor:4:2, catch") == ["keydoor:4:2", "catch"]
    with pytest.raises(ValueError):
        resolve_task_names(" , ")


def test_registry_union_geometry_and_gamma_ladder():
    cfg, specs = build_registry(
        tiny_test(), ["keydoor:4:2", "drift", "banditgrid", "catch"]
    )
    assert cfg.num_tasks == 4
    assert cfg.action_dim == 5  # union over (5, 3, 5, 3)
    assert cfg.task_action_dims == (5, 3, 5, 3)
    assert [s.task_id for s in specs] == [0, 1, 2, 3]
    gammas = list(cfg.task_gammas)
    assert gammas[0] == pytest.approx(tiny_test().gamma)  # task 0 keeps cfg's
    assert all(a > b for a, b in zip(gammas, gammas[1:]))  # ladder descends
    with pytest.raises(ValueError):
        build_registry(tiny_test(), ["drift", "drift"])


def test_multitask_epsilon_and_gamma_ladders():
    eps = multitask_epsilon_ladders(3, 4)
    assert eps.shape == (3, 4)
    for row in eps:
        assert (np.diff(row) < 0).all() and (row > 0).all() and (row <= 0.4).all()
    g = multitask_gamma_ladder(4, 0.97, 0.997)
    assert g.shape == (4,)
    assert g[0] == pytest.approx(0.997) and g[-1] == pytest.approx(0.97)
    # spacing is uniform in log(1 - gamma) (Agent57's horizon spacing)
    log1m = np.log1p(-np.asarray(g))
    np.testing.assert_allclose(np.diff(log1m), np.diff(log1m)[0], rtol=1e-4)
    with pytest.raises(ValueError):
        multitask_gamma_ladder(2, 0.99, 0.97)  # min above max


# ----------------------------------------------------- task-id plumbing


def test_task_id_survives_block_and_replay_roundtrip():
    """A task-stamped accumulator's Block carries its task id through the
    host replay buffer and back out of sample_batch."""
    cfg, _ = build_registry(
        tiny_test().replace(
            block_length=12, buffer_capacity=120, learning_starts=12,
            batch_size=4, burn_in_steps=4, learning_steps=4, forward_steps=2,
        ),
        ["drift", "banditgrid"],
    )
    acc = SequenceAccumulator(cfg, task_id=1, gamma=0.98)
    assert acc.gamma == pytest.approx(0.98)
    acc.reset(np.zeros(cfg.obs_shape, np.uint8))
    for t in range(12):
        acc.add(
            action=t % 3, reward=1.0,
            next_obs=np.zeros(cfg.obs_shape, np.uint8),
            q_value=np.zeros(cfg.action_dim, np.float32),
            hidden=np.zeros((2, cfg.hidden_dim), np.float32),
        )
    block, prios, _ = acc.finish(
        last_qval=np.zeros(cfg.action_dim, np.float32)
    )
    assert block.task == 1

    replay = ReplayBuffer(cfg)
    while not replay.can_sample():
        replay.add_block(block, prios, None)
    batch = replay.sample_batch(np.random.default_rng(0))
    assert batch.task is not None
    np.testing.assert_array_equal(batch.task, np.ones_like(batch.task))


def test_single_task_cfg_has_no_task_leaves():
    """num_tasks=1 (the golden path): no task field in store specs, no
    task column out of sampling — the gating the jaxpr contracts pin."""
    from r2d2_tpu.replay.block import store_field_specs

    cfg = tiny_test().replace(
        block_length=12, buffer_capacity=120, learning_starts=12, batch_size=4
    )
    assert "task" not in store_field_specs(cfg)
    acc = SequenceAccumulator(cfg)
    acc.reset(np.zeros(cfg.obs_shape, np.uint8))
    for t in range(12):
        acc.add(
            action=0, reward=1.0,
            next_obs=np.zeros(cfg.obs_shape, np.uint8),
            q_value=np.zeros(cfg.action_dim, np.float32),
            hidden=np.zeros((2, cfg.hidden_dim), np.float32),
        )
    block, prios, _ = acc.finish(last_qval=np.zeros(cfg.action_dim, np.float32))
    assert block.task == 0
    replay = ReplayBuffer(cfg)
    while not replay.can_sample():
        replay.add_block(block, prios, None)
    assert replay.sample_batch(np.random.default_rng(0)).task is None


# ------------------------------------------------------------ the trainer


def test_multitask_trainer_one_learner_end_to_end():
    """ONE learner over two tasks: warmup opens every task's gate,
    stratified updates produce finite loss and split priorities back, and
    evaluation emits one row PER TASK."""
    cfg = tiny_test().replace(
        num_actors=4, batch_size=8, buffer_capacity=640, learning_starts=32,
    )
    trainer = MultiTaskTrainer(cfg, ["drift", "banditgrid"])
    assert trainer.cfg.num_tasks == 2
    assert len(trainer.replays) == 2 and len(trainer.actors) == 2
    trainer.warmup()
    for replay in trainer.replays:
        assert replay.can_sample()
    m = trainer.train(3, collect_steps_per_update=1)
    assert np.isfinite(float(m["loss"]))
    rows = trainer.evaluate(episodes=2, horizon=8)
    assert [r["task"] for r in rows] == [0, 1]
    assert all(np.isfinite(r["mean_return"]) for r in rows)
    # the actors really stamped their task ids: sampled batches carry both
    dev, segs = trainer._sample_stratified()
    tasks = np.asarray(dev.task)
    assert set(tasks.tolist()) == {0, 1}
    assert len(segs) == 2


@pytest.mark.slow
def test_multitask_convergence_smoke_beats_random():
    """Slow convergence smoke (out of tier-1; `pytest -m multitask` or
    `-m slow` runs it): one learner over the two dense-reward family
    members must beat a seeded random policy PER TASK after a few hundred
    updates — the miniature of round 13's acceptance bar."""
    from r2d2_tpu.multitask.trainer import rollout_returns

    cfg = tiny_test().replace(
        num_actors=8, batch_size=16, buffer_capacity=2560,
        learning_starts=128, target_net_update_interval=40, lr=1e-3,
    )
    trainer = MultiTaskTrainer(cfg, ["drift", "banditgrid"])
    trainer.warmup()
    trainer.train(300, collect_steps_per_update=4)
    params, _ = trainer.param_store.latest()
    for spec in trainer.specs:
        ev_seed = 10_000 + 17 * spec.task_id
        trained = np.mean(rollout_returns(
            trainer.cfg, trainer.net, params, spec, episodes=8, horizon=32,
            seed=ev_seed, policy="greedy"))
        rand = np.mean(rollout_returns(
            trainer.cfg, None, None, spec, episodes=8, horizon=32,
            seed=ev_seed, policy="random"))
        assert trained > rand, (spec.env_name, float(trained), float(rand))
