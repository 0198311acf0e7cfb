"""The recurrent core's seam (models/core.py): each core states what it
stores in replay and whether it cuts the gradient at burn-in; every writer
and reader of the stored state is built from that statement.

(a) one rule: for every registered core (and a toy third one whose state is
    NOT two rows) x precision, every holder of the stored state has the
    core's shape at cfg.state_dtype, and pack -> unpack is the identity;
(b) a toy core defined HERE, registered for the test only, runs init -> one
    fused collect chunk -> device-store write -> gather -> one K-update
    dispatch -> snapshot save / load with no module of r2d2_tpu/ edited:
    the program's counterpart of tests/benchmark's fourth-configuration test;
(c) the holders that still keep an `h` and a `c` array (serve cache, live
    loop tap: ROADMAP D1b) refuse such a core where they are built.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import importlib

import pytest

from r2d2_tpu import config as config_mod
from r2d2_tpu.collect import DeviceCollector, initial_carry, make_collect_core
from r2d2_tpu.config import tiny_test
from r2d2_tpu.envs.catch import CatchEnv
from r2d2_tpu.learner import init_train_state, make_multi_update_core, make_store_gather
from r2d2_tpu.models.core import (
    core_class,
    pack_state,
    state_spec,
    unpack_state,
    zero_carry,
    zero_state,
)
from r2d2_tpu.replay.block import store_field_specs
from r2d2_tpu.replay.device_store import DeviceReplayBuffer


class ToyCore(nn.Module):
    """A third core whose stored state is three rows of H: a tanh cell, its
    slow average and a decaying trace. Nothing in r2d2_tpu/ knows it."""

    hidden_dim: int
    in_dim: int
    dtype: jnp.dtype = jnp.float32

    cuts_at_burn_in = False

    @staticmethod
    def state_shape(cfg):
        return (3, cfg.hidden_dim)

    @classmethod
    def from_config(cls, cfg, in_dim, tp_size=1):
        return cls(cfg.hidden_dim, in_dim, jnp.dtype(cfg.resolved_compute_dtype))

    def setup(self):
        init = nn.initializers.lecun_normal()
        self.w_in = self.param("w_in", init, (self.in_dim, self.hidden_dim))
        self.w_rec = self.param("w_rec", init, (self.hidden_dim, self.hidden_dim))

    def step(self, x, carry):
        a, slow, trace = (c.astype(jnp.float32) for c in carry)
        z = x.astype(self.dtype) @ self.w_in.astype(self.dtype)
        z = z + a.astype(self.dtype) @ self.w_rec.astype(self.dtype)
        a = jnp.tanh(z.astype(jnp.float32))
        slow = 0.9 * slow + 0.1 * a
        trace = 0.5 * trace + a
        return (a + slow + 0.1 * trace).astype(self.dtype), (a, slow, trace)

    def __call__(self, xs, carry, burn_in=None):
        def body(carry, x):
            out, carry = self.step(x, carry)
            return carry, out

        carry, outs = jax.lax.scan(body, carry, jnp.swapaxes(xs, 0, 1))
        return jnp.swapaxes(outs, 0, 1), carry


@pytest.fixture
def toy_registered(monkeypatch):
    monkeypatch.setitem(config_mod.RECURRENT_CORES, "toy", ToyCore)


def _catch_cfg(core, precision="fp32", **kw):
    base = dict(
        env_name="catch", obs_shape=(10, 8, 1), action_dim=3, num_actors=4,
        max_episode_steps=8, block_length=16, buffer_capacity=640,
        learning_starts=32, collector="device", replay_plane="device",
        updates_per_dispatch=2, recurrent_core=core, precision=precision,
    )
    return tiny_test().replace(**{**base, **kw})


def _fn_env(cfg):
    return CatchEnv(height=cfg.obs_shape[0], width=cfg.obs_shape[1])


# ------------------------------------------------------------------ (a) rule


def test_registry_names_are_what_validation_accepts():
    assert set(config_mod.RECURRENT_CORES) == {"lstm", "lru", "hybrid_stack"}
    for name in ("lstm", "lru"):
        cfg = tiny_test().replace(recurrent_core=name)
        cls = core_class(cfg)
        assert cls.state_shape(cfg) == (2, cfg.hidden_dim)
        assert isinstance(cls.cuts_at_burn_in, bool)
    # the stack states the rule's (1, S) form (tests/test_hybrid_stack.py)
    stack = importlib.import_module("r2d2_tpu.models.hybrid_stack").HybridStack
    assert (stack.cuts_at_burn_in, stack.keeps_window_starts) == (False, True)
    assert core_class(tiny_test()).cuts_at_burn_in                       # lstm
    assert not core_class(tiny_test().replace(recurrent_core="lru")).cuts_at_burn_in
    with pytest.raises(ValueError, match="unknown recurrent_core 'toy'"):
        tiny_test().replace(recurrent_core="toy")


@pytest.mark.parametrize("precision", ["fp32", "bf16"])
@pytest.mark.parametrize("core", ["lstm", "lru", "toy"])
def test_every_holder_of_the_stored_state_has_the_cores_shape_and_dtype(
    core, precision, toy_registered, tmp_path
):
    from r2d2_tpu.analysis import jaxpr_rules
    from r2d2_tpu.models.r2d2 import init_params
    from r2d2_tpu.replay.accumulator import SequenceAccumulator
    from r2d2_tpu.replay.replay_buffer import ReplayBuffer
    from r2d2_tpu.replay.tiered_store import TieredReplayBuffer

    cfg = _catch_cfg(core, precision)
    shape, dtype = state_spec(cfg)
    S, E, H = cfg.seqs_per_block, cfg.num_actors, cfg.hidden_dim
    assert shape == ((3, H) if core == "toy" else (2, H))
    assert dtype == cfg.state_dtype
    assert dtype.itemsize == (2 if precision == "bf16" else 4)

    def is_state(x, *lead, dt=dtype):
        assert tuple(x.shape) == (*lead, *shape), (x.shape, lead)
        assert np.dtype(x.dtype) == np.dtype(dt), (x.dtype, dt)

    # the rule itself
    assert store_field_specs(cfg)["hidden"] == ((S, *shape), dtype)
    is_state(zero_state(cfg, 5), 5, dt=np.float32)
    carry = zero_carry(cfg, E)
    assert len(carry) == shape[0]
    assert all(c.shape == (E, H) and c.dtype == jnp.float32 for c in carry)

    # pack -> unpack is the identity bit for bit, numpy in numpy out, traced in traced out
    rng = np.random.default_rng(33)
    rows = tuple(rng.normal(size=(E, H)).astype(np.float32) for _ in range(shape[0]))
    packed = pack_state(rows)
    assert isinstance(packed, np.ndarray)
    is_state(packed, E, dt=np.float32)
    for got, want in zip(unpack_state(packed), rows):
        assert isinstance(got, np.ndarray) and got.tobytes() == want.tobytes()
    traced = jax.jit(lambda c: unpack_state(pack_state(c)))(tuple(map(jnp.asarray, rows)))
    for got, want in zip(traced, rows):
        assert np.asarray(got).tobytes() == want.tobytes()
    assert pack_state(unpack_state(packed)).tobytes() == packed.tobytes()

    # the stores: host buffer, tiered store with its disk tier's extension, device store
    host_cfg = cfg.replace(replay_plane="host", collector="host", updates_per_dispatch=1)
    is_state(ReplayBuffer(host_cfg).hidden_store, cfg.num_blocks, S)
    tiered_cfg = host_cfg.replace(
        replay_plane="tiered", replay_disk_dir=str(tmp_path / "disk"),
        replay_disk_capacity=4 * cfg.block_length,
    )
    is_state(TieredReplayBuffer(tiered_cfg).hidden_store, cfg.num_blocks + 4, S)
    is_state(DeviceReplayBuffer(cfg).stores["hidden"], cfg.num_blocks, S)

    # the writers: init_params' dummy (the core unpacks it), collect's chunk
    # output (both carry modes), and the host actor's packed row through the
    # accumulator and pad_block_fields
    net, params = init_params(jax.random.PRNGKey(0), cfg)
    fn_env = _fn_env(cfg)
    for carry_episodes in (False, True):
        collect = make_collect_core(cfg, net, fn_env, E, 8, carry_episodes=carry_episodes)
        env0 = (
            initial_carry(cfg, fn_env, E, jax.random.PRNGKey(1)) if carry_episodes
            else jax.vmap(fn_env.reset)(jax.random.split(jax.random.PRNGKey(1), E))
        )
        out = jax.eval_shape(collect, params, env0, jnp.zeros(E), jax.random.PRNGKey(2))
        is_state(out[0]["hidden"], E, S)
        if carry_episodes:
            assert jax.tree.structure(out[6]) == jax.tree.structure(env0)
    obs = jnp.zeros((E, *cfg.obs_shape), jnp.uint8)
    q, carry2 = net.apply(
        params, obs, jnp.zeros(E, jnp.int32), jnp.zeros(E), carry, method=net.act
    )
    row = pack_state(tuple(np.asarray(c) for c in carry2))
    acc = SequenceAccumulator(cfg)
    acc.reset(np.zeros(cfg.obs_shape, np.uint8))
    acc.add(0, 0.0, np.zeros(cfg.obs_shape, np.uint8), np.asarray(q[0]), row[0])
    block, _, _ = acc.finish(last_qval=None)
    is_state(block.hidden, block.num_sequences, dt=np.float32)
    is_state(DeviceReplayBuffer.pad_block_fields(cfg, block)["hidden"], S)

    # the analysis entry points' abstract inputs
    is_state(jaxpr_rules._stacked_struct_from_cfg(cfg, 3).hidden, 3, cfg.batch_size)
    is_state(jaxpr_rules._state_struct(cfg, 7), 7)
    assert jax.tree.map(lambda s: (s.shape, s.dtype), jaxpr_rules._carry_struct(cfg, E)) == \
        jax.tree.map(lambda c: (c.shape, c.dtype), carry)
    if core == "lstm":  # the entry points that build their own (lstm) cfg from the precision
        own = jaxpr_rules._cfg(precision)
        own_shape, own_dtype = state_spec(own)
        hid = jaxpr_rules.fused_unroll_jaxpr(precision).in_avals[-4]
        assert (hid.shape, np.dtype(hid.dtype)) == ((own.batch_size, *own_shape), own_dtype)
        manual = jaxpr_rules._manual_batch_struct(precision, 2, 2, 1).hidden
        assert (manual.shape, np.dtype(manual.dtype)) == ((own.batch_size, *own_shape), own_dtype)
        assert jaxpr_rules.check_store_field_dtypes(precision) == []


# ------------------------------------------------------------- (b) toy core


def test_a_third_core_with_a_three_row_state_runs_collect_store_update_and_snapshot(
    toy_registered, tmp_path
):
    from r2d2_tpu.replay.snapshot import restore_replay, save_replay

    # burn-in shorter than a window, so that window 1 starts mid-episode
    cfg = _catch_cfg("toy", burn_in_steps=2)
    assert cfg.resolved_core_backend == "toy"
    E, S, H, K = cfg.num_actors, cfg.seqs_per_block, cfg.hidden_dim, cfg.updates_per_dispatch
    fn_env = _fn_env(cfg)
    net, state = init_train_state(cfg, jax.random.PRNGKey(0))
    assert isinstance(net.core, ToyCore)
    assert set(state.params["params"]["core"]) == {"w_in", "w_rec"}

    # fused collect chunks into the device store
    replay = DeviceReplayBuffer(cfg)
    assert replay.stores["hidden"].shape == (cfg.num_blocks, S, 3, H)

    class _Params:
        def latest(self):
            return state.params, 0

    col = DeviceCollector(cfg, net, _Params(), fn_env, replay, seed=7)
    while not replay.can_sample():
        col.step()
    stored = np.asarray(replay.stores["hidden"])
    # window 0 starts from the zero state; later windows hold the core's own
    # three rows, the third one not a copy of the first two
    assert not stored[:, 0].any()
    filled = stored[: replay.block_ptr, 1]
    assert np.abs(filled[:, 2]).max() > 0
    assert not np.array_equal(filled[:, 2], filled[:, 0])

    # gather, then one K-update dispatch
    draws = [replay.sample_indices(np.random.default_rng(11)) for _ in range(K)]
    b, s, w = (jnp.asarray(np.stack([getattr(d, k) for d in draws])) for k in ("b", "s", "is_weights"))
    batch = jax.jit(make_store_gather(cfg))(replay.stores, b[0], s[0], w[0])
    assert batch.hidden.shape == (cfg.batch_size, 3, H)
    np.testing.assert_array_equal(
        np.asarray(batch.hidden), stored[np.asarray(b[0]), np.asarray(s[0])]
    )
    multi = jax.jit(make_multi_update_core(cfg, net, K))
    new_state, metrics, prios = multi(state, replay.stores, b, s, w)
    assert np.isfinite(np.asarray(metrics["loss"])).all()
    assert np.asarray(prios).shape[0] == K and np.isfinite(np.asarray(prios)).all()
    assert int(new_state.step) == int(state.step) + K
    moved = jax.tree.map(
        lambda a, b_: bool(np.any(np.asarray(a) != np.asarray(b_))),
        new_state.params["params"]["core"], state.params["params"]["core"],
    )
    assert all(moved.values()), moved

    # snapshot save / load
    path = str(tmp_path / "replay_snapshot.npz")
    save_replay(replay, path, extra=col.carry_state())
    fresh = DeviceReplayBuffer(cfg)
    extra = restore_replay(fresh, path)
    for k in replay.stores:
        np.testing.assert_array_equal(np.asarray(fresh.stores[k]), np.asarray(replay.stores[k]))
    assert fresh.block_ptr == replay.block_ptr and len(fresh) == len(replay)
    np.testing.assert_array_equal(extra["key"], np.asarray(col.key))


def test_a_third_core_carries_episodes_across_chunks_and_through_the_host_actor(
    toy_registered,
):
    from tests.test_actor import build_actor

    # device collector, episodes longer than a chunk: the carry's three rows
    # ride CollectCarry and its preemption carry
    cfg = _catch_cfg("toy", max_episode_steps=64)
    fn_env = _fn_env(cfg)
    net, state = init_train_state(cfg, jax.random.PRNGKey(0))
    replay = DeviceReplayBuffer(cfg)

    class _Params:
        def latest(self):
            return state.params, 0

    col = DeviceCollector(cfg, net, _Params(), fn_env, replay, seed=3, chunk_len=4)
    assert col.carry_episodes
    col.step()
    assert len(col.env_state.core) == 3
    assert all(np.abs(np.asarray(x)).max() > 0 for x in col.env_state.core)
    saved = col.carry_state()
    col2 = DeviceCollector(cfg, net, _Params(), fn_env, DeviceReplayBuffer(cfg), seed=9, chunk_len=4)
    col2.restore_carry(saved)
    for got, want in zip(jax.tree.leaves(col2.env_state), jax.tree.leaves(col.env_state)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    # host actor: blocks carry (ns, 3, H), and the preemption carry keeps
    # the two old keys and adds one for the third row
    host = tiny_test().replace(recurrent_core="toy")
    actor, pushed, _, _ = build_actor(host, episode_len=9)
    actor.run_steps(5)
    d = actor.carry_state()
    assert {"carry_h", "carry_c", "carry_2"} <= set(d)
    actor.run_steps(4)
    assert len(pushed) == 2
    for block, _, _ in pushed:
        assert block.hidden.shape == (block.num_sequences, 3, host.hidden_dim)
    other, _, _, _ = build_actor(host, episode_len=9)
    other.restore_carry(d)
    assert len(other.carry) == 3
    for got, key in zip(other.carry, ("carry_h", "carry_c", "carry_2")):
        np.testing.assert_array_equal(np.asarray(got), d[key])


# ------------------------------------------------- (c) the two-row holders


@pytest.mark.parametrize("holder", ["cache", "cache_with_spill", "server", "tap"])
def test_the_two_row_holders_refuse_another_state_where_they_are_built(
    holder, toy_registered
):
    from r2d2_tpu.liveloop.tap import TransitionTap
    from r2d2_tpu.serve.state_cache import RecurrentStateCache

    cfg = tiny_test().replace(recurrent_core="toy")
    H = cfg.hidden_dim
    build = {
        "cache": lambda: RecurrentStateCache(4, H, state_shape=(3, H), core="toy"),
        "cache_with_spill": lambda: RecurrentStateCache(
            4, H, spill_capacity=2, state_shape=state_spec(cfg)[0], core=cfg.recurrent_core
        ),
        "server": lambda: _build_server(cfg),
        "tap": lambda: TransitionTap(cfg),
    }[holder]
    with pytest.raises(ValueError, match=rf"'toy' stores \(3, {H}\)"):
        build()
    # and take the cores that exist
    ok = tiny_test()
    RecurrentStateCache(4, H, state_shape=state_spec(ok)[0], core=ok.recurrent_core)
    RecurrentStateCache(4, H)
    TransitionTap(ok.replace(recurrent_core="lru"))


def _build_server(cfg):
    from r2d2_tpu.serve.server import PolicyServer, ServeConfig

    return PolicyServer(cfg, ServeConfig(buckets=(2,), cache_capacity=4))
