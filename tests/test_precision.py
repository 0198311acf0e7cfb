"""Mixed-precision plane (config.precision): bf16-vs-fp32 drift bounds on
acting and training, fp32 golden-path cast-freedom, the no-float64 guard,
bf16 recurrent-state storage across replay planes and their snapshots, the
serve cache's precision footprint, and bucketed-batch bit parity in both
precisions. All CPU tier-1 except the convergence smoke (slow) and the MXU
speedup assertion (tpu)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from r2d2_tpu.config import tiny_test
from r2d2_tpu.learner import init_train_state, make_train_step
from r2d2_tpu.models.core import zero_carry
from r2d2_tpu.models.r2d2 import R2D2Network, init_params

from tests.test_learner import random_batch

BF16 = np.dtype(ml_dtypes.bfloat16)


def bf16_cfg():
    return tiny_test().replace(precision="bf16")


# ------------------------------------------------------------------ config


def test_precision_knob_resolution():
    cfg = tiny_test()
    assert cfg.precision == "fp32"
    assert cfg.resolved_compute_dtype == cfg.compute_dtype
    assert cfg.state_dtype == np.float32

    b = bf16_cfg()
    assert b.resolved_compute_dtype == "bfloat16"
    assert b.state_dtype == BF16

    # fp32 precision defers to the legacy compute knob: a bf16-compute
    # preset keeps bf16 matmuls (and its goldens) without the bf16 plane
    mixed = tiny_test().replace(compute_dtype="bfloat16")
    assert mixed.resolved_compute_dtype == "bfloat16"
    assert mixed.state_dtype == np.float32

    with pytest.raises(ValueError):
        tiny_test().replace(precision="fp16").validate()
    with pytest.raises(ValueError):
        tiny_test().replace(compute_dtype="float16").validate()


# ------------------------------------------------------- act / train parity


@pytest.fixture(scope="module")
def shared_params():
    """One fp32 master param set driven through both compute dtypes —
    exactly the deployment relationship (params stay fp32; precision only
    changes the cast-on-use dtype)."""
    net32, params = init_params(jax.random.PRNGKey(0), tiny_test())
    net16 = R2D2Network.from_config(bf16_cfg())
    return params, net32, net16


def _act_inputs(cfg, B=8, seed=0):
    rng = np.random.default_rng(seed)
    obs = rng.integers(0, 255, size=(B, *cfg.obs_shape), dtype=np.uint8)
    la = rng.integers(0, cfg.action_dim, size=B).astype(np.int32)
    lr = rng.normal(size=B).astype(np.float32)
    carry = zero_carry(cfg, B)
    return jnp.asarray(obs), jnp.asarray(la), jnp.asarray(lr), carry


def test_act_parity_bf16_vs_fp32(shared_params):
    """bf16 acting stays within bf16 rounding of the fp32 Q values — the
    bound that makes --precision bf16 safe for the serving plane."""
    params, net32, net16 = shared_params
    cfg = tiny_test()
    obs, la, lr, carry = _act_inputs(cfg)
    q32, (h32, c32) = net32.apply(params, obs, la, lr, carry, method=R2D2Network.act)
    q16, (h16, c16) = net16.apply(params, obs, la, lr, carry, method=R2D2Network.act)
    assert q32.dtype == jnp.float32  # dueling head is an fp32 island
    assert q16.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(q16), np.asarray(q32), atol=0.05)
    # carries drift by at most bf16 ulp of their fp32 values
    np.testing.assert_allclose(
        np.asarray(h16, np.float32), np.asarray(h32), atol=0.05
    )
    np.testing.assert_allclose(
        np.asarray(c16, np.float32), np.asarray(c32), atol=0.05
    )


def test_train_step_parity_bf16_vs_fp32():
    """One train step from identical fp32 state: loss and the emitted
    priorities agree within bf16 drift bounds (the fp32 islands keep the
    target/TD/priority math from amplifying matmul rounding)."""
    cfg32, cfg16 = tiny_test(), bf16_cfg()
    net32, state32 = init_train_state(cfg32, jax.random.PRNGKey(0))
    net16, state16 = init_train_state(cfg16, jax.random.PRNGKey(0))
    for a, b in zip(jax.tree.leaves(state32.params), jax.tree.leaves(state16.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    batch = random_batch(cfg32, seed=7)
    _, m32, p32 = make_train_step(cfg32, net32, donate=False)(state32, batch)
    _, m16, p16 = make_train_step(cfg16, net16, donate=False)(state16, batch)
    assert p16.dtype == p32.dtype == jnp.float32
    l32, l16 = float(m32["loss"]), float(m16["loss"])
    assert abs(l16 - l32) <= 0.05 * max(abs(l32), 1.0), (l32, l16)
    np.testing.assert_allclose(
        np.asarray(p16), np.asarray(p32), rtol=0.2, atol=0.05
    )


def test_fp32_train_step_has_no_bf16_casts():
    """The golden-path guarantee by construction: under precision=fp32 the
    train-step program contains no bfloat16 values at all, so the fp32
    islands added for the bf16 plane are exact no-ops on existing runs.
    Backed by the shared analysis-plane scanner (the same trace the
    tier-1 analysis gate and the CLI's --jaxpr mode check)."""
    from r2d2_tpu.analysis import jaxpr_rules

    assert jaxpr_rules.scan_train_step("fp32") == []


def test_no_float64_in_train_step():
    """Tier-1 dtype-promotion guard: no op in either precision's train
    step promotes to float64 (a silent 2x memory + TPU-unsupported trap),
    and the x64 flag stays off. The float64 walk lives in the shared
    scanner; bf16 additionally asserts the fp32 islands survive."""
    from r2d2_tpu.analysis import jaxpr_rules

    assert not jax.config.jax_enable_x64
    for precision in ("fp32", "bf16"):
        assert jaxpr_rules.scan_train_step(precision) == []


# ------------------------------------------------- carry storage + snapshot


def _fill(replay, cfg, n_blocks=4, seed=0):
    from synth import synth_block

    rng = np.random.default_rng(seed)
    for _ in range(n_blocks):
        replay.add_block(
            synth_block(cfg, rng),
            rng.uniform(0.5, 2.0, cfg.seqs_per_block).astype(np.float32),
            float(rng.normal()),
        )


@pytest.mark.parametrize("plane", ["host", "tiered", "device"])
def test_bf16_carry_storage_and_snapshot_round_trip(tmp_path, plane):
    """Under precision=bf16 every replay plane stores carries half-width,
    and the npz round trip (replay/snapshot.py's bf16 bit-view shim)
    restores them bit-exactly with the dtype intact — the property that
    keeps --resume bit-exact per plane."""
    from r2d2_tpu.replay.device_store import DeviceReplayBuffer
    from r2d2_tpu.replay.replay_buffer import ReplayBuffer
    from r2d2_tpu.replay.snapshot import restore_replay, save_replay
    from r2d2_tpu.replay.tiered_store import TieredReplayBuffer

    cfg = bf16_cfg().replace(
        replay_plane={"host": "host", "tiered": "tiered", "device": "device"}[plane]
    )
    cls = {
        "host": ReplayBuffer,
        "tiered": TieredReplayBuffer,
        "device": DeviceReplayBuffer,
    }[plane]
    replay = cls(cfg)
    _fill(replay, cfg)

    if plane == "device":
        hidden = np.asarray(replay.stores["hidden"])
    else:
        hidden = replay.hidden_store
    assert hidden.dtype == BF16
    assert hidden.dtype.itemsize == 2

    path = str(tmp_path / "snap.npz")
    save_replay(replay, path)
    fresh = cls(cfg)
    restore_replay(fresh, path)
    restored = (
        np.asarray(fresh.stores["hidden"]) if plane == "device" else fresh.hidden_store
    )
    assert restored.dtype == BF16
    np.testing.assert_array_equal(
        restored.view(np.uint16), hidden.view(np.uint16)
    )


def test_fp32_snapshot_dtype_unchanged(tmp_path):
    """The default precision still snapshots fp32 carries fp32 — the shim
    must not rewrite anything on the golden path."""
    from r2d2_tpu.replay.replay_buffer import ReplayBuffer
    from r2d2_tpu.replay.snapshot import restore_replay, save_replay

    cfg = tiny_test()
    replay = ReplayBuffer(cfg)
    _fill(replay, cfg)
    assert replay.hidden_store.dtype == np.float32
    path = str(tmp_path / "snap.npz")
    save_replay(replay, path)
    fresh = ReplayBuffer(cfg)
    restore_replay(fresh, path)
    assert fresh.hidden_store.dtype == np.float32
    np.testing.assert_array_equal(fresh.hidden_store, replay.hidden_store)


# ---------------------------------------------------------------- serving


def test_state_cache_precision_footprint():
    from r2d2_tpu.serve.state_cache import RecurrentStateCache

    f32 = RecurrentStateCache(4, 16)
    b16 = RecurrentStateCache(4, 16, dtype=jnp.bfloat16)
    assert f32.stats()["cache_dtype"] == "float32"
    assert f32.stats()["session_carry_bytes"] == 2 * 16 * 4
    assert b16.stats()["cache_dtype"] == "bfloat16"
    assert b16.stats()["session_carry_bytes"] == 2 * 16 * 2
    assert b16.h.dtype == jnp.bfloat16 and b16.c.dtype == jnp.bfloat16


@pytest.mark.parametrize("precision", ["fp32", "bf16"])
def test_serve_bucketed_parity_both_precisions(precision):
    """Bucketed-batch serving stays BIT-identical to the per-session
    reference path in both precisions: under bf16 the compute dtype equals
    the cache storage dtype, so the carry scatter-back is lossless and
    batch composition still cannot change any response."""
    from r2d2_tpu.serve import LocalClient, PolicyServer, ServeConfig
    from tests.test_serve import SessionReference

    cfg = tiny_test().replace(precision=precision)
    srv = PolicyServer(
        cfg, ServeConfig(buckets=(2, 4), max_wait_ms=2.0, cache_capacity=16)
    )
    srv.warmup()
    srv.start()
    try:
        assert srv.cache.dtype == jnp.dtype(
            jnp.bfloat16 if precision == "bf16" else jnp.float32
        )
        client = LocalClient(srv)
        params = srv._published[0]
        rng = np.random.default_rng(3)
        n_sessions, n_steps = 3, 6
        streams = [
            [
                (
                    rng.integers(0, 255, cfg.obs_shape, dtype=np.uint8),
                    float(rng.normal()),
                    bool(t == 3 and s == 1),
                )
                for t in range(n_steps)
            ]
            for s in range(n_sessions)
        ]
        # interleave sessions round-robin so batches mix compositions
        responses = [[] for _ in range(n_sessions)]
        for t in range(n_steps):
            for s in range(n_sessions):
                obs, reward, reset = streams[s][t]
                responses[s].append(
                    client.act(f"prec-{s}", obs, reward=reward, reset=reset)
                )
        for s in range(n_sessions):
            ref = SessionReference(srv.net, cfg.hidden_dim)
            for (obs, reward, reset), res in zip(streams[s], responses[s]):
                q_ref, a_ref = ref.step(params, obs, reward, reset,
                                        bucket=res.bucket)
                np.testing.assert_array_equal(q_ref, np.asarray(res.q))
                assert a_ref == res.action
    finally:
        srv.stop()


# ------------------------------------------------------------ convergence


@pytest.mark.slow
def test_bf16_catch_convergence_smoke(tmp_path):
    """End-to-end learning still happens under the full bf16 plane: a
    short catch run's loss trends down and the training loop stays finite
    (the drift bounds above say bf16 is close; this says it LEARNS)."""
    import json

    from r2d2_tpu.train import Trainer

    cfg = bf16_cfg().replace(
        env_name="catch",
        checkpoint_dir=str(tmp_path / "ckpt"),
        metrics_path=str(tmp_path / "metrics.jsonl"),
        training_steps=150,
        save_interval=1_000,
        learning_starts=48,
        lr=2e-3,
    )
    trainer = Trainer(cfg)
    trainer.run_inline(env_steps_per_update=4)
    recs = [json.loads(l) for l in open(cfg.metrics_path)]
    losses = np.array([r["loss"] for r in recs])
    assert np.isfinite(losses).all()
    assert losses[-20:].mean() < losses[:20].mean(), (
        losses[:20].mean(), losses[-20:].mean(),
    )


@pytest.mark.tpu
def test_bf16_train_step_faster_on_tpu():
    """On a real TPU the bf16 arm must beat fp32 on the same train-step
    shape (MXU native bf16) — meaningless on CPU, auto-skipped there."""
    import time

    results = {}
    for name, cfg in (("fp32", tiny_test()), ("bf16", bf16_cfg())):
        net, state = init_train_state(cfg, jax.random.PRNGKey(0))
        step = make_train_step(cfg, net, donate=False)
        batch = random_batch(cfg)
        state, _, _ = step(state, batch)  # compile
        jax.block_until_ready(state.params)
        t0 = time.perf_counter()
        for _ in range(10):
            state, _, _ = step(state, batch)
        jax.block_until_ready(state.params)
        results[name] = time.perf_counter() - t0
    assert results["bf16"] < results["fp32"], results
