"""The plain float32 reference against the program at tiny_test size, on
seeded random weights and a synthetic batch — and proof that the comparison
has teeth (a changed rescaling epsilon, a dropped burn-in seam and a wrong
weight are each caught)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correct
from benchmark.reference import model as ref
from r2d2_tpu.config import tiny_test
from r2d2_tpu.learner import DeviceBatch, init_train_state


def _batch(cfg, rng, B=6):
    T, L = cfg.seq_len, cfg.learning_steps
    burn = rng.integers(0, cfg.burn_in_steps + 1, B).astype(np.int32)
    learn = rng.integers(1, L + 1, B).astype(np.int32)
    fwd = rng.integers(1, cfg.forward_steps + 1, B).astype(np.int32)
    return DeviceBatch(
        obs=jnp.asarray(rng.integers(0, 256, (B, T, *cfg.obs_shape), dtype=np.uint8)),
        last_action=jnp.asarray(rng.integers(0, cfg.action_dim, (B, T)).astype(np.int32)),
        last_reward=jnp.asarray(rng.normal(size=(B, T)).astype(np.float32)),
        hidden=jnp.asarray(rng.normal(size=(B, 2, cfg.hidden_dim)).astype(np.float32) * 0.3),
        action=jnp.asarray(rng.integers(0, cfg.action_dim, (B, L)).astype(np.int32)),
        n_step_reward=jnp.asarray(rng.normal(size=(B, L)).astype(np.float32)),
        gamma=jnp.asarray(np.full((B, L), 0.98, np.float32)),
        burn_in_steps=jnp.asarray(burn), learning_steps=jnp.asarray(learn),
        forward_steps=jnp.asarray(fwd),
        is_weights=jnp.asarray(rng.uniform(0.2, 1.0, B).astype(np.float32)),
    )


def _make(core):
    cfg = tiny_test().replace(recurrent_core=core)
    net, state = init_train_state(cfg, jax.random.PRNGKey(11))
    # online and target nets must differ for the double-Q path to matter
    state = state.replace(target_params=jax.tree.map(lambda x: x * 0.9, state.params))
    return cfg, net, state, _batch(cfg, np.random.default_rng(2))


@pytest.fixture(scope="module", params=["lstm", "lru"])
def setup(request):
    return _make(request.param)


def test_program_step_matches_reference(setup):
    cfg, net, state, batch = setup
    out = correct.system_vs_reference(cfg, net, state, batch)
    assert out["ok"], out
    assert out["q_err_over_scale"] < 1e-5 and out["loss_rel"] < 1e-4 and out["grad_norm_rel"] < 1e-4


def test_a_different_rescaling_epsilon_is_caught(setup, monkeypatch):
    cfg, net, state, batch = setup
    real = correct.sizes_of
    monkeypatch.setattr(correct, "sizes_of", lambda c: real(c)._replace(eps=1e-2))
    out = correct.system_vs_reference(cfg, net, state, batch)
    assert not out["ok"] and out["loss_rel"] > correct.TOL["float32"]["loss"]


def test_a_wrong_weight_is_caught(setup, monkeypatch):
    cfg, net, state, batch = setup
    real = ref.dueling
    monkeypatch.setattr(ref, "dueling", lambda p, h: real(p, h) * 1.01)
    out = correct.system_vs_reference(cfg, net, state, batch)
    assert not out["ok"] and out["q_err_over_scale"] > correct.TOL["float32"]["q"]


def test_lstm_burn_in_seam_matters_for_the_gradient(monkeypatch):
    # (the LRU reference follows the program: no seam, PERF.md open question)
    cfg, net, state, batch = _make("lstm")
    monkeypatch.setattr(jax.lax, "stop_gradient", lambda x: x)
    sz = correct.sizes_of(cfg)
    rb = {"obs": batch.obs, "last_action": batch.last_action, "last_reward": batch.last_reward,
          "hidden": batch.hidden, "action": batch.action, "n_step_reward": batch.n_step_reward,
          "gamma": batch.gamma, "burn_in": batch.burn_in_steps, "learning": batch.learning_steps,
          "forward": batch.forward_steps, "is_weights": batch.is_weights}
    _, _, g_noseam = ref.loss_q_gradnorm(state.params["params"], state.target_params["params"], rb, sz)
    monkeypatch.undo()
    _, _, g_seam = ref.loss_q_gradnorm(state.params["params"], state.target_params["params"], rb, sz)
    assert abs(float(g_noseam) - float(g_seam)) / float(g_seam) > 1e-3


def test_act_unroll_matches_the_programs_act_steps(setup):
    cfg, net, state, _ = setup
    rng = np.random.default_rng(3)
    S, T = 3, 7
    obs = rng.integers(0, 256, (S, T, *cfg.obs_shape), dtype=np.uint8)
    la = rng.integers(0, cfg.action_dim, (S, T)).astype(np.int32)
    lr = rng.normal(size=(S, T)).astype(np.float32)
    carry = (jnp.zeros((S, cfg.hidden_dim)), jnp.zeros((S, cfg.hidden_dim)))
    qs = []
    for t in range(T):
        q, carry = net.apply(state.params, jnp.asarray(obs[:, t]), jnp.asarray(la[:, t]),
                             jnp.asarray(lr[:, t]), carry, method=net.act)
        qs.append(np.asarray(q))
    want = ref.act_unroll(state.params["params"], jnp.asarray(obs), jnp.asarray(la), jnp.asarray(lr),
                          correct.sizes_of(cfg))
    np.testing.assert_allclose(np.stack(qs, axis=1), np.asarray(want), rtol=1e-4, atol=1e-5)


def test_value_rescale_is_invertible():
    x = jnp.linspace(-50.0, 50.0, 41)
    np.testing.assert_allclose(ref.inverse_value_rescale(ref.value_rescale(x, 1e-3), 1e-3), x,
                               rtol=1e-4, atol=1e-4)
