"""The LRU recurrence as a sequential Pallas pass (ops/pallas_lru.py), under
the Pallas interpreter on the CPU: the same states as `LRU.step` applied T
times and as the associative scan, in both directions, across chunk seams and
padding; the same gradients as the associative scan's autodiff; and the rule
that says where the kernel runs (config.resolved_lru_recurrence)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from r2d2_tpu.config import tiny_test
from r2d2_tpu.models.lru import LRU
from r2d2_tpu.ops import pallas_lru as pk

TOL = 1e-5  # of the compared array's scale, float32


def _close(got, want, tol=TOL):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1e-30), np.abs(got - want).max()


def _inputs(T, B, H, seed=0):
    rng = np.random.default_rng(seed)
    mod, theta = rng.uniform(0.9, 0.999, H), rng.uniform(0.0, 6.283, H)
    f32 = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    lam = jnp.asarray(mod * np.cos(theta), jnp.float32), jnp.asarray(mod * np.sin(theta), jnp.float32)
    return lam, (f32(T, B, H), f32(T, B, H)), (f32(B, H), f32(B, H))


def _sequential(lam, u, h0, reverse=False):
    """The recurrence step by step in numpy float32: LRU.step's arithmetic."""
    (a, b), (u_re, u_im) = (np.asarray(x) for x in lam), (np.asarray(x) for x in u)
    p, q = (np.asarray(x) for x in h0)
    h_re, h_im = np.empty_like(u_re), np.empty_like(u_im)
    for t in (range(len(u_re) - 1, -1, -1) if reverse else range(len(u_re))):
        p, q = a * p - b * q + u_re[t], a * q + b * p + u_im[t]
        h_re[t], h_im[t] = p, q
    return h_re, h_im


# (T, chunk): one chunk; one chunk of the cell's own length; 166 + 3 steps in
# chunks of 83 (padded to 249 on the side processed last); the cell's T in its
# own 7 chunks of 83; what `chunk_len` picks by itself; three blocks of H
CASES = [(5, 5, 128), (83, 83, 128), (169, 83, 128), (581, 83, 128), (581, None, 128), (10, 5, 384)]


@pytest.mark.parametrize("reverse", [False, True], ids=["forward", "reversed"])
@pytest.mark.parametrize("T,chunk,H", CASES)
def test_kernel_states_equal_the_sequential_recurrence(T, chunk, H, reverse):
    B = 8
    lam, u, h0 = _inputs(T, B, H, seed=T)
    call = pk._lru_rev_call if reverse else pk._lru_fwd_call
    got = call(*lam, *u, *h0, chunk=chunk or pk.chunk_len(T, B), interpret=True)
    for g, w in zip(got, _sequential(lam, u, h0, reverse)):
        _close(g, w)


def test_reversed_is_forward_over_flipped_time():
    lam, u, h0 = _inputs(24, 8, 256)
    got = pk.lru_states(*lam, *u, *h0, reverse=True)
    want = pk.lru_states(*lam, u[0][::-1], u[1][::-1], *h0)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w)[::-1])


@pytest.mark.parametrize("T,B,want", [(581, 32, 83), (85, 64, 17), (85, 16, 85), (5, 8, 5), (97, 64, 33), (1024, 16, 128)])
def test_chunk_len_takes_a_divisor_where_one_is_near(T, B, want):
    assert pk.chunk_len(T, B) == want


@pytest.mark.parametrize("rows,hidden,fits", [(32, 512, True), (8, 128, True), (4, 128, False), (32, 96, False), (12, 256, False)])
def test_kernel_fits_whole_tiles_only(rows, hidden, fits):
    assert pk.kernel_fits(rows, hidden) is fits
    if not fits:
        lam, u, h0 = _inputs(3, rows, hidden)
        with pytest.raises(ValueError, match="whole"):
            pk.lru_states(*lam, *u, *h0)


def _scan_states(lam, u, h0):
    """The associative scan over the same time-major operands (the module's
    other implementation, without the module)."""
    def combine(e1, e2):
        a1r, a1i, b1r, b1i = e1
        a2r, a2i, b2r, b2i = e2
        return (a2r * a1r - a2i * a1i, a2r * a1i + a2i * a1r,
                a2r * b1r - a2i * b1i + b2r, a2r * b1i + a2i * b1r + b2i)

    a = [jnp.broadcast_to(x, u[0].shape) for x in lam]
    A_re, A_im, B_re, B_im = jax.lax.associative_scan(combine, (*a, *u), axis=0)
    return A_re * h0[0] - A_im * h0[1] + B_re, A_re * h0[1] + A_im * h0[0] + B_im


@pytest.mark.parametrize("T", [5, 37])
def test_op_gradients_equal_the_associative_scans(T):
    """lru_scan's VJP (the reversed kernel, dh0, and the lambda reduction)
    against autodiff of the associative scan, with a cotangent on every state
    and on the final carry."""
    lam, u, h0 = _inputs(T, 8, 128, seed=3)

    def loss(states):
        def f(lam_re, lam_im, u_re, u_im, p, q):
            h_re, h_im = states(lam_re, lam_im, u_re, u_im, p, q)
            return jnp.sum(jnp.sin(h_re) * h_im) + jnp.sum(h_re[-1] ** 2 - h_im[-1])
        return f

    got = jax.grad(loss(pk.lru_scan), argnums=tuple(range(6)))(*lam, *u, *h0)
    want = jax.grad(loss(lambda a, b, ur, ui, p, q: _scan_states((a, b), (ur, ui), (p, q))),
                    argnums=tuple(range(6)))(*lam, *u, *h0)
    for g, w in zip(got, want):
        _close(g, w)


@pytest.fixture(scope="module")
def modules():
    B, T, D, H = 8, 70, 12, 128
    rng = np.random.default_rng(1)
    xs = jnp.asarray(rng.normal(size=(B, T, D)), jnp.float32)
    carry = tuple(jnp.asarray(rng.normal(size=(B, H)) * 0.3, jnp.float32) for _ in range(2))
    mods = {"scan": LRU(H, in_dim=D), "pallas": LRU(H, in_dim=D, backend="pallas"), "chunk64": LRU(H, in_dim=D, chunk=64)}
    return mods, mods["scan"].init(jax.random.PRNGKey(1), xs, carry), xs, carry


def test_module_with_the_kernel_equals_step_applied_T_times(modules):
    mods, params, xs, carry = modules
    outs, final = mods["pallas"].apply(params, xs, carry)
    c, seq = carry, []
    for t in range(xs.shape[1]):
        o, c = mods["pallas"].apply(params, xs[:, t], c, method=LRU.step)
        seq.append(o)
    _close(outs, jnp.stack(seq, axis=1))
    _close(final[0], c[0])
    _close(final[1], c[1])


@pytest.mark.parametrize("other", ["scan", "chunk64"])
def test_module_with_the_kernel_equals_the_other_formulations(modules, other):
    mods, params, xs, carry = modules
    outs, final = mods["pallas"].apply(params, xs, carry)
    want_outs, want_final = mods[other].apply(params, xs, carry)
    tol = TOL if other == "scan" else 1e-4  # the chunked form sums in another order
    _close(outs, want_outs, tol)
    _close(final[0], want_final[0], tol)
    _close(final[1], want_final[1], tol)


def test_module_gradients_with_the_kernel_equal_the_scans(modules):
    """Every parameter (nu_log and theta_log through `_decay`, outside the
    kernel), the input and the initial carry."""
    mods, params, xs, carry = modules

    def loss(m):
        def f(p, x, c):
            outs, (h_re, h_im) = m.apply(p, x, c)
            return jnp.sum(outs ** 2) + jnp.sum(h_re * h_im)
        return f

    got = jax.grad(loss(mods["pallas"]), argnums=(0, 1, 2))(params, xs, carry)
    want = jax.grad(loss(mods["scan"]), argnums=(0, 1, 2))(params, xs, carry)
    names = [jax.tree_util.keystr(k) for k, _ in jax.tree_util.tree_leaves_with_path(want)]
    assert any("nu_log" in n for n in names) and any("theta_log" in n for n in names)
    for name, g, w in zip(names, jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.abs(np.asarray(w)).max() > 0, name
        _close(g, w, 2e-5)


def test_module_falls_back_to_the_scan_on_shapes_the_kernel_refuses(modules, monkeypatch):
    """backend="pallas" at 4 rows or H = 96: no kernel call, the scan's result."""
    def refuse(*a, **k):
        raise AssertionError("the kernel was called")

    monkeypatch.setattr("r2d2_tpu.models.lru.lru_scan", refuse)
    for B, H in [(4, 128), (8, 96)]:
        rng = np.random.default_rng(2)
        xs = jnp.asarray(rng.normal(size=(B, 6, 5)), jnp.float32)
        carry = (jnp.zeros((B, H)), jnp.zeros((B, H)))
        scan, kernel = LRU(H, in_dim=5), LRU(H, in_dim=5, backend="pallas")
        params = scan.init(jax.random.PRNGKey(0), xs, carry)
        np.testing.assert_array_equal(np.asarray(kernel.apply(params, xs, carry)[0]),
                                      np.asarray(scan.apply(params, xs, carry)[0]))


def _lru_cfg(**over):
    return tiny_test().replace(**{"recurrent_core": "lru", "hidden_dim": 128, "batch_size": 8, **over})


RULE = [
    # on a TPU: (overrides, resolved)
    ({}, "pallas"),
    ({"hidden_dim": 96}, "scan"),
    ({"batch_size": 4}, "scan"),
    ({"lru_chunk": 4}, "chunked"),
    ({"dp_size": 4, "batch_size": 32}, "scan"),  # plain-jit plane over a dp mesh: GSPMD partitions the step
    ({"dp_size": 4, "batch_size": 32, "replay_plane": "sharded", "buffer_capacity": 1280}, "pallas"),  # manual body
    ({"dp_size": 4, "batch_size": 16, "replay_plane": "sharded", "buffer_capacity": 1280}, "scan"),  # 4 rows per device
    ({"dp_size": 2, "tp_size": 2, "batch_size": 32, "replay_plane": "sharded", "buffer_capacity": 1280}, "scan"),
]


@pytest.mark.parametrize("over,want", RULE, ids=[w + ":" + ",".join(f"{k}={v}" for k, v in o.items()) for o, w in RULE])
def test_recurrence_resolves_by_platform_placement_and_shape(over, want, monkeypatch):
    cfg = _lru_cfg(**over)
    assert cfg.resolved_lru_recurrence == ("chunked" if cfg.lru_chunk else "scan")  # a CPU here
    assert LRU.from_config(cfg, in_dim=5).backend == "scan"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert cfg.resolved_lru_recurrence == want
    assert cfg.resolved_core_backend == "lru"
    assert LRU.from_config(cfg, in_dim=5).backend == ("pallas" if want == "pallas" else "scan")


def test_runtime_line_says_which_recurrence_was_resolved(monkeypatch):
    from r2d2_tpu.utils.runtime import describe_runtime

    rt = describe_runtime(_lru_cfg())
    assert rt["core"] == "lru" and rt["lru_recurrence"] == "scan" and rt["pallas_interpreted"] is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert describe_runtime(_lru_cfg())["lru_recurrence"] == "pallas"
    assert "lru_recurrence" not in describe_runtime(tiny_test().replace(lstm_backend="scan"))
