"""The rules PR 21 (first run on the chip) pinned, all CPU and near-free:
one compile-cache directory rule, chip_smoke.py never falling back, the
native core keyed on its source's content. (The launch-counter rule sits
with the other analysis tests, tests/test_analysis.py.)"""

import ast
import json
import os
import shutil
import subprocess
import sys

import pytest

from r2d2_tpu.utils import compilation_cache as cc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------- compile-cache directory


def test_cache_dir_rule():
    """Variable set -> the program sets nothing (jax already has its
    directory); unset on a TPU -> <checkout>/.jax_cache; unset elsewhere
    -> no cache."""
    env = {"JAX_COMPILATION_CACHE_DIR": "/some/dir"}
    assert cc.cache_dir_to_set(env, "tpu") is None
    assert cc.cache_dir_to_set(env, "cpu") is None
    assert cc.cache_dir_to_set({}, "tpu") == os.path.join(REPO, ".jax_cache")
    assert cc.cache_dir_to_set({"JAX_COMPILATION_CACHE_DIR": ""}, "tpu") == (
        os.path.join(REPO, ".jax_cache")
    )
    assert cc.cache_dir_to_set({}, "cpu") is None


def _py_files():
    for root in ("r2d2_tpu", "runs", "examples"):
        for d, _, names in os.walk(os.path.join(REPO, root)):
            yield from (os.path.join(d, n) for n in sorted(names) if n.endswith(".py"))
    yield from (os.path.join(REPO, n) for n in ("chip_smoke.py", "__graft_entry__.py"))


def test_no_second_cache_rule_anywhere():
    """enable_compilation_cache takes no directory (so no mkdtemp, pid or
    time can feed it — a directory that moves never hits), nobody else
    writes jax's cache-dir option, and the duplicate variable is gone."""
    rule_home = os.path.join(REPO, "r2d2_tpu", "utils", "compilation_cache.py")
    for path in _py_files():
        with open(path) as f:
            src = f.read()
        assert "R2D2_COMPILE_CACHE" not in src, path
        for node in ast.walk(ast.parse(src)):
            if not isinstance(node, ast.Call):
                continue
            name = getattr(node.func, "attr", getattr(node.func, "id", ""))
            if name == "enable_compilation_cache":
                assert not node.args and not node.keywords, (
                    f"{path}:{node.lineno} passes a directory to "
                    "enable_compilation_cache"
                )
            if name == "update" and node.args and isinstance(
                node.args[0], ast.Constant
            ) and node.args[0].value == "jax_compilation_cache_dir":
                assert path == rule_home, (
                    f"{path}:{node.lineno} sets the compile-cache directory "
                    "outside utils/compilation_cache.py"
                )


# ------------------------------------------------------- chip_smoke.py


def _run_smoke(cwd, env):
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "chip_smoke.py")], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120,
    )


def test_chip_smoke_refuses_cpu():
    """The no-fallback rule: held to the CPU it exits non-zero in seconds,
    names the missing chip, and prints no result line."""
    r = _run_smoke(REPO, {**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "no TPU" in r.stderr and "JAX_PLATFORMS='cpu'" in r.stderr
    assert '"ok"' not in r.stdout


def test_chip_smoke_alone_fails(tmp_path):
    """In a directory holding chip_smoke.py and nothing else of the repo
    the first child cannot import the package: non-zero, no result."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "PYTHONPATH")}
    r = _run_smoke(str(tmp_path), env)
    assert r.returncode != 0
    assert "FAILED" in r.stderr and "kernels" in r.stderr
    assert '"ok"' not in r.stdout


_TINY_STORE = ["num_actors=8", "batch_size=8", "buffer_capacity=512"]
# the Nature trunk's smallest frame that its stride divides: the store keeps
# each frame's bytes in 4x4 blocks (PR 38), 1,296 bytes in 11 rows with a tail
_TINY_BLOCKED = [*_TINY_STORE, "encoder=nature", "obs_shape=36,36,1"]


@pytest.mark.parametrize("sets, block", [(_TINY_STORE, 1), (_TINY_BLOCKED, 4)], ids=["frames", "blocked"])
def test_chip_smoke_store_bytes_reads_every_plane_on_four_devices(sets, block):
    """The store-bytes phase at tiny size on four host devices: the plain
    jit, the sharded plane's shard_map and the GSPMD gather, each before, in
    the same program as, and after an in-place slab write — all bit for bit,
    as canonical frames and as the store keeps them (in 4x4 blocks under the
    Nature trunk)."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4", "PYTHONPATH": REPO}
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--phase", "store_bytes",
         "--preset", "tiny_test", "--sets", *sets],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    rows = [json.loads(l[12:]) for l in r.stdout.splitlines() if l.startswith("STORE_BYTES ")]
    assert {(v["plane"], v["moment"]) for v in rows} == {
        (p, m) for p in ("jit", "shard_map") for m in ("before", "same_program", "after")
    } | {("gspmd", "before"), ("gspmd", "after")}
    assert {v["frame_block"] for v in rows} == {block}
    for order in ("canonical", "stored"):  # every plane and moment, in both orders
        assert {(v["plane"], v["moment"]) for v in rows if v["order"] == order} == {
            (v["plane"], v["moment"]) for v in rows}
    assert all(v["verdict"] == "ok" and v["mismatched_bytes"] == 0 for v in rows)
    # the write lands on slots the gathers read, in every plane and moment
    assert all(v["read_from_slab_slots"] > 0 for v in rows)
    assert "STORE_BYTES_DONE failed=0" in r.stdout


@pytest.mark.parametrize("fault", ["next_row", "next_byte", "write_skips_obs", "order_not_undone"])
def test_chip_smoke_store_bytes_sees_wrong_bytes(fault, monkeypatch, capsys):
    """The pattern tells a gather that is off by one slot row or by one byte
    inside the frame, a slab write that leaves the obs store as it was, and a
    canonical gather that hands the store's block order back as it is, from
    the right ones."""
    import jax.numpy as jnp

    import chip_smoke
    from r2d2_tpu import learner, megastep

    good_gather, good_write = learner.make_store_gather, megastep._slab_write

    def broken_gather(cfg, as_stored=False):
        gather = good_gather(cfg, as_stored)

        def gather_batch(stores, b, s, w):
            if fault == "next_row":
                stores = {**stores, "obs": jnp.roll(stores["obs"], -1, axis=1)}
            if fault == "order_not_undone":  # what `correct` could not see (PERF.md finding 25.5)
                stored = good_gather(cfg, True)(stores, b, s, w)
                return stored if as_stored else stored._replace(
                    obs=stored.obs.reshape(*stored.obs.shape[:2], *cfg.obs_shape))
            batch = gather(stores, b, s, w)
            if fault == "next_byte":
                flat = batch.obs.reshape(*batch.obs.shape[:2], -1)
                batch = batch._replace(obs=jnp.roll(flat, -1, axis=-1).reshape(batch.obs.shape))
            return batch

        return gather_batch

    if fault == "write_skips_obs":
        monkeypatch.setattr(megastep, "_slab_write",
                            lambda stores, fields, start: {**good_write(stores, fields, start), "obs": stores["obs"]})
    else:
        monkeypatch.setattr(learner, "make_store_gather", broken_gather)
    sets = _TINY_BLOCKED if fault == "order_not_undone" else _TINY_STORE
    assert chip_smoke._store_bytes_child("tiny_test", sets, batches=1) == 1
    rows = [json.loads(l[12:]) for l in capsys.readouterr().out.splitlines() if l.startswith("STORE_BYTES ")]
    bad = {(v["plane"], v["moment"], v["order"]) for v in rows if v["verdict"] != "ok"}
    planes = {v["plane"] for v in rows}
    if fault == "write_skips_obs":  # only what follows the write is wrong
        assert bad == {(p, "after", o) for p in planes for o in ("canonical", "stored")}
    elif fault == "order_not_undone":  # the as-stored gather is right, the canonical one is not
        assert bad == {(v["plane"], v["moment"], "canonical") for v in rows}
    else:
        assert bad == {(v["plane"], v["moment"], v["order"]) for v in rows}


# ------------------------------------------------------- native replay core


def test_native_library_is_keyed_on_source_content(tmp_path):
    """The library's name carries a hash of replay_core.cpp, so a build of
    other source (a stale .so a tree copy brought along, whatever its
    mtime) has another name and is never the one loaded."""
    from r2d2_tpu import _native

    a, b = tmp_path / "a.cpp", tmp_path / "b.cpp"
    a.write_text("int f() { return 1; }\n")
    b.write_text("int f() { return 2; }\n")
    assert _native.lib_path(str(a)) != _native.lib_path(str(b))
    b.write_text("int f() { return 1; }\n")
    os.utime(b, (0, 0))  # mtime plays no part
    assert os.path.basename(_native.lib_path(str(a))) == os.path.basename(
        _native.lib_path(str(b))
    )
    core = _native.load_native()
    if core is None:
        pytest.skip("no C++ toolchain: numpy core")
    assert core._lib._name == _native.lib_path()


# ------------------------------------------- where the Pallas core may live


def test_pallas_core_only_where_no_mesh_axis_is_auto(monkeypatch):
    """Mosaic refuses a pallas_call under any GSPMD-auto mesh axis (the
    first --dp 4 run on real chips). So on a TPU `auto` picks the kernel
    on one device and under the fully-manual dp planes, and the scan core
    everywhere else — and those planes' shard_maps are fully manual
    exactly when every non-dp axis has size 1."""
    import jax

    from r2d2_tpu.config import tiny_test
    from r2d2_tpu.parallel.mesh import dp_manual_axes, make_mesh

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    base = tiny_test()
    assert base.resolved_core_backend == "pallas"
    assert base.replace(recurrent_core="lru").resolved_core_backend == "lru"
    assert base.replace(lstm_backend="scan").resolved_core_backend == "scan"
    sharded = base.replace(dp_size=4, replay_plane="sharded", buffer_capacity=1280)
    assert sharded.resolved_core_backend == "pallas"
    assert sharded.replace(dp_size=2, tp_size=2).resolved_core_backend == "scan"
    # plain-jit plane over a dp mesh: GSPMD partitions the whole step
    assert base.replace(dp_size=4).resolved_core_backend == "scan"

    devices = jax.devices()
    assert dp_manual_axes(make_mesh(dp=4, tp=1, devices=devices[:4])) is None
    assert dp_manual_axes(make_mesh(dp=2, tp=2, devices=devices[:4])) == {"dp"}


@pytest.mark.parametrize("preset, block, order", [
    ("atari", 4, "blocked"), ("atari_v4_8", 4, "blocked"),
    ("long_context", 1, "frames"), ("procgen_impala", 1, "frames"), ("tiny_test", 1, "frames"),
])
def test_runtime_line_says_the_frame_block_and_the_store_order(preset, block, order):
    """`[runtime]` names the block in which the encoder's first conv reads a
    frame and the byte order of the device stores' rows (PR 38): 4 / blocked
    under the Nature trunk on 84x84, 1 / frames for every other encoder, and
    for the Nature trunk where its stride does not divide the frame."""
    from r2d2_tpu.config import PRESETS
    from r2d2_tpu.utils.runtime import describe_runtime

    cfg = PRESETS[preset]()
    info = describe_runtime(cfg)
    assert (info["frame_block"], info["store_order"]) == (block, order) == (cfg.resolved_frame_block, order)
    if cfg.encoder == "nature":
        odd = describe_runtime(cfg.replace(obs_shape=(86, 86, 1)))
        assert (odd["frame_block"], odd["store_order"]) == (1, "frames")
