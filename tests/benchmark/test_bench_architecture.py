"""A new architecture is files and entries: a toy reference module, a
configuration that names it and a cell are ADDED to a temporary copy of the
benchmark (every file that was there hashes the same afterwards) and run
through `run_cell` at tiny size on the CPU. Also: what the toy's reference and
operation count are for (a wrong weight and a wrong count are caught), a
reference that has no file, and the reference check's operating point."""

import gc
import hashlib
import json
import os
import shutil

import pytest

from benchmark import correct, flops, harness, manifest
from benchmark import trace as tr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TINY = {"env_name": "drift", "action_dim": 3, "max_episode_steps": 16, "collector": "device",
        "replay_plane": "device", "updates_per_dispatch": 2, "num_actors": 2}

# Dense encoder -> LSTM -> dueling heads as a Python loop over time: written
# apart from reference/model.py (no scan, its own Sizes, the carry read off
# batch["hidden"] here), sharing only the parameter tree's names.
TOY = '''"""Toy architecture (tests only)."""
from typing import NamedTuple

import jax
import jax.numpy as jnp

HEAD_SCALE = 1.0
FLOPS_PER_FRAME = 1000


class Sizes(NamedTuple):
    action_dim: int
    learning: int
    forward: int
    eps: float


def sizes_of(cfg):
    return Sizes(cfg.action_dim, cfg.learning_steps, cfg.forward_steps, cfg.value_rescale_eps)


def update_flops(cfg):
    return FLOPS_PER_FRAME * cfg.batch_size * cfg.seq_len


def _dense(p, x):
    return x @ p["kernel"] + p["bias"]


def _q(p, h):
    adv = _dense(p["adv_out"], jax.nn.relu(_dense(p["adv_hidden"], h)))
    val = _dense(p["val_out"], jax.nn.relu(_dense(p["val_hidden"], h)))
    return HEAD_SCALE * (val + adv - adv.mean(-1, keepdims=True))


def _step(p, obs, a, r, h, c, sz):
    x = obs.reshape(obs.shape[0], -1).astype(jnp.float32) / 255.0
    x = jax.nn.relu(_dense(p["enc"]["Dense_0"], x))
    x = jnp.concatenate([x, jax.nn.one_hot(a, sz.action_dim), r.astype(jnp.float32)[:, None]], -1)
    i, f, g, o = jnp.split(x @ p["core"]["wi"] + p["core"]["b"] + h @ p["core"]["wh"], 4, axis=-1)
    c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    return jax.nn.sigmoid(o) * jnp.tanh(c), c


def _outputs(p, obs, last_action, last_reward, h, c, burn_in, sz):
    outs = []
    for t in range(obs.shape[1]):
        if burn_in is not None:  # burn-in only refreshes the state
            seam = (t == burn_in)[:, None]
            h, c = (jnp.where(seam, jax.lax.stop_gradient(x), x) for x in (h, c))
        h, c = _step(p, obs[:, t], last_action[:, t], last_reward[:, t], h, c, sz)
        outs.append(h)
    return jnp.stack(outs, 1)


def _rescale(x, eps):
    return jnp.sign(x) * (jnp.sqrt(jnp.abs(x) + 1.0) - 1.0) + eps * x


def _unrescale(x, eps):
    t = (jnp.sqrt(1.0 + 4.0 * eps * (jnp.abs(x) + 1.0 + eps)) - 1.0) / (2.0 * eps)
    return jnp.sign(x) * (t * t - 1.0)


def _views(p, b, sz):
    T = b["obs"].shape[1]
    hidden = b["hidden"].astype(jnp.float32)
    outs = _outputs(p, b["obs"], b["last_action"], b["last_reward"], hidden[:, 0], hidden[:, 1], b["burn_in"], sz)
    t = jnp.arange(sz.learning)
    learn = jnp.clip(b["burn_in"][:, None] + t, 0, T - 1)
    end = (b["burn_in"] + b["learning"] + b["forward"])[:, None] - 1
    boot = jnp.clip(jnp.minimum(b["burn_in"][:, None] + sz.forward + t, end), 0, T - 1)
    take = lambda idx: jnp.take_along_axis(outs, idx[:, :, None], axis=1)
    return _q(p, take(learn)), _q(p, take(boot)), (t[None] < b["learning"][:, None]).astype(jnp.float32)


def _loss(p, tp, b, sz):
    q_learn, q_boot, mask = _views(p, b, sz)
    q_boot_target = _views(tp, b, sz)[1]
    best = jnp.argmax(jax.lax.stop_gradient(q_boot), -1)
    q_next = jnp.take_along_axis(q_boot_target, best[..., None], -1)[..., 0]
    y = _rescale(b["n_step_reward"] + b["gamma"] * _unrescale(q_next, sz.eps), sz.eps)
    td = jax.lax.stop_gradient(y) - jnp.take_along_axis(q_learn, b["action"][..., None], -1)[..., 0]
    w = b["is_weights"].astype(jnp.float32)[:, None]
    return jnp.sum(w * td * td * mask) / jnp.maximum(jnp.sum(mask), 1.0), q_learn


def loss_q_gradnorm(params, target_params, batch, sz):
    f32 = lambda tree: jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), tree)
    (loss, q), grads = jax.value_and_grad(_loss, has_aux=True)(f32(params), f32(target_params), batch, sz)
    return loss, q, jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))


def act_unroll(params, obs, last_action, last_reward, sz):
    p = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), params)
    zero = jnp.zeros((obs.shape[0], p["core"]["wh"].shape[0]), jnp.float32)
    return _q(p, _outputs(p, obs, last_action, last_reward, zero, zero, None, sz))
'''


def _hashes(bench_dir):
    out = {}
    for d, _, files in os.walk(bench_dir):
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                out[os.path.join(d, f)] = hashlib.sha256(fh.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("toyroot"))
    bench = os.path.join(root, "benchmark")
    shutil.copytree(os.path.join(ROOT, "benchmark"), bench, ignore=shutil.ignore_patterns("__pycache__"))
    before = _hashes(bench)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        m = json.load(fh)
    references = {
        "toy": TOY,
        # one wrong weight (every head 1 % too large) and a wrong operation count
        "toy_wrong": TOY.replace("HEAD_SCALE = 1.0", "HEAD_SCALE = 1.01").replace(
            "FLOPS_PER_FRAME = 1000", "FLOPS_PER_FRAME = 2000"),
    }
    for name, source in references.items():
        with open(os.path.join(bench, "reference", name + ".py"), "w") as fh:
            fh.write(source)
    for name in (*references, "nowhere"):
        with open(os.path.join(bench, "configs", name + ".json"), "w") as fh:
            json.dump({"name": name, "source": "test", "preset": "tiny_test", "overrides": TINY,
                       "reduced": [], "reference": name}, fh)
        m["configs"].append({"name": name, "source": "test", "why": "test", "reduced": [],
                             "file": f"benchmark/configs/{name}.json"})
        m["workloads"].append({"name": name + ".learn", "config": name, "traffic": "learn",
                               "chips": 1, "why": "test"})
    for e in m["end_to_end"] + m["per_layer"]:
        if "workloads" in e:
            e["workloads"] = e["workloads"] + [n + ".learn" for n in (*references, "nowhere")]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(m, fh)
    # on a CPU the XLA ops sit on the host plane's client threads
    pats = harness.load_json(os.path.join(bench, "trace_patterns.json"))
    pats.update(device_plane="^/host:CPU$", op_lines=["^tf_XLA"], module_lines=["^no such line$"])
    with open(os.path.join(bench, "trace_patterns_cpu.json"), "w") as fh:
        json.dump(pats, fh)
    yield root
    after = _hashes(bench)
    assert {p: h for p, h in after.items() if p in before} == before  # byte for byte


def _run(root, cell, seconds=0.4, seed=3, trace=False):
    return harness.run_cell(root, cell, seed=seed, seconds=seconds, trace=trace, require_tpu=False)


@pytest.fixture(scope="module")
def toy_result(toy_root):
    return _run(toy_root, "toy.learn")


def test_a_toy_architecture_runs_as_files_and_entries(toy_result):
    assert toy_result["correct"] is True and toy_result["failed"] == 0
    checks = toy_result["notes"]["checks"]
    assert checks["reference"]["ok"] and checks["reference"]["q_err_over_scale"] < 1e-5
    assert checks["reference"]["loss_rel"] < 1e-5 and checks["reference"]["grad_norm_rel"] < 1e-4
    assert "names no kernel" in checks["kernels"]["skipped"]  # the toy module has no kernel_checks


def test_every_key_of_the_checks_is_there(toy_result):
    checks = toy_result["notes"]["checks"]
    assert set(checks) == {"kernels", "reference", "reference_end", "loss_island"}
    # the toy module provides no island inputs: skipped with the reason, as a module without kernels is
    assert checks["loss_island"]["ok"] and "no island inputs" in checks["loss_island"]["skipped"]
    # the end state is judged on Q alone
    assert checks["reference"]["judged"] == list(checks["reference"]["limits"])
    assert checks["reference_end"]["judged"] == list(correct.END_STATE) == ["q_err_over_scale"]
    for name in ("reference", "reference_end"):
        assert {"q_err_over_scale", "loss_rel", "grad_norm_rel", "loss", "loss_ref", "sequences", "ok",
                "loss_abs_err", "grad_norm_abs_err", "updates_at_check", "limits"} <= set(checks[name])
        # each number compared stands beside its limit
        assert set(checks[name]["limits"]) == {"q_err_over_scale", "loss_abs_err", "grad_norm_abs_err"}
    assert checks["reference_end"]["updates_at_check"] == (
        checks["reference"]["updates_at_check"] + toy_result["attempted"])


@pytest.mark.parametrize("cell,flops_per_frame", [("toy.learn", 1000), ("toy_wrong.learn", 2000)])
def test_mfu_is_read_through_the_modules_update_flops(toy_root, cell, flops_per_frame, monkeypatch, tmp_path):
    real = tr.load_patterns
    monkeypatch.setattr(tr, "load_patterns",
                        lambda path=None: real(os.path.join(toy_root, "benchmark", "trace_patterns_cpu.json")))
    peaks = tmp_path / "peaks.json"
    peaks.write_text(json.dumps({"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}))
    monkeypatch.setattr(flops, "_PEAKS_PATH", str(peaks))
    r = _run(toy_root, cell, seconds=0.2, trace=True)
    # tiny_test: batch 8, T = 4 + 4 + 2; a wrong count is off by its factor
    per_update = flops_per_frame * 8 * 10
    rate = r["attempted"] / r["notes"]["window_s"]
    assert r["metrics"]["model.mfu"]["value"] == pytest.approx(100.0 * per_update * rate / 1e12)
    # the program's spans name the idle gaps, not only the benchmark's own
    assert any(name.startswith(("r2d2.", "bench.")) for name, _ in r["breakdown"]["idle_gaps"])


def test_a_wrong_weight_in_the_reference_is_caught(toy_root):
    r = _run(toy_root, "toy_wrong.learn")
    ref = r["notes"]["checks"]["reference"]
    assert r["correct"] is False and not ref["ok"]
    assert ref["q_err_over_scale"] > ref["limits"]["q_err_over_scale"]
    assert r["notes"]["compiles_in_window"] == 0 and r["failed"] == 0  # nothing else is at fault


def test_a_reference_without_a_file_is_an_error_that_names_the_path(toy_root):
    with pytest.raises(harness.BenchmarkError) as e:
        _run(toy_root, "nowhere.learn")
    assert os.path.join(toy_root, "benchmark", "reference", "nowhere.py") in str(e.value)
    cell = harness.load_cell(toy_root, "toy.learn")
    cell.config["reference"] = "../configs/toy"
    with pytest.raises(harness.BenchmarkError, match="no file"):
        harness.reference_for(cell)


def test_a_reference_module_must_keep_the_contract(toy_root, tmp_path):
    bench = tmp_path / "benchmark"
    (bench / "reference").mkdir(parents=True)
    (bench / "reference" / "half.py").write_text("def sizes_of(cfg):\n    return None\n")
    cell = harness.load_cell(toy_root, "toy.learn")
    cell.bench_dir, cell.config = str(bench), {"reference": "half"}
    with pytest.raises(harness.BenchmarkError, match="does not define.*loss_q_gradnorm.*update_flops"):
        harness.reference_for(cell)


def test_one_loader_serves_the_cells_and_the_copy_alike(toy_root):
    """Each cell's reference module is the file its configuration names
    (default `model`), loaded from that file and once per file: the repo's
    cells that name none share `reference/model.py`, and in the temporary copy
    a cell with a reference of its own gets its own file beside them."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        m = json.load(fh)
    default = os.path.realpath(os.path.join(ROOT, "benchmark", "reference", "model.py"))
    files = manifest.reference_files(ROOT, m)
    for cell, path in files.items():
        named = harness.load_cell(ROOT, cell).config.get("reference", "model")
        assert path == (default if named == "model" else os.path.realpath(
            os.path.join(ROOT, "benchmark", "reference", named + ".py")))
    assert default in files.values()
    mod = harness.reference_for(harness.load_cell(ROOT, next(c for c, f in files.items() if f == default)))
    assert callable(mod.island_inputs) and callable(mod.kernel_checks)
    # the copy: the same rule, the same loader (`nowhere` names a file that is not there)
    with open(os.path.join(toy_root, "BENCHMARK.json")) as fh:
        tm = json.load(fh)
    tm["workloads"] = [w for w in tm["workloads"] if w["config"] != "nowhere"]
    copied = manifest.reference_files(toy_root, tm)
    bench = os.path.realpath(os.path.join(toy_root, "benchmark", "reference"))
    assert copied["toy.learn"] == os.path.join(bench, "toy.py")
    assert copied["toy_wrong.learn"] == os.path.join(bench, "toy_wrong.py")
    assert {copied[c] for c in files} == {os.path.join(bench, "model.py")}


# ------------------------------------------------ a fourth configuration, tests included

FOURTH = {
    "name": "toy-share",
    "source": "test: the toy architecture of tests/benchmark/test_bench_architecture.py, as a chip's share",
    "deployment": "one chip of 32 that share a layer (test)",
    "preset": "tiny_test",
    "overrides": TINY,
    "reference": "toy",
    "expect": {"hidden_dim": 32, "seq_len": 10, "batch_size": 8, "num_blocks": 40, "encoder": "mlp",
               "obs_shape": [12, 12, 1]},
    "reduced": ["num_hidden_layers", "num_experts_held", "num_key_value_heads"],
    "reduced_why": {"num_hidden_layers": "test", "num_experts_held": "test", "num_key_value_heads": "test"},
    "deployment_share": {"chips_per_layer": 32, "num_experts_held": {"published": 256, "held": 8},
                         "num_key_value_heads": {"published": 8, "held": 1}},
    "assumed": {"action_dim": "3, the drift env's"},
}
OWN_SCOPE = {"name": "model.core_matmul_ms_per_update", "layer": "model", "unit": "ms",
             "moves": "learn_steps_per_s", "reader": "trace_scope", "op_name": "dot_general",
             "within": "core", "per": "updates", "scale": 1000.0}


@pytest.fixture(scope="module")
def fourth_root(tmp_path_factory):
    """The repo's benchmark with a FOURTH configuration, its reference file,
    its cell and a layer file of its own added as files and entries."""
    root = str(tmp_path_factory.mktemp("fourth"))
    bench = os.path.join(root, "benchmark")
    shutil.copytree(os.path.join(ROOT, "benchmark"), bench, ignore=shutil.ignore_patterns("__pycache__"))
    before = _hashes(bench)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        m = json.load(fh)
    with open(os.path.join(bench, "reference", "toy.py"), "w") as fh:
        fh.write(TOY)
    with open(os.path.join(bench, "configs", "toy-share.json"), "w") as fh:
        json.dump(FOURTH, fh)
    with open(os.path.join(bench, "layers", OWN_SCOPE["name"] + ".json"), "w") as fh:
        json.dump(OWN_SCOPE, fh)
    m["configs"].append({"name": "toy-share", "source": FOURTH["source"], "why": "test", "reduced": FOURTH["reduced"],
                         "file": "benchmark/configs/toy-share.json"})
    m["workloads"].append({"name": "toy-share.learn", "config": "toy-share", "traffic": "learn", "chips": 1,
                           "why": "test"})
    for e in m["end_to_end"] + m["per_layer"]:
        # what the new cell's architecture has: no LSTM kernel, no second chip
        if "workloads" in e and e.get("layer") not in ("kernels", "collectives"):
            e["workloads"] = e["workloads"] + ["toy-share.learn"]
    m["per_layer"].append({"name": OWN_SCOPE["name"], "unit": "ms", "better": "lower", "source": "device_trace",
                           "layer": "model", "moves": "learn_steps_per_s", "workloads": ["toy-share.learn"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(m, fh)
    pats = harness.load_json(os.path.join(bench, "trace_patterns.json"))
    pats.update(device_plane="^/host:CPU$", op_lines=["^tf_XLA"], module_lines=["^no such line$"])
    with open(os.path.join(bench, "trace_patterns_cpu.json"), "w") as fh:
        json.dump(pats, fh)
    yield root, m
    after = _hashes(bench)
    assert {p: h for p, h in after.items() if p in before} == before  # byte for byte


def test_a_fourth_configuration_passes_every_manifest_check_and_runs_as_files_and_entries(
        fourth_root, monkeypatch, tmp_path):
    """The tripwire: every rule that tests/benchmark/test_bench_manifest.py
    asks of the repo's manifest is asked of the copy, the reference loader's
    rule too; then the new cell runs traced to `correct: true` and its own
    layer file (the `op_name` form) is read. A rule that pins the cells or the
    configurations that are there today fails HERE, not in the PR that adds one."""
    root, m = fourth_root
    assert manifest.check_all(root, m) > 100
    files = manifest.reference_files(root, m)
    bench = os.path.realpath(os.path.join(root, "benchmark", "reference"))
    assert files.pop("toy-share.learn") == os.path.join(bench, "toy.py")
    assert set(files.values()) == {os.path.join(bench, "model.py")}
    real = tr.load_patterns
    monkeypatch.setattr(tr, "load_patterns",
                        lambda path=None: real(os.path.join(root, "benchmark", "trace_patterns_cpu.json")))
    peaks = tmp_path / "peaks.json"
    peaks.write_text(json.dumps({"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}))
    monkeypatch.setattr(flops, "_PEAKS_PATH", str(peaks))
    gc.collect()  # what earlier tests of this process still hold is not this run's
    base = harness.device_bytes_in_use() / 1e9
    r = _run(root, "toy-share.learn", seconds=0.2, trace=True)
    assert r["correct"] is True and r["failed"] == 0 and r["notes"]["checks"]["reference"]["ok"]
    assert r["notes"]["check_resident_gb"] - base < 0.02 * (r["notes"]["window_resident_gb"] - base)
    got = r["metrics"]
    own, core = got[OWN_SCOPE["name"]]["value"], got["model.core_ms_per_update"]["value"]
    assert 0.0 < own <= core
    # the line carries what the manifest lists for the cell, less the readers with nothing to read on one CPU
    listed = {e["name"] for e in m["per_layer"] if manifest.applies(e, "toy-share.learn")}
    assert set(got) <= listed and {"cli.compile_misses", "model.mfu", "device.unscoped_share"} <= set(got)
    assert not {"kernels.lstm_ms_per_update", "kernels.lstm_roofline", "collectives.exposed_ms_per_update"} & listed


@pytest.mark.parametrize("break_it,why", [
    (lambda conf, entry: conf.pop("expect"), "has no 'expect'"),
    (lambda conf, entry: conf["expect"].update(seq_len=11), "'expect' says seq_len = 11"),
    (lambda conf, entry: conf.pop("deployment_share"), "needs 'deployment_share'"),
    (lambda conf, entry: (conf["reduced"].append("hidden_dim"), entry["reduced"].append("hidden_dim"),
                          conf["reduced_why"].update(hidden_dim="test")), "names a width"),
    (lambda conf, entry: conf.update(reference="toy2"), "no file"),
])
def test_the_copys_checks_have_teeth(fourth_root, tmp_path, break_it, why):
    """The same copy with one thing wrong in the new configuration's file."""
    root, m = fourth_root
    broken = str(tmp_path / "root")
    shutil.copytree(root, broken, ignore=shutil.ignore_patterns("__pycache__", ".benchmark_work"))
    m = json.loads(json.dumps(m))
    conf = json.loads(json.dumps(FOURTH))
    break_it(conf, m["configs"][-1])
    with open(os.path.join(broken, "benchmark", "configs", "toy-share.json"), "w") as fh:
        json.dump(conf, fh)
    with open(os.path.join(broken, "BENCHMARK.json"), "w") as fh:
        json.dump(m, fh)
    with pytest.raises((manifest.ManifestError, harness.BenchmarkError), match=why):
        manifest.check_all(broken, m)


def test_the_check_is_taken_where_the_window_starts(toy_root, toy_result):
    """Two runs of one seed with windows of different lengths are judged after
    the same number of updates and on the same batch: the operating point does
    not move with how far the window gets."""
    longer = _run(toy_root, "toy.learn", seconds=1.2)
    a, b = toy_result["notes"]["checks"], longer["notes"]["checks"]
    assert longer["attempted"] > toy_result["attempted"]
    assert a["reference"]["updates_at_check"] == b["reference"]["updates_at_check"] > 0
    assert a["reference"]["loss_ref"] == b["reference"]["loss_ref"]
    assert a["reference"]["grad_norm_ref"] == b["reference"]["grad_norm_ref"]
    assert a["reference_end"]["updates_at_check"] < b["reference_end"]["updates_at_check"]
    assert a["reference_end"]["loss_ref"] != b["reference_end"]["loss_ref"]
