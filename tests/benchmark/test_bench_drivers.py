"""The drivers end to end at tiny size on the CPU, through the same
`run_cell` the CLI calls — in a temporary COPY of the benchmark to which a
throw-away configuration, traffic mix and `workloads` entries are ADDED as
files and manifest entries only (no existing file is edited: that a later PR
can add a cell as data is the point). Also: the CLI's refusal of a CPU."""

import gc
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from benchmark import flops, harness
from benchmark.drivers import train_fused

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TINY = {"env_name": "drift", "action_dim": 3, "max_episode_steps": 16, "collector": "device",
        "replay_plane": "device", "updates_per_dispatch": 2, "num_actors": 2}
SERVE_LAYER_METRICS = ["device.idle_share_serve", "serve.batch_occupancy", "serve.device_ms_per_batch",
                       "loadgen.late_p99_ms"]


@pytest.fixture(scope="module")
def tmp_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("benchroot"))
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        m = json.load(fh)
    before = {os.path.join(d, f): os.path.getmtime(os.path.join(d, f))
              for d, _, fs in os.walk(os.path.join(root, "benchmark")) for f in fs}
    configs = {
        "tiny": TINY,
        "tiny-lru": dict(TINY, recurrent_core="lru"),
        "tiny-bf16": dict(TINY, compute_dtype="bfloat16"),
        "tiny-dp4": dict(TINY, replay_plane="sharded", dp_size=4, num_actors=4, buffer_capacity=2560),
    }
    for name, over in configs.items():
        with open(os.path.join(root, "benchmark", "configs", name + ".json"), "w") as fh:
            json.dump({"name": name, "source": "test", "preset": "tiny_test", "overrides": over,
                       "reduced": []}, fh)
        m["configs"].append({"name": name, "source": "test", "why": "test", "reduced": [],
                             "file": f"benchmark/configs/{name}.json"})
        m["workloads"].append({"name": name + ".learn", "config": name, "traffic": "learn",
                               "chips": 4 if name == "tiny-dp4" else 1, "why": "test"})
    with open(os.path.join(root, "benchmark", "traffic", "serve-tiny.json"), "w") as fh:
        json.dump({"driver": "serve_open_loop", "rate_per_s": 150.0, "sessions": 16,
                   "cache_capacity": 64, "buckets": [2, 4], "max_wait_ms": 2.0, "queue_depth": 64,
                   "correct_sessions": 2, "correct_steps": 6, "trace_seconds": 0.4}, fh)
    m["workloads"].append({"name": "tiny.serve-tiny", "config": "tiny", "traffic": "serve-tiny",
                           "chips": 1, "why": "test"})
    learn = [w["name"] for w in m["workloads"] if w["name"].startswith("tiny") and w["name"].endswith(".learn")]
    for e in m["end_to_end"] + m["per_layer"]:
        if "workloads" in e:
            e["workloads"] = e["workloads"] + learn
    # the serve cell comes back as entries only: its metric, the layer files that
    # are kept for it, and one new layer file (the device's idle share beside it)
    m["end_to_end"].append({"name": "serve_p99_ms", "unit": "ms", "better": "lower", "bound": 0.1,
                            "source": "host_clock", "workloads": ["tiny.serve-tiny"]})
    with open(os.path.join(root, "benchmark", "layers", "device.idle_share_serve.json"), "w") as fh:
        json.dump({"name": "device.idle_share_serve", "layer": "device", "unit": "%",
                   "moves": "serve_p99_ms", "reader": "trace_idle"}, fh)
    for name in SERVE_LAYER_METRICS:
        spec = harness.load_json(os.path.join(root, "benchmark", "layers", name + ".json"))
        m["per_layer"].append({"name": name, "unit": spec["unit"], "better": "lower", "source": "host_clock",
                               "layer": spec["layer"], "moves": spec["moves"], "workloads": ["tiny.serve-tiny"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(m, fh)
    yield root
    # nothing that existed was edited
    for path, mtime in before.items():
        assert os.path.getmtime(path) == mtime, path


def _check_line(r, metrics):
    assert set(r) >= {"correct", "attempted", "failed", "metrics", "device"}
    json.dumps(r)  # serialisable as it stands
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == set(metrics)
    for v in r["metrics"].values():
        assert set(v) == {"value", "unit"} and np.isfinite(v["value"]) and v["value"] > 0
    assert set(r["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}


@pytest.fixture(scope="module")
def learn_result(tmp_root):
    return harness.run_cell(tmp_root, "tiny.learn", seed=3, seconds=1.0, trace=False, require_tpu=False)


def test_learn_last_line_contract(learn_result):
    _check_line(learn_result, ["learn_steps_per_s", "setup_s"])
    n = learn_result["notes"]
    assert n["compiles_in_window"] == 0 and n["valid_step_share"] == 1.0
    # steps are counted 1:1: updates x batch x learning_steps / window
    got = learn_result["metrics"]["learn_steps_per_s"]["value"]
    assert got == pytest.approx(learn_result["attempted"] * 8 * 4 / n["window_s"])
    assert n["window_s"] <= 1.0 + n["period_s"]


def test_learn_line_says_where_a_low_window_lost_its_time(learn_result):
    """The notes that tell a pause of the machine from a slower program: whole
    periods, the median one, and the time beyond it."""
    n = learn_result["notes"]
    assert n["periods"] >= 1 and 0 < n["period_s_median"] <= n["period_s_max"]
    assert 0 <= n["stall_s"] < n["window_s"]
    assert n["steady_steps_per_s"] >= learn_result["metrics"]["learn_steps_per_s"]["value"]


@pytest.mark.parametrize("boundaries, elapsed, stall, rate", [
    ([2.0, 4.0, 6.0, 8.0], 8.1, 0.0, 800 / 8.1),            # no pause: the steady rate is the rate
    ([2.0, 4.0, 6.8, 8.8], 8.9, 0.8, 800 / 8.1),            # one period held a pause of 0.8 s
    ([2.0, 5.0, 7.0, 9.0, 11.5], 11.6, 1.5, 800 / 10.1),    # two did
    ([3.0], 3.1, 0.0, 800 / 3.1),                           # one period: nothing to hold it against
])
def test_stall_notes_give_the_time_beyond_the_median_period(boundaries, elapsed, stall, rate):
    n = train_fused._stall_notes(boundaries, elapsed, 800)
    assert n["periods"] == len(boundaries)
    assert n["stall_s"] == pytest.approx(stall) and n["steady_steps_per_s"] == pytest.approx(rate)
    assert train_fused._stall_notes([], 1.0, 800) == {}


def test_learn_matches_plain_reference(learn_result):
    ref = learn_result["notes"]["checks"]["reference"]
    assert ref["ok"] and ref["sequences"] == 8
    assert ref["q_err_over_scale"] < 1e-5 and ref["loss_rel"] < 1e-5 and ref["grad_norm_rel"] < 1e-5
    assert "skipped" in learn_result["notes"]["checks"]["kernels"]  # scan core on a CPU


def test_lru_core_matches_plain_reference(tmp_root):
    r = harness.run_cell(tmp_root, "tiny-lru.learn", seed=4, seconds=0.5, trace=False, require_tpu=False)
    _check_line(r, ["learn_steps_per_s", "setup_s"])
    ref = r["notes"]["checks"]["reference"]
    assert ref["q_err_over_scale"] < 1e-4 and ref["loss_rel"] < 1e-4 and ref["grad_norm_rel"] < 1e-4
    assert r["notes"]["runtime"]["core"] == "lru"


def test_bf16_program_sits_between_the_two_tolerance_classes(tmp_root):
    """A bf16 program against the f32 reference passes the bf16 limits and
    would fail the float32 ones: the limits tell the precisions apart."""
    from benchmark import correct

    r = harness.run_cell(tmp_root, "tiny-bf16.learn", seed=5, seconds=0.5, trace=False, require_tpu=False)
    ref = r["notes"]["checks"]["reference"]
    assert r["correct"] and ref["ok"]
    assert ref["q_err_over_scale"] > correct.TOL["float32"]["q"]
    assert ref["q_err_over_scale"] < correct.TOL["bfloat16"]["q"]
    # judged where the window starts (four warm-up dispatches of K = 2), and
    # well inside the limits there: the floors are not what lets it pass
    assert ref["updates_at_check"] == 8 < r["notes"]["checks"]["reference_end"]["updates_at_check"]
    assert ref["loss_abs_err"] < 0.5 * correct.TOL["bfloat16"]["loss"] * ref["loss_ref"]
    assert ref["grad_norm_abs_err"] < 0.5 * correct.TOL["bfloat16"]["grad_norm"] * ref["grad_norm_ref"]


def test_loss_math_in_bfloat16_fails_at_the_window_start_state(tmp_root, monkeypatch):
    """The control on the program's side: the loss island's target math (the
    two value rescalings) computed in bfloat16, where the configuration states
    float32. The reference check reads false at the same window-start losses
    that the sound bf16 program passes above; nothing else is at fault."""
    import jax.numpy as jnp

    import r2d2_tpu.learner as learner
    from benchmark import correct

    for name in ("value_rescale", "inverse_value_rescale"):
        real = getattr(learner, name)
        monkeypatch.setattr(learner, name, lambda x, eps, real=real: real(
            x.astype(jnp.bfloat16), eps).astype(jnp.float32))
    r = harness.run_cell(tmp_root, "tiny-bf16.learn", seed=5, seconds=0.5, trace=False, require_tpu=False)
    ref = r["notes"]["checks"]["reference"]
    assert r["correct"] is False and not ref["ok"] and r["failed"] == 0
    assert ref["loss_abs_err"] > 10 * ref["limits"]["loss_abs_err"]
    assert ref["q_err_over_scale"] < correct.TOL["bfloat16"]["q"]  # the network itself is untouched
    # the island alone, on the reference's own Q views, says the same with no
    # bf16 trunk in the way (on the chip this is the check that catches it)
    island = r["notes"]["checks"]["loss_island"]
    assert not island["ok"] and island["loss_rel"] > 100 * island["limits"]["loss_rel"]


def _host_state(obj, depth=0):
    """Everything the host keeps in `obj`: arrays byte for byte, counters,
    generators' states, the program's own objects within (the sum tree, the
    shards); device buffers by identity."""
    out = {}
    for k, v in sorted(vars(obj).items()):
        if isinstance(v, np.ndarray):
            out[k] = (v.dtype.str, v.shape, v.tobytes())
        elif isinstance(v, (bool, int, float, str, type(None))):
            out[k] = v
        elif isinstance(v, np.random.Generator):
            out[k] = repr(v.bit_generator.state)
        elif callable(getattr(v, "leaves", None)):  # the sum tree: every priority
            out[k] = np.asarray(v.leaves()).tobytes()
        elif isinstance(v, dict):
            out[k] = {kk: id(vv) for kk, vv in v.items()}
        elif isinstance(v, (list, tuple)) and v and all(hasattr(x, "__dict__") for x in v) and depth < 3:
            out[k] = [_host_state(x, depth + 1) for x in v]
        elif type(v).__module__.startswith("r2d2_tpu") and hasattr(v, "__dict__") and depth < 3:
            out[k] = _host_state(v, depth + 1)
    return out


@pytest.mark.parametrize("cell_name", ["tiny.learn", "tiny-dp4.learn"])
def test_the_start_state_capture_changes_nothing_in_the_replay_or_the_pacer(tmp_root, tmp_path, cell_name):
    """What train_fused does between the `setup_s` stamp and `t0` (a draw
    through the replay's own sampler with the benchmark's generator, a gather
    from the device stores, a readback of the parameters) leaves the replay's
    control plane (priorities, counters, pointers), the trainer's sampling
    stream and the runner's pacing byte for byte as they were, on both planes."""
    import jax

    from benchmark.drivers import train_fused as drv
    from r2d2_tpu.learner import make_store_gather
    from r2d2_tpu.train import Trainer

    cell = harness.load_cell(tmp_root, cell_name)
    cfg = harness.build_config(cell.config, 3, {
        "samples_per_insert": 8.0, "training_steps": 10**9, "save_interval": 10**9, "log_interval": 3600.0,
        "checkpoint_dir": str(tmp_path), "metrics_path": None})
    cfg = cfg.replace(learning_starts=cfg.buffer_capacity // 2)
    trainer = Trainer(cfg)
    trainer.warmup()
    runner = drv._make_runner(trainer, cfg)
    state = trainer.state
    for _ in range(3):
        state, _, _ = runner.step(state)
    gather = jax.jit(make_store_gather(cfg))
    snapshot = lambda: (_host_state(trainer.replay), _host_state(runner), repr(trainer.sample_rng.bit_generator.state))
    before = snapshot()
    planes = before[0].get("shards", [before[0]])
    assert all(len(p["tree"]) > 0 and "ptr_advances" in p and "learning_sum" in p for p in planes)
    assert "_consumed" in before[1] and "replay_rng" in before[1]
    at = drv._operating_point(cfg, trainer, gather, state, 8, 3)
    assert at["updates"] == 3 * cfg.updates_per_dispatch and np.asarray(at["batch"].obs).shape[0] == 8
    assert snapshot() == before
    # and the program goes on from there as if nothing had been asked
    state, _, _ = runner.step(state)
    runner.finish()


@pytest.mark.parametrize("cell_name", ["tiny.learn", "tiny-dp4.learn"])
def test_the_checks_run_with_the_programs_device_state_released(tmp_root, monkeypatch, cell_name):
    """After the window the driver lets go of trainer, runner, state and stores
    before it builds the first comparison: what the device still holds then is
    a small fraction of what it held after the window, on every local device.
    The comparisons are functions of host arrays, so they read the same numbers
    as with the trainer kept on the device, which is what the driver did until
    PR 31 (a window of one collect period: the same updates in both runs)."""
    import r2d2_tpu.train as train

    def run():
        return harness.run_cell(tmp_root, cell_name, seed=3, seconds=0.0, trace=False, require_tpu=False)

    gc.collect()  # what earlier tests of this process still hold is not this run's
    base = harness.device_bytes_in_use() / 1e9
    released = run()
    n = released["notes"]
    assert released["correct"] and n["window_resident_gb"] > base
    assert n["check_resident_gb"] - base < 0.02 * (n["window_resident_gb"] - base)
    assert n["check_peak_gb"] >= 0.0  # the CPU reports no peak; on the chip: the process's, after the last comparison
    kept = []
    real = train.Trainer.__init__

    def init_and_keep(self, *args, **kwargs):
        real(self, *args, **kwargs)
        kept.append(self)

    monkeypatch.setattr(train.Trainer, "__init__", init_and_keep)
    held = run()
    assert len(kept) == 1 and held["correct"]
    h = held["notes"]
    assert h["check_resident_gb"] - base > 0.5 * (h["window_resident_gb"] - base)  # the reading sees a holder
    assert held["attempted"] == released["attempted"]
    for name in ("reference", "reference_end", "loss_island"):
        assert held["notes"]["checks"][name] == released["notes"]["checks"][name], name


def test_dp4_driver_on_virtual_devices(tmp_root):
    r = harness.run_cell(tmp_root, "tiny-dp4.learn", seed=6, seconds=0.5, trace=False, require_tpu=False)
    _check_line(r, ["learn_steps_per_s", "setup_s"])
    assert r["device"]["count"] >= 4 and r["notes"]["checks"]["reference"]["ok"]


def test_more_chips_than_present_is_refused(tmp_root, monkeypatch):
    import jax

    monkeypatch.setattr(jax, "devices", lambda *a: jax.local_devices()[:1])
    with pytest.raises(harness.BenchmarkError, match="needs 4 chips"):
        harness.run_cell(tmp_root, "tiny-dp4.learn", 0, 0.2, False, require_tpu=False)


def test_a_cpu_is_refused_unless_a_test_says_otherwise(tmp_root):
    with pytest.raises(harness.BenchmarkError, match="no TPU"):
        harness.run_cell(tmp_root, "tiny.learn", 0, 0.2, False, require_tpu=True)
    with pytest.raises(harness.BenchmarkError, match="no workload"):
        harness.run_cell(tmp_root, "nope", 0, 0.2, False, require_tpu=False)


@pytest.fixture(scope="module")
def cpu_trace_patterns(tmp_root):
    """On a CPU the XLA ops sit on the host plane's client threads; the
    patterns file is data, so the temporary copy gets one that says so."""
    path = os.path.join(tmp_root, "benchmark", "trace_patterns_cpu.json")
    with open(os.path.join(tmp_root, "benchmark", "trace_patterns.json")) as fh:
        pats = json.load(fh)
    pats.update(device_plane="^/host:CPU$", op_lines=["^tf_XLA"], module_lines=["^no such line$"])
    with open(path, "w") as fh:
        json.dump(pats, fh)
    return path


def test_serve_last_line_contract_and_reference(tmp_root):
    r = harness.run_cell(tmp_root, "tiny.serve-tiny", seed=3, seconds=1.0, trace=False, require_tpu=False)
    _check_line(r, ["serve_p99_ms", "setup_s"])
    n = r["notes"]
    assert n["check"]["ok"] and n["check"]["steps"] == 6 and n["check"]["q_err_over_scale_all"] < 1e-5
    assert n["compiles_in_window"] == 0 and n["rejected"] == 0 and n["cache_hit_rate"] == 100.0
    assert n["late_p99_ms"] >= 0.0 and n["requests"] == r["attempted"]
    assert n["p99_ms"] >= n["p50_ms"] > 0 and r["metrics"]["serve_p99_ms"]["value"] == n["p99_ms"]


def test_traced_runs_yield_layer_metrics_and_breakdown(tmp_root, cpu_trace_patterns, monkeypatch, tmp_path):
    from benchmark import trace as tr

    real = tr.load_patterns
    monkeypatch.setattr(tr, "load_patterns", lambda path=None: real(cpu_trace_patterns))
    peaks = tmp_path / "peaks.json"
    peaks.write_text(json.dumps({"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}))
    monkeypatch.setattr(flops, "_PEAKS_PATH", str(peaks))
    r = harness.run_cell(tmp_root, "tiny.serve-tiny", seed=3, seconds=0.4, trace=True, require_tpu=False)
    # every per-layer metric listed for the serve cell is read, and no other
    assert r["correct"] and set(r["metrics"]) == set(SERVE_LAYER_METRICS)
    assert 0 < r["device"]["busy_s"] < r["device"]["window_s"]
    assert len(r["breakdown"]["device_ops"]) <= 10 and r["breakdown"]["idle_gaps"]
    # a learn cell: the readers that have nothing to read (one device, no
    # module line on a CPU, no kernel time to hold against a roofline) leave
    # their metric out; a pattern that matches no event of the trace (no Pallas
    # kernel here) reads 0, so that the line of a later PR that removes such an
    # operation still carries the metric
    r = harness.run_cell(tmp_root, "tiny.learn", seed=3, seconds=0.2, trace=True, require_tpu=False)
    assert r["correct"] and {"device.idle_share", "model.mfu", "replay.valid_step_share",
                             "cli.compile_misses"} <= set(r["metrics"])
    assert not {"kernels.lstm_roofline", "collectives.exposed_ms_per_update",
                "dispatch.gap_ms"} & set(r["metrics"])
    assert r["metrics"]["kernels.lstm_ms_per_update"]["value"] == 0.0
    assert r["metrics"]["replay.valid_step_share"]["value"] == 100.0


def test_cli_refuses_a_machine_without_a_tpu():
    """The command itself pins JAX to the TPU: here it must exit non-zero and
    print no result line, whatever JAX_PLATFORMS the caller exported."""
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
         "nature-lstm512.learn", "--seed", "0", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert not [l for l in p.stdout.splitlines() if l.startswith("{") and "metrics" in l]
    assert "no result" in p.stderr
