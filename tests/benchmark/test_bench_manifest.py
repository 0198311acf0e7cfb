"""BENCHMARK.json against its contract, and every cell's files found by name."""

import json
import os
import re

import pytest

from benchmark import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _fh:
    M = json.load(_fh)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in M["workloads"]]
METRICS = M["end_to_end"] + M["per_layer"]


def _applies(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def test_top_level_keys_sizes_and_command():
    assert set(M) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(M["paths"]) <= 16 and 1 <= len(M["command"]) <= 32
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51
    for word in M["command"]:
        assert not word.startswith("/") and ".." not in word
        if "/" in word:  # a file of the repo: must lie under `paths`
            assert any(word.startswith(p + "/") for p in M["paths"])
    # the full check with 24 cells fits the driver's budget
    runs = 2 + 14 * 24
    assert runs * (M["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("entry", METRICS + M["workloads"] + M["configs"], ids=lambda e: e["name"])
def test_names_units_and_keys(entry):
    assert NAME.match(entry["name"])
    if "unit" in entry:
        assert UNIT.match(entry["unit"]) and entry["better"] in ("lower", "higher")
        assert entry["source"] in SOURCES
        allowed = {"name", "unit", "better", "source", "workloads"}
        allowed |= {"bound"} if "bound" in entry else {"layer", "moves"}
        assert set(entry) <= allowed
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] and "\t" not in entry[key]
    for key in ("config", "traffic"):
        if key in entry:
            assert NAME.match(entry[key])


def test_names_are_unique_and_cells_bounded():
    for group in (METRICS, M["workloads"], M["configs"]):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    assert 2 <= len(M["workloads"]) <= 24 and 1 <= len(M["configs"]) <= 24
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = [w for w in M["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in M["workloads"])
    assert len(four) <= max(1, len(M["workloads"]) // 4)
    assert {c["name"] for c in M["configs"]} == {w["config"] for w in M["workloads"]}


def test_end_to_end_bounds():
    e2e = {m["name"]: m for m in M["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in M["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_its_files_and_metrics(cell):
    c = harness.load_cell(ROOT, cell)
    assert c.traffic["driver"] in ("train_fused", "serve_open_loop")
    assert os.path.exists(os.path.join(c.bench_dir, "drivers", c.traffic["driver"] + ".py"))
    entry = c.config_entry
    assert any(entry["file"].startswith(p + "/") for p in M["paths"])
    assert c.config["name"] == entry["name"] and sorted(c.config["reduced"]) == sorted(entry["reduced"])
    assert len(entry["reduced"]) <= 16
    # a width is never reduced
    for key in entry["reduced"]:
        assert NAME.match(key)
        assert not re.search(r"hidden|intermediate|latent|state|_dim$|_rank$|head|expan|experts_per", key)
    e2e = [m["name"] for m in M["end_to_end"] if _applies(m, cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = [m for m in M["per_layer"] if _applies(m, cell)]
    assert layer and all(m["moves"] in e2e for m in layer)


@pytest.mark.parametrize("metric", M["per_layer"], ids=lambda m: m["name"])
def test_layer_metric_has_a_reader_file(metric):
    cell = harness.load_cell(ROOT, CELLS[0])
    spec = harness.layer_spec(cell, metric["name"])
    assert spec["name"] == metric["name"] and spec["layer"] == metric["layer"]
    assert spec["unit"] == metric["unit"] and spec["moves"] == metric["moves"]
    assert os.path.exists(os.path.join(cell.bench_dir, "readers", spec["reader"] + ".py"))
    for w in metric.get("workloads", []):
        assert w in CELLS
    if "category" in spec:
        from benchmark import trace

        assert spec["category"] in trace.load_patterns()["categories"]


def test_files_under_paths_are_named_from_name_characters():
    for p in M["paths"]:
        for d, _, files in os.walk(os.path.join(ROOT, p)):
            if "__pycache__" in d:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


# hand-computed from each configuration's source: (hidden, T, batch, block slots)
EXPECTED = {
    "nature-lstm512": (512, 40 + 40 + 5, 64, 512000 // 400),
    "lru-seq581": (512, 64 + 512 + 5, 32, 524288 // 1024),
    "nature-lstm512-dp4": (512, 40 + 40 + 5, 64, 4 * 1280),
}


@pytest.mark.parametrize("config", M["configs"], ids=lambda c: c["name"])
def test_config_builds_and_validates(config):
    """The file's preset + overrides is a valid R2D2Config with the published widths."""
    with open(os.path.join(ROOT, config["file"])) as fh:
        conf = json.load(fh)
    cfg = harness.build_config(conf, seed=5, extra={"samples_per_insert": 8.0})
    hidden, seq_len, batch, slots = EXPECTED[config["name"]]
    assert cfg.seed == 5 and cfg.hidden_dim == hidden and cfg.seq_len == seq_len
    assert tuple(cfg.obs_shape) == (84, 84, 1) and cfg.batch_size == batch
    assert cfg.num_blocks == slots and cfg.encoder == "nature"
    # the fused collector's rule: an episode fits one chunk, and fills the block
    assert cfg.max_episode_steps == cfg.block_length
    assert conf["source"] == config["source"] and len(conf["source"]) <= 200
    # every changed key says why; a width that follows from a swap is at least stated
    assert set(conf["reduced"]) == set(conf["reduced_why"]) and "action_dim" in conf["assumed"]


def _data_files(sub):
    d = os.path.join(ROOT, M["paths"][0], sub)
    return sorted(f[:-5] for f in os.listdir(d) if f.endswith(".json"))


# end-to-end metrics that data files kept for a later cell may name (PERF.md section 7)
PLANNED_E2E = {"serve_p99_ms"}


@pytest.mark.parametrize("name", _data_files("layers"))
def test_every_layer_file_names_a_reader_and_a_metric_to_move(name):
    """Also the files of cells that are not in the manifest today: a later PR
    adds them back as entries only, so the files must already be sound."""
    spec = harness.load_json(os.path.join(ROOT, M["paths"][0], "layers", name + ".json"))
    assert spec["name"] == name and NAME.match(name) and UNIT.match(spec["unit"])
    assert os.path.exists(os.path.join(ROOT, M["paths"][0], "readers", spec["reader"] + ".py"))
    assert spec["moves"] in {m["name"] for m in M["end_to_end"]} | PLANNED_E2E
    listed = {m["name"] for m in M["per_layer"]}
    assert (name in listed) == (spec["moves"] not in PLANNED_E2E)


@pytest.mark.parametrize("name", _data_files("traffic"))
def test_every_traffic_file_names_a_driver(name):
    t = harness.load_json(os.path.join(ROOT, M["paths"][0], "traffic", name + ".json"))
    assert NAME.match(name)
    assert os.path.exists(os.path.join(ROOT, M["paths"][0], "drivers", t["driver"] + ".py"))
    if t["driver"] == "serve_open_loop":
        # no reserved pool the traffic never fills: the cache holds the resident sessions
        assert t["cache_capacity"] == t["sessions"] and t["rate_per_s"] > 0


def test_last_line_holds_only_finite_numbers():
    from benchmark import harness

    notes = {"a": float("nan"), "b": [1.0, float("inf")], "c": {"d": 2, "e": "x", "f": -float("inf")}}
    clean = harness.finite(notes)
    assert clean == {"a": None, "b": [1.0, None], "c": {"d": 2, "e": "x", "f": None}}
    json.dumps(clean, allow_nan=False)


# The instructions that carry each cell's trace-read metrics, as the chip's
# compiler names them for that cell's step programs (trace events of PR 22's
# chip calls; dp4's after the SPMD partitioner, from a compile for a described
# v5e:2x2). Kept small: one instruction per category.
_CONV = "%fusion.536 = bf16[5440,20,20,32]{0,3,2,1:T(8,128)(2,1)} fusion(bf16[5440,84,84,1]{0,2,3,1} %f.4, f32[8,8,1,32]{3,2,1,0} %p.1), kind=kOutput"
_LSTM = "%_lstm_seq_bwd_call.7 = f32[85,16,2048]{2,1,0:T(8,128)S(1)} custom-call(f32[85,16,512]{2,1,0} %s.2), custom_call_target=\"tpu_custom_call\""
_ALLREDUCE = "%all-reduce.4 = (f32[512]{0:T(512)S(1)}, f32[512,512]{1,0}) all-reduce(f32[512]{0} %copy-done.108, f32[512,512]{1,0} %custom-call.85), channel_id=1"
_COPY = "%copy.{n} = u8[1280,441,84,84,1]{{3,2,1,0,4:T(8,128)(4,1)}} copy(u8[1280,441,84,84,1]{{0,1,4,3,2:T(8,128)(4,1)}} %{operand})"
_CELL_NAMES = {
    1: ([_CONV, _LSTM, _COPY.format(n=187, operand="stores__obs__.1")], "jit_mega(123)"),
    4: ([_CONV, _LSTM, _ALLREDUCE, _COPY.format(n=222, operand="param.186")], "jit_body(4122400780077301311)"),
}


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_line_carries_every_per_layer_metric_of_the_cell(cell, monkeypatch, tmp_path):
    """The driver wants each per-layer metric listed for a cell in its traced
    line (the first version was refused over dp4's, which had lost one to a
    pattern that the partitioned program's names did not match)."""
    import jax
    import jax.numpy as jnp

    from benchmark import flops
    from benchmark import trace as tr
    from r2d2_tpu.utils import profiling

    peaks = tmp_path / "peaks.json"
    peaks.write_text(json.dumps({"cpu": {"bf16_flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11}}))
    monkeypatch.setattr(flops, "_PEAKS_PATH", str(peaks))
    # one registered step program, as a run would have by now (it re-lays nothing out)
    step = profiling._Program("mega", jax.jit(lambda x: x + 1))
    step.signature = (jax.ShapeDtypeStruct((4,), jnp.float32),)
    monkeypatch.setattr(profiling, "_programs", {"mega": step})
    c = harness.load_cell(ROOT, cell)
    chips = c.workload["chips"]
    names, module = _CELL_NAMES[chips]
    ops = {f"/device:TPU:{d}": tr.with_self_times(tr.Event(n, 100.0 * i, 90.0, n) for i, n in enumerate(names * 2))
           for d in range(chips)}
    modules = {d: [tr.Event(module, 0.0, 390.0, module), tr.Event(module, 400.0, 390.0, module)] for d in ops}
    ctx = harness.Context(cell=c, seed=0, seconds=1.0, trace=True, t_start=0.0, require_tpu=False)
    ctx.cfg = harness.build_config(c.config, 0)
    ctx.patterns, ctx.trace_data = tr.load_patterns(), tr.Trace(ops, modules, [])
    ctx.counters.update({"updates": 32, "updates_per_s": 80.0, "replay.valid_step_share": 100.0,
                         "memory_peak_bytes": 5.86e9, "cli.compile_misses": 150})
    got = harness.read_layer_metrics(ctx)
    want = {m["name"] for m in M["per_layer"] if _applies(m, cell)}
    assert set(got) == want
    if "kernels.lstm_ms_per_update" in want:  # a pattern-sourced metric finds its two events
        assert got["kernels.lstm_ms_per_update"]["value"] == pytest.approx(2 * 90e-9 * 1e3 / 32)
    assert all(v["value"] >= 0.0 for v in got.values())
