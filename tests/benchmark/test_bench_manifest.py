"""BENCHMARK.json against its contract, and every cell's files found by name.
The rules are functions of (root, manifest) in benchmark/manifest.py; here each
is asked of the repo's own manifest, entry by entry, and
tests/benchmark/test_bench_architecture.py asks all of them of a temporary copy
with a fourth configuration added. Also: both sides of the rules that a new
configuration meets first (`expect`, `_held` + `deployment_share`)."""

import copy
import json
import os

import pytest

from benchmark import harness, manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _fh:
    M = json.load(_fh)
CELLS = [w["name"] for w in M["workloads"]]
_applies = manifest.applies


def test_top_level_keys_sizes_and_command():
    manifest.check_top_level(ROOT, M)


@pytest.mark.parametrize("entry", manifest.metrics(M) + M["workloads"] + M["configs"], ids=lambda e: e["name"])
def test_names_units_and_keys(entry):
    manifest.check_entry(M, entry)


def test_names_are_unique_and_cells_bounded():
    manifest.check_unique_and_bounded(M)


def test_end_to_end_bounds():
    manifest.check_end_to_end_bounds(M)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_its_files_and_metrics(cell):
    manifest.check_cell(ROOT, M, cell)


@pytest.mark.parametrize("metric", M["per_layer"], ids=lambda m: m["name"])
def test_layer_metric_has_a_reader_file(metric):
    manifest.check_layer_metric(ROOT, M, metric)


def test_files_under_paths_are_named_from_name_characters():
    manifest.check_file_names(ROOT, M)


@pytest.mark.parametrize("config", M["configs"], ids=lambda c: c["name"])
def test_config_builds_and_validates(config):
    """The file's preset + overrides is a valid R2D2Config that reads what the
    file's own `expect` says (hand-computed from its source)."""
    manifest.check_config(ROOT, M, config)


@pytest.mark.parametrize("name", manifest.data_files(ROOT, M, "layers"))
def test_every_layer_file_names_a_reader_and_a_metric_to_move(name):
    manifest.check_layer_file(ROOT, M, name)


@pytest.mark.parametrize("name", manifest.data_files(ROOT, M, "traffic"))
def test_every_traffic_file_names_a_driver(name):
    manifest.check_traffic_file(ROOT, M, name)


def test_a_per_layer_metric_without_a_list_of_cells_is_refused():
    """Such a metric is owed by every cell that reports what it moves, those
    of later PRs too (`cli.compile_misses` moved `setup_s` with no list until
    PR 31: no PR could add a cell)."""
    for entry in M["per_layer"]:
        bare = {k: v for k, v in entry.items() if k != "workloads"}
        with pytest.raises(manifest.ManifestError, match="lists its cells"):
            manifest.check_entry(M, bare)


# -------------------------------- `expect`: each configuration's own file says what it builds


def _copy_with_config(tmp_path, change):
    """A root holding the repo's manifest and one configuration file, the
    first configuration's, changed by `change(conf)`."""
    m = copy.deepcopy(M)
    entry = m["configs"][0]
    conf = harness.load_json(os.path.join(ROOT, entry["file"]))
    change(conf)
    path = tmp_path / entry["file"]
    path.parent.mkdir(parents=True)
    path.write_text(json.dumps(conf))
    return str(tmp_path), m, entry


def test_a_configuration_without_expect_is_refused_by_name(tmp_path):
    root, m, entry = _copy_with_config(tmp_path, lambda conf: conf.pop("expect"))
    with pytest.raises(manifest.ManifestError, match="has no 'expect'"):
        manifest.check_config(root, m, entry)
    root, m, entry = _copy_with_config(tmp_path / "b", lambda conf: conf["expect"].pop("num_blocks"))
    with pytest.raises(manifest.ManifestError, match=r"'expect' lacks \['num_blocks'\]"):
        manifest.check_config(root, m, entry)


@pytest.mark.parametrize("key", [k for k in manifest.EXPECT_KEYS if k not in ("encoder", "obs_shape")])
def test_an_expect_that_is_wrong_by_one_is_caught(tmp_path, key):
    def off_by_one(conf):
        conf["expect"][key] += 1

    root, m, entry = _copy_with_config(tmp_path, off_by_one)
    with pytest.raises(manifest.ManifestError, match=f"'expect' says {key} = "):
        manifest.check_config(root, m, entry)


def test_an_expect_of_another_encoder_or_frame_is_caught(tmp_path):
    root, m, entry = _copy_with_config(tmp_path, lambda conf: conf["expect"].update(encoder="mlp"))
    with pytest.raises(manifest.ManifestError, match="'expect' says encoder = 'mlp'"):
        manifest.check_config(root, m, entry)
    root, m, entry = _copy_with_config(tmp_path / "b", lambda conf: conf["expect"].update(obs_shape=[84, 84, 4]))
    with pytest.raises(manifest.ManifestError, match="'expect' says obs_shape"):
        manifest.check_config(root, m, entry)


# ------------------- `reduced`: never a width; a count of what is held here ends in `_held`

_SHARE = {"chips_per_layer": 8, "num_kv_heads_held": {"published": 8, "held": 1},
          "num_experts_held": {"published": 256, "held": 32}}


@pytest.mark.parametrize("key", ["hidden_dim", "head_dim", "num_heads", "num_kv_heads", "experts_per_tok",
                                 "intermediate_size", "kv_lora_rank", "ssm_state_size", "expand", "q_latent"])
def test_a_width_is_never_reduced(key):
    with pytest.raises(manifest.ManifestError, match="names a width"):
        manifest.check_reduced({"name": "x", "reduced": ["buffer_capacity", key]}, {"deployment_share": _SHARE})


@pytest.mark.parametrize("key", ["head_dim_held", "kv_lora_rank_held", "experts_per_tok_held", "hidden_size_held",
                                 "head_dim", "hidden_size", "experts_per_tok"])
def test_a_size_is_not_let_through_by_its_ending_or_by_an_entry(key):
    with pytest.raises(manifest.ManifestError, match="names a width"):
        manifest.check_reduced({"name": "x", "reduced": [key]},
                               {"deployment_share": dict(_SHARE, **{key: {"published": 128, "held": 64}})})


def test_a_count_of_what_is_held_here_passes_beside_its_deployment():
    """Also depth (`num_hidden_layers` holds the word `hidden` and is no width)
    and a published key that a catalog file cannot rename, which passes by its
    entry under `deployment_share` alone."""
    entry = {"name": "x", "reduced": ["num_hidden_layers", "num_kv_heads_held", "num_experts_held",
                                      "num_key_value_heads"]}
    share = dict(_SHARE, num_key_value_heads={"published": 8, "held": 1})
    manifest.check_reduced(entry, {"deployment_share": share, "overrides": {"num_experts_held": 32}})


@pytest.mark.parametrize("conf,why", [
    ({}, "needs 'deployment_share'"),
    ({"deployment_share": {"num_kv_heads_held": {"published": 8, "held": 1}}}, "chips_per_layer"),
    ({"deployment_share": {"chips_per_layer": 8}}, "gives no"),
    ({"deployment_share": {"chips_per_layer": 8, "num_kv_heads_held": {"published": 8, "held": 8}}}, "gives no"),
    ({"deployment_share": {"chips_per_layer": 8, "num_kv_heads_held": {"published": 8, "held": 1}},
      "overrides": {"num_kv_heads_held": 2}}, "runs 2"),
])
def test_a_held_key_without_its_deployment_share_is_refused(conf, why):
    with pytest.raises(manifest.ManifestError, match=why):
        manifest.check_reduced({"name": "x", "reduced": ["num_kv_heads_held"]}, conf)


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


def _cells_of_the_default_reference():
    """The cells whose trace the instruction names above are taken from: those
    of the architecture that `reference/model.py` describes. A cell of another
    architecture brings instruction names, and such a test, of its own."""
    return [c for c in CELLS if harness.load_cell(ROOT, c).config.get("reference", "model") == "model"]


@pytest.mark.parametrize("cell", _cells_of_the_default_reference())
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
