"""The three readers of the program's own tracing (host_span, trace_scope,
program_counter): on a hand-made trace with a hand-written scope map (the
arithmetic, through the real xplane reader), against a program without the
facility (a parent commit: nothing to read, nothing raised), and end to end on
a temporary copy of the benchmark at tiny size on the CPU, the serve cell's
three kept layer files included."""

import json
import os
import shutil

import numpy as np
import pytest

from benchmark import flops, harness
from benchmark import trace as tr
from benchmark.readers import host_span, program_counter, trace_scope

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "program_spans.xplane.txt")
NS = 1e-9
with open(os.path.join(ROOT, "BENCHMARK.json")) as _fh:
    M = json.load(_fh)
NEW = ["dispatch.host_busy_ms_per_dispatch", "dispatch.readback_wait_ms_per_dispatch",
       "dispatch.launch_ms_per_dispatch", "replay.sample_ms_per_dispatch",
       "replay.writeback_ms_per_dispatch", "replay.priority_applied_share",
       "device.idle_in_program_spans_share", "dispatch.collect_ms_per_update",
       "replay.gather_ms_per_update", "replay.slab_write_ms_per_update",
       "model.encoder_ms_per_update", "model.core_ms_per_update", "model.heads_loss_ms_per_update",
       "model.optimizer_ms_per_update", "device.unscoped_share", "cli.init_s", "cli.ring_fill_s",
       "cli.step_program_load_s", "cli.compile_s"]
SERVE_NEW = ["serve.queue_wait_ms_per_batch", "serve.stage_ms_per_batch", "serve.complete_ms_per_batch"]
BUCKETS = ["collect", "slab_write", "gather", "optimizer", "heads_loss", "encoder", "core", "unscoped"]

_UPD = "jit(mega)/jit(r2d2_update)/while/body/closed_call/"
MEGA = {  # instruction -> op_name, as compiled.as_text() gives them (hand-written)
    "while.1": "jit(mega)/jit(r2d2_update)/while",
    "fusion.1": _UPD + "jvp(R2D2Network)/R2D2Network.unroll/R2D2Network._core_input/enc/Conv_0/conv_general_dilated",
    "fusion.2": _UPD + "transpose(jvp(R2D2Network))/R2D2Network.unroll/core/jit(_lstm_seq_bwd_call)/_lstm_seq_bwd_call",
    "fusion.3": _UPD + "jvp(jit(r2d2_loss))/sub",
    "fusion.4": _UPD + "jit(r2d2_optimizer)/mul",
    "fusion.5": _UPD + "jit(r2d2_gather)/gather",
    # collection's own encoder: collect comes first in the ordered buckets
    "fusion.6": "jit(mega)/jit(r2d2_collect)/while/body/closed_call/R2D2Network.act_select/R2D2Network.act/"
                "R2D2Network._core_input/enc/Conv_0/conv_general_dilated",
    "dynamic-update-slice.7": "jit(mega)/jit(r2d2_slab_write)/dynamic_update_slice",
    "fusion.9": "jit(mega)/jit(r2d2_collect)/add",  # runs outside every execution in the fixture
}
MULTI = {  # the update-only program numbers its instructions anew: fusion.1 is its gather
    "fusion.1": "jit(multi)/jit(r2d2_update)/while/body/closed_call/jit(r2d2_gather)/gather",
    "fusion.77": "jit(multi)/jit(r2d2_update)/while/body/closed_call/jit(r2d2_optimizer)/mul",
}


@pytest.fixture(scope="module")
def trace_dir(tmp_path_factory):
    from jax.profiler import ProfileData

    with open(FIXTURE) as fh:
        blob = ProfileData.text_proto_to_serialized_xspace(fh.read())
    root = tmp_path_factory.mktemp("spans")
    d = root / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(blob)
    return str(root)


@pytest.fixture(scope="module")
def fixture_trace(trace_dir):
    return tr.load(tr.find_xplane(trace_dir), tr.load_patterns())


@pytest.fixture(scope="module")
def spans(trace_dir):
    return host_span.load_spans(tr.find_xplane(trace_dir), tr.load_patterns()["host_plane"])


@pytest.fixture(scope="module")
def scopes():
    return trace_scope.load_scopes(os.path.join(ROOT, "benchmark"))


# ---------------------------------------------------------------- trace_scope


def test_buckets_and_unscoped_add_up_to_the_devices_busy_time(fixture_trace, scopes):
    got = trace_scope.attribute(fixture_trace, {"mega": MEGA, "multi": MULTI}, scopes)
    assert list(got["seconds"]) == BUCKETS
    busy_s = tr.busy_seconds(fixture_trace)[0]
    assert busy_s == pytest.approx(135 * NS)
    assert got["busy"] == pytest.approx(busy_s) and sum(got["seconds"].values()) == pytest.approx(busy_s)


@pytest.mark.parametrize("bucket,ns", [
    ("encoder", 30),      # fusion.1 inside jit_mega only: in jit_multi the same name is the gather
    ("core", 20), ("heads_loss", 10),
    ("optimizer", 10 + 5),  # fusion.4 in mega, fusion.77 in multi
    ("gather", 5 + 20),   # fusion.5 in mega, fusion.1 in multi: the execution decides the program
    ("collect", 10),      # collection's encoder is collection's, not the model's
    ("slab_write", 5),
    ("unscoped", 5 + 5 + 10),  # the while's SELF time, the copy without op_name, the event after both programs
])
def test_each_bucket_gets_self_time_once(fixture_trace, scopes, bucket, ns):
    got = trace_scope.attribute(fixture_trace, {"mega": MEGA, "multi": MULTI}, scopes)
    assert got["seconds"][bucket] == pytest.approx(ns * NS)


def test_a_container_keeps_only_its_self_time_and_the_largest_unscoped_are_named(fixture_trace, scopes):
    got = trace_scope.attribute(fixture_trace, {"mega": MEGA, "multi": MULTI}, scopes)
    top = {label: (op, s) for label, op, s in got["top"]["unscoped"]}
    assert top["while.1 s32[]"] == ("jit(mega)/jit(r2d2_update)/while", pytest.approx(5 * NS))  # 80 - 75
    assert top["copy.8 u8[1280,441,84,84,1]"] == ("", pytest.approx(5 * NS))
    # an event outside every execution on the module line is unscoped, whatever a map says of its name
    assert top["fusion.9 f32[8]"] == ("", pytest.approx(10 * NS))


def test_without_a_registered_program_everything_is_unscoped(fixture_trace, scopes):
    got = trace_scope.attribute(fixture_trace, {}, scopes)
    assert got["seconds"]["unscoped"] == pytest.approx(135 * NS)
    assert sum(v for k, v in got["seconds"].items() if k != "unscoped") == 0.0


def test_cpu_event_names_are_accepted_too(scopes):
    """A CPU trace has no module line and names events `dot_general.1`, not
    `%dot_general.1 = ...`: both forms reach the same instruction."""
    ops = {"/host:CPU": tr.with_self_times([
        tr.Event("dot_general.1", 0.0, 40.0, "dot_general.1"),
        tr.Event("%dot_general.2 = f32[8]{0} dot(f32[8]{0} %p)", 50.0, 10.0, "x")])}
    maps = {"mega": {"dot_general.1": "jit(mega)/jit(r2d2_collect)/dot_general",
                     "dot_general.2": "jit(mega)/jit(r2d2_update)/jit(r2d2_gather)/dot_general"}}
    got = trace_scope.attribute(tr.Trace(ops, {}, []), maps, scopes)
    assert got["seconds"]["collect"] == pytest.approx(40 * NS)
    assert got["seconds"]["gather"] == pytest.approx(10 * NS)


# A layer file's own scope (`op_name` within a bucket): what a new kind of layer
# inside the core reads its time from, with trace_scopes.json left as it is
_OWN = {"name": "model.kernel_ms_per_update", "op_name": "_lstm_seq_bwd_call", "within": "core",
        "per": "updates", "scale": 1000.0}


@pytest.fixture()
def scoped_ctx(fixture_trace, monkeypatch):
    trace_scope._done.clear()
    monkeypatch.setattr(trace_scope, "program_maps", lambda: {"mega": MEGA, "multi": MULTI})
    yield _ctx(trace=fixture_trace)
    trace_scope._done.clear()


@pytest.mark.parametrize("within,op_name,ns", [
    ("core", ".", 20),                       # every member has an op_name: `.` is the bucket
    ("core", "_lstm_seq_bwd_call", 20),
    ("encoder", r"enc/Conv_0", 30),
    ("gather", r"jit\(multi\)", 20),          # the update-only program's gather alone, of 25
    ("collect", r"R2D2Network\._core_input", 10),  # collection's encoder stays collection's
    ("optimizer", "jit\\(r2d2_optimizer\\)", 15),
])
def test_a_layer_files_own_scope_sums_the_members_its_regex_finds(scoped_ctx, within, op_name, ns):
    spec = dict(_OWN, within=within, op_name=op_name)
    assert trace_scope.read(spec, scoped_ctx) == pytest.approx(ns * NS * 1000.0 / 32)
    bucket = trace_scope.read({"bucket": within, "per": "updates", "scale": 1000.0}, scoped_ctx)
    assert trace_scope.read(dict(spec, op_name="."), scoped_ctx) == pytest.approx(bucket)


def test_an_own_scope_that_finds_nothing_reads_zero_and_says_so(scoped_ctx, capsys):
    assert trace_scope.read(dict(_OWN, op_name="attention"), scoped_ctx) == 0.0
    out = capsys.readouterr().out
    assert "model.kernel_ms_per_update" in out and "finds nothing in bucket 'core'" in out
    with pytest.raises(KeyError, match="no bucket 'kernel'"):
        trace_scope.read(dict(_OWN, within="kernel"), scoped_ctx)


def test_an_own_scope_has_nothing_to_read_without_the_facility_or_a_trace(fixture_trace, monkeypatch):
    from r2d2_tpu.utils import profiling

    ctx = _ctx(trace=None)
    assert trace_scope.read(_OWN, ctx) is None
    for attr in ("registered_programs", "program_scopes"):
        monkeypatch.delattr(profiling, attr)
    trace_scope._done.clear()
    assert trace_scope.read(_OWN, _ctx(trace=fixture_trace)) is None


# ------------------------------------------------------------------ host_span


def test_the_xplane_reader_keeps_the_programs_spans_and_their_ids(spans):
    assert {s.name for s in spans} == {
        "r2d2.dispatch", "r2d2.replay.sample", "r2d2.dispatch.launch", "r2d2.dispatch.readback",
        "r2d2.replay.account", "r2d2.replay.priorities"}  # bench.step and PjitFunction stay out
    ids = [(int(s.stats["dispatch"]), int(s.stats["collect"])) for s in spans if s.name == "r2d2.dispatch"]
    assert ids == [(1, 1), (2, 0)]


def test_innermost_segments_are_disjoint_and_cover_the_outermost_span(spans):
    segs = host_span.innermost_segments(spans)
    assert all(a < b for a, b, _ in segs) and all(x[1] <= y[0] for x, y in zip(segs, segs[1:]))
    assert sum(b - a for a, b, _ in segs) == pytest.approx(130 + 80)
    by = {}
    for a, b, name in segs:
        by[name] = by.get(name, 0) + b - a
    assert by["r2d2.dispatch.readback"] == pytest.approx(140)
    assert by["r2d2.dispatch"] == pytest.approx(210 - (8 + 10 + 90 + 15) - (4 + 10 + 50 + 5))  # self time


def test_idle_time_goes_to_the_innermost_program_span_over_it(fixture_trace, spans):
    idle, covered, by_name = host_span.idle_by_innermost_span(fixture_trace, spans)
    assert idle == pytest.approx(75.0) and covered == pytest.approx(75.0)
    assert by_name == pytest.approx({
        "r2d2.replay.priorities": 15.0, "r2d2.dispatch": 16.0, "r2d2.replay.sample": 4.0,
        "r2d2.dispatch.launch": 10.0, "r2d2.dispatch.readback": 25.0, "r2d2.replay.account": 5.0})


def _ctx(cell="nature-lstm512.learn", trace=None, root=ROOT):
    c = harness.load_cell(root, cell)
    ctx = harness.Context(cell=c, seed=0, seconds=1.0, trace=True, t_start=0.0, require_tpu=False)
    ctx.patterns, ctx.trace_data = tr.load_patterns(), trace
    ctx.counters["updates"] = 32
    return ctx


@pytest.mark.parametrize("metric,want", [
    ("dispatch.host_busy_ms_per_dispatch", (210 - 140) / 2 * 1e-6),
    ("dispatch.readback_wait_ms_per_dispatch", 140 / 2 * 1e-6),
    ("dispatch.launch_ms_per_dispatch", 20 / 2 * 1e-6),
    ("replay.sample_ms_per_dispatch", 12 / 2 * 1e-6),
    ("replay.writeback_ms_per_dispatch", (15 + 5) / 2 * 1e-6),
    ("device.idle_in_program_spans_share", 100.0),
])
def test_span_metrics_are_means_over_the_dispatches_found_in_the_window(
        metric, want, fixture_trace, spans, monkeypatch):
    monkeypatch.setattr(host_span, "spans_of", lambda ctx: spans)
    ctx = _ctx(trace=fixture_trace)
    spec = harness.layer_spec(ctx.cell, metric)
    assert host_span.read(spec, ctx) == pytest.approx(want)


def test_a_span_id_can_be_summed_instead_of_the_duration(spans, fixture_trace, monkeypatch):
    monkeypatch.setattr(host_span, "spans_of", lambda ctx: spans)
    spec = {"name": "x", "span": r"^r2d2\.dispatch$", "stat": "dispatch", "per": r"^r2d2\.dispatch$", "scale": 1.0}
    assert host_span.read(spec, _ctx(trace=fixture_trace)) == pytest.approx((1 + 2) / 2)


def _priorities(start, offered, applied):
    return host_span.Span("r2d2.replay.priorities", start, 5.0, 0, {"offered": offered, "applied": applied})


@pytest.mark.parametrize("stamps,want", [
    # running totals from process start, warm-up included: the window's share is last less first
    ([(1000, 900), (1100, 1000), (1200, 1090)], 95.0),
    ([(1000, 900)], 0.0),                 # one span: nothing grew, said so
    ([(1000, 900), (1000, 900)], 0.0),    # nothing offered in the window
])
def test_a_running_total_is_read_as_the_windows_growth(stamps, want, fixture_trace, monkeypatch, capsys):
    made = [_priorities(10.0 * i, o, a) for i, (o, a) in enumerate(stamps)]
    monkeypatch.setattr(host_span, "spans_of", lambda ctx: made)
    ctx = _ctx(trace=fixture_trace)
    spec = harness.layer_spec(ctx.cell, "replay.priority_applied_share")
    assert host_span.read(spec, ctx) == pytest.approx(want)
    assert ("did not grow" in capsys.readouterr().out) == (want == 0.0)


def test_a_child_span_outside_its_parent_reads_negative_not_zero(fixture_trace, monkeypatch):
    made = [host_span.Span("r2d2.dispatch", 0.0, 10.0, 0, {}),
            host_span.Span("r2d2.dispatch.readback", 20.0, 30.0, 0, {})]
    monkeypatch.setattr(host_span, "spans_of", lambda ctx: made)
    ctx = _ctx(trace=fixture_trace)
    spec = harness.layer_spec(ctx.cell, "dispatch.host_busy_ms_per_dispatch")
    assert host_span.read(spec, ctx) == pytest.approx(-20.0 * 1e-6)


# ------------------------------------------------------------ program_counter


def test_program_counter_reads_and_scales(fixture_trace, monkeypatch):
    from r2d2_tpu.utils import profiling

    monkeypatch.setattr(profiling, "counters", lambda: {"r2d2.setup.init.total_ns": 2.5e9, "setup.first_call_s": 6.5})
    ctx = _ctx(trace=fixture_trace)
    assert program_counter.read(harness.layer_spec(ctx.cell, "cli.init_s"), ctx) == 2.5
    assert program_counter.read(harness.layer_spec(ctx.cell, "cli.step_program_load_s"), ctx) == 6.5
    assert program_counter.read(harness.layer_spec(ctx.cell, "cli.ring_fill_s"), ctx) == 0.0  # not counted: 0, said so


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_the_facility_gives_nothing_and_raises_nothing(metric, fixture_trace, monkeypatch):
    """The driver lays these files over the parent's checkout too: there
    utils/profiling has no SPANS, counters, registered_programs or
    program_scopes, and each new metric is left out of the line."""
    from r2d2_tpu.utils import profiling

    for attr in ("SPANS", "counters", "registered_programs", "program_scopes"):
        monkeypatch.delattr(profiling, attr)
    trace_scope._done.clear()
    ctx = _ctx(trace=fixture_trace)
    spec = harness.layer_spec(ctx.cell, metric)
    reader = {"host_span": host_span, "trace_scope": trace_scope, "program_counter": program_counter}[spec["reader"]]
    assert reader.read(spec, ctx) is None
    ctx.trace_data = None  # and without a trace, whatever the program has
    assert reader.read(spec, ctx) is None


@pytest.mark.parametrize("metric", [m["name"] for m in M["per_layer"] if m["name"] in NEW])
def test_every_new_metric_lists_the_three_learn_cells_and_moves_what_they_report(metric):
    """At least the three learn cells PR 23 read them in; whatever else a later
    PR lists is a cell of the manifest that the same driver runs."""
    entry = {m["name"]: m for m in M["per_layer"]}[metric]
    assert {"nature-lstm512.learn", "lru-seq581.learn", "nature-lstm512-dp4.learn"} <= set(entry["workloads"])
    cells = {w["name"]: w for w in M["workloads"]}
    for name in entry["workloads"]:
        assert name in cells, name
        assert harness.load_cell(ROOT, name).traffic["driver"] == "train_fused", name
    assert entry["moves"] in ("learn_steps_per_s", "setup_s")
    assert entry["source"] in ("program_span", "program_counter", "device_trace")


# ----------------------------------------------- end to end, tiny, on the CPU


@pytest.fixture(scope="module")
def tmp_root(tmp_path_factory):
    """A temporary copy of the benchmark with a tiny learn cell and the serve
    cell added as files and entries, as tests/benchmark/test_bench_drivers.py
    does; the serve cell lists the three serve files this PR keeps for it."""
    root = str(tmp_path_factory.mktemp("benchroot"))
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    m = json.loads(json.dumps(M))
    tiny = {"env_name": "drift", "action_dim": 3, "max_episode_steps": 16, "collector": "device",
            "replay_plane": "device", "updates_per_dispatch": 2, "num_actors": 2}
    with open(os.path.join(root, "benchmark", "configs", "tiny.json"), "w") as fh:
        json.dump({"name": "tiny", "source": "test", "preset": "tiny_test", "overrides": tiny, "reduced": []}, fh)
    m["configs"].append({"name": "tiny", "source": "test", "why": "test", "reduced": [],
                         "file": "benchmark/configs/tiny.json"})
    m["workloads"].append({"name": "tiny.learn", "config": "tiny", "traffic": "learn", "chips": 1, "why": "test"})
    with open(os.path.join(root, "benchmark", "traffic", "serve-tiny.json"), "w") as fh:
        json.dump({"driver": "serve_open_loop", "rate_per_s": 150.0, "sessions": 16, "cache_capacity": 64,
                   "buckets": [2, 4], "max_wait_ms": 2.0, "queue_depth": 64, "correct_sessions": 2,
                   "correct_steps": 6, "trace_seconds": 0.4}, fh)
    m["workloads"].append({"name": "tiny.serve-tiny", "config": "tiny", "traffic": "serve-tiny",
                           "chips": 1, "why": "test"})
    for e in m["end_to_end"] + m["per_layer"]:
        if "workloads" in e:
            e["workloads"] = e["workloads"] + ["tiny.learn"]
    m["end_to_end"].append({"name": "serve_p99_ms", "unit": "ms", "better": "lower", "bound": 0.1,
                            "source": "host_clock", "workloads": ["tiny.serve-tiny"]})
    for name in SERVE_NEW:
        spec = harness.load_json(os.path.join(root, "benchmark", "layers", name + ".json"))
        m["per_layer"].append({"name": name, "unit": spec["unit"], "better": "lower", "source": "program_span",
                               "layer": spec["layer"], "moves": spec["moves"], "workloads": ["tiny.serve-tiny"]})
    pats = harness.load_json(os.path.join(root, "benchmark", "trace_patterns.json"))
    pats.update(device_plane="^/host:CPU$", op_lines=["^tf_XLA"], module_lines=["^no such line$"])
    with open(os.path.join(root, "benchmark", "trace_patterns_cpu.json"), "w") as fh:
        json.dump(pats, fh)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(m, fh)
    return root


@pytest.fixture()
def cpu_trace(tmp_root, monkeypatch, tmp_path):
    real = tr.load_patterns
    monkeypatch.setattr(tr, "load_patterns",
                        lambda path=None: real(os.path.join(tmp_root, "benchmark", "trace_patterns_cpu.json")))
    peaks = tmp_path / "peaks.json"
    peaks.write_text(json.dumps({"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}))
    monkeypatch.setattr(flops, "_PEAKS_PATH", str(peaks))


def test_a_traced_learn_run_carries_every_new_metric(tmp_root, cpu_trace, capsys):
    r = harness.run_cell(tmp_root, "tiny.learn", seed=3, seconds=0.3, trace=True, require_tpu=False)
    got = r["metrics"]
    assert r["correct"] and set(NEW) <= set(got)
    assert all(np.isfinite(got[n]["value"]) and got[n]["value"] >= 0.0 for n in NEW)
    # the spans were found in the window, on the trace's own clock
    for n in ("dispatch.host_busy_ms_per_dispatch", "dispatch.launch_ms_per_dispatch",
              "replay.sample_ms_per_dispatch", "replay.writeback_ms_per_dispatch"):
        assert got[n]["value"] > 0.0, n
    busy = got["dispatch.host_busy_ms_per_dispatch"]["value"]
    assert got["dispatch.launch_ms_per_dispatch"]["value"] + got["replay.sample_ms_per_dispatch"]["value"] <= busy
    assert 0.0 < got["replay.priority_applied_share"]["value"] <= 100.0
    assert 0.0 <= got["device.idle_in_program_spans_share"]["value"] <= 100.0 + 1e-6
    # set-up: the trainer, the ring fill and the step programs' first calls were timed
    for n in ("cli.init_s", "cli.ring_fill_s", "cli.step_program_load_s", "cli.compile_s"):
        assert got[n]["value"] > 0.0, n
    # device time: named through the executables' own op_names (CPU event names, no module line)
    scoped = sum(got[n]["value"] for n in NEW if n.endswith("_ms_per_update"))
    assert scoped > 0.0 and got["model.encoder_ms_per_update"]["value"] > 0.0
    assert got["dispatch.collect_ms_per_update"]["value"] > 0.0
    assert 0.0 <= got["device.unscoped_share"]["value"] < 100.0
    out = capsys.readouterr().out
    assert "device time by scope (2 step programs)" in out and "by innermost span" in out


def test_the_serve_cell_reads_its_three_kept_span_files(tmp_root, cpu_trace):
    r = harness.run_cell(tmp_root, "tiny.serve-tiny", seed=3, seconds=0.4, trace=True, require_tpu=False)
    got = r["metrics"]
    # the learn cells' new metrics list their cells: none of them reaches the serve line
    assert r["correct"] and set(got) == set(SERVE_NEW)
    assert got["serve.stage_ms_per_batch"]["value"] > 0.0
    assert got["serve.complete_ms_per_batch"]["value"] > 0.0
    # queue wait of a batch's oldest request: at least part of the 2 ms batching wait, below the window
    assert 0.0 < got["serve.queue_wait_ms_per_batch"]["value"] < 400.0


def test_server_stats_sum_the_same_stamps(tmp_root):
    """PolicyServer.stats() carries the per-batch sums an operator reads
    without a trace: a window is the difference of two calls."""
    from benchmark.drivers import serve_open_loop as drv

    cell = harness.load_cell(tmp_root, "tiny.serve-tiny")
    cfg = harness.build_config(cell.config, 1, {"serve_pipeline": True})
    server, _ = drv.start_server(cfg, cell.traffic, 1)
    try:
        before = server.stats()
        drv.fill_sessions(server, cfg, 8, np.random.default_rng(0))
        after = server.stats()
    finally:
        server.stop()
    batches = after["completed_batches"] - before["completed_batches"]
    assert batches > 0
    for key in ("queue_wait_s_sum", "stage_s_sum", "device_wait_s_sum", "complete_s_sum"):
        assert after[key] > before[key] >= 0.0, key
    assert (after["stage_s_sum"] - before["stage_s_sum"]) / batches < 1.0
