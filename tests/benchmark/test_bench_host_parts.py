"""PR 42's additions to the benchmark, which are files and entries only: eight
layer files for the `host_span` reader (the parts of the sample and launch
spans, the interpreter's collections, and three sums of `cpu_us`). Each passes
every rule of the manifest; on a hand-made trace each reads what the fixture's
numbers say and the parts stay inside their parents; a program without the
facility reads nothing and a program without the PARTS (PR 41's) reads zero;
a traced CPU learn run in a temporary copy carries all eight."""

import json
import os
import shutil

import numpy as np
import pytest

from benchmark import flops, harness, manifest
from benchmark import trace as tr
from benchmark.readers import host_span

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
with open(os.path.join(ROOT, "BENCHMARK.json")) as _fh:
    M = json.load(_fh)
LEARN_CELLS = ["nature-lstm512.learn", "lru-seq581.learn", "nature-lstm512-dp4.learn"]
MS = 1e-6  # ns -> ms
# metric -> what host_parts.xplane.txt says of it, per dispatch (two dispatches)
WANT = {
    "replay.reserve_ms_per_dispatch": 6 / 2 * MS,            # on the collecting dispatch only
    "replay.draw_ms_per_dispatch": (28 + 16) / 2 * MS,
    "dispatch.upload_ms_per_dispatch": (20 + 10) / 2 * MS,
    "dispatch.call_ms_per_dispatch": (22 + 14) / 2 * MS,
    "dispatch.gc_ms_per_dispatch": (8 + 10) / 2 * MS,
    # cpu_us of the two dispatches less the two readbacks', us -> ms
    "dispatch.host_cpu_ms_per_dispatch": ((0.090 + 0.075) - (0.002 + 0.001)) / 2 * 1e-3,
    "replay.sample_cpu_ms_per_dispatch": (0.030 + 0.020) / 2 * 1e-3,
    "dispatch.launch_cpu_ms_per_dispatch": (0.030 + 0.020) / 2 * 1e-3,
}
EIGHT = list(WANT)


def _write_trace(tmp_path_factory, fixture):
    from jax.profiler import ProfileData

    with open(os.path.join(FIXTURES, fixture)) as fh:
        blob = ProfileData.text_proto_to_serialized_xspace(fh.read())
    d = tmp_path_factory.mktemp(fixture.split(".")[0]) / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(blob)
    path = tr.find_xplane(str(d.parent.parent.parent))
    return tr.load(path, tr.load_patterns()), host_span.load_spans(path, tr.load_patterns()["host_plane"])


@pytest.fixture(scope="module")
def parts(tmp_path_factory):
    return _write_trace(tmp_path_factory, "host_parts.xplane.txt")


@pytest.fixture(scope="module")
def without_parts(tmp_path_factory):
    """PR 23's fixture, untouched: dispatch, sample, launch, readback, account
    and priorities without children and without `cpu_us`, as PR 41's tree writes them."""
    return _write_trace(tmp_path_factory, "program_spans.xplane.txt")


def _ctx(trace, root=ROOT, cell="nature-lstm512.learn"):
    ctx = harness.Context(cell=harness.load_cell(root, cell), seed=0, seconds=1.0, trace=True, t_start=0.0,
                          require_tpu=False)
    ctx.patterns, ctx.trace_data = tr.load_patterns(), trace
    return ctx


def _read(metric, trace, spans, monkeypatch):
    monkeypatch.setattr(host_span, "spans_of", lambda ctx: spans)
    ctx = _ctx(trace)
    return host_span.read(harness.layer_spec(ctx.cell, metric), ctx)


# ------------------------------------------------------- files and entries


@pytest.mark.parametrize("metric", EIGHT)
def test_each_layer_file_and_its_entry_pass_every_rule_of_the_manifest(metric):
    entry = {m["name"]: m for m in M["per_layer"]}[metric]
    manifest.check_entry(M, entry)
    manifest.check_layer_metric(ROOT, M, entry)
    manifest.check_layer_file(ROOT, M, metric)
    assert entry["workloads"] == LEARN_CELLS and entry["moves"] == "learn_steps_per_s"
    assert (entry["unit"], entry["better"], entry["source"]) == ("ms", "lower", "program_span")
    spec = harness.load_json(os.path.join(ROOT, "benchmark", "layers", metric + ".json"))
    assert spec["reader"] == "host_span" and spec["layer"] == entry["layer"] == metric.split(".")[0]
    # a duration is read in ns, a `cpu_us` sum in us: both come out as ms
    assert spec["scale"] == (1e-3 if spec.get("stat") == "cpu_us" else 1e-6)


def test_the_eight_are_the_last_entries_and_nothing_before_them_moved():
    assert [m["name"] for m in M["per_layer"]][-8:] == EIGHT
    assert len(M["workloads"]) == 3 and len(M["configs"]) == 3 and len(M["end_to_end"]) == 2


# ----------------------------------------------------- the hand-made trace


def test_the_fixture_holds_the_new_spans_with_their_ids_inside_their_parents(parts):
    _, spans = parts
    by = {}
    for s in spans:
        by.setdefault(s.name, []).append(s)
    assert [len(by[n]) for n in ("r2d2.dispatch", "r2d2.replay.reserve", "r2d2.replay.draw", "r2d2.dispatch.upload",
                                 "r2d2.dispatch.call", "r2d2.host.gc")] == [2, 1, 2, 2, 2, 2]
    assert all("cpu_us" in s.stats for s in spans)
    assert [s.stats["program"] for s in by["r2d2.dispatch.call"]] == ["mega", "multi"]
    assert [int(s.stats["generation"]) for s in by["r2d2.host.gc"]] == [0, 2]
    inside = lambda child, parents: any(p.start <= child.start and child.end <= p.end for p in parents)
    for child, parent in (("r2d2.replay.reserve", "r2d2.replay.sample"), ("r2d2.replay.draw", "r2d2.replay.sample"),
                          ("r2d2.dispatch.upload", "r2d2.dispatch.launch"), ("r2d2.dispatch.call", "r2d2.dispatch.launch")):
        assert all(inside(c, by[parent]) for c in by[child]), child
    # the reserve sits in the collecting dispatch, where the program is `mega`
    collecting = [d for d in by["r2d2.dispatch"] if int(d.stats["collect"]) == 1]
    assert inside(by["r2d2.replay.reserve"][0], collecting) and inside(by["r2d2.dispatch.call"][0], collecting)


@pytest.mark.parametrize("metric", EIGHT)
def test_each_of_the_eight_reads_what_the_fixture_says(metric, parts, monkeypatch):
    assert _read(metric, *parts, monkeypatch) == pytest.approx(WANT[metric])


@pytest.mark.parametrize("children,parent,rest_ns", [
    # sample = its locks (self time) + reserve + draw; launch = upload + call + kick-off and install
    (("replay.reserve_ms_per_dispatch", "replay.draw_ms_per_dispatch"), "replay.sample_ms_per_dispatch", 10 / 2),
    (("dispatch.upload_ms_per_dispatch", "dispatch.call_ms_per_dispatch"), "dispatch.launch_ms_per_dispatch", 14 / 2),
])
def test_the_parts_stay_within_their_parent_and_the_rest_is_its_self_time(children, parent, rest_ns, parts, monkeypatch):
    whole = _read(parent, *parts, monkeypatch)
    some = sum(_read(c, *parts, monkeypatch) for c in children)
    assert some <= whole and whole - some == pytest.approx(rest_ns * MS)


def test_a_cpu_sum_with_minus_subtracts_the_readbacks_and_stays_under_the_wall_time(parts, monkeypatch):
    cpu = _read("dispatch.host_cpu_ms_per_dispatch", *parts, monkeypatch)
    wall = _read("dispatch.host_busy_ms_per_dispatch", *parts, monkeypatch)  # the same two spans by duration
    assert wall == pytest.approx(((200 + 150) - (80 + 70)) / 2 * MS)
    assert cpu == pytest.approx(0.162 / 2 * 1e-3) and cpu <= wall
    # without `minus` the readbacks' 3 ns of CPU would be in it
    spec = dict(harness.layer_spec(_ctx(parts[0]).cell, "dispatch.host_cpu_ms_per_dispatch"))
    spec.pop("minus")
    monkeypatch.setattr(host_span, "spans_of", lambda ctx: parts[1])
    assert host_span.read(spec, _ctx(parts[0])) == pytest.approx(0.165 / 2 * 1e-3)


def test_idle_time_under_a_new_span_is_named_after_it(parts, capsys, monkeypatch):
    """`device.idle_in_program_spans_share` (hence `breakdown.idle_gaps`) goes
    by the innermost span: a collection inside the draw takes its idle time
    from the draw, one between the children from bare `r2d2.dispatch`."""
    trace, spans = parts
    idle, covered, by_name = host_span.idle_by_innermost_span(trace, spans)
    assert idle == pytest.approx(199.0) and covered == pytest.approx(99.0)
    assert by_name == pytest.approx({
        "r2d2.replay.draw": 7.0 + 16.0, "r2d2.host.gc": 8.0 + 4.0, "r2d2.dispatch.call": 20.0 + 2.0,
        "r2d2.replay.priorities": 16.0, "r2d2.dispatch": 4.0 + 2.0 + 2.0, "r2d2.replay.sample": 2.0 + 2.0,
        "r2d2.dispatch.launch": 2.0 + 2.0, "r2d2.dispatch.upload": 10.0})
    share = _read("device.idle_in_program_spans_share", trace, spans, monkeypatch)
    assert share == pytest.approx(100.0 * 99 / 199)
    out = capsys.readouterr().out
    assert "r2d2.host.gc 0.000 ms" in out and "r2d2.replay.draw" in out and "r2d2.dispatch.call" in out


def test_the_table_script_reads_self_time_and_names_what_a_collection_fell_under(parts):
    """`runs/host_span_table.py` (PERF.md's host tables come from it): self
    time is a span less its children on the same thread, by wall and by CPU."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("host_span_table", os.path.join(ROOT, "runs", "host_span_table.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    t = mod.table(parts[1])
    rows = t["per_dispatch_ms"]
    assert t["dispatches"] == 2
    # sample = its locks (self) + reserve + draw; the draw's self time leaves its collection out
    assert rows["r2d2.replay.sample"]["self_wall"] == pytest.approx(((40 - 6 - 28) + (20 - 16)) / 2 * MS, abs=1e-9)
    assert rows["r2d2.replay.draw"]["self_wall"] == pytest.approx(((28 - 8) + 16) / 2 * MS, abs=1e-9)
    assert rows["r2d2.dispatch.launch"]["self_wall"] == pytest.approx(((50 - 20 - 22) + (30 - 10 - 14)) / 2 * MS, abs=1e-9)
    assert rows["r2d2.dispatch"]["self_wall"] == pytest.approx(
        ((200 - 40 - 50 - 80 - 18) + (150 - 20 - 30 - 10 - 70 - 8)) / 2 * MS, abs=1e-9)
    assert rows["r2d2.dispatch"]["cpu"] == pytest.approx((0.090 + 0.075) / 2 * 1e-3, abs=1e-9)
    assert t["gc"]["by_generation"][0]["collected"] == 5 and t["gc"]["by_generation"][2]["n"] == 1
    assert t["gc"]["per_dispatch"] == 1.0 and t["gc"]["long"] == []  # none of 1 ms or more here
    assert [d["dispatch"] for d in t["longest_by_host_busy"]] == [1, 2]


# --------------------------------------- programs that lack what is read


@pytest.mark.parametrize("metric", EIGHT)
def test_a_program_without_the_facility_reads_nothing_and_raises_nothing(metric, parts, monkeypatch):
    """The driver lays these files over the parent's checkout too. A program
    without `profiling.SPANS` has nothing to read: None, and the line leaves
    the metric out; so has any program without a trace."""
    from r2d2_tpu.utils import profiling

    ctx = _ctx(parts[0])
    spec = harness.layer_spec(ctx.cell, metric)
    ctx.trace_data = None
    assert host_span.read(spec, ctx) is None
    monkeypatch.delattr(profiling, "SPANS")
    assert host_span.read(spec, _ctx(parts[0])) is None


@pytest.mark.parametrize("metric", EIGHT)
def test_a_program_with_the_table_but_without_the_parts_reads_zero(metric, without_parts, monkeypatch):
    """PR 41's tree has the table, the dispatch span and its five children,
    but no reserve / draw / upload / call / gc span and no `cpu_us`: every one
    of the eight reads 0.0 there (a span that is not in the trace, a stat that
    is not on the span), none raises."""
    assert _read(metric, *without_parts, monkeypatch) == 0.0


# ----------------------------------------------- end to end, tiny, on the CPU


@pytest.fixture(scope="module")
def tmp_root(tmp_path_factory):
    """A temporary copy of the benchmark with a tiny learn cell added as files
    and entries, as tests/benchmark/test_bench_spans.py does."""
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
    for e in m["end_to_end"] + m["per_layer"]:
        if "workloads" in e:
            e["workloads"] = e["workloads"] + ["tiny.learn"]
    pats = harness.load_json(os.path.join(root, "benchmark", "trace_patterns.json"))
    pats.update(device_plane="^/host:CPU$", op_lines=["^tf_XLA"], module_lines=["^no such line$"])
    with open(os.path.join(root, "benchmark", "trace_patterns_cpu.json"), "w") as fh:
        json.dump(pats, fh)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(m, fh)
    return root


def test_a_traced_learn_run_carries_all_eight_and_the_sums_hold(tmp_root, monkeypatch, tmp_path, capsys):
    real = tr.load_patterns
    monkeypatch.setattr(tr, "load_patterns",
                        lambda path=None: real(os.path.join(tmp_root, "benchmark", "trace_patterns_cpu.json")))
    peaks = tmp_path / "peaks.json"
    peaks.write_text(json.dumps({"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}))
    monkeypatch.setattr(flops, "_PEAKS_PATH", str(peaks))
    r = harness.run_cell(tmp_root, "tiny.learn", seed=5, seconds=0.3, trace=True, require_tpu=False)
    got = {k: v["value"] for k, v in r["metrics"].items()}
    assert r["correct"] and set(EIGHT) <= set(got)
    assert all(np.isfinite(got[n]) and got[n] >= 0.0 for n in EIGHT)
    for n in ("replay.draw_ms_per_dispatch", "dispatch.upload_ms_per_dispatch", "dispatch.call_ms_per_dispatch",
              "dispatch.host_cpu_ms_per_dispatch"):
        assert got[n] > 0.0, n  # every dispatch draws, uploads and calls, and the thread computes
    assert got["replay.reserve_ms_per_dispatch"] + got["replay.draw_ms_per_dispatch"] <= got["replay.sample_ms_per_dispatch"]
    assert got["dispatch.upload_ms_per_dispatch"] + got["dispatch.call_ms_per_dispatch"] <= got["dispatch.launch_ms_per_dispatch"]
    # CPU stays under wall (how far under is the machine's load, not the program's); the thread's CPU
    # clock and the profiler's clock are two clocks (their rates have read 1 % apart on this sandbox): 3 % of room
    for cpu, wall in (("dispatch.host_cpu_ms_per_dispatch", "dispatch.host_busy_ms_per_dispatch"),
                      ("replay.sample_cpu_ms_per_dispatch", "replay.sample_ms_per_dispatch"),
                      ("dispatch.launch_cpu_ms_per_dispatch", "dispatch.launch_ms_per_dispatch")):
        assert 0.0 < got[cpu] <= 1.03 * got[wall], (cpu, got[cpu], got[wall])
    assert "by innermost span" in capsys.readouterr().out
