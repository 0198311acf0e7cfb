"""The reader of device time by OWNER (readers/trace_heirs.py) and the nine
layer files of PR 58: on the hand-made trace of test_bench_spans.py with
hand-written heirs (the arithmetic: owned buckets + unowned = busy), against a
program without `program_heirs` (a parent commit: nothing to read, nothing
raised), and end to end on a temporary copy of the benchmark at tiny size on
the CPU, where no `*-done` runs and the prefetch waits read 0."""

import json
import os

import pytest
from test_bench_spans import MEGA, MULTI, _ctx, cpu_trace, fixture_trace, tmp_root, trace_dir  # noqa: F401 (fixtures)

from benchmark import harness, manifest
from benchmark import trace as tr
from benchmark.readers import trace_heirs, trace_scope

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NS = 1e-9
with open(os.path.join(ROOT, "BENCHMARK.json")) as _fh:
    M = json.load(_fh)
OWNED = ["device.prefetch_wait_share", "device.unowned_share", "dispatch.collect_owned_ms_per_update",
         "model.core_owned_ms_per_update", "model.encoder_owned_ms_per_update"]
COLLECT_SPLIT = ["dispatch.collect_moe_ms_per_update", "dispatch.collect_attention_ms_per_update",
                 "dispatch.collect_ssm_ms_per_update", "dispatch.collect_gdn_ms_per_update"]
_GATHER = "jit(mega)/jit(r2d2_update)/while/body/closed_call/jit(r2d2_gather)/gather"
# the fixture's one instruction without op_name, the layout-assignment copy of the store, is the gather's operand
HEIRS = {"copy.8": _GATHER + "\tfeeds"}


@pytest.fixture()
def owned_ctx(fixture_trace, monkeypatch):  # noqa: F811
    trace_heirs._done.clear()
    monkeypatch.setattr(trace_heirs, "program_maps", lambda: {"mega": {**MEGA, **HEIRS}, "multi": MULTI})
    yield _ctx(trace=fixture_trace)
    trace_heirs._done.clear()


def test_owned_buckets_and_unowned_add_up_to_the_devices_busy_time(owned_ctx, fixture_trace, capsys):  # noqa: F811
    got = trace_heirs.attribution(owned_ctx)
    busy_s = tr.busy_seconds(fixture_trace)[0]
    assert got["busy"] == pytest.approx(busy_s) and sum(got["seconds"].values()) == pytest.approx(135 * NS)
    # what the names alone leave unscoped (20: the while's self time, the copy, the event after both
    # programs) less the copy, which the gather inherits
    assert got["seconds"]["unscoped"] == pytest.approx(15 * NS)
    assert got["by_how"]["gather"] == pytest.approx({"own": 25 * NS, "feeds": 5 * NS})
    assert got["wait"] == 0.0
    row = next(r for r in got["rows"] if r[1].startswith("%copy.8"))
    assert row[0] == "gather" and row[2:4] == (_GATHER, "feeds")
    out = capsys.readouterr().out
    assert "gather 0.0000s + feeds 0.0000s" in out
    assert "largest unowned: fusion.9 f32[8] [no op_name]" in out and "while.1 s32[] [jit(mega)/jit(r2d2_update)/while]" in out
    assert "no asynchronous `*-done` event" in out
    with open(os.path.join(owned_ctx.work_dir("scopes"), owned_ctx.cell.name + ".heirs.json")) as fh:
        members = json.load(fh)
    copy = next(r for r in members["rows"] if r["instruction"].startswith("copy.8"))
    assert copy == {"bucket": "gather", "instruction": "copy.8 u8[1280,441,84,84,1]", "how": "feeds",
                    "seconds": pytest.approx(5 * NS), "heir": _GATHER}
    assert all(("op_name" in r) == (r["how"] == "own") for r in members["rows"])


@pytest.mark.parametrize("spec,want", [
    ({"share": "unowned"}, 100.0 * 15 / 135),
    ({"share": "prefetch_wait"}, 0.0),
    ({"bucket": "gather", "per": "updates", "scale": 1000.0}, 30 * NS * 1000.0 / 32),
    ({"bucket": "encoder", "per": "updates", "scale": 1000.0}, 30 * NS * 1000.0 / 32),  # inherits nothing: the bucket
])
def test_the_reader_reads_a_bucket_with_what_it_inherits_or_a_share_of_busy(owned_ctx, spec, want):
    assert trace_heirs.read(spec, owned_ctx) == pytest.approx(want)


def test_a_bucket_that_is_none_is_an_error_that_names_the_buckets(owned_ctx):
    with pytest.raises(KeyError, match="no bucket 'kernel'"):
        trace_heirs.read({"name": "x", "bucket": "kernel", "per": "updates"}, owned_ctx)


def test_the_wait_share_is_the_self_time_of_the_done_instructions_whoever_owns_them():
    scopes = trace_scope.load_scopes(os.path.join(ROOT, "benchmark"))
    events = [("%copy-done.3 = bf16[516,128]{1,0:T(8,128)(2,1)S(1)} copy-done(%copy-start.3)", 0.0, 30.0),
              ("%slice-done = f32[4,8]{1,0} slice-done(%slice-start)", 30.0, 10.0),
              ("%all-gather-done.1 = f32[8]{0} all-gather-done(%all-gather-start.1)", 40.0, 5.0),
              ("%fusion.4 = f32[8]{0} fusion(%copy-done.3), kind=kLoop", 50.0, 55.0)]
    ops = {"/device:TPU:0": tr.with_self_times([tr.Event(name, start, dur, name) for name, start, dur in events])}
    maps = {"mega": {"fusion.4": "jit(mega)/jit(r2d2_collect)/dot_general",
                     "copy-done.3": "jit(mega)/jit(r2d2_collect)/dot_general\twaits_for",
                     "all-gather-done.1": "jit(mega)/jit(r2d2_update)/while/body/closed_call/jit(r2d2_optimizer)/psum"}}
    got = trace_heirs.owned(trace_scope.attribute(tr.Trace(ops, {}, []), maps, scopes))
    assert got["wait"] == pytest.approx(45 * NS)
    assert got["by_how"]["collect"] == pytest.approx({"own": 55 * NS, "waits_for": 30 * NS})
    assert got["seconds"]["optimizer"] == pytest.approx(5 * NS)      # a named `-done` waits all the same
    assert got["seconds"]["unscoped"] == pytest.approx(10 * NS)      # the slice-done nobody names


@pytest.mark.parametrize("metric", OWNED)
def test_a_program_without_heirs_gives_nothing_and_raises_nothing(metric, fixture_trace, monkeypatch):  # noqa: F811
    """The driver lays these files over the parent's checkout too: there
    utils/profiling has program_scopes and no program_heirs, and each of the
    five is left out of the line."""
    from r2d2_tpu.utils import profiling

    monkeypatch.delattr(profiling, "program_heirs")
    trace_heirs._done.clear()
    ctx = _ctx(trace=fixture_trace)
    spec = harness.layer_spec(ctx.cell, metric)
    assert spec["reader"] == "trace_heirs" and trace_heirs.read(spec, ctx) is None
    monkeypatch.undo()
    ctx.trace_data = None  # and without a trace, whatever the program has
    assert trace_heirs.read(spec, ctx) is None


def test_the_nine_are_appended_with_their_cells_in_the_manifests_order():
    names = [m["name"] for m in M["per_layer"]]
    assert names[-9:] == OWNED + COLLECT_SPLIT
    cells = [w["name"] for w in M["workloads"]]
    fourth, fifth = "nemotron-twotower-30b-a3b-ep16.learn", "qwen3-next-80b-a3b-ep32.learn"
    want = dict.fromkeys(OWNED, cells[:5])
    want.update({COLLECT_SPLIT[0]: [fourth, fifth], COLLECT_SPLIT[1]: [fourth, fifth],
                 COLLECT_SPLIT[2]: [fourth], COLLECT_SPLIT[3]: [fifth]})
    for entry in M["per_layer"][-9:]:
        assert entry["workloads"] == want[entry["name"]], entry["name"]
        assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert (entry["moves"], entry["source"], entry["better"]) == ("learn_steps_per_s", "device_trace", "lower")
        manifest.check_layer_metric(ROOT, M, entry)
        spec = harness.layer_spec(harness.load_cell(ROOT, cells[0]), entry["name"])
        assert spec["reader"] == ("trace_heirs" if entry["name"] in OWNED else "trace_scope")
        if entry["name"] in COLLECT_SPLIT:  # data only: the reader every tree since PR 31 has
            assert spec["within"] == "collect" and set(spec) == {
                "name", "layer", "unit", "moves", "reader", "op_name", "within", "per", "scale"}


def test_a_traced_tiny_cell_reads_every_new_layer_file(tmp_root, cpu_trace, capsys):  # noqa: F811
    trace_heirs._done.clear()
    r = harness.run_cell(tmp_root, "tiny.learn", seed=3, seconds=0.3, trace=True, require_tpu=False)
    got = {k: v["value"] for k, v in r["metrics"].items()}
    assert r["correct"] and set(OWNED + COLLECT_SPLIT) <= set(got)
    out = capsys.readouterr().out
    # a CPU runs no asynchronous copy: the share is 0, and a progress line says why
    assert got["device.prefetch_wait_share"] == 0.0 and "no asynchronous `*-done` event" in out
    assert "heirs of step program 'mega'" in out and "heirs of step program 'multi'" in out
    assert "device time by owner, own + inherited" in out
    # an owner takes nothing away: each owned bucket holds at least its named members, and unowned at most unscoped
    assert 0.0 <= got["device.unowned_share"] <= got["device.unscoped_share"] < 100.0
    for owned_metric, named in (("dispatch.collect_owned_ms_per_update", "dispatch.collect_ms_per_update"),
                                ("model.core_owned_ms_per_update", "model.core_ms_per_update"),
                                ("model.encoder_owned_ms_per_update", "model.encoder_ms_per_update")):
        assert got[owned_metric] >= got[named] > 0.0, owned_metric
    # the tiny MLP-and-LSTM cell has no mixture, attention or mixer in its collector: 0, said so
    assert all(got[n] == 0.0 for n in COLLECT_SPLIT) and "finds nothing in bucket 'collect'" in out
    scopes_dir = os.path.join(tmp_root, ".benchmark_work", "scopes")
    with open(os.path.join(scopes_dir, "tiny.learn.heirs.json")) as fh:
        by_owner = json.load(fh)
    with open(os.path.join(scopes_dir, "tiny.learn.json")) as fh:
        by_name = json.load(fh)
    # the identity: the seven owned buckets + unowned = the device's busy time, the same busy time
    assert list(by_owner["seconds"]) == list(by_name["seconds"]) and len(by_owner["seconds"]) == 8
    assert sum(by_owner["seconds"].values()) == pytest.approx(by_owner["busy"], rel=1e-9)
    assert by_owner["busy"] == pytest.approx(by_name["busy"], rel=1e-9)
    assert got["device.unowned_share"] == pytest.approx(100 * by_owner["seconds"]["unscoped"] / by_owner["busy"])
    inherited = sum(v for b, hows in by_owner["by_how"].items() if b != "unscoped" for how, v in hows.items() if how != "own")
    assert inherited == pytest.approx(by_name["seconds"]["unscoped"] - by_owner["seconds"]["unscoped"], abs=1e-9)
