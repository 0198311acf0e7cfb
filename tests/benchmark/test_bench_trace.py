"""The trace reducer: on the committed fixture (through the real xplane
reader) and on synthetic events (the arithmetic alone)."""

import os

import pytest

from benchmark import trace as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "two_device.xplane.txt")
NS = 1e-9


@pytest.fixture(scope="module")
def fixture_trace(tmp_path_factory):
    from jax.profiler import ProfileData

    with open(FIXTURE) as fh:
        blob = ProfileData.text_proto_to_serialized_xspace(fh.read())
    d = tmp_path_factory.mktemp("trace") / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(blob)
    return tr.load(tr.find_xplane(str(d.parent.parent.parent)), tr.load_patterns())


def test_fixture_planes_lines_and_host_spans(fixture_trace):
    t = fixture_trace
    assert sorted(t.ops) == ["/device:TPU:0", "/device:TPU:1"]
    assert [e.name for e in t.modules["/device:TPU:0"]] == ["jit_mega(123)", "jit_multi(5)"]
    # only the benchmark's own spans are kept from the host plane
    assert [e.name for e in t.host] == ["bench.step", "bench.sync"]
    assert len(t.ops["/device:TPU:0"]) == 6 and len(t.ops["/device:TPU:1"]) == 3


def test_fixture_busy_union_and_idle_share(fixture_trace):
    busy_s, window_s, idle = tr.busy_seconds(fixture_trace)
    assert window_s == pytest.approx(180 * NS)           # 1000 .. 1180 ns over both devices
    assert busy_s == pytest.approx((120 + 110) / 2 * NS)  # while covers its children once
    assert idle == pytest.approx([60 / 180, 70 / 180])


def test_fixture_self_time_of_container(fixture_trace):
    evs = fixture_trace.ops["/device:TPU:0"]
    by = {tr.op_label(e.name): e.self_dur for e in evs}
    assert by["while.1 s32[]"] == pytest.approx(10.0)  # 100 - (30 + 20 + 30 + 10)
    assert by["fusion.536 bf16[5440,20,20,32]"] == pytest.approx(30.0)


@pytest.mark.parametrize("category,expect_ns", [
    # a layer file may carry a pattern of its own: here one over activation shapes
    (r"\[\d+,20,20,32\]|\[\d+,9,9,64\]", (50 + 60) / 2), ("lstm_kernel", (20 + 0) / 2), ("collective", (30 + 50) / 2),
])
def test_fixture_category_sums(fixture_trace, category, expect_ns):
    pat = tr.load_patterns()["categories"].get(category, category)
    assert tr.category_seconds(fixture_trace, pat) == pytest.approx(expect_ns * NS)


def test_fixture_module_gap_exposed_and_breakdown(fixture_trace):
    pats = tr.load_patterns()
    assert tr.module_gaps_ms(fixture_trace, pats["categories"]["step_program"]) == pytest.approx([50e-6])
    # one op line per device: nothing overlaps the collectives, all of it is exposed
    assert tr.exposed_seconds(fixture_trace, pats["categories"]["collective"],
                              pats["container"]) == pytest.approx(40 * NS)
    top = dict(tr.top_ops(fixture_trace, pats["container"]))
    assert top["fusion.536 bf16[5440,20,20,32]"] == pytest.approx(30 * NS)
    assert top["all-reduce.3 f32[512,2048]"] == pytest.approx(30 * NS)
    assert top["_lstm_fwd_call.18 bf16[85,64,512]"] == pytest.approx(20 * NS)
    assert top["while.1 s32[]"] == pytest.approx(10 * NS)
    assert tr.op_label("fusion.12") == "fusion" and tr.op_label("%copy.3 = u8[4,4]{1,0} copy(%x)") == "copy.3 u8[4,4]"
    # both of device 0's idle gaps fall mostly under bench.sync
    assert tr.idle_gaps_by_host_span(fixture_trace) == [["bench.sync", pytest.approx(60 * NS)]]


def _ev(name, start, dur, text=None):
    return tr.Event(name, float(start), float(dur), text or name)


def test_union_subtract_and_gaps():
    u = tr.union([(0, 10), (5, 12), (20, 30), (30, 31), (40, 40)])
    assert u == [(0, 12), (20, 31)]
    assert tr.total(u) == 23
    assert tr.subtract([(0, 100)], u) == [(12, 20), (31, 100)]
    assert tr.gaps(u, (0, 35)) == [(12, 20), (31, 35)]
    assert tr.subtract([(0, 10), (20, 30)], [(5, 25)]) == [(0, 5), (25, 30)]


def test_self_times_nested_two_levels():
    evs = sorted([_ev("while", 0, 100), _ev("inner", 10, 50), _ev("leaf", 20, 10), _ev("tail", 70, 20)],
                 key=lambda e: (e.start, -e.dur))
    assert tr.self_times(evs) == [30.0, 40.0, 10.0, 20.0]


def test_exposed_collectives_two_devices_with_overlap():
    """Device 0 hides 20 of its 30 ns collective behind compute on another
    line; device 1 hides nothing; a `while` container never counts as cover."""
    d0 = tr.with_self_times([_ev("while.1", 0, 100), _ev("all-reduce.1", 10, 30),
                             _ev("fusion.2", 20, 20), _ev("fusion.3", 60, 10)])
    d1 = tr.with_self_times([_ev("all-reduce.1", 0, 50), _ev("fusion.2", 50, 10)])
    t = tr.Trace({"/device:TPU:0": d0, "/device:TPU:1": d1}, {}, [])
    pats = tr.load_patterns()
    got = tr.exposed_seconds(t, pats["categories"]["collective"], pats["container"])
    assert got == pytest.approx((10 + 50) / 2 * NS)


def test_empty_trace_is_an_error_not_a_zero():
    with pytest.raises(ValueError):
        tr.window_of(tr.Trace({}, {}, []))
    with pytest.raises(FileNotFoundError):
        tr.find_xplane(os.path.dirname(FIXTURE))


# Instruction texts as the v5e's "XLA Ops" events carry them (operands with
# their shapes) and as `compiled.as_text()` prints them for a described
# v5e:2x2 (operands bare). The whole-store copy reads the program's own obs
# parameter: `%stores__obs__.1` on one chip, `%param.N` once the SPMD
# partitioner has renamed the entry parameters (dp4), where a pattern on the
# name alone found nothing and the cell's traced line lost the metric.
_STORE = "u8[1280,441,84,84,1]{0,1,4,3,2:T(8,128)(4,1)}"
_COPIED = "u8[1280,441,84,84,1]{3,2,1,0,4:T(8,128)(4,1)}"


@pytest.mark.parametrize("text,hit", [
    (f"%copy.187 = {_COPIED} copy({_STORE} %stores__obs__.1)", True),
    (f"%copy.222 = {_COPIED} copy({_STORE} %param.186), metadata={{op_name=\"jit(body)/shard_map/while\"}}", True),
    (f"%copy.380 = {_COPIED} copy(%param.211), backend_config={{}}", True),
    (f"%copy.149 = {_COPIED} copy(%stores__obs__.1)", True),
    ("%copy.143 = u8[512,1089,84,84,1]{3,2,1,0,4:T(8,128)(4,1)} copy(u8[512,1089,84,84,1]{0,1,4,3,2} %stores__obs__)", True),
    # collection's per-step copy reads a fusion's result, not a parameter
    ("%copy.496 = u8[1,64,84,84,1]{1,0,4,3,2:T(4,128)(4,1)S(1)} copy(u8[1,64,84,84,1]{4,3,2,1,0} %select_convert_fusion.7)", False),
    ("%copy.456 = u8[1360,84,84]{2,1,0} copy(u8[1360,84,84]{0,2,1} %fusion.976)", False),
    ("%copy.272 = s32[16,1]{1,0:T(8,128)S(1)} copy(s32[16,1]{0,1} %param.12)", False),
    (f"%dynamic_update_slice.247 = {_STORE} dynamic-update-slice({_STORE} %param.211, u8[400,64,84,84,1]{{4,3,2,1,0}} %pad.51)", False),
])
def test_store_copy_pattern_on_one_chip_and_partitioned_programs(text, hit):
    import re

    assert bool(re.search(tr.load_patterns()["categories"]["store_copy"], text)) is hit


def test_a_pattern_without_a_match_reads_zero_not_nothing():
    """The check of a later PR wants every per-layer metric of the cell in
    the line: an operation that is gone (or fully hidden) is 0 ms, not absent."""
    import types

    from benchmark.readers import scaled, trace_exposed, trace_pattern

    d0 = tr.with_self_times([_ev("fusion.1", 0, 10)])
    t = tr.Trace({"/device:TPU:0": d0, "/device:TPU:1": d0}, {}, [])
    ctx = types.SimpleNamespace(trace_data=t, counters={"updates": 4.0}, patterns=tr.load_patterns())
    spec = {"name": "x", "category": "store_copy", "per": "updates", "scale": 1000.0}
    assert trace_pattern.read(spec, ctx) == 0.0
    assert trace_exposed.read(dict(spec, category="collective"), ctx) == 0.0
    assert scaled(spec, ctx, 0.002) == pytest.approx(0.5)
    ctx.counters = {}
    assert scaled(spec, ctx, 0.002) is None and trace_pattern.read(spec, ctx) is None
    ctx.trace_data = None
    assert trace_pattern.read(spec, ctx) is None


def test_text_is_cached_by_event_name(fixture_trace):
    """load() builds an event's text once per name (a dp4 trace holds millions
    of events of a few thousand names): same name, same text, on both devices."""
    texts = {}
    for evs in fixture_trace.ops.values():
        for e in evs:
            assert texts.setdefault(e.name, e.text) == e.text and e.text.startswith(e.name)


@pytest.mark.parametrize("name,hit", [
    ("jit_mega(1234567890)", True), ("jit_multi(42)", True),
    # the sharded plane's two step programs, as dp4's module line names them (chip trace, PR 22)
    ("jit_body(4122400780077301311)", True), ("jit_body(7758897911374833444)", True),
    # the small programs between dispatches are not steps
    ("jit__multi_slice(7728734105562371704)", False), ("jit_convert_element_type(9)", False),
])
def test_step_program_pattern_on_module_names(name, hit):
    import re

    assert bool(re.search(tr.load_patterns()["categories"]["step_program"], name)) is hit


def test_matcher_remembers_each_text_once():
    found = tr.matcher("^%?copy")
    assert found("%copy.1 = u8[4]{0} copy(%p)") and not found("%fusion.2 = f32[] fusion(%copy.1)")
    assert found("%copy.1 = u8[4]{0} copy(%p)")


def test_an_idle_gap_goes_to_the_innermost_span_that_covers_it():
    """The program's spans nest inside the benchmark's `bench.step`: the gap
    is named after the one that says what the host was doing."""
    ops = {"/device:TPU:0": tr.with_self_times([_ev("%a", 0, 10), _ev("%b", 100, 10), _ev("%c", 150, 10)])}
    host = [_ev("bench.step", 0, 200), _ev("r2d2.dispatch", 5, 190), _ev("r2d2.dispatch.readback", 8, 95),
            _ev("r2d2.dispatch.launch", 104, 2), _ev("r2d2.replay.sample", 112, 30)]
    got = tr.idle_gaps_by_host_span(tr.Trace(ops, {}, host))
    # 10..100 lies in the readback; 110..150 is covered most by the dispatch as a whole
    assert got == [["r2d2.dispatch.readback", pytest.approx(90 * NS)], ["r2d2.dispatch", pytest.approx(40 * NS)]]


def test_the_program_spans_are_kept_from_the_host_plane():
    assert "r2d2." in tr.load_patterns()["host_span_prefixes"]
