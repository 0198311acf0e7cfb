"""The fused path's own spans, scopes and counters (utils/profiling.SPANS): a
tiny fused run under a CPU trace yields every host span of the table, tied
together by the `dispatch` id and by nesting; the step programs carry every
device scope in the executable that ran; the priority counters count."""

import glob
import json
import os

import jax
import numpy as np
import pytest

from r2d2_tpu.config import tiny_test
from r2d2_tpu.utils import profiling

K = 2
DISPATCHES = 10
DISPATCH_CHILDREN = ["r2d2.replay.sample", "r2d2.dispatch.launch", "r2d2.dispatch.readback",
                     "r2d2.replay.account", "r2d2.replay.priorities"]
# part -> the child of the dispatch it sits in (PR 42)
PARTS = {"r2d2.replay.reserve": "r2d2.replay.sample", "r2d2.replay.draw": "r2d2.replay.sample",
         "r2d2.dispatch.upload": "r2d2.dispatch.launch", "r2d2.dispatch.call": "r2d2.dispatch.launch"}
UPDATE_SCOPES = ["r2d2_update", "r2d2_gather", "r2d2_loss", "r2d2_optimizer"]


def _cfg(tmp_path, **over):
    fields = dict(
        env_name="catch", obs_shape=(10, 8, 1), action_dim=3, num_actors=4,
        max_episode_steps=8, block_length=16, buffer_capacity=640, learning_starts=32,
        collector="device", replay_plane="device", updates_per_dispatch=K,
        samples_per_insert=4.0,  # paced: collecting and update-only dispatches both run
        training_steps=DISPATCHES * K, checkpoint_dir=str(tmp_path / "ckpt"), save_interval=10**6,
        metrics_path=str(tmp_path / "metrics.jsonl"), log_interval=0.0)
    return tiny_test().replace(**{**fields, **over})


def _events(trace_dir):
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    assert files, "the trainer wrote no trace"
    return [(e.name, dict(e.stats), e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(files[-1]).planes
            for line in plane.lines for e in line.events if e.name.startswith("r2d2.")]


@pytest.fixture(scope="module")
def fused_run(tmp_path_factory):
    """One traced fused run of the Trainer: the spans it wrote, the counter
    deltas, and the metrics rows."""
    from r2d2_tpu.train import Trainer

    tmp = tmp_path_factory.mktemp("fused")
    before = profiling.counters()
    trace_dir = str(tmp / "prof")
    tr = Trainer(_cfg(tmp), profile_dir=trace_dir, profile_steps=(DISPATCHES - 2) * K)
    tr.run_fused()
    after = profiling.counters()
    with open(tmp / "metrics.jsonl") as fh:
        rows = [json.loads(l) for l in fh if l.strip()]
    delta = {k: after[k] - before.get(k, 0) for k in after}
    return _events(trace_dir), delta, rows


@pytest.fixture(scope="module")
def sharded_run(tmp_path_factory):
    """The same on the sharded plane, dp=4 on virtual devices: the trainer
    after its run, and the spans it wrote."""
    from r2d2_tpu.train import Trainer

    tmp = tmp_path_factory.mktemp("sharded")
    trace_dir = str(tmp / "prof")
    cfg = _cfg(tmp, replay_plane="sharded", dp_size=4, buffer_capacity=2560,
               training_steps=6 * K, metrics_path=None)
    tr = Trainer(cfg, profile_dir=trace_dir, profile_steps=5 * K)
    tr.run_fused()
    return tr, _events(trace_dir)


@pytest.mark.parametrize("name", ["r2d2.dispatch"] + DISPATCH_CHILDREN)
def test_a_traced_fused_run_yields_every_host_span_of_the_table(fused_run, name):
    events, _, _ = fused_run
    assert any(n == name for n, *_ in events), sorted({n for n, *_ in events})


def test_children_nest_inside_their_dispatch_and_share_its_id(fused_run):
    events, _, _ = fused_run
    dispatches = [(int(st["dispatch"]), int(st["collect"]), s, e)
                  for n, st, s, e in events if n == "r2d2.dispatch"]
    assert len(dispatches) >= 3 and len({d for d, *_ in dispatches}) == len(dispatches)
    assert {c for _, c, *_ in dispatches} == {0, 1}  # both step programs ran under the trace
    for name in DISPATCH_CHILDREN:
        for n, _, s, e in events:
            if n == name:
                owners = [d for d, _, ds, de in dispatches if ds <= s and e <= de]
                assert len(owners) == 1, (name, s, e)  # nested in exactly one dispatch: its id
    # the wait for the device is inside the dispatch, after its launch
    d, _, ds, de = dispatches[-1]
    inside = {n: (s, e) for n, _, s, e in events if ds <= s and e <= de}
    assert inside["r2d2.dispatch.launch"][1] <= inside["r2d2.dispatch.readback"][0]
    assert inside["r2d2.replay.sample"][1] <= inside["r2d2.dispatch.launch"][0]


@pytest.mark.parametrize("plane", ["device", "sharded"])
def test_the_sample_and_launch_spans_have_their_parts_at_the_same_boundaries(plane, fused_run, sharded_run):
    """FusedSystemRunner and ShardedFusedRunner carry the same names: reserve
    on collecting dispatches only, draw, upload and call once per dispatch,
    each inside its parent, and the call says which step program it was."""
    events = fused_run[0] if plane == "device" else sharded_run[1]
    dispatches = [(int(st["collect"]), s, e) for n, st, s, e in events if n == "r2d2.dispatch"]
    assert {c for c, *_ in dispatches} == {0, 1}
    for collect, ds, de in dispatches:
        inside = [(n, st, s, e) for n, st, s, e in events if ds <= s and e <= de]
        got = [n for n, *_ in inside if n in PARTS]
        want = ["r2d2.replay.draw", "r2d2.dispatch.upload", "r2d2.dispatch.call"]
        assert sorted(got) == sorted(want + ["r2d2.replay.reserve"] * collect), (collect, got)
        for n, st, s, e in inside:
            if n in PARTS:
                (ps, pe), = [(s2, e2) for n2, _, s2, e2 in inside if n2 == PARTS[n]]
                assert ps <= s and e <= pe, n
                assert "cpu_us" in st
            if n == "r2d2.dispatch.call":
                assert st["program"] == ("mega" if collect else "multi")
            if n == "r2d2.replay.draw":
                assert int(st["k"]) == K
            if n == "r2d2.replay.reserve":
                assert int(st["slots"]) >= 1
        # in order on the one thread: reserve, draw | upload, call
        order = [n for n, _, s, _ in sorted(inside, key=lambda ev: ev[2]) if n in PARTS]
        assert order == ["r2d2.replay.reserve"] * collect + want


def test_priority_rows_are_counted_where_they_are_applied(fused_run):
    _, delta, _ = fused_run
    offered, applied = delta["replay.priority_rows_offered"], delta["replay.priority_rows_applied"]
    assert offered >= applied > 0
    assert offered % 8 == 0  # whole batches of the tiny preset's 8 rows


def test_the_priorities_span_carries_the_running_totals(fused_run):
    """A traced window's applied share is read from these stamps (last less
    first), not from the counters, which run from process start."""
    events, _, _ = fused_run
    stamps = [(int(st["offered"]), int(st["applied"])) for n, st, *_ in sorted(events, key=lambda e: e[2])
              if n == "r2d2.replay.priorities"]
    assert len(stamps) >= 3
    assert all(b[0] - a[0] == K * 8 and 0 <= b[1] - a[1] <= K * 8 for a, b in zip(stamps, stamps[1:]))
    assert all(o >= a for o, a in stamps)


def test_setup_is_read_through_the_aggregates(fused_run):
    _, delta, _ = fused_run
    assert delta["r2d2.setup.init.count"] == 1 and delta["r2d2.setup.init.total_ns"] > 0
    assert delta["r2d2.setup.ring_fill.count"] == 1 and delta["r2d2.setup.ring_fill.total_ns"] > 0
    assert delta["setup.first_call_s"] > 0 and delta["r2d2.dispatch.count"] == DISPATCHES
    # every dispatch's priorities are drained once: one dispatch later, the last by finish()
    assert delta["r2d2.replay.priorities.count"] == DISPATCHES
    assert delta["r2d2.replay.sample.count"] == delta["r2d2.dispatch.launch.count"] == DISPATCHES
    # the parts, once a dispatch (PR 42); the reserve on collecting dispatches only
    for part in ("r2d2.replay.draw", "r2d2.dispatch.upload", "r2d2.dispatch.call"):
        assert delta[part + ".count"] == DISPATCHES and 0 < delta[part + ".cpu_ns"] <= 1.03 * delta[part + ".total_ns"]
    assert 0 < delta["r2d2.replay.reserve.count"] < DISPATCHES


def test_the_metrics_row_carries_host_ms_per_dispatch(fused_run):
    _, _, rows = fused_run
    with_host = [r for r in rows if "host_busy_ms" in r]
    assert with_host
    for r in with_host:
        assert r["host_busy_ms"] >= r["host_sample_ms"] >= 0.0 and r["host_readback_ms"] >= 0.0
        # by the thread's CPU clock the same spans read no more than by wall time (two clocks: 3 %)
        assert 0.0 < r["host_cpu_ms"] <= 1.03 * r["host_busy_ms"] + 1e-3 and r["host_gc_ms"] >= 0.0
    shares = [r["priority_applied_pct"] for r in rows if "priority_applied_pct" in r]
    assert shares and all(0.0 < s <= 100.0 for s in shares)


@pytest.mark.parametrize("program,scopes", [
    ("mega", UPDATE_SCOPES + ["r2d2_collect", "r2d2_slab_write"]),
    ("multi", UPDATE_SCOPES),
])
def test_the_step_programs_carry_every_device_scope(fused_run, program, scopes):
    """From the executable itself (program_scopes compiles from the signature
    of the first call; the test asks, the program never does)."""
    assert program in profiling.registered_programs()
    names = profiling.program_scopes(program)
    for scope in scopes:
        assert any(f"jit({scope})" in v for v in names.values()), scope
    if program == "multi":
        assert not any("r2d2_collect" in v for v in names.values())
    # the model's own buckets come from flax's module paths, under the update's scope
    for path in ("R2D2Network._core_input", "R2D2Network.unroll/core", "R2D2Network._dueling"):
        assert any("jit(r2d2_update)" in v and path in v for v in names.values()), path
    # gradients keep the scope of what they differentiate
    assert any("transpose(jvp(jit(r2d2_loss)))" in v or "jvp(jit(r2d2_loss))" in v for v in names.values())


def test_the_sharded_step_programs_carry_the_scopes_too(sharded_run):
    """dp=4 on virtual devices: the psum inside r2d2_optimizer and the slab
    write run as named inner jits under shard_map."""
    tr, _ = sharded_run
    assert int(np.asarray(tr.state.step)) == 6 * K
    names = profiling.program_scopes("mega")
    for scope in UPDATE_SCOPES + ["r2d2_collect", "r2d2_slab_write"]:
        assert any(f"jit({scope})" in v for v in names.values()), scope
    assert len(jax.devices()) >= 4
