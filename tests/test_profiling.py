"""The tracing facility (utils/profiling.py): spans are cheap when idle and
aggregate always, ids reach the trace as stats, device scopes reach the
executable's op_name AND the compilation cache's key, and a bounded trainer
trace lands on disk."""

import gc
import glob
import importlib
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from r2d2_tpu.utils import profiling
from r2d2_tpu.utils.profiling import (
    SPANS, count, counters, program_scopes, register_program, scoped, span, spanned, step_span,
)


def _host_events(trace_dir, prefix="r2d2."):
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    assert files, "no .xplane.pb written"
    data = ProfileData.from_file(sorted(files, key=os.path.getmtime)[-1])
    return [(e.name, dict(e.stats), e.start_ns, e.duration_ns)
            for plane in data.planes for line in plane.lines for e in line.events
            if e.name.startswith(prefix)]


def _spin(seconds):
    """Keep this thread on the CPU for `seconds` of its own CPU clock."""
    t_end = time.thread_time() + seconds
    while time.thread_time() < t_end:
        pass


def test_spans_are_noops_when_idle():
    with span("r2d2.replay.sample"):
        x = jnp.ones(4) + 1
    with step_span("r2d2.step.update", 3):
        y = x * 2
    assert float(y.sum()) == 16.0


def test_start_trace_writes_a_trace_with_the_python_tracer_off(tmp_path):
    d = str(tmp_path / "trace")
    profiling.start_trace(d)
    with span("r2d2.replay.sample"):
        jnp.dot(jnp.ones((8, 8)), jnp.ones((8, 8))).block_until_ready()
    profiling.stop_trace()
    events = _host_events(d, prefix="")
    assert any(n == "r2d2.replay.sample" for n, *_ in events)
    # the Python tracer would record this test function's own frame ("$... test_..." events)
    assert not any("test_start_trace_writes" in n for n, *_ in events)


def test_no_trace_session_leaves_nothing_behind(tmp_path):
    with span("r2d2.replay.sample"):
        jnp.ones(2).block_until_ready()
    assert not os.listdir(tmp_path)


def test_trainer_profile_dir(tmp_path):
    from r2d2_tpu.config import tiny_test
    from r2d2_tpu.train import Trainer

    d = str(tmp_path / "prof")
    cfg = tiny_test().replace(
        env_name="catch",
        training_steps=4,
        checkpoint_dir=str(tmp_path / "ckpt"),
    )
    tr = Trainer(cfg, profile_dir=d, profile_steps=2)
    tr.run_inline()
    files = glob.glob(os.path.join(d, "**", "*"), recursive=True)
    assert any(os.path.isfile(f) for f in files), "trainer wrote no trace"


def test_span_aggregates_count_and_total():
    before = counters()
    for ms in (1, 3):
        with span("r2d2.replay.account"):
            time.sleep(ms / 1e3)
    after = counters()
    d = lambda k: after[f"r2d2.replay.account.{k}"] - before.get(f"r2d2.replay.account.{k}", 0)
    assert d("count") == 2
    assert 4e6 <= d("total_ns") < 1e9


def test_spanned_runs_the_function_under_its_span_and_keeps_the_signature():
    import inspect

    @spanned("r2d2.setup.ring_fill")
    def fill(steps: int, beat=None) -> int:
        """doc"""
        return steps + 1

    before = counters().get("r2d2.setup.ring_fill.count", 0)
    assert fill(2) == 3 and fill(steps=4, beat=None) == 5
    assert counters()["r2d2.setup.ring_fill.count"] == before + 2
    assert list(inspect.signature(fill).parameters) == ["steps", "beat"] and fill.__doc__ == "doc"
    with pytest.raises(KeyError, match="profiling.SPANS"):
        spanned("setup/init")


def test_counts_add_up_in_one_flat_dict():
    before = counters().get("replay.priority_rows_offered", 0)
    count("replay.priority_rows_offered", 5)
    count("replay.priority_rows_offered")
    c = counters()
    assert c["replay.priority_rows_offered"] == before + 6
    assert all(isinstance(v, (int, float)) for v in c.values())


@pytest.mark.parametrize("use", ["span", "count", "step_span", "scoped"])
def test_a_name_outside_the_table_is_refused(use):
    with pytest.raises(KeyError, match="profiling.SPANS"):
        if use == "span":
            with span("replay/sample"):
                pass
        elif use == "count":
            count("some.counter")
        elif use == "step_span":
            step_span("learner_update", 1)
        else:
            scoped(lambda x: x, "my_scope")


def test_every_name_in_the_program_is_in_the_table():
    """No span, scope or counter outside the one table: every literal first
    argument of span / spanned / step_span / count / counted / put / scoped
    in r2d2_tpu/ is a key."""
    import ast

    root = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "r2d2_tpu")
    used = set()
    for d, _, files in os.walk(root):
        for f in files:
            if not f.endswith(".py"):
                continue
            tree = ast.parse(open(os.path.join(d, f)).read())
            for node in ast.walk(tree):
                if (isinstance(node, ast.Call) and getattr(node.func, "id", getattr(node.func, "attr", ""))
                        in ("span", "spanned", "step_span", "count", "counted", "put", "scoped") and node.args):
                    lit = [a for a in node.args if isinstance(a, ast.Constant) and isinstance(a.value, str)]
                    used.update(a.value for a in lit if a.value.startswith(("r2d2", "setup.", "replay.", "moe.")))
    assert used and used <= set(SPANS), used - set(SPANS)
    # and nothing in the table that no code uses
    assert set(SPANS) - used <= {"setup.compile_s"}  # set by key in _Program.__call__


def test_an_idle_span_costs_microseconds_not_more():
    """Under 2 us on the benchmark's host (PERF.md); asserted here to an
    order of magnitude, against a machine that may be busy."""
    n = 20000
    t0 = time.perf_counter()
    for _ in range(n):
        with span("r2d2.dispatch.launch"):
            pass
    per = (time.perf_counter() - t0) / n
    assert per < 20e-6, f"{per * 1e6:.2f} us per idle span"


def test_ids_become_the_events_stats_and_children_nest(tmp_path):
    d = str(tmp_path / "trace")
    profiling.start_trace(d)
    with span("r2d2.dispatch", dispatch=7, collect=1):
        with span("r2d2.dispatch.readback"):
            time.sleep(0.001)
    profiling.stop_trace()
    ev = {n: (stats, s, dur) for n, stats, s, dur in _host_events(d)}
    stats, s0, d0 = ev["r2d2.dispatch"]
    assert int(stats["dispatch"]) == 7 and int(stats["collect"]) == 1
    _, s1, d1 = ev["r2d2.dispatch.readback"]
    assert s0 <= s1 and s1 + d1 <= s0 + d0


def test_a_closed_span_carries_cpu_us_beside_its_ids_and_its_children_still_nest(tmp_path):
    """`cpu_us` is stamped at close (TraceAnnotation.set_metadata), so it sits
    beside the ids given at open; wall less CPU is time the thread did not run."""
    d = str(tmp_path / "trace")
    profiling.start_trace(d)
    with span("r2d2.dispatch", dispatch=7, collect=1):
        with span("r2d2.dispatch.readback"):
            time.sleep(0.02)
        with span("r2d2.dispatch.call", program="mega"):
            _spin(0.02)
    profiling.stop_trace()
    ev = {n: (stats, s, dur) for n, stats, s, dur in _host_events(d)}
    stats, s0, d0 = ev["r2d2.dispatch"]
    assert int(stats["dispatch"]) == 7 and int(stats["collect"]) == 1
    for child in ("r2d2.dispatch.readback", "r2d2.dispatch.call"):
        _, s1, d1 = ev[child]
        assert s0 <= s1 and s1 + d1 <= s0 + d0
    assert ev["r2d2.dispatch.call"][0]["program"] == "mega"
    cpu = {n: float(ev[n][0]["cpu_us"]) for n in ev}
    assert cpu["r2d2.dispatch.readback"] < 5e3          # asleep: off the CPU
    assert 19e3 <= cpu["r2d2.dispatch.call"] < 40e3     # a busy loop: on it
    assert cpu["r2d2.dispatch.call"] <= ev["r2d2.dispatch.call"][2] / 1e3 * 1.03  # CPU within wall (two clocks)
    assert cpu["r2d2.dispatch"] >= cpu["r2d2.dispatch.call"] + cpu["r2d2.dispatch.readback"]


def test_the_aggregate_keeps_cpu_ns_beside_total_ns():
    def grown(fn):
        before = counters()
        with span("r2d2.replay.account"):
            fn()
        after = counters()
        return [after[f"r2d2.replay.account.{k}"] - before.get(f"r2d2.replay.account.{k}", 0)
                for k in ("count", "total_ns", "cpu_ns")]

    n, wall, cpu = grown(lambda: _spin(0.03))
    assert n == 1 and 29e6 <= cpu <= wall * 1.03 and cpu < 60e6
    n, wall, cpu = grown(lambda: time.sleep(0.05))
    assert n == 1 and wall >= 49e6 and cpu < wall / 5


def test_a_collection_under_an_open_span_is_one_gc_span_inside_it(tmp_path):
    d = str(tmp_path / "trace")
    junk = [[] for _ in range(1000)]
    for a, b in zip(junk, junk[1:]):
        a.append(b), b.append(a)  # cycles: only the collector frees them
    del junk, a, b
    gc.disable()  # no collection of the allocator's own choosing inside the traced span
    try:
        before = counters().get("r2d2.host.gc.count", 0)
        profiling.start_trace(d)
        with span("r2d2.replay.priorities", offered=0, applied=0):
            gc.collect()
        profiling.stop_trace()
    finally:
        gc.enable()
    ev = _host_events(d)
    (_, _, s0, d0), = [e for e in ev if e[0] == "r2d2.replay.priorities"]
    (_, stats, s1, d1), = [e for e in ev if e[0] == "r2d2.host.gc"]
    assert s0 <= s1 and s1 + d1 <= s0 + d0
    assert int(stats["generation"]) == 2 and int(stats["collected"]) >= 1000 and "cpu_us" in stats
    assert counters()["r2d2.host.gc.count"] == before + 1
    assert not profiling._gc_open  # closed: nothing left open for the next collection


def test_the_gc_callbacks_are_installed_once_around_every_other_callback(tmp_path):
    """The start handler first and the stop handler last, so that jax's own
    collection callback runs inside the span; however often the module is
    imported, the installer called or a Trainer built."""
    from r2d2_tpu.config import tiny_test
    from r2d2_tpu.train import Trainer

    def ours():
        return [cb for cb in gc.callbacks if getattr(cb, "__module__", None) == profiling.__name__]

    assert ours() == [profiling._gc_span_start, profiling._gc_span_stop]
    importlib.import_module("r2d2_tpu.utils.profiling")
    profiling._install_gc_span()
    Trainer(tiny_test().replace(env_name="catch", checkpoint_dir=str(tmp_path / "ckpt")))
    assert ours() == [profiling._gc_span_start, profiling._gc_span_stop]
    assert gc.callbacks[0] is profiling._gc_span_start and gc.callbacks[-1] is profiling._gc_span_stop
    assert len(gc.callbacks) >= 3  # jax's own is between them


def test_program_scopes_finds_the_scope_of_a_registered_program():
    enc = scoped(lambda x, w: jnp.tanh(x @ w), "r2d2_gather")

    def body(x, w):
        return enc(x, w).sum() + 1.0

    prog = register_program("test_body", jax.jit(body))
    with pytest.raises(ValueError, match="not been called"):
        program_scopes("test_body")
    before = counters().get("setup.first_call_s", 0.0)
    out = prog(jnp.ones((4, 8)), jnp.ones((8, 8)))
    assert float(out) == pytest.approx(33.0, rel=1e-3)
    assert counters()["setup.first_call_s"] > before
    assert "setup.compile_s" in counters()
    scopes = program_scopes("test_body")
    assert "test_body" in profiling.registered_programs()
    inside = [v for v in scopes.values() if "r2d2_gather" in v]
    assert inside and any(v.endswith("dot_general") or "tanh" in v for v in inside)
    assert any("r2d2_gather" not in v for v in scopes.values())  # the add outside it


_CACHE_CASE = """
import sys
import jax, jax.numpy as jnp
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from r2d2_tpu.utils import compilation_cache as cc, profiling
cc._install_listener()
enc = lambda x, w: jnp.tanh(x @ w)
if sys.argv[2] == "scoped":
    enc = profiling.scoped(enc, "r2d2_gather")
def body(x, w):
    y, _ = jax.lax.scan(lambda c, _: (enc(c, w), None), x, None, length=3)
    return y.sum()
prog = profiling.register_program("body", jax.jit(body))
prog(jnp.ones((4, 8)), jnp.ones((8, 8))).block_until_ready()
s0 = cc.compile_cache_stats()
scopes = profiling.program_scopes("body")
s1 = cc.compile_cache_stats()
print("RESULT", s0["misses"], s1["misses"] - s0["misses"], int(any("r2d2_gather" in v for v in scopes.values())))
"""


def test_a_scope_changes_the_cache_key_so_the_executable_carries_its_names(tmp_path):
    """Fact 2 of ISSUE 23: jax strips metadata from the persistent cache's
    key, so a bare named_scope added to a program HITS the entry its parent
    wrote and runs an executable without the name. `scoped` puts the name in
    the IR: the scoped program misses once, and from then on hits an entry
    that has the name. Each run is a process of its own, as parent and change
    are."""
    cache = str(tmp_path / "cache")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)

    def run(mode):
        p = subprocess.run([sys.executable, "-c", _CACHE_CASE, cache, mode], env=env,
                           capture_output=True, text=True, timeout=300,
                           cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        line = [l for l in p.stdout.splitlines() if l.startswith("RESULT")]
        assert line, p.stderr[-2000:]
        return tuple(map(int, line[0].split()[1:]))

    body_missed, reader_missed, has_scope = run("plain")
    assert body_missed >= 1 and has_scope == 0
    # program_scopes compiles nothing new: the executable the run built serves it
    assert reader_missed == 0
    misses_plain_again = run("plain")[0]
    assert misses_plain_again < body_missed  # the cache serves an unchanged program
    body_missed2, _, has_scope = run("scoped")
    assert body_missed2 > misses_plain_again and has_scope == 1  # missed once, names inside
    body_missed3, reader_missed3, has_scope = run("scoped")
    assert body_missed3 < body_missed2 and reader_missed3 == 0 and has_scope == 1  # hits, still named


def test_compile_listener_sums_seconds_per_function():
    from r2d2_tpu.utils import compilation_cache as cc

    cc._install_listener()
    t0 = cc.compile_seconds()

    def a_function_with_this_name(x):
        return jnp.sin(x) * 3

    jax.jit(a_function_with_this_name)(jnp.ones(7)).block_until_ready()
    assert cc.compile_seconds() > t0
    rows = {name: (tr, lo, co) for name, tr, lo, co in cc.costliest_compiles(n=10**6)}
    tr, lo, co = rows["a_function_with_this_name"]
    assert tr > 0 and lo > 0 and co > 0


@pytest.mark.parametrize(
    "line, found",
    [
        # the parent's whole-store copy, as its compiled module has it
        ("  %copy.187 = u8[1280,441,84,84,1]{3,2,1,0,4:T(8,128)(4,1)} copy(%stores__obs__.1), metadata={op_name=\"x\"}", True),
        ("  ROOT %transpose.3 = u8[1280,441,56,128]{3,2,1,0} transpose(%p.1), dimensions={0,1,3,2}", True),
        ("  %reshape.9 = bf16[640000,7168]{1,0} reshape(%fusion.1)", True),
        # inside a fusion's computation the line reads the same
        ("    %copy.5 = f32[1000,1000,1000]{2,1,0} copy(%param_0.7)", True),
        # a reshape that moves nothing, a small copy, and an in-place update are not re-layouts
        ("  %bitcast.4 = u8[564480,56,128]{2,1,0} bitcast(%stores__obs__.1)", False),
        ("  %copy.297 = u8[5440,84,84]{2,1,0} copy(%fusion.522)", False),
        ("  %dynamic-update-slice.15 = u8[1280,441,56,128]{3,2,1,0} dynamic-update-slice(%a, %b, %c, %d, %e, %f)", False),
        # a dtype the table does not know is skipped, not guessed
        ("  %copy.1 = token[] copy(%t)", False),
    ],
    ids=["parent-copy", "root-transpose", "reshape", "in-fusion", "bitcast", "small", "in-place-write", "unknown-dtype"],
)
def test_relayouts_at_least_finds_store_sized_passes_only(line, found):
    store_bytes = 1280 * 441 * 56 * 128
    got = profiling.relayouts_at_least("HloModule m\n" + line + "\n", store_bytes // 2)
    assert bool(got) == found
    if found:  # the instruction up to its operands, without the attributes after them
        body = line.strip().removeprefix("ROOT ")
        assert len(got) == 1 and body.startswith(got[0]) and got[0].endswith(")") and "metadata" not in got[0]
