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


# ------------------------------------------------ owners for what has no op_name

_FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
_GATHER = "jit(multi)/jit(r2d2_update)/while/body/closed_call/jit(r2d2_gather)/gather"
_SELECT = "jit(multi)/jit(r2d2_update)/while/body/closed_call/jit(r2d2_optimizer)/jit(_where)/select_n"
_ENC = "jit(multi)/jit(r2d2_update)/while/body/closed_call/jvp(R2D2Network)/R2D2Network.unroll/R2D2Network._core_input/enc/"
_ENC_T = _ENC.replace("jvp(R2D2Network)", "transpose(jvp(R2D2Network))")


@pytest.fixture(scope="module")
def v5e_excerpt():
    """Cut from `runs/rehearse_step_programs.py nature-lstm512 --hlo-dir`'s
    `multi` (scheduled, for the described v5e): the instructions the cases
    below name with their neighbours, the scan's `while` with its operand
    tuple, body and condition whole lines, `backend_config` cut off all lines
    but two."""
    with open(os.path.join(_FIXTURES, "v5e_nature_multi_excerpt.hlo")) as fh:
        return fh.read()


@pytest.mark.parametrize("instruction, heir", [
    # a prefetch into the fast memory: the wait is its consumer's
    ("copy-done.110", ("jit(multi)/jit(r2d2_update)/while/body/closed_call/jvp(jit(r2d2_loss))/reduce_max", "waits_for")),
    # a weight slice inside the scan's body, joined by an unnamed ConcatBitcast before conv2 reads it
    ("slice-done.19", (_ENC + "Conv_1/conv_general_dilated", "waits_for")),
    ("custom-call.63", (_ENC + "Conv_1/conv_general_dilated", "feeds")),
    # reached through an unnamed bitcast
    ("copy-done.2", (_ENC_T + "Conv_0/conv_general_dilated", "waits_for")),
    # before the scan, consumed inside it: through the operand tuple and the body's get-tuple-element
    ("copy-done.10", (_GATHER, "waits_for")),
    ("slice-done.130", (_GATHER, "waits_for")),
    # an unnamed convert of the hidden store, named by its consumer (inside the scan too)
    ("convert.159", (_GATHER, "feeds")),
    # the ReLU mask packer: the fused computation's root is an unnamed reduce over the encoder's own `gt`
    ("fusion.661", (_ENC + "gt", "fused")),
    # written back to the loop's carry, nobody consumes it here: its producer's
    ("copy-done.82", (_SELECT, "feeds")),
    # after the scan: element k of the result is operand k of the body's root
    ("copy-done.7", (_SELECT, "feeds")),
    # a prefetch for the NEXT iteration's gather, carried: no rule follows a loop's carry
    ("copy-done.11", None),
    # a reducer's own add: no neighbour has a name
    ("add.2259", None),
], ids=lambda v: v if isinstance(v, str) else None)
def test_an_instruction_without_op_name_gets_its_owner_from_the_text(v5e_excerpt, instruction, heir):
    assert instruction not in profiling.parse_op_names(v5e_excerpt)
    assert f"%{instruction} = " in v5e_excerpt
    assert profiling.parse_heirs(v5e_excerpt).get(instruction) == heir


def test_parse_op_names_reads_what_it_read_and_heirs_name_only_the_rest(v5e_excerpt):
    import json

    with open(os.path.join(_FIXTURES, "v5e_nature_multi_excerpt.op_names.json")) as fh:
        parents = json.load(fh)  # the parent commit's parse_op_names on the same text
    named = profiling.parse_op_names(v5e_excerpt)
    assert named == parents and len(named) == 29
    heirs = profiling.parse_heirs(v5e_excerpt)
    assert heirs and not set(heirs) & set(named)
    assert {how for _, how in heirs.values()} == {"waits_for", "fused", "feeds"}
    # whatever name the scan's own `while` and its results carry, a walk goes through them
    assert named["while.178"].endswith("jit(r2d2_update)/while")
    assert not any(op == named["while.178"] for op, _ in heirs.values())


def test_a_fusion_takes_its_roots_name_before_any_other_inside():
    text = "\n".join([
        "HloModule m, is_scheduled=true", "",
        "%fused_computation.1 (p: f32[8]) -> f32[8] {",
        "  %p = f32[8]{0} parameter(0)",
        '  %mul.1 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(f)/first"}',
        '  ROOT %add.1 = f32[8]{0} add(%mul.1, %p), metadata={op_name="jit(f)/root"}',
        "}", "",
        "%fused_computation.2 (q: f32[8]) -> f32[8] {",
        "  %q = f32[8]{0} parameter(0)",
        "  ROOT %neg.1 = f32[8]{0} negate(%q)",
        "}", "",
        "ENTRY %main (a: f32[8]) -> f32[8] {",
        "  %a = f32[8]{0} parameter(0)",
        "  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1",
        "  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2",
        '  ROOT %copy.1 = f32[8]{0} copy(%fusion.2), metadata={op_name="jit(f)/out"}',
        "}", ""])
    heirs = profiling.parse_heirs(text)
    assert heirs["fusion.1"] == ("jit(f)/root", "fused")
    assert heirs["fusion.2"] == ("jit(f)/out", "feeds")  # its computation names nothing: the third rule
    assert heirs["a"] == ("jit(f)/root", "feeds") and set(heirs) == {"a", "fusion.1", "fusion.2"}


def test_program_heirs_shares_one_compiled_text_with_program_scopes(monkeypatch):
    from r2d2_tpu.utils.compilation_cache import compile_cache_stats

    enc = scoped(lambda x, w: jnp.tanh(x @ w), "r2d2_gather")
    prog = register_program("test_heirs", jax.jit(lambda x, w: enc(x, w).sum() + 1.0))
    with pytest.raises(ValueError, match="not been called"):
        profiling.program_heirs("test_heirs")
    prog(jnp.ones((4, 8)), jnp.ones((8, 8)))
    entry = profiling._programs["test_heirs"]
    assert entry.text is None  # nothing is lowered before a reader asks

    class Counting:
        def __init__(self, jitted):
            self.jitted, self.lowered = jitted, 0

        def lower(self, *args):
            self.lowered += 1
            return self.jitted.lower(*args)

    monkeypatch.setattr(entry, "jitted", Counting(entry.jitted))
    scopes = program_scopes("test_heirs")
    asked = compile_cache_stats()
    heirs = profiling.program_heirs("test_heirs")
    assert program_scopes("test_heirs") == scopes and entry.jitted.lowered == 1
    assert compile_cache_stats() == asked  # the second reader compiled and loaded nothing
    assert scopes == profiling.parse_op_names(entry.text) and heirs == profiling.parse_heirs(entry.text)
    assert not set(heirs) & set(scopes) and all(how in ("waits_for", "fused", "feeds") for _, how in heirs.values())


def test_the_rehearsal_counts_fast_memory_bytes_under_the_owners_bucket(v5e_excerpt):
    """ROADMAP D9's count on the saved text: bytes of the arrays outside the
    fusions' bodies whose layout carries `S(1)`, by the bucket of the
    instruction's own op_name or of its heir's."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "rehearse_step_programs", os.path.join(os.path.dirname(_FIXTURES), os.pardir, "runs", "rehearse_step_programs.py"))
    rehearse = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rehearse)
    got = rehearse.s1_bytes_by_owner(v5e_excerpt)
    assert list(got) == ["collect", "slab_write", "gather", "optimizer", "heads_loss", "encoder", "core", "unowned"]
    # the next iteration's prefetch that no rule names, `copy-done.11 s32[1280,10]{0,1:T(8,128)S(1)}`, and nothing else
    assert got["unowned"] == 1280 * 10 * 4
    # conv2's four weight slices `slice-done.19..22 u8[2560,1768]{..S(1)}` are the encoder's through their heir,
    # the hidden store's `convert.159 bf16[1280,10,2,512]`, which stays in HBM, is nobody's
    assert got["encoder"] == 139289728 and got["encoder"] >= 4 * 2560 * 1768
    assert (got["gather"], got["optimizer"], got["heads_loss"]) == (4811776, 8390656, 21504)
    assert got["collect"] == got["slab_write"] == got["core"] == 0
