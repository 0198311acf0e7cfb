"""Analysis plane: per-rule positive/negative fixtures, suppression
syntax, the repo-wide zero-findings gate, the jaxpr entry-point gate, and
the CLI. The jaxpr traces are lru_cached inside jaxpr_rules, so this file
and tests/test_precision.py share one trace per entry point per precision
across the pytest process (tier-1 timing)."""

from __future__ import annotations

import json
import os
import textwrap

import numpy as np
import pytest

from r2d2_tpu.analysis import ast_rules
from r2d2_tpu.analysis.findings import Finding, render_json, render_text

PKG_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "r2d2_tpu")


def lint(src: str, path: str = "learner.py"):
    """AST-lint a snippet as if it lived at `path` (hot-path by default so
    the host-sync rule is armed)."""
    findings, suppressed = ast_rules.analyze_source(textwrap.dedent(src), path)
    return findings, suppressed


def rules_of(findings):
    return sorted({f.rule for f in findings})


# ------------------------------------------------------------ findings model


def test_finding_model_and_rendering():
    a = Finding("r", "error", "b.py", 2, 0, "m2")
    b = Finding("r", "warning", "a.py", 9, 3, "m1", hint="do x")
    text = render_text([a, b])
    # stable sort: path first, so a.py renders before b.py
    assert text.index("a.py:9:3") < text.index("b.py:2:0")
    assert "hint: do x" in text and "2 findings" in text
    payload = json.loads(render_json([a, b]))
    assert payload["count"] == 2
    assert [f["path"] for f in payload["findings"]] == ["a.py", "b.py"]
    assert render_text([]) == "no findings"
    with pytest.raises(ValueError):
        Finding("r", "fatal", "a.py", 1, 0, "m")


# ------------------------------------------------------------- host-sync rule


def test_host_sync_fires_in_hot_loop():
    src = """
    import numpy as np
    def drain(xs):
        out = []
        for x in xs:
            out.append(x.item())
            out.append(np.asarray(x))
            flag = bool(x)
        return out
    """
    findings, _ = lint(src)
    assert rules_of(findings) == ["host-sync-in-hot-path"]
    assert len(findings) == 3


def test_host_sync_quiet_outside_loops_and_cold_files():
    hoisted = """
    import numpy as np
    def f(x):
        return np.asarray(x)  # no loop: one deliberate transfer
    """
    findings, _ = lint(hoisted)
    assert findings == []
    # same looped code in a non-hot-path module does not gate
    loop = """
    def g(xs):
        return [x.item() for x in xs] or [x.item() for x in xs]
    """
    in_loop = """
    def g(xs):
        out = []
        for x in xs:
            out.append(x.item())
        return out
    """
    findings, _ = lint(in_loop, path="utils/summaries.py")
    assert findings == []
    del loop


def test_host_sync_serve_dir_uses_serve_step_rule():
    # serve/* loop bodies migrated from host-sync-in-hot-path onto the
    # pipeline-aware serve rule: same loop coverage, serve-specific id
    src = """
    def g(xs):
        out = []
        for x in xs:
            out.append(x.item())
        return out
    """
    findings, _ = lint(src, path="r2d2_tpu/serve/loop.py")
    assert rules_of(findings) == ["blocking-host-sync-in-serve-step"]


def test_serve_step_rule_flags_stage_dispatch_function_wide():
    # inside _stage*/_dispatch*/_run_batch bodies the blocking calls are
    # banned even OUTSIDE loops — one materialization there collapses the
    # depth-2 overlap
    bad = """
    import numpy as np
    def _stage_and_dispatch(self, batch):
        q, action = self._step(batch)
        q_np = np.asarray(q)
        jax.block_until_ready(action)
        return q_np.item()
    """
    findings, _ = lint(bad, path="r2d2_tpu/serve/server.py")
    assert rules_of(findings) == ["blocking-host-sync-in-serve-step"]
    assert len(findings) == 3
    # float()/bool() stay loop-only: scalar host math at stage time is fine
    ok = """
    def _stage_and_dispatch(self, batch, eps):
        if float(eps.max()) > 0.0:
            return True
        return bool(len(batch))
    """
    findings, _ = lint(ok, path="r2d2_tpu/serve/server.py")
    assert findings == []


def test_serve_step_rule_exempts_completion_and_warmup():
    # materializing results is the completion worker's JOB (and warmup
    # deliberately blocks per bucket); neither side is flagged
    src = """
    import numpy as np
    def _complete(self, rec):
        q = np.asarray(rec.q)
        out = []
        for r in rec.batch:
            out.append(float(q[0]))
        return out
    def warmup(self):
        for b in self.buckets:
            jax.block_until_ready(self.step(b))
    """
    findings, _ = lint(src, path="r2d2_tpu/serve/server.py")
    assert findings == []


# ---------------------------------------------------------------- jit-in-loop


def test_jit_in_loop_fires():
    src = """
    import jax
    def f(fns, x):
        for fn in fns:
            x = jax.jit(fn)(x)
        return x
    """
    findings, _ = lint(src, path="utils/tools.py")
    assert rules_of(findings) == ["jit-in-loop"]
    assert findings[0].severity == "error"


def test_jit_outside_loop_clean():
    src = """
    import jax
    def f(fn, xs):
        jfn = jax.jit(fn)
        out = []
        for x in xs:
            out.append(jfn(x))
        return out
    """
    findings, _ = lint(src, path="utils/tools.py")
    assert findings == []


# ---------------------------------------------------- unhashable static args


def test_unhashable_static_arg_fires():
    src = """
    import functools, jax
    @functools.partial(jax.jit, static_argnames=("opts",))
    def f(x, opts=[]):
        return x
    """
    findings, _ = lint(src, path="ops/thing.py")
    assert rules_of(findings) == ["unhashable-static-arg"]


def test_hashable_static_arg_clean():
    src = """
    import functools, jax
    @functools.partial(jax.jit, static_argnames=("interpret",))
    def f(x, interpret=False):
        return x

    @functools.partial(jax.jit, static_argnums=(1,))
    def g(x, shape=(2, 2)):
        return x
    """
    findings, _ = lint(src, path="ops/thing.py")
    assert findings == []


# ------------------------------------------------------------- shape branches


def test_shape_branch_in_jit_fires():
    src = """
    import jax
    @jax.jit
    def f(x):
        if x.shape[0] > 2:
            x = x * 2
        return x
    """
    findings, _ = lint(src, path="ops/thing.py")
    assert rules_of(findings) == ["shape-branch-in-jit"]


def test_shape_guard_raise_is_exempt():
    src = """
    import jax
    @jax.jit
    def f(x):
        if x.shape[0] != 4:
            raise ValueError("bad shape")
        return x * 2
    """
    findings, _ = lint(src, path="ops/thing.py")
    assert findings == []


# ------------------------------------------------------------------- float64


def test_float64_device_ops_fire():
    src = """
    import jax, jax.numpy as jnp
    jax.config.update("jax_enable_x64", True)
    def f(x):
        y = jnp.asarray(x, jnp.float64)
        return jnp.zeros(3, dtype="float64") + y
    """
    findings, _ = lint(src, path="ops/thing.py")
    assert rules_of(findings) == ["float64-op"]
    assert len(findings) == 3  # x64 flag + jnp.float64 attr + dtype kwarg


def test_host_numpy_float64_is_fine():
    src = """
    import numpy as np
    def prefix(tree):
        # sum-tree/accumulator math is host-side and MAY be f64
        return np.cumsum(np.asarray(tree, np.float64))
    """
    findings, _ = lint(src, path="replay/sum_tree.py")
    assert findings == []


# --------------------------------------------------------------- fault sites


def test_unknown_fault_site_fires_known_clean():
    src = """
    from r2d2_tpu.utils.faults import fault_point
    def f():
        fault_point("trainer.update")
        fault_point("trainer.updaet")
    """
    findings, _ = lint(src, path="train.py")
    assert rules_of(findings) == ["unknown-fault-site"]
    assert "trainer.updaet" in findings[0].message


def test_dynamic_fault_site_fires():
    src = """
    from r2d2_tpu.utils.faults import fault_point
    def f(site):
        fault_point(site)
    """
    findings, _ = lint(src, path="train.py")
    assert rules_of(findings) == ["dynamic-fault-site"]


def test_serve_chaos_sites_are_known_to_lint():
    """The scenario engine's chaos verbs (replica stall/kill, slow client)
    are registered sites: referencing them lints clean, and a typo'd
    variant is flagged like any other unknown site."""
    src = """
    from r2d2_tpu.utils.faults import fault_point
    def f():
        fault_point("serve.replica_stall")
        fault_point("serve.replica_kill")
        fault_point("serve.slow_client")
    """
    findings, _ = lint(src, path="serve/scenarios.py")
    assert findings == []

    typo = """
    from r2d2_tpu.utils.faults import fault_point
    def f():
        fault_point("serve.replica_kil")
    """
    findings, _ = lint(typo, path="serve/scenarios.py")
    assert rules_of(findings) == ["unknown-fault-site"]
    assert "serve.replica_kil" in findings[0].message


def test_snapshot_missing_topology_fires_and_clean():
    src = """
    from r2d2_tpu.replay.snapshot import save_replay
    def f(replay, path):
        save_replay(replay, path)
    """
    findings, _ = lint(src, path="train.py")
    assert rules_of(findings) == ["snapshot-missing-topology"]
    assert "reshard" in findings[0].message

    clean = """
    from r2d2_tpu.replay.snapshot import save_replay, snapshot_topology
    def f(replay, path, kw):
        save_replay(replay, path, topology=snapshot_topology(replay))
        save_replay(replay, path, **kw)  # splat: statically unverifiable
    """
    findings, _ = lint(clean, path="train.py")
    assert findings == []


# ------------------------------------------------------------ lock discipline


def test_lock_discipline_fires_on_bare_write():
    src = """
    import threading
    class Store:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0
        def add(self, n):
            with self._lock:
                self.count += n
        def reset(self):
            self.count = 0
    """
    findings, _ = lint(src, path="replay/thing.py")
    assert rules_of(findings) == ["lock-discipline"]
    assert findings[0].line == 11


def test_lock_discipline_clean_when_guarded_everywhere():
    src = """
    import threading
    class Store:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0  # __init__ is pre-publication: bare is fine
        def add(self, n):
            with self._lock:
                self.count += n
        def reset(self):
            with self._lock:
                self.count = 0
    """
    findings, _ = lint(src, path="replay/thing.py")
    assert findings == []


def test_lock_discipline_covers_spill_tier_shape():
    """The session-tier threaded state (serve/state_cache.py): slab maps
    and counters written under the cache lock must never be written bare —
    the exact spill/promote bookkeeping shape, reduced."""
    src = """
    import threading
    class Cache:
        def __init__(self):
            self._lock = threading.Lock()
            self._spill_slots = {}
            self._spill_free = []
            self.spills = 0
        def demote(self, sid, row):
            with self._lock:
                self._spill_slots[sid] = row
                self.spills += 1
        def evict(self, sid):
            row = self._spill_slots.pop(sid, None)  # read: not flagged
            self.spills = 0  # bare write to guarded counter: flagged
    """
    findings, _ = lint(src, path="serve/state_cache.py")
    assert rules_of(findings) == ["lock-discipline"]
    assert "spills" in findings[0].message


def test_lock_discipline_covers_affinity_router_shape():
    """The session-affinity map (serve/multi.py SessionRouter): routing
    writes the sid->replica map and per-replica counts under the router
    lock from many client threads; a bare write races them."""
    src = """
    import threading
    class Router:
        def __init__(self):
            self._lock = threading.Lock()
            self._counts = [0, 0]
            self.routed = 0
        def route(self, sid):
            with self._lock:
                self.routed += 1
                self._counts = list(self._counts)
            return 0
        def forget(self, sid):
            self._counts = [0, 0]
    """
    findings, _ = lint(src, path="serve/multi.py")
    assert rules_of(findings) == ["lock-discipline"]
    assert "_counts" in findings[0].message


# ------------------------------------------------------ host-tree-in-hot-loop


def test_host_tree_in_hot_loop_fires():
    """A host SumTree call in a learner hot-loop body: under
    priority_plane='device' that work belongs in-jit (the superstep), so
    the lint flags each call site."""
    src = """
    def drain(self, batches):
        for b in batches:
            idx, w = self.tree.sample(64, self.rng)
            self.tree.update(idx, b)
            n = sum_tree.leaves()
        return n
    """
    findings, _ = lint(src, path="megastep.py")
    assert rules_of(findings) == ["host-tree-in-hot-loop"]
    assert len(findings) == 3
    assert "priority_plane" in findings[0].message


def test_host_tree_rule_ignores_device_ops_and_pytrees():
    """The in-jit device ops (dst.tree_update / device_sum_tree module
    functions), jax.tree pytree calls, and non-tree receivers never
    flag; cold files are exempt entirely; suppression works in place."""
    src = """
    import jax
    from r2d2_tpu.replay import device_sum_tree as dst
    def superstep(tree, rows, cache):
        for row in rows:
            tree = dst.tree_update(tree, 4, row[0], row[1], 0.9)
            flat = jax.tree.leaves(tree)
            cache.update(row)
        return tree
    """
    findings, _ = lint(src, path="megastep.py")
    assert [f for f in findings if f.rule == "host-tree-in-hot-loop"] == []
    hot = """
    def drain(self, xs):
        for x in xs:
            self.tree.update(x, x)  # r2d2: disable=host-tree-in-hot-loop
    """
    findings, suppressed = lint(hot, path="learner.py")
    assert findings == []
    assert [f.rule for f in suppressed] == ["host-tree-in-hot-loop"]
    # the same source in a cold (non-hot-path) module never arms the rule
    findings, _ = lint(hot.replace("  # r2d2: disable=host-tree-in-hot-loop", ""),
                       path="replay/control_plane.py")
    assert findings == []


# ------------------------------------------------- codec-decode-in-hot-loop


def test_codec_decode_in_hot_loop_fires():
    """decode/mmap calls inside loop bodies of hot-path or serve modules:
    the disk tier's contract is that decode happens on the staging thread,
    never per-iteration on the learner or serve step."""
    src = """
    import mmap
    import numpy as np
    from r2d2_tpu.replay.codec import decode_field
    def drain(self, blobs, paths):
        out = []
        for blob in blobs:
            arr, _ = decode_field(blob)
            out.append(arr)
        while paths:
            m = np.memmap(paths.pop(), dtype=np.uint8, mode="r")
            out.append(m)
        return out
    """
    findings, _ = lint(src)  # learner.py: hot path
    hits = [f for f in findings if f.rule == "codec-decode-in-hot-loop"]
    assert len(hits) == 2
    assert all(f.severity == "warning" for f in hits)
    # serve modules are equally latency-bound
    findings, _ = lint(src, path="r2d2_tpu/serve/server.py")
    assert [f.rule for f in findings
            if f.rule == "codec-decode-in-hot-loop"] != []


def test_codec_decode_quiet_outside_loops_cold_files_and_suppressed():
    hoisted = """
    from r2d2_tpu.replay.codec import decode_field
    def load_one(blob):
        arr, _ = decode_field(blob)  # one deliberate decode, no loop
        return arr
    """
    findings, _ = lint(hoisted)
    assert [f for f in findings if f.rule == "codec-decode-in-hot-loop"] == []
    # the staging thread / disk tier itself decodes in loops BY DESIGN:
    # cold modules never arm the rule
    looped = """
    from r2d2_tpu.replay.codec import decode_field
    def gather(self, blobs):
        return [decode_field(b)[0] for b in blobs] or [
            decode_field(b)[0] for b in blobs]
    """
    in_loop = """
    from r2d2_tpu.replay.codec import decode_field
    def gather(self, blobs):
        out = []
        for b in blobs:
            arr, _ = decode_field(b)
            out.append(arr)
        return out
    """
    findings, _ = lint(in_loop, path="r2d2_tpu/replay/disk_tier.py")
    assert findings == []
    del looped
    # in-place suppression for the deliberate exception
    sup = """
    from r2d2_tpu.replay.codec import decode_field
    def drain(self, blobs):
        for b in blobs:
            yield decode_field(b)  # r2d2: disable=codec-decode-in-hot-loop
    """
    findings, suppressed = lint(sup)
    assert findings == []
    assert [f.rule for f in suppressed] == ["codec-decode-in-hot-loop"]


# ---------------------------------------------------------------- suppression


def test_suppression_same_line_and_line_above():
    src = """
    def f(xs):
        out = []
        for x in xs:
            out.append(x.item())  # r2d2: disable=host-sync-in-hot-path
            # r2d2: disable=host-sync-in-hot-path
            out.append(x.item())
            out.append(x.item())
        return out
    """
    findings, suppressed = lint(src)
    assert len(findings) == 1  # only the third, uncommented call gates
    assert len(suppressed) == 2
    assert all(f.rule == "host-sync-in-hot-path" for f in suppressed)


def test_suppression_disable_all_and_wrong_rule():
    src = """
    def f(xs):
        out = []
        for x in xs:
            out.append(x.item())  # r2d2: disable=all
            out.append(x.item())  # r2d2: disable=float64-op
        return out
    """
    findings, suppressed = lint(src)
    assert len(findings) == 1  # a disable for a DIFFERENT rule doesn't hide
    assert len(suppressed) == 1


# ------------------------------------------------------------ repo-wide gates


def test_repo_wide_zero_findings():
    """The shipped tree is lint-clean: every deliberate exception carries
    its suppression comment in place. This is the tier-1 analysis gate."""
    findings, suppressed = ast_rules.analyze_paths([PKG_DIR])
    assert findings == [], render_text(findings)
    # suppressions exist and each one actually masks a real finding
    assert suppressed, "expected deliberate, documented suppressions in-tree"


def test_jaxpr_entry_point_gate():
    """Every canonical entry point at both precisions passes every jaxpr
    checker — dtype policy, fp32 islands, donation, store-field dtypes."""
    from r2d2_tpu.analysis import jaxpr_rules

    findings = jaxpr_rules.scan_entry_points()
    assert findings == [], render_text(findings)


def test_jaxpr_superstep_gate_both_precisions():
    """The N×K priority superstep traces clean at fp32 AND bf16: no f64
    anywhere (the device tree is the f32 arm of the parity contract),
    fp32 path bf16-free, bf16 path keeps its islands, and the donated
    (TrainState, tree) pair aliases fully (ISSUE 9 acceptance)."""
    from r2d2_tpu.analysis import jaxpr_rules

    for precision in ("fp32", "bf16"):
        findings = jaxpr_rules.scan_superstep(precision)
        assert findings == [], render_text(findings)
    # the gate actually traces the superstep program: the tree-descent
    # gathers and the train scan both appear in the jaxpr text
    text = jaxpr_rules.priority_superstep_jaxpr("fp32")
    assert "scan" in text and "f32[" in text


# --------------------------------------------------- jaxpr checker negatives


def test_jaxpr_text_checkers_fire_on_synthetic_programs():
    from r2d2_tpu.analysis import jaxpr_rules as j

    assert rules_of(j.check_no_float64("a:f64[3] = add b c", "t")) == ["jaxpr-float64"]
    assert j.check_no_float64("a:f32[3] = add b c", "t") == []
    assert rules_of(j.check_no_bf16("a:bf16[3] = mul b c", "t")) == ["jaxpr-bf16-in-fp32"]
    assert j.check_no_bf16("a:f32[3] = mul b c", "t") == []
    # healthy bf16 program: both dtypes present
    assert j.check_fp32_island("a:bf16[3] b:f32[]", "t") == []
    assert rules_of(j.check_fp32_island("a:f32[3]", "t")) == ["jaxpr-no-bf16-under-bf16"]
    assert rules_of(j.check_fp32_island("a:bf16[3]", "t")) == ["jaxpr-missing-fp32-island"]
    # host-callback checker: any callback primitive inside a hot step
    assert j.check_no_host_callback("a:f32[2] = add b c", "t") == []
    for prim in ("pure_callback", "io_callback", "debug_callback"):
        assert rules_of(
            j.check_no_host_callback(f"a:f32[2] = {prim}[...] b", "t")
        ) == ["jaxpr-host-callback"]


def test_multi_serve_step_gate():
    """Every replica of the dp=2 serve fleet traces to an identical,
    callback-free, f64-free program at both precisions (plus the int8
    arm) — the static half of the multi-chip bit-parity story."""
    import jax

    from r2d2_tpu.analysis import jaxpr_rules as j

    if len(jax.local_devices()) < 2:
        pytest.skip("needs >= 2 devices")
    for precision in ("fp32", "bf16"):
        findings = j.scan_multi_serve_step(precision)
        assert findings == [], render_text(findings)
    findings = j.scan_multi_serve_step("fp32", "int8")
    assert findings == [], render_text(findings)


def test_multitask_train_step_gate_both_precisions():
    """The task-conditioned stacked train step (ISSUE 13) traces clean at
    fp32 AND bf16, and the fp32 trace really carries the (K, B) int32
    task leaf through the batch scan — the head is task-conditioned, not
    silently single-task."""
    from r2d2_tpu.analysis import jaxpr_rules

    for precision in ("fp32", "bf16"):
        findings = jaxpr_rules.scan_multitask_train_step(precision)
        assert findings == [], render_text(findings)
    text = jaxpr_rules.multitask_train_step_jaxpr("fp32")
    assert "scan" in text and "i32[" in text


def test_manual_train_step_gate_both_precisions():
    """The explicitly-partitioned tp x fsdp train step (ISSUE 16:
    learner.make_manual_train_step on the dp2 x tp2 x fsdp2 mesh) traces
    clean at fp32 AND bf16 — no f64, no host callbacks, fp32 plane
    bf16-free, bf16 plane keeps its islands, full TrainState donation —
    and the trace shows the EXPLICIT collective program (the whole point
    of leaving GSPMD): the shard_map body with gate-seam all_gathers, the
    psum gradient reductions, and the ZeRO-2 reduce-scatter."""
    from r2d2_tpu.analysis import jaxpr_rules

    for precision in ("fp32", "bf16"):
        findings = jaxpr_rules.scan_manual_train_step(precision)
        assert findings == [], render_text(findings)
    text = jaxpr_rules.manual_train_step_jaxpr("fp32", 2, 2, 2)
    assert "shard_map" in text
    assert "all_gather" in text  # tp gate seam + ZeRO-2 update re-gather
    assert "psum" in text  # data-axis (and replicated-leaf tp) reductions
    assert "reduce_scatter" in text  # ZeRO-2 grads onto moment shards


def test_raw_shard_map_import_fires_and_shim_exempt():
    """Every shard_map must come through parallel/jax_compat.py (the one
    wrapper stating the manual-axis convention): a raw import anywhere
    else is an error finding, in every spelling; the wrapper itself and
    the blessed re-export are clean."""
    for src in (
        "from jax import shard_map\n",
        "from jax.experimental.shard_map import shard_map\n",
        "from jax.experimental import shard_map\n",
        "import jax.experimental.shard_map as shmap\n",
    ):
        findings, _ = lint(src)
        assert rules_of(findings) == ["raw-shard-map-import"], src
    # the wrapper file is the one place the raw import is the point
    findings, _ = lint(
        "from jax import shard_map as _shard_map\n",
        path="parallel/jax_compat.py",
    )
    assert findings == []
    # the blessed path never fires
    findings, _ = lint("from r2d2_tpu.parallel.jax_compat import shard_map\n")
    assert findings == []


def test_kernel_launch_count_checker_fires_on_budget_overrun():
    """Negative fixture for the per-arm launch budget: a program with one
    launch too many (the classic regression: dWh split back out into a
    4th launch) is a finding; the exact budget is clean."""
    import jax

    from r2d2_tpu.analysis import jaxpr_rules as j

    unroll = j.fused_unroll_jaxpr("fp32")  # one launch, cached by the gate
    assert rules_of(j.check_kernel_launch_count(unroll, "t", 3, "step")) == [
        "jaxpr-kernel-launch-count"
    ]
    assert j.check_kernel_launch_count(unroll, "t", 1, "step") == []


def test_launch_counter_counts_call_sites_not_printed_definitions():
    """jax prints a jitted sub-function that is called twice with equal
    shapes ONCE (`let f = {...}`) and names it at both call sites, so a
    text count under-reports — the train step's online and target forward
    share one kernel wrapper. The counter walks equations instead."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from r2d2_tpu.analysis.jaxpr_rules import count_pallas_launches

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    @jax.jit
    def launch(x):
        return pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=True,
        )(x)

    def twice_then_scan(x):
        y = launch(x) + launch(x + 1.0)
        # a loop body is one static launch site whatever its trip count
        return jax.lax.scan(lambda c, _: (launch(c), None), y, None, length=4)[0]

    jaxpr = jax.make_jaxpr(twice_then_scan)(jnp.zeros((8, 128), jnp.float32))
    assert count_pallas_launches(jaxpr) == 3
    assert str(jaxpr).count("pallas_call") < 3  # what the text gate saw


def test_host_sync_fires_in_multitask_serve_batch_loop():
    """The per-request task gather in serve _run_batch is the shape most
    likely to regress into a host sync: device-array conversion inside the
    per-request loop. The looped form fires; the hoisted form (what
    server.py actually does) stays clean."""
    bad = """
    import numpy as np
    def run_batch(batch, q):
        tasks = []
        for r in batch:
            tasks.append(np.asarray(r.task))
            tasks.append(q.item())
        return tasks
    """
    findings, _ = lint(bad, path="r2d2_tpu/serve/server.py")
    assert rules_of(findings) == ["blocking-host-sync-in-serve-step"]
    assert len(findings) == 2
    good = """
    import numpy as np
    def run_batch(batch, dims):
        task_full = np.zeros(len(batch), np.int32)
        for i, r in enumerate(batch):
            task_full[i] = r.task
        bounds = np.asarray(dims, np.int64)
        return task_full, bounds
    """
    findings, _ = lint(good, path="r2d2_tpu/serve/server.py")
    assert findings == []


def test_donation_checker_fires_on_mismatch():
    import jax

    from r2d2_tpu.analysis import jaxpr_rules as j

    sds = jax.ShapeDtypeStruct
    ok = j.compare_donated_leaves(
        {"w": sds((4, 4), np.float32)}, {"w": sds((4, 4), np.float32)}, "t"
    )
    assert ok == []
    bad = j.compare_donated_leaves(
        {"w": sds((4, 4), np.float32)}, {"w": sds((4, 4), np.float16)}, "t"
    )
    assert rules_of(bad) == ["jaxpr-donation-mismatch"]


def test_store_field_checker_fires_on_pr4_bug_class():
    """The exact PR-4 shape: a float32 hidden slab padded for a bf16
    store. The shared checker must catch it."""
    from r2d2_tpu.analysis import jaxpr_rules as j

    specs = {"hidden": ((2, 2, 8), np.dtype("bfloat16"))}
    good = {"hidden": np.zeros((2, 2, 8), np.dtype("bfloat16"))}
    bad = {"hidden": np.zeros((2, 2, 8), np.float32)}
    assert j.compare_store_fields(good, specs, "t") == []
    assert rules_of(j.compare_store_fields(bad, specs, "t")) == [
        "jaxpr-store-field-mismatch"
    ]


def test_trace_budget_checker():
    from r2d2_tpu.analysis.jaxpr_rules import check_trace_budget

    assert check_trace_budget(2, (2, 4)) == []
    assert rules_of(check_trace_budget(3, (2, 4))) == ["jaxpr-trace-budget"]


# ------------------------------------------------------------------------ CLI


def _write(tmp_path, name, src):
    p = tmp_path / name
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(src))
    return str(p)


def test_cli_text_and_exit_codes(tmp_path, capsys):
    from r2d2_tpu.analysis.cli import main

    dirty = _write(
        tmp_path, "learner.py",
        """
        def f(xs):
            out = []
            for x in xs:
                out.append(x.item())
            return out
        """,
    )
    assert main([dirty]) == 1
    out = capsys.readouterr().out
    assert "host-sync-in-hot-path" in out and "1 finding" in out

    clean = _write(tmp_path, "clean.py", "x = 1\n")
    assert main([clean]) == 0
    assert "no findings" in capsys.readouterr().out


def test_cli_json_stable_sorted(tmp_path, capsys):
    from r2d2_tpu.analysis.cli import main

    _write(
        tmp_path, "serve/b.py",
        """
        def f(xs):
            for x in xs:
                y = x.item()
        """,
    )
    _write(
        tmp_path, "serve/a.py",
        """
        def f(xs):
            for x in xs:
                y = x.item()
                z = x.item()
        """,
    )
    assert main(["--format", "json", str(tmp_path)]) == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload["count"] == 3
    keys = [
        (f["path"], f["line"], f["col"], f["rule"]) for f in payload["findings"]
    ]
    assert keys == sorted(keys)  # stable-sorted for diffing
    assert keys[0][0].endswith("a.py")


def test_cli_changed_only(tmp_path, capsys, monkeypatch):
    from r2d2_tpu.analysis import cli

    dirty = _write(
        tmp_path, "learner.py",
        """
        def f(xs):
            for x in xs:
                y = x.item()
        """,
    )
    monkeypatch.setattr(cli, "_changed_files", lambda root: [dirty])
    assert cli.main(["--changed-only"]) == 1
    assert "host-sync-in-hot-path" in capsys.readouterr().out
    monkeypatch.setattr(cli, "_changed_files", lambda root: [])
    assert cli.main(["--changed-only"]) == 0


def test_cli_syntax_error_reported(tmp_path, capsys):
    from r2d2_tpu.analysis.cli import main

    bad = _write(tmp_path, "broken.py", "def f(:\n")
    assert main([bad]) == 1
    assert "syntax-error" in capsys.readouterr().out


# ----------------------------------------------------- findings determinism


def test_findings_dedupe_overlapping_scans():
    """Identical findings from overlapping scans collapse to one record in
    every renderer — the SARIF/JSON outputs must be diff-stable in CI."""
    from r2d2_tpu.analysis.findings import stable_sort

    f = Finding("r", "error", "a.py", 1, 0, "m")
    g = Finding("r", "error", "a.py", 1, 0, "m")
    distinct = Finding("r", "error", "a.py", 1, 0, "other message")
    assert stable_sort([f, g]) == [f]
    assert len(stable_sort([f, g, distinct])) == 2
    assert "1 finding" in render_text([f, g])
    assert json.loads(render_json([f, g, f]))["count"] == 1


def test_sarif_rendering():
    from r2d2_tpu.analysis.findings import render_sarif

    a = Finding("rule-b", "error", "b.py", 2, 4, "m", hint="h")
    b = Finding("rule-a", "info", "<jaxpr:x>", 0, 0, "m2")
    doc = json.loads(render_sarif([a, b, a]))  # dupe collapses
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "r2d2-analyze"
    # stable rule ids, sorted
    assert [r["id"] for r in run["tool"]["driver"]["rules"]] == [
        "rule-a", "rule-b"
    ]
    assert len(run["results"]) == 2
    by_rule = {r["ruleId"]: r for r in run["results"]}
    assert by_rule["rule-a"]["level"] == "note"  # info maps to SARIF note
    assert by_rule["rule-b"]["level"] == "error"
    # jaxpr pseudo-paths keep a positive startLine (SARIF requirement)
    region = by_rule["rule-a"]["locations"][0]["physicalLocation"]["region"]
    assert region["startLine"] == 1
    loc = by_rule["rule-b"]["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == "b.py"
    assert loc["region"] == {"startLine": 2, "startColumn": 5}  # col is 1-based
    assert "(hint: h)" in by_rule["rule-b"]["message"]["text"]


def test_cli_sarif_format(tmp_path, capsys):
    from r2d2_tpu.analysis.cli import main

    dirty = _write(
        tmp_path, "learner.py",
        """
        def f(xs):
            for x in xs:
                y = x.item()
        """,
    )
    assert main(["--format", "sarif", dirty]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["runs"][0]["results"][0]["ruleId"] == "host-sync-in-hot-path"


# ------------------------------------------------------- jaxpr result cache


def test_jaxpr_source_fingerprint_stable():
    from r2d2_tpu.analysis import jaxpr_rules as j

    files = j.entry_point_source_files()
    # the canonical traced surfaces are all in the closure
    rels = {os.path.relpath(p, PKG_DIR).replace(os.sep, "/") for p in files}
    for must in ("learner.py", "megastep.py", "serve/server.py",
                 "serve/multi.py", "replay/block.py",
                 "analysis/jaxpr_rules.py"):
        assert must in rels, must
    assert j.source_fingerprint() == j.source_fingerprint()


def test_jaxpr_cache_roundtrip(tmp_path, monkeypatch):
    """scan_entry_points_cached: first call scans and writes the cache,
    second call is served from it (no retrace), a fingerprint mismatch
    forces a rescan, a corrupt cache falls through to a real scan."""
    from r2d2_tpu.analysis import jaxpr_rules as j

    calls = []

    def fake_scan(precisions=("fp32", "bf16")):
        calls.append(1)
        return [Finding("jaxpr-float64", "error", "<jaxpr:x>", 0, 0, "m")]

    monkeypatch.setattr(j, "scan_entry_points", fake_scan)
    cache = str(tmp_path / "cache.json")
    out1 = j.scan_entry_points_cached(cache)
    assert len(calls) == 1 and out1[0].rule == "jaxpr-float64"
    out2 = j.scan_entry_points_cached(cache)
    assert len(calls) == 1  # cache hit: no retrace
    assert out2 == out1
    with open(cache, encoding="utf-8") as fh:
        data = json.load(fh)
    data["fingerprint"] = "stale"
    with open(cache, "w", encoding="utf-8") as fh:
        json.dump(data, fh)
    j.scan_entry_points_cached(cache)
    assert len(calls) == 2  # source hash mismatch -> rescan
    with open(cache, "w", encoding="utf-8") as fh:
        fh.write("not json")
    j.scan_entry_points_cached(cache)
    assert len(calls) == 3  # corrupt cache -> rescan


def test_cli_changed_only_jaxpr_uses_cache(monkeypatch, capsys):
    from r2d2_tpu.analysis import cli, jaxpr_rules

    monkeypatch.setattr(cli, "_changed_files", lambda root: [])
    seen = {}

    def fake_cached(path):
        seen["path"] = path
        return []

    monkeypatch.setattr(jaxpr_rules, "scan_entry_points_cached", fake_cached)
    assert cli.main(["--changed-only", "--jaxpr"]) == 0
    assert seen["path"].endswith(".r2d2_jaxpr_cache.json")
    capsys.readouterr()


# -------------------------------------------------------- concurrency pass


def conc(tmp_path, files):
    """Run the interprocedural concurrency pass over a fixture package."""
    from r2d2_tpu.analysis import concurrency

    for name, src in files.items():
        _write(tmp_path, name, src)
    return concurrency.analyze_paths([str(tmp_path)])


def test_lock_order_cycle_fires_and_consistent_order_clean(tmp_path):
    cyclic = """
    import threading
    class S:
        def __init__(self):
            self._a = threading.Lock()
            self._b = threading.Lock()
        def fwd(self):
            with self._a:
                with self._b:
                    pass
        def rev(self):
            with self._b:
                with self._a:
                    pass
    """
    findings, _ = conc(tmp_path / "pos", {"mod.py": cyclic})
    assert rules_of(findings) == ["lock-order-cycle"]
    assert "S._a" in findings[0].message and "S._b" in findings[0].message

    consistent = cyclic.replace(
        "with self._b:\n                with self._a:",
        "with self._a:\n                with self._b:",
    )
    findings, _ = conc(tmp_path / "neg", {"mod.py": consistent})
    assert findings == []


def test_nonreentrant_reacquire_is_deadlock_rlock_is_not(tmp_path):
    """Holding a plain Lock while calling a helper that re-acquires it is
    a guaranteed self-deadlock (threading.Lock is non-reentrant); the same
    shape on an RLock is legal."""
    src = """
    import threading
    class T:
        def __init__(self):
            self._lock = threading.Lock()
        def _helper(self):
            with self._lock:
                pass
        def run(self):
            with self._lock:
                self._helper()
    """
    findings, _ = conc(tmp_path / "pos", {"mod.py": src})
    assert rules_of(findings) == ["lock-order-cycle"]
    assert "non-reentrant" in findings[0].message

    findings, _ = conc(
        tmp_path / "neg",
        {"mod.py": src.replace("threading.Lock()", "threading.RLock()")},
    )
    assert findings == []


def test_cross_thread_unguarded_write_fires(tmp_path):
    src = """
    import threading
    class W:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0
            self._t = threading.Thread(target=self._loop, daemon=True)
        def _loop(self):
            while True:
                self.count += 1
        def bump(self):
            self.count += 1
    """
    findings, _ = conc(tmp_path, {"mod.py": src})
    assert rules_of(findings) == ["cross-thread-unguarded-write"]
    assert all(f.severity == "error" for f in findings)
    assert "W.count" in findings[0].message
    assert "2 thread roots" in findings[0].message


def test_cross_thread_write_clean_when_guarded_everywhere(tmp_path):
    src = """
    import threading
    class W:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0
            self._t = threading.Thread(target=self._loop, daemon=True)
        def _loop(self):
            while True:
                with self._lock:
                    self.count += 1
        def bump(self):
            with self._lock:
                self.count += 1
    """
    findings, _ = conc(tmp_path, {"mod.py": src})
    assert findings == []


def test_cross_thread_write_exempts_threadsafe_and_unthreaded(tmp_path):
    """queue.Queue/Event attrs are internally synchronized; a class with
    no lock and no thread spawn is presumed single-thread-confined."""
    src = """
    import queue
    import threading
    class Plumbing:
        def __init__(self):
            self._q = queue.Queue()
            self._stop = threading.Event()
            self._t = threading.Thread(target=self._loop, daemon=True)
            self._lock = threading.Lock()
        def _loop(self):
            self._q.put(1)
        def close(self):
            self._stop.set()
    class PlainCounter:
        def fail(self):
            self.failures = getattr(self, "failures", 0) + 1
        def reset(self):
            self.failures = 0
    """
    findings, _ = conc(tmp_path, {"mod.py": src})
    assert findings == []


def test_guarded_by_def_annotation_asserts_contract(tmp_path):
    """The def-line `# r2d2: guarded-by(<lock>)` form declares a caller-
    holds-lock contract: annotated helpers' writes count as guarded, and
    the annotation is CHECKED — re-acquiring the same non-reentrant lock
    inside is flagged as a deadlock."""
    clean = """
    import threading
    class W:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0
            self._t = threading.Thread(target=self._loop, daemon=True)
        def _loop(self):
            with self._lock:
                self._bump()
        # r2d2: guarded-by(_lock)
        def _bump(self):
            self.count += 1
        def bump(self):
            with self._lock:
                self._bump()
    """
    findings, _ = conc(tmp_path / "clean", {"mod.py": clean})
    assert findings == []

    checked = clean.replace(
        "def _bump(self):\n            self.count += 1",
        "def _bump(self):\n            with self._lock:\n"
        "                self.count += 1",
    )
    findings, _ = conc(tmp_path / "checked", {"mod.py": checked})
    assert "lock-order-cycle" in rules_of(findings)


def test_guarded_by_write_line_annotation(tmp_path):
    src = """
    import threading
    class W:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0
            self._t = threading.Thread(target=self._loop, daemon=True)
        def _loop(self):
            with self._lock:
                self.count += 1
        def external(self):
            self.count += 1  # r2d2: guarded-by(_lock)
    """
    findings, _ = conc(tmp_path, {"mod.py": src})
    assert findings == []


def test_guarded_by_silences_ast_lock_discipline():
    """The annotation reuses the suppression machinery in the AST lint:
    an annotated write is moved to suppressed, not reported."""
    src = """
    import threading
    class Store:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0
        def add(self):
            with self._lock:
                self.count += 1
        # r2d2: guarded-by(_lock)
        def reset(self):
            self.count = 0
    """
    findings, suppressed = lint(src, path="replay/thing.py")
    assert findings == []
    assert [f.rule for f in suppressed] == ["lock-discipline"]


def test_blocking_under_lock_fires_direct_and_interprocedural(tmp_path):
    src = """
    import threading
    import time
    class B:
        def __init__(self):
            self._lock = threading.Lock()
        def slow(self):
            with self._lock:
                time.sleep(1.0)
        def outer(self):
            with self._lock:
                self._inner()
        def _inner(self):
            time.sleep(0.1)
    """
    findings, _ = conc(tmp_path, {"mod.py": src})
    assert rules_of(findings) == ["blocking-under-lock"]
    assert len(findings) == 2
    assert all(f.severity == "warning" for f in findings)
    # the interprocedural one names the caller-holds contract
    inner = [f for f in findings if "_inner" in f.message]
    assert inner and "caller-holds-lock contract" in inner[0].message


def test_blocking_outside_lock_clean(tmp_path):
    src = """
    import threading
    import time
    class B:
        def __init__(self):
            self._lock = threading.Lock()
        def ok(self):
            with self._lock:
                n = 1
            time.sleep(0.1)
            return n
    """
    findings, _ = conc(tmp_path, {"mod.py": src})
    assert findings == []


def test_concurrency_suppression_in_place(tmp_path):
    src = """
    import threading
    class W:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0
            self._t = threading.Thread(target=self._loop, daemon=True)
        def _loop(self):
            self.count += 1  # r2d2: disable=cross-thread-unguarded-write
        def bump(self):
            # r2d2: disable=cross-thread-unguarded-write
            self.count += 1
    """
    findings, suppressed = conc(tmp_path, {"mod.py": src})
    assert findings == []
    assert {f.rule for f in suppressed} == {"cross-thread-unguarded-write"}


def test_thread_root_inventory_repo_wide():
    """The inventory covers every threaded plane: raw Thread constructions,
    supervision spawn sites (body AND restart hook run on the worker),
    socketserver handlers, and the synthetic main root."""
    from r2d2_tpu.analysis import concurrency

    roots = concurrency.thread_roots([PKG_DIR])
    kinds = {r.kind for r in roots}
    assert {"thread", "spawn", "handler", "main"} <= kinds
    spawn_names = {r.name for r in roots if r.kind == "spawn"}
    assert "ckpt-watcher-multi" in spawn_names  # the fleet watcher
    # the PR 11 degradation controller is a supervised worker like every
    # other serve-plane thread — it must be inventoried, not invisible
    assert any(n.startswith("degrade-controller") for n in spawn_names), (
        sorted(spawn_names)
    )
    # the PR 12 live-loop workers (tap drain + replay ingest) run under the
    # same supervision contract and must be inventoried with the fleet
    assert "liveloop-tap" in spawn_names, sorted(spawn_names)
    assert "liveloop-ingest" in spawn_names, sorted(spawn_names)
    # the depth-2 serve pipeline's halves: the staging/dispatching serve
    # loop and the per-replica completion worker. Both spawn with a
    # replica-suffix BinOp name ("serve-loop" + suffix) — the analyzer
    # extracts the stable left constant, so neither may go inventoried
    # as an anonymous root.
    assert "serve-loop" in spawn_names, sorted(spawn_names)
    assert "serve-complete" in spawn_names, sorted(spawn_names)
    # the PR 17 elastic autoscaler is its own supervised root — scale
    # events block for whole seconds (warmup, migration) and must never
    # share a worker with the sub-second degrade/watch ticks
    assert "autoscaler" in spawn_names, sorted(spawn_names)
    paths = {os.path.relpath(r.path, PKG_DIR) for r in roots if r.path}
    for mod in ("serve/server.py", "serve/multi.py", "serve/client.py",
                "serve/scenarios.py", "serve/autoscale.py",
                "liveloop/loop.py",
                "utils/supervision.py", "replay/tiered_store.py", "train.py"):
        assert mod in paths, f"no thread root found in {mod}"


def test_concurrency_repo_wide_gate():
    """The shipped tree has zero unsuppressed concurrency findings: no
    lock-order cycles, no cross-thread unguarded writes, nothing blocking
    under a lock. Deliberate exceptions (the state-cache single-writer
    contract) are annotated in place. This is the tier-1 race gate."""
    from r2d2_tpu.analysis import concurrency

    findings, suppressed = concurrency.analyze_paths([PKG_DIR])
    assert findings == [], render_text(findings)
    assert suppressed, "expected documented single-writer exceptions in-tree"


def test_cli_concurrency_flag(capsys):
    from r2d2_tpu.analysis.cli import main

    assert main(["--concurrency", PKG_DIR]) == 0
    assert "no findings" in capsys.readouterr().out


def test_seeded_mutation_trips_concurrency_gate(tmp_path):
    """Delete ONE lock acquisition from the real serve/state_cache.py
    source (the assign fast path) inside a fixture package that drives the
    cache from two thread roots — the gate must trip. The unmutated copy
    of the same fixture is clean, so the trip is attributable to exactly
    the removed acquisition."""
    from r2d2_tpu.analysis import concurrency

    with open(os.path.join(PKG_DIR, "serve", "state_cache.py"),
              encoding="utf-8") as fh:
        real = fh.read()
    driver = """
    import threading

    from cachemod import RecurrentStateCache

    class Driver:
        def __init__(self):
            self.cache = RecurrentStateCache(4, 8)
            self._thread = threading.Thread(target=self._loop, daemon=True)
        def _loop(self):
            while True:
                self.cache.assign(["s"])
        def evict(self, sid):
            self.cache.evict(sid)
    """
    intact = tmp_path / "intact"
    _write(intact, "cachemod.py", real)
    _write(intact, "driver.py", driver)
    findings, _ = concurrency.analyze_paths([str(intact)])
    assert findings == [], render_text(findings)

    i = real.index("def assign")
    j = real.index("with self._lock:", i)
    mutated = real[:j] + "if True:" + real[j + len("with self._lock:"):]
    broken = tmp_path / "mutated"
    _write(broken, "cachemod.py", mutated)
    _write(broken, "driver.py", driver)
    findings, _ = concurrency.analyze_paths([str(broken)])
    assert findings, "removing a lock acquisition must trip the gate"
    assert "cross-thread-unguarded-write" in rules_of(findings)
    assert any("cachemod.py" in f.path for f in findings)


# -------------------------------------------------------- determinism pass


def det(tmp_path, files):
    """Run the interprocedural determinism pass over a fixture package."""
    from r2d2_tpu.analysis import determinism

    for name, src in files.items():
        _write(tmp_path, name, src)
    return determinism.analyze_paths([str(tmp_path)])


def test_resume_complete_class_is_clean_and_uncaptured_fires(tmp_path):
    complete = """
    class Acc:
        def __init__(self):
            self.total = 0.0
            self.n = 0
        def add(self, x):
            self.total += x
            self.n += 1
        def carry_state(self):
            return {"total": self.total, "n": self.n}
        def restore_carry(self, d):
            self.total = d["total"]
            self.n = d["n"]
    """
    findings, _ = det(tmp_path / "ok", {"mod.py": complete})
    assert findings == [], render_text(findings)

    # drop `n` from the carry dict: mutated state that no snapshot carries
    uncaptured = complete.replace('"total": self.total, "n": self.n', '"total": self.total')
    findings, _ = det(tmp_path / "pos", {"mod.py": uncaptured})
    assert rules_of(findings) == ["resume-uncaptured-field"]
    assert "Acc.n" in findings[0].message


def test_unrestored_field_fires(tmp_path):
    src = """
    class Acc:
        def __init__(self):
            self.n = 0
        def add(self):
            self.n += 1
        def carry_state(self):
            return {"n": self.n}
        def restore_carry(self, d):
            pass
    """
    findings, _ = det(tmp_path, {"mod.py": src})
    assert rules_of(findings) == ["resume-unrestored-field"]
    assert "Acc.n" in findings[0].message


def test_unpack_and_subscript_mutations_inventoried(tmp_path):
    """Tuple-unpacking targets (the collector's `(..., self.env_state,
    self.key) = ...` idiom) and subscript stores both count as mutations."""
    src = """
    class C:
        def __init__(self):
            self.a = 0
            self.b = 0
            self.d = {}
        def step(self, f):
            (self.a, self.b) = f()
            self.d["k"] = self.a
        def capture_pending(self):
            return {"a": self.a}
        def restore_pending(self, d):
            self.a = d["a"]
    """
    findings, _ = det(tmp_path, {"mod.py": src})
    assert rules_of(findings) == ["resume-uncaptured-field"]
    flagged = {f.message.split(" ")[0] for f in findings}
    assert flagged == {"C.b", "C.d"}


def test_ephemeral_exempts_and_is_inventoried(tmp_path):
    """An ephemeral-annotated attribute is exempt, but the would-be
    finding lands in the suppressed list — the exemption inventory stays
    visible to the gate instead of vanishing."""
    src = """
    class Tap:
        def __init__(self):
            self.blocks = []
            # r2d2: ephemeral(monitoring counter; restarts at 0 on resume)
            self.emitted = 0
        def push(self, b):
            self.blocks.append(b)
            self.blocks = self.blocks[-4:]
            self.emitted += 1
        def carry_state(self):
            return {"blocks": list(self.blocks)}
        def restore_carry(self, d):
            self.blocks = list(d["blocks"])
    """
    findings, suppressed = det(tmp_path, {"mod.py": src})
    assert findings == [], render_text(findings)
    assert [f.rule for f in suppressed] == ["resume-uncaptured-field"]
    assert "Tap.emitted" in suppressed[0].message


def test_bad_ephemeral_annotations_flagged(tmp_path):
    empty = """
    class S:
        def __init__(self):
            # r2d2: ephemeral()
            self.n = 0
        def bump(self):
            self.n += 1
        def carry_state(self):
            return {}
        def restore_carry(self, d):
            pass
    """
    findings, _ = det(tmp_path / "empty", {"mod.py": empty})
    assert rules_of(findings) == ["bad-ephemeral-annotation"]
    assert "empty reason" in findings[0].message

    stray = '''
    """Docs may mention # r2d2: ephemeral(x) without it being an annotation."""
    class P:
        def carry_state(self):
            return {}
        def restore_carry(self, d):
            pass
        def go(self):
            # r2d2: ephemeral(this line assigns no attribute)
            y = 1
            return y
    '''
    findings, _ = det(tmp_path / "stray", {"mod.py": stray})
    assert rules_of(findings) == ["bad-ephemeral-annotation"]
    assert len(findings) == 1  # the docstring mention is NOT an annotation
    assert "attaches to no" in findings[0].message


def test_wallclock_taint_direct_and_audit_allowlist(tmp_path):
    hot = """
    import time
    from blocks import Block
    def derive(key, sock):
        t = time.time()
        key = key.fold_in(t)
        sock.send(seq=time.time())
        return key, Block(obs=time.time())
    """
    findings, _ = det(tmp_path / "pos", {"mod.py": hot})
    assert rules_of(findings) == ["nondet-taint"]
    assert len(findings) == 3  # fold_in input, seq kwarg, Block field

    # audit/metrics destinations are the EXPLICIT wall-clock allowlist
    ok = """
    import time
    from blocks import Block
    def stamp(sock):
        return Block(t_serve=time.time(), lag_stamp=time.time())
    """
    findings, _ = det(tmp_path / "neg", {"mod.py": ok})
    assert findings == [], render_text(findings)


def test_wallclock_taint_interprocedural(tmp_path):
    """Taint crosses the call graph both ways: a helper RETURNING a
    wall-clock value taints its caller's sink, and a tainted argument to a
    helper whose PARAM reaches a sink is flagged at the call site."""
    ret = """
    import time
    def now():
        return time.time()
    def derive(key):
        return key.fold_in(now())
    """
    findings, _ = det(tmp_path / "ret", {"mod.py": ret})
    assert rules_of(findings) == ["nondet-taint"]

    param = """
    import time
    class S:
        def __init__(self):
            self.mark = 0.0
        def _set(self, v):
            self.mark = v
        def tick(self):
            self._set(time.time())
        def bump(self):
            self._set(self.mark + 1.0)
        def carry_state(self):
            return {"mark": self.mark}
        def restore_carry(self, d):
            self.mark = d["mark"]
    """
    findings, _ = det(tmp_path / "param", {"mod.py": param})
    assert rules_of(findings) == ["nondet-taint"]
    assert len(findings) == 1  # at the tainted call site, not inside _set
    assert "via _set" in findings[0].message


def test_unsorted_scan_and_unseeded_random(tmp_path):
    pos = """
    import glob
    import os
    import numpy as np
    def spool(d):
        names = [n for n in os.listdir(d)]
        files = glob.glob(d + "/*.npz")
        return names, files, np.random.uniform()
    """
    findings, _ = det(tmp_path / "pos", {"mod.py": pos})
    assert rules_of(findings) == ["unseeded-random", "unsorted-scan"]
    assert len(findings) == 3

    neg = """
    import glob
    import os
    import numpy as np
    def spool(d, rng):
        names = sorted(os.listdir(d))
        files = sorted(glob.glob(d + "/*.npz"))
        gen = np.random.default_rng(0)
        return names, files, gen.uniform(), rng.normal()
    """
    findings, _ = det(tmp_path / "neg", {"mod.py": neg})
    assert findings == [], render_text(findings)


def test_set_iteration_and_id_keys(tmp_path):
    pos = """
    def evict(server, trace, cache, obj):
        for sid in {ev.session for ev in trace}:
            server.evict(sid)
        cache[id(obj)] = 1
        return {id(obj): 2}
    """
    findings, _ = det(tmp_path / "pos", {"mod.py": pos})
    assert rules_of(findings) == ["nondet-taint"]
    assert len(findings) == 3

    neg = """
    def evict(server, trace):
        for sid in sorted({ev.session for ev in trace}):
            server.evict(sid)
    """
    findings, _ = det(tmp_path / "neg", {"mod.py": neg})
    assert findings == [], render_text(findings)


def test_chaos_coverage_fixture(tmp_path):
    """A fixture registry drives all three chaos directions: registered-
    but-unguarded, registered-but-undrilled (no literal in the sibling
    test tree), and guarded-but-unregistered."""
    _write(tmp_path, "pkg/pkgfaults.py", """
    KNOWN_SITES = (
        "alpha.load",
        "beta.save",
        "gamma.send",
    )
    def fault_point(site):
        pass
    """)
    _write(tmp_path, "pkg/mod.py", """
    from pkgfaults import fault_point
    def load():
        fault_point("alpha.load")
    def send():
        fault_point("gamma.send")
        fault_point("delta.recv")
    """)
    _write(tmp_path, "tests/test_drill.py", """
    def test_drill():
        for site in ("alpha.load", "gamma.send"):
            assert site
    """)
    from r2d2_tpu.analysis import determinism

    findings, _ = determinism.analyze_paths([str(tmp_path / "pkg")])
    by_rule = {}
    for f in findings:
        by_rule.setdefault(f.rule, []).append(f)
    assert sorted(by_rule) == [
        "chaos-undrilled-site", "chaos-unguarded-site",
        "chaos-unregistered-site",
    ]
    assert "beta.save" in by_rule["chaos-unguarded-site"][0].message
    assert "beta.save" in by_rule["chaos-undrilled-site"][0].message
    assert "delta.recv" in by_rule["chaos-unregistered-site"][0].message
    # findings point at the registry entry / the guarding call site
    assert by_rule["chaos-unguarded-site"][0].path.endswith("pkgfaults.py")
    assert by_rule["chaos-unregistered-site"][0].path.endswith("mod.py")


def test_determinism_repo_wide_gate_and_budget():
    """The shipped tree has zero unsuppressed determinism findings: every
    mutable attribute on the snapshot path is carried+restored or
    ephemeral-annotated with its invariant, no wall-clock value reaches a
    deterministic sink, every directory scan feeding recovery is sorted,
    and every registered fault site is guarded AND drilled. This is the
    tier-1 bit-exact-resume gate. The same run doubles as the analyzer's
    wall-clock budget assert: the full interprocedural pass must stay a
    negligible slice of the 870 s tier-1 gate."""
    import time as _time

    from r2d2_tpu.analysis import determinism

    t0 = _time.perf_counter()
    findings, suppressed = determinism.analyze_paths([PKG_DIR])
    elapsed = _time.perf_counter() - t0
    assert findings == [], render_text(findings)
    # the audited ephemeral inventory stays visible (tap counters, the
    # tiered plane's lazily rebuilt pipeline)
    assert any(f.rule.startswith("resume-") for f in suppressed), suppressed
    assert elapsed < 60.0, f"determinism pass took {elapsed:.1f}s"


def test_cli_determinism_flag(capsys):
    """Flag wiring end-to-end on a subtree (repo-wide zero is pinned by
    test_determinism_repo_wide_gate_and_budget over the same
    analyze_paths the flag dispatches to)."""
    from r2d2_tpu.analysis.cli import main

    assert main(["--determinism", os.path.join(PKG_DIR, "analysis")]) == 0
    assert "no findings" in capsys.readouterr().out


def test_determinism_sarif_rule_indices_stable():
    """SARIF rule indices for the new family are stable: the driver rule
    table is the sorted set of rule ids present, so adding a finding of an
    existing rule never renumbers the table."""
    from r2d2_tpu.analysis import determinism
    from r2d2_tpu.analysis.findings import render_sarif

    fs = [
        Finding("unsorted-scan", "warning", "a.py", 1, 0, "m"),
        Finding("nondet-taint", "error", "b.py", 1, 0, "m"),
        Finding("chaos-undrilled-site", "error", "c.py", 1, 0, "m"),
    ]
    doc = json.loads(render_sarif(fs))
    rules = [r["id"] for r in doc["runs"][0]["tool"]["driver"]["rules"]]
    assert rules == sorted(rules)
    assert set(rules) <= set(determinism.ALL_RULES)


def test_seeded_mutation_trips_determinism_gate(tmp_path):
    """Delete ONE field ("sum_reward") from the real SequenceAccumulator
    carry_state inside a fixture copy — the gate must trip with
    resume-uncaptured-field. The unmutated copy of the same file is
    clean, so the trip is attributable to exactly the removed capture."""
    from r2d2_tpu.analysis import determinism

    with open(os.path.join(PKG_DIR, "replay", "accumulator.py"),
              encoding="utf-8") as fh:
        real = fh.read()
    _write(tmp_path / "intact", "acc.py", real)
    findings, _ = determinism.analyze_paths([str(tmp_path / "intact")])
    assert findings == [], render_text(findings)

    dropped = '"sum_reward": np.asarray(self.sum_reward, np.float64),'
    assert dropped in real
    _write(tmp_path / "mutated", "acc.py", real.replace(dropped, ""))
    findings, _ = determinism.analyze_paths([str(tmp_path / "mutated")])
    assert "resume-uncaptured-field" in rules_of(findings)
    assert any("sum_reward" in f.message for f in findings)
