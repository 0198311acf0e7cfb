"""The nemotron-twotower-30b-a3b-ep16 configuration and its reference
(benchmark/reference/nemotron_h.py): what the file says against the source's
numbers, `update_flops` by hand for one layer of each kind, the reference's
independence of the program, and the whole cell at tiny widths on the CPU
through `run_cell` (Trainer, the device collector, the fused megastep, the
reference check, and the counters the new per-layer metrics read)."""

import json
import os
import re
import shutil

import gc

import pytest
from test_bench_architecture import OWN_SCOPE, fourth_root  # noqa: F401 (the fixture: the benchmark's copy with one more configuration)
from test_bench_host_parts import EIGHT

from benchmark import flops, harness, manifest
from benchmark import trace as tr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "nemotron-twotower-30b-a3b-ep16"
CELL = NAME + ".learn"
with open(os.path.join(ROOT, "BENCHMARK.json")) as _fh:
    M = json.load(_fh)
CONF = harness.load_json(os.path.join(ROOT, "benchmark", "configs", NAME + ".json"))
# the source's config.json, the numbers a builder needs, typed in from the catalog row
PUBLISHED = {
    "hidden_size": 2688, "num_attention_heads": 32, "num_key_value_heads": 2, "head_dim": 128, "chunk_size": 128,
    "conv_kernel": 4, "expand": 2, "intermediate_size": 1856, "mamba_head_dim": 64, "mamba_num_heads": 64,
    "moe_intermediate_size": 1856, "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05, "layer_norm_epsilon": 1e-05,
    "num_experts_per_tok": 6, "routed_scaling_factor": 2.5, "ssm_state_size": 128, "topk_group": 1,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001, "rope_theta": 10000,
    "partial_rotary_factor": 1, "max_position_embeddings": 262144, "num_logits_to_keep": 1,
}
NEW_METRICS = ["model.ssm_ms_per_update", "model.attention_ms_per_update", "model.moe_ms_per_update",
               "model.moe_experts_ms_per_update", "model.moe_dropped_share", "model.moe_load_max_over_mean"]


def test_the_manifest_has_the_fourth_configuration_and_its_cell_last():
    assert (len(M["configs"]), len(M["workloads"])) == (4, 4)
    assert M["configs"][-1]["name"] == NAME and M["configs"][-1]["file"] == f"benchmark/configs/{NAME}.json"
    assert M["workloads"][-1] == {**M["workloads"][-1], "name": CELL, "config": NAME, "traffic": "learn", "chips": 1}
    assert [m["name"] for m in M["per_layer"]][-len(NEW_METRICS):] == NEW_METRICS
    assert all(m["workloads"] == [CELL] and m["moves"] == "learn_steps_per_s" for m in M["per_layer"][-len(NEW_METRICS):])
    listed = {m["name"] for m in M["per_layer"] if CELL in m["workloads"]}
    assert not listed & {"kernels.lstm_ms_per_update", "kernels.lstm_roofline", "model.lru_recurrence_ms_per_update",
                         "collectives.exposed_ms_per_update"}
    assert {"model.mfu", "model.core_ms_per_update", "device.peak_hbm_gb", "device.idle_share"} <= listed


def test_nothing_before_the_new_entries_moved():
    """What tests/benchmark/test_bench_host_parts.py pins as the manifest's END
    (three configurations, PR 42's eight entries last) fails there since this
    cell came (PERF.md section 7 asks a `benchmark` issue to reword it); what
    it guards is held here in the form an added configuration leaves it: the
    eight stand together, in order, right before this PR's entries, and their
    lists of cells are as they were."""
    names = [m["name"] for m in M["per_layer"]]
    assert names[-len(NEW_METRICS) - 8:-len(NEW_METRICS)] == EIGHT
    assert [w["name"] for w in M["workloads"]][:3] == ["nature-lstm512.learn", "lru-seq581.learn", "nature-lstm512-dp4.learn"]
    assert len(M["end_to_end"]) == 2 and all(CELL not in m["workloads"] for m in M["per_layer"] if m["name"] in EIGHT)


def test_one_more_configuration_beside_this_one_still_runs_as_files_and_entries(fourth_root, monkeypatch, tmp_path):  # noqa: F811
    """The tripwire of tests/benchmark/test_bench_architecture.py (a further
    configuration added to a copy as files and entries, every manifest rule
    asked of it, its cell run traced to `correct: true`, its own layer file
    read) stops at its pin of reference/model.py as the cells' one reference
    file since this cell names nemotron_h. From that line on it is repeated
    here with the pin as it now has to read, so that what it guards stays
    guarded."""
    root, m = fourth_root
    assert manifest.check_all(root, m) > 100 and len(m["configs"]) == 5
    files = manifest.reference_files(root, m)
    bench = os.path.realpath(os.path.join(root, "benchmark", "reference"))
    assert files.pop("toy-share.learn") == os.path.join(bench, "toy.py")
    assert files.pop(CELL) == os.path.join(bench, "nemotron_h.py")
    assert set(files.values()) == {os.path.join(bench, "model.py")} and len(files) == 3
    real = tr.load_patterns
    monkeypatch.setattr(tr, "load_patterns",
                        lambda path=None: real(os.path.join(root, "benchmark", "trace_patterns_cpu.json")))
    peaks = tmp_path / "peaks.json"
    peaks.write_text(json.dumps({"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}))
    monkeypatch.setattr(flops, "_PEAKS_PATH", str(peaks))
    gc.collect()
    r = harness.run_cell(root, "toy-share.learn", seed=3, seconds=0.2, trace=True, require_tpu=False)
    assert r["correct"] is True and r["failed"] == 0 and r["notes"]["checks"]["reference"]["ok"]
    got = r["metrics"]
    assert 0.0 < got[OWN_SCOPE["name"]]["value"] <= got["model.core_ms_per_update"]["value"]
    listed = {e["name"] for e in m["per_layer"] if manifest.applies(e, "toy-share.learn")}
    assert set(got) <= listed and {"cli.compile_misses", "model.mfu", "device.unscoped_share"} <= set(got)
    # the copy lists the added cell under this cell's own six too (every list outside kernels and collectives);
    # a program without such layers or counters gives the accepted readers nothing to read: 0, and no error
    assert set(NEW_METRICS) <= listed and all(got[k]["value"] == 0.0 for k in NEW_METRICS if k in got)


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_every_published_number_is_in_the_file_under_its_own_key(key):
    assert CONF[key] == PUBLISHED[key]
    core = CONF["overrides"]["core_config"]
    assert core.get(key, PUBLISHED[key]) == PUBLISHED[key]   # and the core runs the same width


def test_reduced_is_depth_experts_held_vocabulary_and_the_shell():
    assert CONF["num_hidden_layers"] == 7 and len(CONF["overrides"]["core_config"]["hybrid_override_pattern"]) == 7
    assert CONF["hybrid_override_pattern"].count("EMEMEM*") >= 4
    assert set(CONF["reduced"]) == {"num_hidden_layers", "num_experts_held", "vocab_size", "buffer_capacity",
                                    "num_actors", "env_name"} == set(CONF["reduced_why"])
    share = CONF["deployment_share"]
    assert share["chips_per_layer"] * share["num_experts_held"]["held"] == share["num_experts_held"]["published"] == 128
    assert CONF["overrides"]["core_config"]["num_experts_held"] == 8
    manifest.check_reduced(M["configs"][-1], CONF)
    for needed in ("tower", "rope_theta", "attention_memory", "input_projection", "capacity_factor", "initialisers"):
        assert needed in CONF["assumed"]


def test_update_flops_by_hand_for_one_layer_of_each_kind():
    ref = harness.reference_for(harness.load_cell(ROOT, CELL))
    cfg = harness.build_config(CONF, 0)
    per = ref.layer_flops_per_token(ref.stack_of(cfg), cfg.seq_len)
    # M: in_proj 2,688 x 10,304, out_proj 4,096 x 2,688, the recurrence's update and read-out 64 x 64 x 128 each
    assert per["M"] == 2 * 2688 * 10304 + 2 * 4096 * 2688 + 4 * 64 * 64 * 128 == 79511552
    # E: router, shared expert 2 x 2,688 x 3,712, and 6 x 8 / 128 = 0.375 rows of a routed expert 2 x 2,688 x 1,856
    assert per["E"] == 2 * 2688 * 128 + 4 * 2688 * 3712 + 0.375 * 4 * 2688 * 1856 == 48082944.0
    # *: q and o 2,688 x 4,096, k and v 2,688 x 256, scores and values over (581 + 1) / 2 keys a query
    assert per["*"] == 2 * 2688 * (2 * 4096 + 2 * 256) + 4 * 4096 * 291 == 51560448.0
    from benchmark import flops

    trunk = (flops.nature_encoder_flops_per_frame((84, 84, 1), 2688) + 2 * 2692 * 2688
             + 3 * per["M"] + 3 * per["E"] + per["*"])
    heads = 2 * (2 * 2688 * 2688 + 2688 * 3 + 2688)
    assert ref.update_flops(cfg) == int(cfg.batch_size * (trunk * (581 + 2 * 512 + 581) + heads * 5 * 512))
    # padding cannot raise it: the capacity is no part of the count
    padded = cfg.replace(core_config={**dict(cfg.core_config), "capacity_factor": 8.0})
    assert ref.update_flops(padded) == ref.update_flops(cfg)
    assert 7e12 < ref.update_flops(cfg) * 8 / cfg.batch_size < 10e12


def test_the_reference_is_plain_and_imports_nothing_of_the_programs_models():
    text = open(os.path.join(ROOT, "benchmark", "reference", "nemotron_h.py")).read()
    # the mathematics imports nothing of the program; the layer checks, which call the program's
    # layers to compare them, import them inside `kernel_checks` (as reference/model.py its kernel)
    assert not [m for m in re.findall(r"^(?:from|import)\s+([\w.]+)", text, re.M) if m.startswith("r2d2_tpu")]
    inside = re.findall(r"^\s+(?:from|import)\s+(r2d2_tpu[\w.]*)", text, re.M)
    assert inside == ["r2d2_tpu.models"] and text.index("def kernel_checks") < text.index("from r2d2_tpu.models")
    assert "pallas" not in text and "lax.scan" in text
    for departure in ("causal tower only", "no rotary", "attention memory", "capacity", "input projection"):
        assert departure in text, departure


@pytest.mark.parametrize("pattern,want", [
    ("EMEMEM*", ("EM", 3)), ("MEMEM*EMEMEM*", ("ME", 2)), ("MMMM", ("M", 4)), ("EM*EM*E", ("EM*", 2)),
    ("M*E", ("", 0)), ("*", ("", 0)),
])
def test_the_reference_finds_the_unit_that_repeats(pattern, want):
    ref = harness.reference_for(harness.load_cell(ROOT, CELL))
    assert ref.repeats(pattern) == want


def test_the_references_convolutions_are_the_shared_references():
    """`nemotron_h.encode` writes the Nature trunk's convolutions as shifted
    matmuls (compile time on the chip); it is reference/model.encode."""
    import jax
    import numpy as np

    from benchmark.reference import model as base

    ref = harness.reference_for(harness.load_cell(ROOT, CELL))
    rng = np.random.default_rng(3)
    shapes = {"Conv_0": (8, 8, 1, 32), "Conv_1": (4, 4, 32, 64), "Conv_2": (3, 3, 64, 64), "Dense_0": (3136, 48)}
    p = {name: {"kernel": (rng.normal(size=shape) / np.sqrt(np.prod(shape[:-1]))).astype(np.float32),
                "bias": rng.normal(size=shape[-1:]).astype(np.float32)} for name, shape in shapes.items()}
    obs = rng.integers(0, 256, size=(3, 84, 84, 1)).astype(np.uint8)
    with jax.default_matmul_precision("highest"):
        want, got = base.encode(p, obs, "nature"), ref.encode(p, obs, "nature")
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        d_want = jax.grad(lambda p: (base.encode(p, obs, "nature") ** 2).sum())(p)
        d_got = jax.grad(lambda p: (ref.encode(p, obs, "nature") ** 2).sum())(p)
    for a, b in zip(jax.tree.leaves(d_got), jax.tree.leaves(d_want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


# ------------------------------------------------- the cell, tiny, on the CPU

TINY_CORE = dict(
    hidden_size=64, hybrid_override_pattern="EMEMEM*", mamba_num_heads=4, mamba_head_dim=16, ssm_state_size=16,
    n_groups=2, conv_kernel=4, chunk_size=8, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    n_routed_experts=16, num_experts_per_tok=2, moe_intermediate_size=32, moe_shared_expert_intermediate_size=64,
    routed_scaling_factor=2.5, norm_eps=1e-5, num_experts_held=4,
    time_step_min=0.001, time_step_max=0.1, time_step_floor=1e-4)
TINY = {"env_name": "drift", "action_dim": 3, "max_episode_steps": 16, "collector": "device", "replay_plane": "device",
        "updates_per_dispatch": 2, "num_actors": 2, "hidden_dim": 64, "recurrent_core": "hybrid_stack",
        "core_config": TINY_CORE}


@pytest.fixture(scope="module")
def tiny_line(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("stackroot"))
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    m = json.loads(json.dumps(M))
    with open(os.path.join(root, "benchmark", "configs", "tiny-stack.json"), "w") as fh:
        json.dump({"name": "tiny-stack", "source": "test", "preset": "tiny_test", "reference": "nemotron_h",
                   "overrides": TINY, "reduced": []}, fh)
    m["configs"].append({"name": "tiny-stack", "source": "test", "why": "test", "reduced": [],
                         "file": "benchmark/configs/tiny-stack.json"})
    m["workloads"].append({"name": "tiny-stack.learn", "config": "tiny-stack", "traffic": "learn", "chips": 1,
                           "why": "test"})
    for e in m["end_to_end"] + m["per_layer"]:
        if CELL in e.get("workloads", []):
            e["workloads"].append("tiny-stack.learn")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(m, fh)
    return harness.run_cell(root, "tiny-stack.learn", seed=2**31 + 5, seconds=0.5, trace=False, require_tpu=False)


def test_the_tiny_cell_runs_the_normal_path_and_matches_its_reference(tiny_line):
    r = tiny_line
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"learn_steps_per_s", "setup_s"}
    checks = r["notes"]["checks"]
    assert checks["reference"]["ok"] and checks["reference_end"]["ok"] and checks["loss_island"]["ok"]
    assert checks["kernels"]["ok"] and checks["kernels"]["rows_steps"] == [2, 10]
    assert checks["reference"]["sequences"] == 8
    # float32 at tiny widths: the two agree far inside the float32 class's limits
    assert checks["reference"]["q_err_over_scale"] < 1e-4 and checks["reference"]["loss_rel"] < 1e-4


def _bf16_router(self, x):
    import jax
    import jax.numpy as jnp

    low = jnp.dot(x.astype(jnp.bfloat16), self.router.astype(jnp.bfloat16))
    scores = jax.nn.sigmoid(low).astype(jnp.float32)
    return scores, jax.lax.top_k(scores + self.correction_bias, self.spec.num_experts_per_tok)[1]


@pytest.mark.parametrize("control,fails", [
    (None, set()),
    ("router", {"router_score_err"}),                                   # scores from a bfloat16 matmul
    ("recurrence", {"ssm_state_err_over_scale"}),                       # the chunks' state kept in bfloat16
    ("no_shared", {"moe_out_err_over_scale", "attention_out_err_over_scale"}),  # wrong mathematics: no shared expert
])
def test_the_layer_checks_pass_the_program_and_tell_each_control(control, fails, monkeypatch):
    """`kernel_checks` of the reference module: the program's layers against
    the reference's with the program's routing handed over. What the cell's
    whole-program limits cannot tell (PERF.md 53.4) fails here, each by the
    number that names it."""
    import jax.numpy as jnp

    from r2d2_tpu.models import hybrid_stack as hs

    ref = harness.reference_for(harness.load_cell(ROOT, CELL))
    cfg = harness.build_config({"preset": "tiny_test", "overrides": TINY}, 7, {})
    if control == "router":
        monkeypatch.setattr(hs.ExpertMixture, "scores", _bf16_router)
    elif control == "recurrence":
        real = hs.ssd_chunked
        rounded = lambda *a: (lambda y, h: (y, h.astype(jnp.bfloat16).astype(jnp.float32)))(*real(*a))
        monkeypatch.setattr(hs, "ssd_chunked", rounded)
    elif control == "no_shared":
        monkeypatch.setattr(hs.ExpertMixture, "shared", lambda self, x: jnp.zeros_like(x))
    out = ref.kernel_checks(cfg, 7, 8)
    over = {k for k, limit in out["limits"].items() if not out[k] <= limit}
    # (weights that follow rounded scores move the later outputs too)
    assert fails <= over and bool(over) == bool(fails) and out["ok"] is (not fails), out
    assert out["limits"] == ref.LAYER_LIMITS[cfg.resolved_compute_dtype] and 0.0 <= out["router_flip_share"] <= 1.0


def test_the_runner_published_what_the_mixtures_counted(tiny_line):
    """`program_counter` reads these keys; they are set as a dispatch's
    priorities are drained, from the metrics the update already returns."""
    from r2d2_tpu.utils import profiling

    counters = profiling.counters()
    assert counters["moe.rows_offered"] > 0 and counters["moe.rows_dropped"] >= 0
    assert 0.0 <= counters["moe.dropped_share"] <= 100.0 and counters["moe.load_max_over_mean"] >= 1.0
    for name in NEW_METRICS[-2:]:
        spec = harness.load_json(os.path.join(ROOT, "benchmark", "layers", name + ".json"))
        assert spec["reader"] == "program_counter" and spec["key"] in counters


@pytest.mark.parametrize("metric,found,not_found", [
    ("model.ssm_ms_per_update", "jit(mega)/R2D2Network.unroll/core/core._run/ssm_1/bnigs,bnjgs->bnijg", "core/core._run/moe_0"),
    ("model.attention_ms_per_update", "transpose(jvp(R2D2Network))/R2D2Network.unroll/core/core._run/checkpoint/attention_6/while/body", "core/period/ssm_1"),
    ("model.moe_ms_per_update", "R2D2Network.unroll/core/core._run/moe_2/moe_2.shared/dot_general", "core/core._run/ssm_1"),
    ("model.moe_experts_ms_per_update", "jvp(R2D2Network)/R2D2Network.unroll/core/core._run/moe_2/moe_2.routed/experts/ecd,edf->ecf",
     "core/core._run/moe_2/moe_2.routed/jit(_take)"),
])
def test_each_time_metric_finds_its_layers_op_names_and_no_other(metric, found, not_found):
    spec = harness.load_json(os.path.join(ROOT, "benchmark", "layers", metric + ".json"))
    assert spec["reader"] == "trace_scope" and spec["within"] == "core" and spec["per"] == "updates"
    assert re.search(spec["op_name"], found) and not re.search(spec["op_name"], not_found)
    scopes = harness.load_json(os.path.join(ROOT, "benchmark", "trace_scopes.json"))["buckets"]
    first = next(b for b, rx in scopes if re.search(rx, "jit(r2d2_update)/" + found))
    assert first == "core"
