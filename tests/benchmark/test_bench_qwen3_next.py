"""The qwen3-next-80b-a3b-ep32 configuration and its reference
(benchmark/reference/qwen3_next.py): what the file says against the source's
numbers, `update_flops` by hand for one block of each kind, the reference's
independence of the program, the layer checks and their controls, and the
whole cell at tiny widths on the CPU through `run_cell`, traced. Every entry
of the manifest is found by NAME and held by its place relative to others:
nothing here pins the manifest's end or a count of configurations, so the
next configuration breaks none of it."""

import gc
import json
import os
import re
import shutil

import pytest
from test_bench_architecture import OWN_SCOPE, fourth_root  # noqa: F401 (the fixture: the benchmark's copy with one more configuration)
from test_bench_host_parts import EIGHT

from benchmark import flops, harness, manifest
from benchmark import trace as tr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "qwen3-next-80b-a3b-ep32"
CELL = NAME + ".learn"
FOURTH_CELL = "nemotron-twotower-30b-a3b-ep16.learn"
with open(os.path.join(ROOT, "BENCHMARK.json")) as _fh:
    M = json.load(_fh)
CONF = harness.load_json(os.path.join(ROOT, "benchmark", "configs", NAME + ".json"))
# the source's config.json, typed in from the catalog row
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5120, "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128, "linear_num_key_heads": 16,
    "linear_num_value_heads": 32, "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next", "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False, "use_sliding_window": False,
}
SHARED_WITH_THE_FOURTH = ["model.attention_ms_per_update", "model.moe_ms_per_update", "model.moe_experts_ms_per_update",
                          "model.moe_dropped_share", "model.moe_load_max_over_mean"]
FOURTH_OWN = ["model.ssm_ms_per_update"] + SHARED_WITH_THE_FOURTH
NEW_METRICS = ["model.gdn_ms_per_update", "model.gdn_recurrence_ms_per_update"]


def _places(entries, names):
    order = [e["name"] for e in entries]
    return [order.index(n) for n in names]


def _consecutive(places):
    return places == list(range(places[0], places[0] + len(places)))


def test_the_manifest_has_the_configuration_its_cell_and_its_two_metrics_by_name():
    configs = {c["name"]: c for c in M["configs"]}
    cells = {w["name"]: w for w in M["workloads"]}
    assert configs[NAME]["file"] == f"benchmark/configs/{NAME}.json" and configs[NAME]["source"] == CONF["source"]
    assert cells[CELL] == {**cells[CELL], "config": NAME, "traffic": "learn", "chips": 1}
    # after the fourth configuration's, which were there before
    assert _places(M["configs"], [NAME])[0] > _places(M["configs"], ["nemotron-twotower-30b-a3b-ep16"])[0]
    assert _places(M["workloads"], [CELL])[0] > _places(M["workloads"], [FOURTH_CELL])[0]
    per_layer = {m["name"]: m for m in M["per_layer"]}
    for name in NEW_METRICS:
        assert per_layer[name]["workloads"] == [CELL] and per_layer[name]["moves"] == "learn_steps_per_s"
    new = _places(M["per_layer"], NEW_METRICS)
    assert _consecutive(new) and new[0] > max(_places(M["per_layer"], FOURTH_OWN))
    listed = {m["name"] for m in M["per_layer"] if CELL in m["workloads"]}
    assert not listed & {"kernels.lstm_ms_per_update", "kernels.lstm_roofline", "model.lru_recurrence_ms_per_update",
                         "collectives.exposed_ms_per_update", "model.ssm_ms_per_update", *EIGHT}
    assert set(SHARED_WITH_THE_FOURTH) | {"model.mfu", "model.core_ms_per_update", "device.peak_hbm_gb",
                                          "device.idle_share"} <= listed
    # every metric that lists the four learn cells lists this one, after them
    for m in M["end_to_end"] + M["per_layer"]:
        cells_of = m.get("workloads", [])
        if FOURTH_CELL in cells_of and "lru-seq581.learn" in cells_of:
            assert cells_of.index(CELL) > cells_of.index(FOURTH_CELL), m["name"]


def test_nothing_before_the_new_entries_moved():
    """What tests/benchmark/test_bench_host_parts.py and test_bench_nemotron.py
    pin as the manifest's END and COUNTS (and fail on since a configuration
    came after theirs; PERF.md section 7 asks a `benchmark` issue to reword
    them) is held here in the form any further configuration leaves it: PR
    42's eight stand together, in order; the fourth configuration's six stand
    together, in order, right after them; the first cells are where they were;
    and the eight's lists of cells are as they were."""
    eight, six = _places(M["per_layer"], EIGHT), _places(M["per_layer"], FOURTH_OWN)
    assert _consecutive(eight) and _consecutive(six) and six[0] == eight[-1] + 1
    assert [w["name"] for w in M["workloads"]][:4] == ["nature-lstm512.learn", "lru-seq581.learn",
                                                       "nature-lstm512-dp4.learn", FOURTH_CELL]
    assert [m["name"] for m in M["end_to_end"]] == ["learn_steps_per_s", "setup_s"]
    three = ["nature-lstm512.learn", "lru-seq581.learn", "nature-lstm512-dp4.learn"]
    assert all(m["workloads"] == three for m in M["per_layer"] if m["name"] in EIGHT)
    per_layer = {m["name"]: m for m in M["per_layer"]}
    assert per_layer["model.ssm_ms_per_update"]["workloads"] == [FOURTH_CELL]
    assert all(per_layer[n]["workloads"][0] == FOURTH_CELL for n in SHARED_WITH_THE_FOURTH)


def test_one_more_configuration_beside_these_still_runs_as_files_and_entries(fourth_root, monkeypatch, tmp_path):  # noqa: F811
    """The tripwire of tests/benchmark/test_bench_architecture.py (a further
    configuration added to a copy as files and entries, every manifest rule
    asked of it, its cell run traced to `correct: true`, its own layer file
    read), with its pins as a manifest of any length has to read: each cell's
    reference file by the cell's NAME, the rest `model.py`."""
    root, m = fourth_root
    assert manifest.check_all(root, m) > 100
    assert {c["name"] for c in m["configs"]} == {c["name"] for c in M["configs"]} | {"toy-share"}
    files = manifest.reference_files(root, m)
    bench = os.path.realpath(os.path.join(root, "benchmark", "reference"))
    assert files.pop("toy-share.learn") == os.path.join(bench, "toy.py")
    assert files.pop(CELL) == os.path.join(bench, "qwen3_next.py")
    assert files.pop(FOURTH_CELL) == os.path.join(bench, "nemotron_h.py")
    assert set(files.values()) == {os.path.join(bench, "model.py")}
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
    # the copy lists the added cell under this cell's two as well; a program without such layers gives
    # their reader nothing to read: 0, and no error
    assert set(NEW_METRICS) <= listed and all(got[k]["value"] == 0.0 for k in NEW_METRICS if k in got)


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_every_published_number_is_in_the_file_under_its_own_key(key):
    assert CONF[key] == PUBLISHED[key]
    core = CONF["overrides"]["core_config"]
    assert core.get(key, PUBLISHED[key]) == PUBLISHED[key]   # and the core runs the same width


def test_reduced_is_depth_experts_held_vocabulary_and_the_shell():
    core = CONF["overrides"]["core_config"]
    assert CONF["num_hidden_layers"] == core["num_hidden_layers"] == 4 == core["full_attention_interval"]
    assert CONF["vocab_size"] == 3
    assert set(CONF["reduced"]) == {"num_hidden_layers", "num_experts_held", "vocab_size", "buffer_capacity",
                                    "num_actors", "env_name"} == set(CONF["reduced_why"])
    share = CONF["deployment_share"]
    assert share["chips_per_layer"] == 32 and share["num_experts_held"] == {"published": 512, "held": 16}
    assert share["chips_per_layer"] * share["num_experts_held"]["held"] == CONF["num_experts"] == 512
    assert core["num_experts_held"] == 16 and "first_expert_held" not in core
    entry = next(c for c in M["configs"] if c["name"] == NAME)
    manifest.check_reduced(entry, CONF)
    assert len(CONF["source"]) <= 200 and "layers 0-3" in CONF["source"]
    assert CONF["source"].startswith("https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json")
    for needed in ("in_proj_qkvz_columns", "chunk_size", "capacity_factor", "attention_memory", "initialisers", "mtp",
                   "batch_size", "action_dim"):
        assert needed in CONF["assumed"], needed
    assert set(CONF["limits"]) == set(CONF["limits_why"])
    assert CONF["preset"] == "long_context" and CONF["reference"] == "qwen3_next"
    # the core names no option of the program's own beyond the three
    own = set(core) - set(PUBLISHED) - {"num_hidden_layers"}
    assert own == {"num_experts_held", "capacity_factor"}


def test_update_flops_by_hand_for_one_block_of_each_kind():
    ref = harness.reference_for(harness.load_cell(ROOT, CELL))
    cfg = harness.build_config(CONF, 0)
    per = ref.layer_flops_per_token(ref.stack_of(cfg), cfg.seq_len)
    # D: in_proj_qkvz 2,048 x 12,288 and in_proj_ba 2,048 x 64, out_proj 4,096 x 2,048, and the recurrence's
    # S^T k, the written outer product and S^T q, 32 x 128 x 128 each
    assert per["D"] == 2 * 2048 * 12352 + 2 * 4096 * 2048 + 3 * 2 * 32 * 128 * 128 == 70516736
    # E: router, the shared expert's three matrices and its gate, and 10 x 16 / 512 = 0.3125 rows of a routed expert
    assert per["E"] == 2 * 2048 * 512 + 6 * 2048 * 512 + 2 * 2048 + 0.3125 * 6 * 2048 * 512 == 10358784.0
    # *: q (with its gate) 2,048 x 8,192, k and v 2,048 x 512, o 4,096 x 2,048, scores and values over 291 keys
    assert per["*"] == 2 * 2048 * (8192 + 2 * 512) + 2 * 4096 * 2048 + 4 * 4096 * 291 == 59293696.0
    trunk = (flops.nature_encoder_flops_per_frame((84, 84, 1), 2048) + 2 * 2052 * 2048
             + 3 * per["D"] + 4 * per["E"] + per["*"])
    heads = 2 * (2 * 2048 * 2048 + 2048 * 3 + 2048)
    assert ref.update_flops(cfg) == int(cfg.batch_size * (trunk * (581 + 2 * 512 + 581) + heads * 5 * 512))
    # the delta-rule layers are about two thirds of the stack's count
    assert 0.66 < 3 * per["D"] / (3 * per["D"] + 4 * per["E"] + per["*"]) < 0.70
    # padding cannot raise it: the capacity is no part of the count
    padded = cfg.replace(core_config={**dict(cfg.core_config), "capacity_factor": 8.0})
    assert ref.update_flops(padded) == ref.update_flops(cfg)
    assert 5e12 < ref.update_flops(cfg) < 7e12


def test_the_reference_is_plain_and_imports_nothing_of_the_programs_models():
    text = open(os.path.join(ROOT, "benchmark", "reference", "qwen3_next.py")).read()
    assert not [m for m in re.findall(r"^(?:from|import)\s+([\w.]+)", text, re.M) if m.startswith("r2d2_tpu")]
    inside = re.findall(r"^\s+(?:from|import)\s+(r2d2_tpu[\w.]*)", text, re.M)
    assert inside == ["r2d2_tpu.models"] and text.index("def kernel_checks") < text.index("from r2d2_tpu.models")
    assert "pallas" not in text and "_loop_over_time" in text and "lax.scan" in text
    # what is both stacks' is imported, not copied
    assert "from benchmark.reference import nemotron_h as shared" in text
    for name in ("def _conv_valid", "def _loop_over_time", "def repeats", "def capacity", "def encode"):
        assert name not in text, name
    for departure in ("column order", "multi-token-prediction", "attention memory", "the share", "the capacity",
                      "input projection"):
        assert departure in text, departure


# ------------------------------------------------- the cell, tiny, on the CPU

TINY_CORE = dict(
    model_type="qwen3_next", hidden_size=64, num_hidden_layers=4, full_attention_interval=4,
    linear_num_key_heads=2, linear_key_head_dim=16, linear_num_value_heads=4, linear_value_head_dim=16,
    linear_conv_kernel_dim=4, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    partial_rotary_factor=0.25, rope_theta=1e7, num_experts=16, num_experts_per_tok=2, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, norm_topk_prob=True, rms_norm_eps=1e-6, num_experts_held=4)
TINY = {"env_name": "drift", "action_dim": 3, "max_episode_steps": 16, "collector": "device", "replay_plane": "device",
        "updates_per_dispatch": 2, "num_actors": 2, "hidden_dim": 64, "recurrent_core": "hybrid_stack",
        "core_config": TINY_CORE}


@pytest.fixture(scope="module")
def tiny_line(tmp_path_factory):
    """The cell at tiny widths through `run_cell`, TRACED, from a copy of the
    benchmark to which it was added as a file and entries."""
    root = str(tmp_path_factory.mktemp("qwenroot"))
    bench = os.path.join(root, "benchmark")
    shutil.copytree(os.path.join(ROOT, "benchmark"), bench, ignore=shutil.ignore_patterns("__pycache__"))
    m = json.loads(json.dumps(M))
    with open(os.path.join(bench, "configs", "tiny-qwen.json"), "w") as fh:
        json.dump({"name": "tiny-qwen", "source": "test", "preset": "tiny_test", "reference": "qwen3_next",
                   "overrides": TINY, "reduced": []}, fh)
    m["configs"].append({"name": "tiny-qwen", "source": "test", "why": "test", "reduced": [],
                         "file": "benchmark/configs/tiny-qwen.json"})
    m["workloads"].append({"name": "tiny-qwen.learn", "config": "tiny-qwen", "traffic": "learn", "chips": 1,
                           "why": "test"})
    for e in m["end_to_end"] + m["per_layer"]:
        if CELL in e.get("workloads", []):
            e["workloads"].append("tiny-qwen.learn")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(m, fh)
    pats = harness.load_json(os.path.join(bench, "trace_patterns.json"))
    pats.update(device_plane="^/host:CPU$", op_lines=["^tf_XLA"], module_lines=["^no such line$"])
    with open(os.path.join(bench, "trace_patterns_cpu.json"), "w") as fh:
        json.dump(pats, fh)
    peaks = os.path.join(root, "peaks.json")
    with open(peaks, "w") as fh:
        json.dump({"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}, fh)
    real, real_peaks = tr.load_patterns, flops._PEAKS_PATH
    tr.load_patterns = lambda path=None: real(os.path.join(bench, "trace_patterns_cpu.json"))
    flops._PEAKS_PATH = peaks
    gc.collect()
    try:
        return harness.run_cell(root, "tiny-qwen.learn", seed=2**31 + 7, seconds=0.5, trace=True, require_tpu=False)
    finally:
        tr.load_patterns, flops._PEAKS_PATH = real, real_peaks


def test_the_tiny_cell_runs_the_normal_path_traced_and_matches_its_reference(tiny_line):
    r = tiny_line
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    checks = r["notes"]["checks"]
    assert checks["reference"]["ok"] and checks["reference_end"]["ok"] and checks["loss_island"]["ok"]
    assert checks["kernels"]["ok"] and checks["kernels"]["controls_told"] and checks["kernels"]["rows_steps"] == [2, 10]
    assert checks["reference"]["sequences"] == 8
    # float32 at tiny widths: the two agree far inside the float32 class's limits
    assert checks["reference"]["q_err_over_scale"] < 1e-4 and checks["reference"]["loss_rel"] < 1e-4
    assert r["notes"]["compiles_in_window"] == 0


def test_the_new_buckets_are_read_and_lie_inside_the_core(tiny_line):
    got = {k: v["value"] for k, v in tiny_line["metrics"].items()}
    listed = {e["name"] for e in M["per_layer"] if CELL in e["workloads"]}
    assert set(got) <= listed and set(NEW_METRICS) | set(SHARED_WITH_THE_FOURTH) <= set(got)
    assert 0.0 < got["model.gdn_recurrence_ms_per_update"] < got["model.gdn_ms_per_update"]
    assert 0.0 < got["model.moe_experts_ms_per_update"] < got["model.moe_ms_per_update"]
    parts = got["model.gdn_ms_per_update"] + got["model.attention_ms_per_update"] + got["model.moe_ms_per_update"]
    # the three kinds are the core, less its input projection, final norm and the carry's split and join
    assert 0.5 * got["model.core_ms_per_update"] < parts <= got["model.core_ms_per_update"] * (1 + 1e-9)
    assert 0.0 <= got["model.moe_dropped_share"] <= 100.0 and got["model.moe_load_max_over_mean"] >= 1.0


def _bf16_router(self, x):
    import jax
    import jax.numpy as jnp

    low = jnp.dot(x.astype(jnp.bfloat16), self.router.astype(jnp.bfloat16))
    scores = jax.nn.softmax(low.astype(jnp.float32), axis=-1)
    return scores, jax.lax.top_k(scores, self.sizes.top_k)[1]


@pytest.mark.parametrize("control,fails", [
    (None, set()),
    ("router", {"router_score_err"}),                                       # logits from a bfloat16 matmul
    ("recurrence", {"gdn_state_err_over_scale"}),                           # the chunks' state kept in bfloat16
    ("ungated_shared", {"moe_out_err_over_scale"}),                         # wrong mathematics: no sigmoid on the shared expert
    ("unrotated", {"attention_out_err_over_scale", "attention_keys_err_over_scale"}),  # wrong mathematics: no rotary
])
def test_the_layer_checks_pass_the_program_and_tell_each_control(control, fails, monkeypatch):
    """`kernel_checks` of the reference module: the program's blocks against
    the reference's with the program's routing handed over. What the cell's
    whole-program limits cannot tell fails here, each by the number that
    names it; and the controls that `kernel_checks` runs itself (the
    reference in bfloat16, the attention without its gate and without its
    rotation) are each told on every call."""
    import jax
    import jax.numpy as jnp

    from r2d2_tpu.models import hybrid_stack as hs

    ref = harness.reference_for(harness.load_cell(ROOT, CELL))
    cfg = harness.build_config({"preset": "tiny_test", "overrides": TINY}, 7, {})
    if control == "router":
        monkeypatch.setattr(hs.ExpertMixture, "scores", _bf16_router)
    elif control == "recurrence":
        real = hs.delta_rule_chunked
        rounded = lambda *a: (lambda o, s: (o, s.astype(jnp.bfloat16).astype(jnp.float32)))(*real(*a))
        monkeypatch.setattr(hs, "delta_rule_chunked", rounded)
    elif control == "ungated_shared":
        real = hs.ExpertMixture.shared
        monkeypatch.setattr(hs.ExpertMixture, "shared",
                            lambda self, x: real(self, x) / jax.nn.sigmoid(x @ self.shared_expert_gate))
    elif control == "unrotated":
        monkeypatch.setattr(hs, "rotary", lambda x, positions, rotary_dim, theta: x)
    out = ref.kernel_checks(cfg, 7, 8)
    over = {k for k, limit in out["limits"].items() if not out[k] <= limit}
    assert fails <= over and bool(over) == bool(fails) and out["ok"] is (not fails), out
    assert out["limits"] == ref.LAYER_LIMITS[cfg.resolved_compute_dtype] and 0.0 <= out["router_flip_share"] <= 1.0
    # the controls of the check's own: told whatever the program does
    assert out["controls_told"] is True
    assert any(out["control_bfloat16"][k] > limit for k, limit in out["limits"].items())
    assert out["control_ungated_err_over_scale"] > 0.05 and out["control_unrotated_err_over_scale"] > 0.05


def test_the_runner_published_what_the_mixtures_counted(tiny_line):
    from r2d2_tpu.utils import profiling

    counters = profiling.counters()
    assert counters["moe.rows_offered"] > 0 and counters["moe.rows_dropped"] >= 0
    assert 0.0 <= counters["moe.dropped_share"] <= 100.0 and counters["moe.load_max_over_mean"] >= 1.0


@pytest.mark.parametrize("metric,found,not_found", [
    ("model.gdn_ms_per_update", "jit(mega)/R2D2Network.unroll/core/core._run/gdn_1/gdn_1._project/dot_general", "core/core._run/moe_0"),
    ("model.gdn_ms_per_update", "transpose(jvp(R2D2Network))/R2D2Network.unroll/core/core._run/checkpoint/gdn_2/gdn_2.recurrence/while/body",
     "core/core._run/attention_3"),
    ("model.gdn_recurrence_ms_per_update", "jvp(R2D2Network)/R2D2Network.unroll/core/core._run/gdn_0/gdn_0.recurrence/bkrid,bkrde->bkrie",
     "core/core._run/gdn_0/gdn_0._out/dot_general"),
    ("model.gdn_recurrence_ms_per_update", "R2D2Network.unroll/core/core._run/checkpoint/gdn_2/gdn_2.recurrence/...ij,...jk->...ik",
     "core/core._run/gdn_2/mul"),
    ("model.attention_ms_per_update", "R2D2Network.unroll/core/core._run/checkpoint/attention_3/while/body", "core/core._run/gdn_1"),
    ("model.moe_experts_ms_per_update", "R2D2Network.unroll/core/core._run/moe_3/moe_3.routed/experts/ecd,edf->ecf",
     "core/core._run/moe_3/moe_3.shared"),
])
def test_each_time_metric_finds_its_layers_op_names_and_no_other(metric, found, not_found):
    spec = harness.load_json(os.path.join(ROOT, "benchmark", "layers", metric + ".json"))
    assert spec["reader"] == "trace_scope" and spec["within"] == "core" and spec["per"] == "updates"
    assert re.search(spec["op_name"], found) and not re.search(spec["op_name"], not_found)
    scopes = harness.load_json(os.path.join(ROOT, "benchmark", "trace_scopes.json"))["buckets"]
    first = next(b for b, rx in scopes if re.search(rx, "jit(r2d2_update)/" + found))
    assert first == "core"
