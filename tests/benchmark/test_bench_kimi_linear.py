"""The kimi-linear-48b-a3b-ep32 configuration and its reference
(benchmark/reference/kimi_linear.py): what the file says against the source's
numbers, `update_flops` by hand for one block of each kind, the reference's
independence of the program, the layer checks and their controls, and the
whole cell at tiny widths on the CPU through `run_cell`, traced. Every entry
of the manifest is found by NAME and held by its place relative to others:
nothing here pins the manifest's end, a count of configurations or the whole
of a metric's list of cells, so the next configuration breaks none of it."""

import gc
import json
import os
import re
import shutil

import pytest
from test_bench_architecture import OWN_SCOPE, fourth_root  # noqa: F401 (the fixture: the benchmark's copy with one more configuration)

from benchmark import flops, harness, manifest
from benchmark import trace as tr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "kimi-linear-48b-a3b-ep32"
CELL = NAME + ".learn"
FIFTH, FIFTH_CELL = "qwen3-next-80b-a3b-ep32", "qwen3-next-80b-a3b-ep32.learn"
with open(os.path.join(ROOT, "BENCHMARK.json")) as _fh:
    M = json.load(_fh)
CONF = harness.load_json(os.path.join(ROOT, "benchmark", "configs", NAME + ".json"))
# the source's config.json, typed in from the catalog row
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu", "hidden_size": 2304, "intermediate_size": 9216,
    "kv_lora_rank": 512,
    "linear_attn_config": {"full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
                           "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26],
                           "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576, "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True, "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1, "q_lora_rank": None, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False, "topk_group": 1, "use_grouped_topk": True,
    "v_head_dim": 128,
}
MIXTURES = ["model.moe_ms_per_update", "model.moe_experts_ms_per_update", "model.moe_dropped_share",
            "model.moe_load_max_over_mean", "dispatch.collect_moe_ms_per_update"]
NEW_METRICS = ["model.kda_ms_per_update", "model.kda_recurrence_ms_per_update", "model.mla_ms_per_update",
               "model.mlp_ms_per_update", "dispatch.collect_kda_ms_per_update", "dispatch.collect_mla_ms_per_update"]
NOT_THIS_CELLS = ["kernels.lstm_ms_per_update", "kernels.lstm_roofline", "model.lru_recurrence_ms_per_update",
                  "collectives.exposed_ms_per_update", "model.ssm_ms_per_update", "model.attention_ms_per_update",
                  "model.gdn_ms_per_update", "model.gdn_recurrence_ms_per_update", "dispatch.collect_ssm_ms_per_update",
                  "dispatch.collect_attention_ms_per_update", "dispatch.collect_gdn_ms_per_update"]


def _places(entries, names):
    order = [e["name"] for e in entries]
    return [order.index(n) for n in names]


def test_the_manifest_has_the_configuration_its_cell_and_its_six_metrics_by_name():
    from test_bench_host_parts import EIGHT

    configs = {c["name"]: c for c in M["configs"]}
    cells = {w["name"]: w for w in M["workloads"]}
    assert configs[NAME]["file"] == f"benchmark/configs/{NAME}.json" and configs[NAME]["source"] == CONF["source"]
    assert cells[CELL] == {**cells[CELL], "config": NAME, "traffic": "learn", "chips": 1}
    # after the fifth configuration's, which were there before
    assert _places(M["configs"], [NAME])[0] > _places(M["configs"], [FIFTH])[0]
    assert _places(M["workloads"], [CELL])[0] > _places(M["workloads"], [FIFTH_CELL])[0]
    per_layer = {m["name"]: m for m in M["per_layer"]}
    for name in NEW_METRICS:
        assert per_layer[name]["workloads"][0] == CELL and per_layer[name]["moves"] == "learn_steps_per_s"
        assert (per_layer[name]["source"], per_layer[name]["unit"], per_layer[name]["better"]) == ("device_trace", "ms", "lower")
    new = _places(M["per_layer"], NEW_METRICS)
    assert new == list(range(new[0], new[0] + len(new)))        # together, in this order
    assert new[0] > max(_places(M["per_layer"], MIXTURES + NOT_THIS_CELLS + ["kernels.gdn_solve_ms_per_update"]))
    listed = {m["name"] for m in M["per_layer"] if CELL in m["workloads"]}
    assert not listed & {*NOT_THIS_CELLS, *EIGHT}
    assert set(MIXTURES) | set(NEW_METRICS) | {"model.mfu", "model.core_ms_per_update", "device.peak_hbm_gb",
                                               "device.idle_share", "kernels.gdn_solve_ms_per_update"} <= listed
    # every metric that lists the learn cells before it lists this one, after them
    for m in M["end_to_end"] + M["per_layer"]:
        cells_of = m.get("workloads", [])
        if FIFTH_CELL in cells_of and "lru-seq581.learn" in cells_of:
            assert cells_of.index(CELL) > cells_of.index(FIFTH_CELL), m["name"]
    for name in MIXTURES + ["kernels.gdn_solve_ms_per_update"]:
        assert per_layer[name]["workloads"].index(CELL) > per_layer[name]["workloads"].index(FIFTH_CELL), name


def test_one_more_configuration_beside_these_still_runs_as_files_and_entries(fourth_root, monkeypatch, tmp_path):  # noqa: F811
    """The tripwire of tests/benchmark/test_bench_architecture.py (a further
    configuration added to a copy as files and entries, every manifest rule
    asked of it, its cell run traced to `correct: true`, its own layer file
    read), with its pins as a manifest of any length has to read: this cell's
    reference file by the cell's NAME, and no other cell's is that file."""
    root, m = fourth_root
    assert manifest.check_all(root, m) > 100
    assert {c["name"] for c in m["configs"]} == {c["name"] for c in M["configs"]} | {"toy-share"}
    files = manifest.reference_files(root, m)
    bench = os.path.realpath(os.path.join(root, "benchmark", "reference"))
    assert files.pop("toy-share.learn") == os.path.join(bench, "toy.py")
    assert files.pop(CELL) == os.path.join(bench, "kimi_linear.py")
    assert files[FIFTH_CELL] == os.path.join(bench, "qwen3_next.py")
    assert os.path.join(bench, "kimi_linear.py") not in files.values() and os.path.join(bench, "toy.py") not in files.values()
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
    # the copy lists the added cell under this cell's six as well; a program without such layers gives
    # their reader nothing to read: 0, and no error
    assert set(NEW_METRICS) <= listed and all(got[k]["value"] == 0.0 for k in NEW_METRICS if k in got)


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_every_published_number_is_in_the_file_under_its_own_key(key):
    assert CONF[key] == PUBLISHED[key]
    core = CONF["overrides"]["core_config"]
    assert core.get(key, PUBLISHED[key]) == PUBLISHED[key]   # and the core runs the same width


def test_reduced_is_depth_experts_held_vocabulary_and_the_shell():
    core = CONF["overrides"]["core_config"]
    assert CONF["num_hidden_layers"] == core["num_hidden_layers"] == 5 and CONF["vocab_size"] == 3
    # layers 1-5 of the published lists: the dense layer, then one whole period K K L K
    linear = core["linear_attn_config"]
    assert linear == PUBLISHED["linear_attn_config"] == CONF["linear_attn_config"]
    assert [("L" if i in linear["full_attn_layers"] else "K") for i in range(1, 6)] == ["K", "K", "K", "L", "K"]
    assert all((i in linear["kda_layers"]) != (i in linear["full_attn_layers"]) for i in range(1, 28))
    assert core["first_k_dense_replace"] == 1
    assert set(CONF["reduced"]) == {"num_hidden_layers", "num_experts_held", "vocab_size", "buffer_capacity",
                                    "num_actors", "env_name"} == set(CONF["reduced_why"])
    share = CONF["deployment_share"]
    assert share["chips_per_layer"] == 32 and share["num_experts_held"] == {"published": 256, "held": 8}
    assert share["chips_per_layer"] * share["num_experts_held"]["held"] == CONF["num_experts"] == 256
    assert core["num_experts_held"] == 8 and "first_expert_held" not in core
    entry = next(c for c in M["configs"] if c["name"] == NAME)
    manifest.check_reduced(entry, CONF)
    assert len(CONF["source"]) <= 200 and "layers 1-5" in CONF["source"]
    assert CONF["source"].startswith("https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json")
    for needed in ("gate_rank", "gate_biases", "initialisers", "l2_norm_eps", "chunk_size", "projection_columns",
                   "no_rotation", "e_score_correction_bias", "attention_memory", "input_projection", "capacity_factor",
                   "unused_keys", "precision", "batch_size", "action_dim"):
        assert needed in CONF["assumed"], needed
    assert set(CONF["limits"]) == {"q", "loss", "grad_norm"} and set(CONF["limits_why"]) == set(CONF["limits"]) and "kda_slow_state" in CONF["layer_limits_why"]
    assert CONF["preset"] == "long_context" and CONF["reference"] == "kimi_linear"
    assert CONF["overrides"]["batch_size"] == CONF["expect"]["batch_size"] and CONF["overrides"]["batch_size"] in (4, 8)
    # the core names no option of the program's own beyond the three
    own = set(core) - set(PUBLISHED) - {"num_hidden_layers"}
    assert own == {"num_experts_held", "capacity_factor"}


def test_update_flops_by_hand_for_one_block_of_each_kind():
    ref = harness.reference_for(harness.load_cell(ROOT, CELL))
    cfg = harness.build_config(CONF, 0)
    per = ref.layer_flops_per_token(ref.stack_of(cfg), cfg.seq_len)
    # K: q, k, v 2,304 x 4,096 each; the two gates through rank 128; beta 2,304 x 32; o 4,096 x 2,304; and the
    # recurrence's S^T k, the written outer product and S^T q, 32 x 128 x 128 each
    assert per["K"] == (2 * 2304 * 12288 + 2 * 2 * (2304 * 128 + 128 * 4096) + 2 * 2304 * 32 + 2 * 4096 * 2304
                        + 3 * 2 * 32 * 128 * 128) == 82067456
    # L: q 2,304 x 6,144, the latent 2,304 x 576, ITS OWN up-projection 512 x 8,192 once, o 4,096 x 2,304, and
    # scores (192 a head) and values (128 a head) over 291 keys
    assert per["L"] == (2 * 2304 * 6144 + 2 * 2304 * 576 + 2 * 512 * 8192 + 2 * 4096 * 2304
                        + 2 * 32 * 320 * 291) == 64188416.0
    # F: three matrices 2,304 x 9,216
    assert per["F"] == 6 * 2304 * 9216 == 127401984
    # E: router, the shared expert's three matrices, and 8 x 8 / 256 = 0.25 rows of a routed expert
    assert per["E"] == 2 * 2304 * 256 + 6 * 2304 * 1024 + 0.25 * 6 * 2304 * 1024 == 18874368.0
    trunk = (flops.nature_encoder_flops_per_frame((84, 84, 1), 2304) + 2 * 2308 * 2304
             + 4 * per["K"] + per["L"] + per["F"] + 4 * per["E"])
    heads = 2 * (2 * 2304 * 2304 + 2304 * 3 + 2304)
    assert ref.update_flops(cfg) == int(cfg.batch_size * (trunk * (581 + 2 * 512 + 581) + heads * 5 * 512))
    # the two new mixers are two thirds of the stack's count, the dense MLP a fifth
    stack = 4 * per["K"] + per["L"] + per["F"] + 4 * per["E"]
    assert 0.65 < (4 * per["K"] + per["L"]) / stack < 0.68 and 0.20 < per["F"] / stack < 0.22
    # padding cannot raise it: the capacity is no part of the count
    padded = cfg.replace(core_config={**dict(cfg.core_config), "capacity_factor": 8.0})
    assert ref.update_flops(padded) == ref.update_flops(cfg)
    assert ref.update_flops(cfg) == cfg.batch_size * (ref.update_flops(cfg) // cfg.batch_size)
    assert 1.4e12 < ref.update_flops(cfg) / cfg.batch_size < 1.5e12


def test_the_reference_is_plain_and_imports_nothing_of_the_programs_models():
    text = open(os.path.join(ROOT, "benchmark", "reference", "kimi_linear.py")).read()
    assert not [m for m in re.findall(r"^(?:from|import)\s+([\w.]+)", text, re.M) if m.startswith("r2d2_tpu")]
    inside = re.findall(r"^\s+(?:from|import)\s+(r2d2_tpu[\w.]*)", text, re.M)
    assert inside == ["r2d2_tpu.models"] and text.index("def kernel_checks") < text.index("from r2d2_tpu.models")
    assert "pallas" not in text and "_loop_over_time" in text and "lax.scan" in text
    # no chunk, no absorption: one step at a time, and keys and values for every position
    assert "solve_triangular" not in text and "cumsum(g" not in text and "kv_b_proj\"]).reshape(B, W + T" in text
    # what is both stacks' is imported, not copied
    assert "from benchmark.reference import nemotron_h as shared" in text
    for name in ("def _conv_valid", "def _loop_over_time", "def repeats", "def capacity", "def encode"):
        assert name not in text, name
    for departure in ("the low-rank gates", "initialisers", "column order", "no rotation", "e_score_correction_bias",
                      "attention memory", "the share", "the capacity", "input projection", "num_nextn_predict_layers",
                      "num_key_value_heads", "top-level `head_dim` 72", "topk_group"):
        assert departure in text, departure


# ------------------------------------------------- the cell, tiny, on the CPU

TINY_CORE = dict(
    model_type="kimi_linear", hidden_size=64, num_hidden_layers=5,
    linear_attn_config=dict(kda_layers=[1, 2, 3, 5, 6, 7], full_attn_layers=[4, 8], num_heads=4, head_dim=16,
                            short_conv_kernel_size=4),
    num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    first_k_dense_replace=1, intermediate_size=96, num_experts=16, num_experts_per_token=2, moe_intermediate_size=32,
    num_shared_experts=1, routed_scaling_factor=2.446, moe_renormalize=True, rms_norm_eps=1e-5, num_experts_held=4)
TINY = {"env_name": "drift", "action_dim": 3, "max_episode_steps": 16, "collector": "device", "replay_plane": "device",
        "updates_per_dispatch": 2, "num_actors": 2, "hidden_dim": 64, "recurrent_core": "hybrid_stack",
        "core_config": TINY_CORE}


@pytest.fixture(scope="module")
def tiny_line(tmp_path_factory):
    """The cell at tiny widths through `run_cell`, TRACED, from a copy of the
    benchmark to which it was added as a file and entries."""
    root = str(tmp_path_factory.mktemp("kimiroot"))
    bench = os.path.join(root, "benchmark")
    shutil.copytree(os.path.join(ROOT, "benchmark"), bench, ignore=shutil.ignore_patterns("__pycache__"))
    m = json.loads(json.dumps(M))
    with open(os.path.join(bench, "configs", "tiny-kimi.json"), "w") as fh:
        json.dump({"name": "tiny-kimi", "source": "test", "preset": "tiny_test", "reference": "kimi_linear",
                   "overrides": TINY, "reduced": []}, fh)
    m["configs"].append({"name": "tiny-kimi", "source": "test", "why": "test", "reduced": [],
                         "file": "benchmark/configs/tiny-kimi.json"})
    m["workloads"].append({"name": "tiny-kimi.learn", "config": "tiny-kimi", "traffic": "learn", "chips": 1,
                           "why": "test"})
    for e in m["end_to_end"] + m["per_layer"]:
        if CELL in e.get("workloads", []):
            e["workloads"].append("tiny-kimi.learn")
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
        return harness.run_cell(root, "tiny-kimi.learn", seed=2**31 + 11, seconds=0.5, trace=True, require_tpu=False)
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


def test_the_new_buckets_are_read_and_with_the_mixtures_they_own_the_core(tiny_line):
    got = {k: v["value"] for k, v in tiny_line["metrics"].items()}
    listed = {e["name"] for e in M["per_layer"] if CELL in e["workloads"]}
    assert set(got) <= listed and set(NEW_METRICS) | set(MIXTURES) <= set(got)
    assert all(got[name] > 0.0 for name in NEW_METRICS)
    assert got["model.kda_recurrence_ms_per_update"] < got["model.kda_ms_per_update"]
    assert got["model.moe_experts_ms_per_update"] < got["model.moe_ms_per_update"]
    parts = sum(got[f"model.{kind}_ms_per_update"] for kind in ("kda", "mla", "mlp", "moe"))
    # the four kinds are the core, less its input projection, final norm and the carry's split and join
    assert 0.5 * got["model.core_ms_per_update"] < parts <= got["model.core_ms_per_update"] * (1 + 1e-9)
    collected = got["dispatch.collect_kda_ms_per_update"] + got["dispatch.collect_mla_ms_per_update"] + got[
        "dispatch.collect_moe_ms_per_update"]
    assert collected <= got["dispatch.collect_ms_per_update"] * (1 + 1e-9)
    assert 0.0 <= got["model.moe_dropped_share"] <= 100.0 and got["model.moe_load_max_over_mean"] >= 1.0


def _bf16_router(self, x):
    import jax
    import jax.numpy as jnp

    low = jnp.dot(x.astype(jnp.bfloat16), self.router.astype(jnp.bfloat16))
    scores = jax.nn.sigmoid(low.astype(jnp.float32))
    return scores, jax.lax.top_k(scores + self.correction_bias, self.sizes.top_k)[1]


def _scalar_gate(real):
    """`KimiDeltaAttention._project` with one gate a head: the channel mean of g."""
    def project(self, x):
        import jax.numpy as jnp

        qkv, gate, beta, g = real(self, x)
        heads = g.reshape(*g.shape[:-1], self.spec.heads, self.spec.head_dim)
        return qkv, gate, beta, jnp.broadcast_to(jnp.mean(heads, axis=-1, keepdims=True), heads.shape).reshape(g.shape)

    return project


def _step_without_the_rings_k_pe(self, x, latent, count):
    """`LatentAttention.step` by the sequence form at T = 1 on a ring whose `k_pe` were dropped."""
    out, ring = self(x[:, None], latent.at[..., self.spec.latent:].set(0.0), count)
    return out[:, 0], ring


@pytest.mark.parametrize("control,fails", [
    (None, set()),
    ("router", {"router_score_err"}),                                       # logits from a bfloat16 matmul
    ("recurrence", {"kda_state_err_over_scale", "kda_slow_state_err_over_scale", "kda_fast_state_err_over_scale"}),  # the chunks' state kept in bfloat16
    ("scalar_gate", {"kda_out_err_over_scale", "kda_state_err_over_scale", "kda_step_out_err_over_scale"}),  # wrong mathematics: Gated DeltaNet's gate
    ("ring_k_pe", {"mla_step_out_err_over_scale"}),                         # wrong mathematics: the acting step forgets the stored k_pe
])
def test_the_layer_checks_pass_the_program_and_tell_each_control(control, fails, monkeypatch):
    """`kernel_checks` of the reference module: the program's blocks against
    the reference's with the program's routing handed over. What the cell's
    whole-program limits cannot tell fails here, each by the number that
    names it; and the controls that `kernel_checks` runs itself (the
    reference in bfloat16, a scalar gate in Kimi Delta Attention's place, the
    ring's `k_pe` dropped, a rotation applied) are each told on every call."""
    import jax.numpy as jnp

    from r2d2_tpu.models import hybrid_stack as hs

    ref = harness.reference_for(harness.load_cell(ROOT, CELL))
    cfg = harness.build_config({"preset": "tiny_test", "overrides": TINY}, 7, {})
    if control == "router":
        monkeypatch.setattr(hs.ExpertMixture, "scores", _bf16_router)
    elif control == "recurrence":
        real = hs.kda_chunked
        rounded = lambda *a: (lambda o, s: (o, s.astype(jnp.bfloat16).astype(jnp.float32)))(*real(*a))
        monkeypatch.setattr(hs, "kda_chunked", rounded)
    elif control == "scalar_gate":
        monkeypatch.setattr(hs.KimiDeltaAttention, "_project", _scalar_gate(hs.KimiDeltaAttention._project))
    elif control == "ring_k_pe":
        monkeypatch.setattr(hs.LatentAttention, "step", _step_without_the_rings_k_pe)
    out = ref.kernel_checks(cfg, 7, 8)
    over = {k for k, limit in out["limits"].items() if not out[k] <= limit}
    assert fails <= over and bool(over) == bool(fails) and out["ok"] is (not fails), out
    assert out["limits"] == ref.LAYER_LIMITS[cfg.resolved_compute_dtype] and 0.0 <= out["router_flip_share"] <= 1.0
    # the controls of the check's own: told whatever the program does
    assert out["controls_told"] is True
    assert any(out["control_bfloat16"][k] > limit for k, limit in out["limits"].items())
    assert min(out["control_scalar_gate_err_over_scale"], out["control_ring_k_pe_dropped_err_over_scale"],
               out["control_rotated_err_over_scale"]) > 0.05


def test_the_runner_published_what_the_mixtures_counted(tiny_line):
    from r2d2_tpu.utils import profiling

    counters = profiling.counters()
    assert counters["moe.rows_offered"] > 0 and counters["moe.rows_dropped"] >= 0
    assert 0.0 <= counters["moe.dropped_share"] <= 100.0 and counters["moe.load_max_over_mean"] >= 1.0


@pytest.mark.parametrize("metric,found,not_found", [
    ("model.kda_ms_per_update", "jit(mega)/R2D2Network.unroll/core/core._run/kda_1/kda_1._project/dot_general", "core/core._run/moe_1"),
    ("model.kda_ms_per_update", "transpose(jvp(R2D2Network))/R2D2Network.unroll/core/core._run/checkpoint/kda_4/kda_4.recurrence/while/body",
     "core/core._run/gdn_2/gdn_2.recurrence"),
    ("model.kda_recurrence_ms_per_update", "jvp(R2D2Network)/R2D2Network.unroll/core/core._run/kda_0/kda_0.recurrence/bhid,bhde->bhie",
     "core/core._run/kda_0/kda_0._out/dot_general"),
    ("model.kda_recurrence_ms_per_update", "R2D2Network.unroll/core/core._run/checkpoint/kda_2/kda_2.recurrence/checkpoint/while/body/exp",
     "core/core._run/kda_2/mul"),
    ("model.mla_ms_per_update", "R2D2Network.unroll/core/core._run/checkpoint/mla_3/while/body", "core/core._run/attention_3"),
    ("model.mlp_ms_per_update", "R2D2Network.unroll/core/core._run/mlp_0/dot_general", "core/core._run/moe_1/moe_1.shared"),
    ("dispatch.collect_kda_ms_per_update", "jit(mega)/jit(r2d2_collect)/while/body/core.step_open/core._layers/kda_1/reduce_sum",
     "jit(r2d2_collect)/while/body/core.step_open/core._layers/gdn_1/reduce_sum"),
    ("dispatch.collect_mla_ms_per_update", "jit(mega)/jit(r2d2_collect)/while/body/core.step_open/core._layers/mla_3/bhc,bwc->bhw",
     "jit(r2d2_collect)/while/body/core.step_open/core._layers/attention_3/dot_general"),
])
def test_each_time_metric_finds_its_layers_op_names_and_no_other(metric, found, not_found):
    spec = harness.load_json(os.path.join(ROOT, "benchmark", "layers", metric + ".json"))
    within = "collect" if metric.startswith("dispatch.") else "core"
    assert spec["reader"] == "trace_scope" and spec["within"] == within and spec["per"] == "updates"
    assert set(spec) == {"name", "layer", "unit", "moves", "reader", "op_name", "within", "per", "scale"}   # data only
    assert re.search(spec["op_name"], found) and not re.search(spec["op_name"], not_found)
    scopes = harness.load_json(os.path.join(ROOT, "benchmark", "trace_scopes.json"))["buckets"]
    first = next(b for b, rx in scopes if re.search(rx, found if within == "collect" else "jit(r2d2_update)/" + found))
    assert first == within


@pytest.mark.parametrize("other,ours", [("model.attention_ms_per_update", "core/core._run/mla_3/dot_general"),
                                        ("model.gdn_ms_per_update", "core/core._run/kda_1/kda_1.recurrence/while"),
                                        ("model.gdn_recurrence_ms_per_update", "core/core._run/kda_1/kda_1.recurrence/while"),
                                        ("model.ssm_ms_per_update", "core/core._run/kda_1/kda_1._project"),
                                        ("dispatch.collect_attention_ms_per_update", "core.step_open/core._layers/mla_3/reduce"),
                                        ("dispatch.collect_gdn_ms_per_update", "core.step_open/core._layers/kda_0/reduce")])
def test_the_other_families_layer_files_do_not_find_this_familys_blocks(other, ours):
    """`/mla_` is not found by `/attention_`, `/kda_` not by `/gdn_`: the
    cell is left off those metrics' lists because they would read nothing."""
    spec = harness.load_json(os.path.join(ROOT, "benchmark", "layers", other + ".json"))
    assert not re.search(spec["op_name"], "jit(mega)/R2D2Network.unroll/" + ours)
    assert CELL not in next(m for m in M["per_layer"] if m["name"] == other)["workloads"]
