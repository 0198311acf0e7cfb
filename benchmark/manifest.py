"""BENCHMARK.json against its contract and against the files it names, as
functions of `(root, manifest)`: `root` is a checkout (the repo, or a
temporary copy to which a configuration was added), `manifest` the parsed
BENCHMARK.json found there.

tests/benchmark/test_bench_manifest.py applies each to the repo's manifest,
entry by entry, and `check_all` to a copy with a fourth configuration, its
reference file and its cell added as files and entries
(tests/benchmark/test_bench_architecture.py): a rule that a new configuration
cannot meet without an edit to a file that is there fails that test, not the
PR that brings the configuration. So no rule here names a cell or a
configuration, and what is particular to one configuration sits in its file:

    "expect":           what `build_config` of the file must give, hand-computed
                        from the source (EXPECT_KEYS at least), compared key by key
    "deployment_share": where a `reduced` key counts what this chip HOLDS of a
                        layer (`*_held`, or a published key of heads that cannot
                        be renamed): `chips_per_layer`, and for each such key
                        {"published": n, "held": k}

A failed rule raises ManifestError with the entry and the key it is about.
"""

from __future__ import annotations

import os
import re
from typing import Dict, List

from benchmark import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]+$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
# A width is never reduced: a hidden, intermediate, latent, state or projection
# size, a head size, an expansion factor, the experts per token. A key that
# COUNTS what is held here of a layer shared over chips (heads, experts,
# vocabulary rows: the model-configs guide, section 4) ends in `_held`.
WIDTH = re.compile(r"hidden|intermediate|latent|state|_dim$|_rank$|head|expan|experts_per")
HELD = "_held"
SIZE = re.compile(r"_dim|_rank|_size|experts_per|expan")  # never a count, whatever its ending
LAYERS = re.compile(r"layers$")
EXPECT_KEYS = ("hidden_dim", "seq_len", "batch_size", "num_blocks", "encoder", "obs_shape")
# end-to-end metrics that data files kept for a later cell may name (PERF.md section 7)
PLANNED_E2E = {"serve_p99_ms"}
MAX_CELLS = 24


class ManifestError(AssertionError):
    """A rule of the manifest's contract does not hold."""


def need(ok, message: str) -> None:
    if not ok:
        raise ManifestError(message)


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def metrics(m: dict) -> List[dict]:
    return m["end_to_end"] + m["per_layer"]


def bench_dir(root: str, m: dict) -> str:
    return os.path.join(root, m["paths"][0])


def data_files(root: str, m: dict, sub: str) -> List[str]:
    return sorted(f[:-5] for f in os.listdir(os.path.join(bench_dir(root, m), sub)) if f.endswith(".json"))


def _one_line(text, what: str) -> None:
    need(isinstance(text, str) and 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text,
         f"{what}: 1 to 200 characters on one line, got {text!r}")


def check_top_level(root: str, m: dict) -> None:
    need(set(m) == TOP_KEYS, f"top-level keys {sorted(m)} are not {sorted(TOP_KEYS)}")
    need(os.path.getsize(os.path.join(root, "BENCHMARK.json")) <= 64 * 1024, "BENCHMARK.json is over 64 KiB")
    need(1 <= len(m["paths"]) <= 16 and 1 <= len(m["command"]) <= 32, "paths: 1 to 16, command: 1 to 32 words")
    need(isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51, "run_seconds: a whole number, 1 to 51")
    for word in m["command"]:
        _one_line(word, "a word of command")
        need(not word.startswith("/") and ".." not in word, f"command word {word!r} leads out of the repo")
        if "/" in word:  # a file of the repo: must lie under `paths`
            need(any(word.startswith(p + "/") for p in m["paths"]), f"command names {word!r} outside paths")
    # the full check with the full 24 cells fits the driver's budget
    runs = 2 + 14 * MAX_CELLS
    need(runs * (m["run_seconds"] + 60) + MAX_CELLS * 2 * 90 + 1200 <= 43200,
         f"run_seconds {m['run_seconds']} does not fit a full check of {MAX_CELLS} cells")


def check_entry(m: dict, entry: dict) -> None:
    """Names, units and keys of one metric, cell or configuration entry."""
    what = f"entry {entry.get('name')!r}"
    need(NAME.match(entry["name"]), f"{what}: not a name")
    if "unit" in entry:
        need(UNIT.match(entry["unit"]) and entry["better"] in ("lower", "higher"), f"{what}: unit or better")
        need(entry["source"] in SOURCES, f"{what}: source {entry['source']!r}")
        allowed = {"name", "unit", "better", "source", "workloads"}
        allowed |= {"bound"} if "bound" in entry else {"layer", "moves"}
        need(set(entry) <= allowed, f"{what}: keys {sorted(set(entry) - allowed)} are not allowed")
        if "bound" not in entry:
            # without a list a per-layer metric is owed by every cell that reports
            # what it moves, those that later PRs add too: the driver refuses the
            # PR that adds such a cell
            need(entry.get("workloads"), f"{what}: a per-layer metric lists its cells under 'workloads'")
    for key in ("why", "layer", "source"):
        if key in entry:
            _one_line(entry[key], f"{what}: {key}")
    for key in ("config", "traffic"):
        if key in entry:
            need(NAME.match(entry[key]), f"{what}: {key} {entry[key]!r} is not a name")


def check_unique_and_bounded(m: dict) -> None:
    for group in (metrics(m), m["workloads"], m["configs"]):
        names = [e["name"] for e in group]
        need(len(names) == len(set(names)), f"names repeat: {sorted(n for n in names if names.count(n) > 1)}")
    need(2 <= len(m["workloads"]) <= MAX_CELLS and 1 <= len(m["configs"]) <= 24, "2 to 24 cells, 1 to 24 configurations")
    need(1 <= len(m["end_to_end"]) <= 16 and 1 <= len(m["per_layer"]) <= 128, "1 to 16 / 1 to 128 metrics")
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    need(len(pairs) == len(set(pairs)), "a pair of configuration and traffic appears twice")
    need(all(w["chips"] in (1, 4) for w in m["workloads"]), "chips: 1 or 4")
    four = [w for w in m["workloads"] if w["chips"] == 4]
    need(len(four) <= max(1, len(m["workloads"]) // 4), f"{len(four)} four-chip cells of {len(m['workloads'])}")
    need({c["name"] for c in m["configs"]} == {w["config"] for w in m["workloads"]},
         "every configuration is used by a cell, and every cell names a configuration")


def check_end_to_end_bounds(m: dict) -> None:
    e2e = {e["name"]: e for e in m["end_to_end"]}
    need("setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1, "setup_s with a bound of 0.1 at most")
    for e in m["end_to_end"]:
        need(0.01 <= e["bound"] <= 0.1, f"{e['name']}: bound {e['bound']} outside 0.01..0.1")
        need(e["source"] in ("host_clock", "device_trace"), f"{e['name']}: an end-to-end metric is taken by the benchmark")


def check_reduced(config_entry: dict, conf: dict) -> None:
    """`reduced` names no width. A key that the width pattern finds passes only
    as a count of layers (depth), or as a count of what this chip HOLDS of a
    layer that chips share: its name ends in `_held`, or (a published key that
    cannot be renamed, as `num_key_value_heads`) it has an entry under
    `deployment_share`. Either way the file states the deployment beside it,
    and a size (`_dim`, `_rank`, `_size`, experts per token, an expansion) is
    never such a count."""
    what = f"configuration {config_entry['name']!r}"
    need(len(config_entry["reduced"]) <= 16, f"{what}: over 16 reduced keys")
    share = conf.get("deployment_share")
    stated = share if isinstance(share, dict) else {}
    for key in config_entry["reduced"]:
        need(NAME.match(key), f"{what}: reduced key {key!r} is not a name")
        width = WIDTH.search(key)
        if width and LAYERS.search(key):
            continue  # num_hidden_layers: how many layers, not how wide one is
        if not (key.endswith(HELD) or (width and key in stated)):
            need(not width,
                 f"{what}: reduced key {key!r} names a width; a width is never reduced (a count of what this "
                 f"chip holds ends in {HELD!r} or has its entry under 'deployment_share')")
            continue
        need(not SIZE.search(key), f"{what}: reduced key {key!r} names a width: a size, not a count of what is held")
        need(isinstance(stated.get("chips_per_layer"), int) and stated["chips_per_layer"] >= 2,
             f"{what}: reduced key {key!r} needs 'deployment_share' with 'chips_per_layer' (2 or more)")
        counts = stated.get(key)
        need(isinstance(counts, dict) and isinstance(counts.get("published"), int)
             and isinstance(counts.get("held"), int) and 1 <= counts["held"] < counts["published"],
             f"{what}: 'deployment_share' gives no {{'published': n, 'held': k < n}} for {key!r}")
        ran = conf.get("overrides", {}).get(key, counts["held"])
        need(ran == counts["held"], f"{what}: {key!r} runs {ran}, 'deployment_share' holds {counts['held']}")


def check_cell(root: str, m: dict, cell_name: str) -> None:
    """One cell finds its files: configuration, reference, traffic, driver; and
    reports set-up, another end-to-end metric and a per-layer metric."""
    c = harness.load_cell(root, cell_name)
    what = f"cell {cell_name!r}"
    need(os.path.exists(os.path.join(c.bench_dir, "drivers", c.traffic["driver"] + ".py")),
         f"{what}: traffic {c.workload['traffic']!r} names the driver {c.traffic['driver']!r}, which has no file")
    harness.reference_for(c)  # raises with the path where the file is missing or short of the contract
    entry = c.config_entry
    need(any(entry["file"].startswith(p + "/") for p in m["paths"]), f"{what}: {entry['file']} is outside paths")
    need(c.config["name"] == entry["name"] and sorted(c.config["reduced"]) == sorted(entry["reduced"]),
         f"{what}: name or 'reduced' of {entry['file']} differ from the manifest's")
    check_reduced(entry, c.config)
    e2e = [e["name"] for e in m["end_to_end"] if applies(e, cell_name)]
    need("setup_s" in e2e and len(e2e) >= 2, f"{what}: reports setup_s and one more end-to-end metric")
    layer = [e for e in m["per_layer"] if applies(e, cell_name)]
    need(layer and all(e["moves"] in e2e for e in layer),
         f"{what}: a per-layer metric, and each moves an end-to-end metric the cell reports")


def check_layer_metric(root: str, m: dict, metric: dict) -> None:
    spec = harness.load_json(os.path.join(bench_dir(root, m), "layers", metric["name"] + ".json"))
    what = f"per-layer metric {metric['name']!r}"
    for key in ("name", "layer", "unit", "moves"):
        need(spec[key] == metric[key], f"{what}: {key} is {spec[key]!r} in its layer file, {metric[key]!r} in the manifest")
    need(os.path.exists(os.path.join(bench_dir(root, m), "readers", spec["reader"] + ".py")),
         f"{what}: no reader {spec['reader']!r}")
    cells = {w["name"] for w in m["workloads"]}
    for w in metric.get("workloads", []):
        need(w in cells, f"{what}: lists {w!r}, which is no cell")
    if "category" in spec:
        from benchmark import trace

        need(spec["category"] in trace.load_patterns(os.path.join(bench_dir(root, m), "trace_patterns.json"))["categories"],
             f"{what}: no category {spec['category']!r} in trace_patterns.json")
    if spec["reader"] == "trace_scope":  # either form names a bucket of the shared, ordered list
        buckets = [b for b, _ in harness.load_json(os.path.join(bench_dir(root, m), "trace_scopes.json"))["buckets"]]
        bucket = spec["within"] if "op_name" in spec else spec["bucket"]
        need(bucket in buckets + ["unscoped"], f"{what}: no bucket {bucket!r} in trace_scopes.json")
        try:
            re.compile(spec.get("op_name", ""))
        except re.error as e:
            raise ManifestError(f"{what}: op_name {spec['op_name']!r} is no regex: {e}") from e


def check_file_names(root: str, m: dict) -> None:
    for p in m["paths"]:
        need(PATH.match(p) and len(p) <= 200 and not p.startswith("/") and ".." not in p, f"path {p!r}")
        for d, _, files in os.walk(os.path.join(root, p)):
            if "__pycache__" in d:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), root)
                need(PATH.match(rel), f"file {rel!r} is not named from the characters of a name and '/'")


def check_config(root: str, m: dict, config_entry: dict) -> None:
    """The file's preset + overrides is a valid R2D2Config that reads what the
    file itself expects of it, and every changed key says why."""
    conf = harness.load_json(os.path.join(root, config_entry["file"]))
    what = f"configuration {config_entry['name']!r}"
    cfg = harness.build_config(conf, seed=5, extra={"samples_per_insert": 8.0})
    need(cfg.seed == 5, f"{what}: the seed does not reach the configuration")
    expect = conf.get("expect")
    need(isinstance(expect, dict), f"{what}: {config_entry['file']} has no 'expect' "
                                   f"({', '.join(EXPECT_KEYS)}: hand-computed from the source)")
    missing = [k for k in EXPECT_KEYS if k not in expect]
    need(not missing, f"{what}: 'expect' lacks {missing}")
    for key, want in expect.items():
        if key.startswith("_"):
            continue
        got = getattr(cfg, key)
        got = list(got) if isinstance(got, tuple) else got
        need(got == want, f"{what}: 'expect' says {key} = {want!r}, the configuration builds {got!r}")
    # the fused collector's rule: an episode fits one chunk, and fills the block
    need(cfg.max_episode_steps == cfg.block_length,
         f"{what}: max_episode_steps {cfg.max_episode_steps} is not block_length {cfg.block_length}")
    need(conf["source"] == config_entry["source"], f"{what}: 'source' differs between the file and the manifest")
    _one_line(conf["source"], f"{what}: source")
    # every changed key says why; a width that follows from a swap is at least stated
    need(set(conf["reduced"]) == set(conf.get("reduced_why", {})),
         f"{what}: 'reduced_why' gives a reason for {sorted(conf.get('reduced_why', {}))}, 'reduced' is {sorted(conf['reduced'])}")
    need("action_dim" in conf.get("assumed", {}), f"{what}: 'assumed' does not state action_dim")


def check_layer_file(root: str, m: dict, name: str) -> None:
    """Also the files of cells that are not in the manifest today: a later PR
    adds them back as entries only, so the files must already be sound."""
    spec = harness.load_json(os.path.join(bench_dir(root, m), "layers", name + ".json"))
    what = f"layer file {name!r}"
    need(spec["name"] == name and NAME.match(name) and UNIT.match(spec["unit"]), f"{what}: name or unit")
    need(os.path.exists(os.path.join(bench_dir(root, m), "readers", spec["reader"] + ".py")),
         f"{what}: no reader {spec['reader']!r}")
    need(spec["moves"] in {e["name"] for e in m["end_to_end"]} | PLANNED_E2E, f"{what}: moves {spec['moves']!r}")
    listed = {e["name"] for e in m["per_layer"]}
    need((name in listed) == (spec["moves"] not in PLANNED_E2E),
         f"{what}: a file whose metric moves a present end-to-end metric has a manifest entry, and no other")


def check_traffic_file(root: str, m: dict, name: str) -> None:
    t = harness.load_json(os.path.join(bench_dir(root, m), "traffic", name + ".json"))
    need(NAME.match(name), f"traffic file {name!r}: not a name")
    need(os.path.exists(os.path.join(bench_dir(root, m), "drivers", t["driver"] + ".py")),
         f"traffic file {name!r}: no driver {t['driver']!r}")
    if t["driver"] == "serve_open_loop":
        # no reserved pool the traffic never fills: the cache holds the resident sessions
        need(t["cache_capacity"] == t["sessions"] and t["rate_per_s"] > 0,
             f"traffic file {name!r}: cache_capacity is not sessions, or no rate")


def reference_files(root: str, m: dict) -> Dict[str, str]:
    """{cell: the file its reference module was loaded from}: the file the
    cell's configuration names (default `model`), one module per file."""
    out: Dict[str, str] = {}
    by_file: Dict[str, object] = {}
    for w in m["workloads"]:
        c = harness.load_cell(root, w["name"])
        mod = harness.reference_for(c)
        want = os.path.realpath(os.path.join(c.bench_dir, "reference", c.config.get("reference", "model") + ".py"))
        need(os.path.realpath(mod.__file__) == want, f"cell {c.name!r}: reference loaded from {mod.__file__}, not {want}")
        need(by_file.setdefault(want, mod) is mod, f"{want} was loaded twice")
        out[c.name] = want
    return out


def check_all(root: str, m: dict) -> int:
    """Every rule above, on every entry and file of `root`: the whole of what
    tests/benchmark/test_bench_manifest.py asks of a manifest. Returns how
    many were asked."""
    check_top_level(root, m)
    check_unique_and_bounded(m)
    check_end_to_end_bounds(m)
    check_file_names(root, m)
    reference_files(root, m)
    per_entry = (
        (lambda e: check_entry(m, e), metrics(m) + m["workloads"] + m["configs"]),
        (lambda w: check_cell(root, m, w["name"]), m["workloads"]),
        (lambda e: check_layer_metric(root, m, e), m["per_layer"]),
        (lambda c: check_config(root, m, c), m["configs"]),
        (lambda n: check_layer_file(root, m, n), data_files(root, m, "layers")),
        (lambda n: check_traffic_file(root, m, n), data_files(root, m, "traffic")),
    )
    for check, entries in per_entry:
        for entry in entries:
            check(entry)
    return 5 + sum(len(entries) for _, entries in per_entry)
