"""Finds a cell's files by name and runs it: the part every cell shares.

A cell is one `workloads` entry of BENCHMARK.json. Its pieces are found by
name alone, so a later PR adds a cell by adding files and manifest entries and
edits nothing that exists:

    workloads[i].config  -> configs[j].file      (a JSON of sizes, preset + overrides)
    config["reference"]  -> <paths[0]>/reference/<name>.py      (default "model": what the
                            yardstick knows about the architecture, see reference_for)
    config["limits"]     -> limits of `correct` that this configuration states for itself
    workloads[i].traffic -> <paths[0]>/traffic/<traffic>.json   (parameters + "driver")
    traffic["driver"]    -> benchmark.drivers.<driver>.run(ctx) (code; two kinds today)
    per_layer[k].name    -> <paths[0]>/layers/<name>.json       (reader kind + parameters)
    layer["reader"]      -> benchmark.readers.<reader>.read(spec, ctx)

A driver returns what it measured (`Measured`); this module turns that into
the contract's last line: the cell's end_to_end metrics with --trace 0, its
per_layer metrics (each read by its own reader; one that finds nothing is
left out) with --trace 1.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
import importlib.util
import json
import math
import os
import time
from typing import Any, Dict, List, Optional


class BenchmarkError(RuntimeError):
    """The run cannot produce a result (no result line is printed)."""


@dataclasses.dataclass
class Cell:
    root: str                 # checkout root (holds BENCHMARK.json)
    manifest: dict
    workload: dict            # the workloads entry
    config_entry: dict        # the configs entry
    config: dict              # the configuration file's content
    traffic: dict             # the traffic file's content
    bench_dir: str            # <root>/<paths[0]>

    @property
    def name(self) -> str:
        return self.workload["name"]

    def metric_applies(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]


@dataclasses.dataclass
class Context:
    """What a driver gets, and what readers read afterwards."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_start: float                      # perf_counter at process start
    require_tpu: bool = True
    # filled by the driver:
    cfg: Any = None                     # the resolved R2D2Config
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    trace_data: Any = None              # benchmark.trace.Trace of the traced window
    patterns: Optional[dict] = None

    def work_dir(self, *parts: str) -> str:
        d = os.path.join(self.cell.root, ".benchmark_work", *parts)
        os.makedirs(d, exist_ok=True)
        return d


@dataclasses.dataclass
class Measured:
    """A driver's result, before it is cut to the manifest's metrics."""

    correct: bool
    attempted: int
    failed: int
    end_to_end: Dict[str, float]        # every end-to-end value the driver has
    notes: Dict[str, Any] = dataclasses.field(default_factory=dict)


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_cell(root: str, workload: str) -> Cell:
    manifest = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise BenchmarkError(f"no workload {workload!r} in BENCHMARK.json (have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    if w["config"] not in configs:
        raise BenchmarkError(f"workload {workload!r} names unknown config {w['config']!r}")
    entry = configs[w["config"]]
    bench_dir = os.path.join(root, manifest["paths"][0])
    return Cell(
        root=root, manifest=manifest, workload=w, config_entry=entry,
        config=load_json(os.path.join(root, entry["file"])),
        traffic=load_json(os.path.join(bench_dir, "traffic", w["traffic"] + ".json")),
        bench_dir=bench_dir,
    )


# What a reference module must define, and what it may. The whole contract
# between the benchmark and an architecture (PERF.md section 3):
#   sizes_of(cfg) -> the shape facts its functions need
#   loss_q_gradnorm(params, target_params, batch, sizes) -> (loss, q_learn, grad norm)
#       `batch` is a dict of arrays; batch["hidden"] is the stored state as
#       the replay holds it, whatever its shape
#   act_unroll(params, obs, last_action, last_reward, sizes) -> Q (S, T, A)
#       acting from the module's own zero state
#   update_flops(cfg) -> operations one learner update requires (model.mfu)
#   optional kernel_checks(cfg, seed, batch) -> dict with "ok": each kernel of
#       the architecture against its plain form at the cell's own shapes
#   optional TOL: {compute dtype: limits} where correct.TOL does not fit it
#   optional island_inputs(params, target_params, batch, sizes) -> the Q views the
#       loss island reads (q_learn, q_boot, q_boot_target, mask), with
#       loss_from_q(q_learn, q_boot, q_boot_target, mask, batch, sizes):
#       correct.loss_island asks the program's own island on them, alone
# A configuration file may state limits of its own for its cells (`"limits"`,
# keys of correct.TOL, each with its reason under `"limits_why"`).
REFERENCE_CONTRACT = ("sizes_of", "loss_q_gradnorm", "act_unroll", "update_flops")


@functools.lru_cache(maxsize=None)
def _module_from_file(path: str):
    spec = importlib.util.spec_from_file_location("benchmark_reference_" + os.path.basename(path)[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_for(cell: Cell):
    """The reference module the cell's configuration names (`"reference"`,
    default `model`), loaded from <bench_dir>/reference/<name>.py: by file, so
    that a later PR adds an architecture as a file and edits nothing."""
    name = cell.config.get("reference", "model")
    path = os.path.join(cell.bench_dir, "reference", f"{name}.py")
    if not str(name).isidentifier() or not os.path.isfile(path):
        raise BenchmarkError(f"configuration {cell.config_entry['name']!r} names the reference "
                             f"{name!r}, and there is no file {path}")
    mod = _module_from_file(os.path.realpath(path))
    missing = [f for f in REFERENCE_CONTRACT if not callable(getattr(mod, f, None))]
    if missing:
        raise BenchmarkError(f"reference module {path} does not define {missing}")
    return mod


def layer_spec(cell: Cell, metric_name: str) -> dict:
    return load_json(os.path.join(cell.bench_dir, "layers", metric_name + ".json"))


def build_config(config: dict, seed: int, extra: Optional[dict] = None):
    """The R2D2Config a configuration file describes: its preset, its own
    overrides, then what the traffic mix sets (`extra`), then the seed."""
    from r2d2_tpu.config import PRESETS

    cfg = PRESETS[config["preset"]]()
    fields = {**config.get("overrides", {}), **(extra or {})}
    fields = {k: tuple(v) if isinstance(v, list) else v for k, v in fields.items()}
    return cfg.replace(**fields, seed=seed).validate()


# ------------------------------------------------------------------- device


def device_info(chips: int, require_tpu: bool) -> dict:
    """The device as jax reports it; raises unless it is what the cell needs."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if require_tpu and info["platform"] != "tpu":
        raise BenchmarkError(f"no TPU: jax reports platform {info['platform']!r}")
    if len(devs) < chips:
        raise BenchmarkError(f"the cell needs {chips} chips, jax reports {len(devs)}")
    return info


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest local device (0 where the backend
    reports no memory statistics, as the CPU)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.local_devices()]
    return int(max(peaks)) if peaks else 0


def device_bytes_in_use() -> int:
    """Bytes in use NOW on the fullest local device, as the backend counts
    them (`memory_stats`); where it counts nothing, as the CPU, the bytes of
    the shards jax still holds there."""
    import jax

    stats = [d.memory_stats() for d in jax.local_devices()]
    if all(s and "bytes_in_use" in s for s in stats):
        return int(max(s["bytes_in_use"] for s in stats))
    held: Dict[Any, int] = {}
    for a in jax.live_arrays():
        for shard in a.addressable_shards:
            held[shard.device] = held.get(shard.device, 0) + shard.data.nbytes
    return int(max(held.values(), default=0))


def check_runtime(cfg, chips: int, require_tpu: bool) -> dict:
    """The program's own `[runtime]` facts; on the chip the LSTM core must be
    the compiled Pallas kernel (other cores: whatever the config resolves)."""
    from r2d2_tpu.utils.runtime import describe_runtime

    rt = describe_runtime(cfg)
    if require_tpu:
        if rt["platform"] != "tpu" or rt["device_count"] < chips:
            raise BenchmarkError(f"[runtime] says {rt}")
        if cfg.recurrent_core == "lstm" and (rt["core"] != "pallas" or rt["pallas_interpreted"]):
            raise BenchmarkError(f"the LSTM core is not a compiled Pallas kernel: {rt}")
    return rt


def compile_requests() -> int:
    """Compilations this process has asked for so far (persistent-cache hits
    and misses together): a delta over the window must be 0."""
    from r2d2_tpu.utils.compilation_cache import compile_cache_stats

    s = compile_cache_stats()
    return s["hits"] + s["misses"]


def compile_misses() -> int:
    """Compilations so far that the persistent cache did not serve."""
    from r2d2_tpu.utils.compilation_cache import compile_cache_stats

    return compile_cache_stats()["misses"]


# ------------------------------------------------------------------ tracing


class Tracer:
    """jax's profiler around a short steady window, reduced by trace.py."""

    def __init__(self, ctx: Context):
        self.ctx = ctx
        self.dir = ctx.work_dir("trace", ctx.cell.name)

    def __enter__(self):
        import shutil

        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        # the Python tracer hooks every call of every thread: it cut the served
        # rate from 8,000 to 2,500 req/s (PERF.md 6). TraceAnnotation spans stay.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        return self

    def __exit__(self, *exc):
        import jax

        from benchmark import trace as tr

        jax.profiler.stop_trace()
        if exc[0] is None:
            self.ctx.patterns = tr.load_patterns(
                os.path.join(self.ctx.cell.bench_dir, "trace_patterns.json"))
            self.ctx.trace_data = tr.load(tr.find_xplane(self.dir), self.ctx.patterns)
        return False


def span(name: str):
    """A host span in the profiler's own trace (the benchmark's calls only)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


# ------------------------------------------------------------------- running


def finite(x: Any) -> Any:
    """`x` with every float that is not finite replaced by None: the last line
    must parse as strict JSON, and json.dumps would write NaN or Infinity."""
    if isinstance(x, float):
        return x if math.isfinite(x) else None
    if isinstance(x, dict):
        return {k: finite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [finite(v) for v in x]
    return x


def read_layer_metrics(ctx: Context) -> Dict[str, dict]:
    out: Dict[str, dict] = {}
    e2e = {m["name"] for m in ctx.cell.manifest["end_to_end"] if ctx.cell.metric_applies(m)}
    for m in ctx.cell.manifest["per_layer"]:
        if not ctx.cell.metric_applies(m) or m["moves"] not in e2e:
            continue
        spec = layer_spec(ctx.cell, m["name"])
        reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
        value = reader.read(spec, ctx)
        if value is None:
            print(f"[bench] {m['name']}: nothing to read, left out of the line", flush=True)
        elif not math.isfinite(float(value)):
            raise BenchmarkError(f"per-layer metric {m['name']} is {value}")
        else:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(root: str, workload: str, seed: int, seconds: float, trace: bool,
             t_start: Optional[float] = None, require_tpu: bool = True) -> dict:
    """Run one cell; returns the object the CLI prints as its last line."""
    cell = load_cell(root, workload)
    ctx = Context(cell=cell, seed=seed, seconds=seconds, trace=trace,
                  t_start=time.perf_counter() if t_start is None else t_start,
                  require_tpu=require_tpu)
    device = device_info(cell.workload["chips"], require_tpu)
    driver = importlib.import_module(f"benchmark.drivers.{cell.traffic['driver']}")
    measured: Measured = driver.run(ctx)
    device["memory_peak_bytes"] = int(ctx.counters.get("memory_peak_bytes", memory_peak_bytes()))
    result: Dict[str, Any] = {
        "correct": bool(measured.correct), "attempted": int(measured.attempted),
        "failed": int(measured.failed),
    }
    if trace:
        from benchmark import trace as tr

        if ctx.trace_data is None or not ctx.trace_data.ops:
            raise BenchmarkError("the traced window holds no device operation")
        busy_s, window_s, _ = tr.busy_seconds(ctx.trace_data)
        device.update(busy_s=busy_s, window_s=window_s)
        result["metrics"] = read_layer_metrics(ctx)
        result["breakdown"] = {
            "device_ops": tr.top_ops(ctx.trace_data, ctx.patterns["container"]),
            "idle_gaps": tr.idle_gaps_by_host_span(ctx.trace_data),
        }
    else:
        units = {m["name"]: m["unit"] for m in cell.manifest["end_to_end"] if cell.metric_applies(m)}
        missing = sorted(set(units) - set(measured.end_to_end))
        if missing:
            raise BenchmarkError(f"driver did not measure {missing}")
        result["metrics"] = {k: {"value": float(measured.end_to_end[k]), "unit": u}
                             for k, u in units.items()}
        bad = sorted(k for k, v in result["metrics"].items() if not math.isfinite(v["value"]))
        if bad:
            raise BenchmarkError(f"end-to-end metrics {bad} are not finite")
    result["device"] = device
    result["notes"] = finite(measured.notes)
    return result
