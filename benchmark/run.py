"""The benchmark's command: one cell, one run, one JSON line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Pins JAX to the TPU before jax is imported (the chip machine exports
JAX_PLATFORMS=tpu,cpu, under which stock jax falls back to the CPU in
silence): with no TPU, or fewer chips than the cell asks for, it exits
non-zero and prints no result line. Everything but the last line of stdout is
progress; the last line is the result object of the contract.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    os.environ["JAX_PLATFORMS"] = "tpu"  # never a CPU fallback
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    os.chdir(ROOT)
    try:
        from benchmark.harness import BenchmarkError, load_json, run_cell
    except ImportError as e:
        print(f"benchmark: cannot import the harness: {e}", file=sys.stderr)
        return 3
    seconds = args.seconds
    if seconds is None:
        seconds = float(load_json(os.path.join(ROOT, "BENCHMARK.json"))["run_seconds"])
    try:
        result = run_cell(ROOT, args.workload, args.seed, seconds, bool(args.trace),
                          t_start=_T_START, require_tpu=True)
    except (BenchmarkError, ImportError, RuntimeError, OSError, KeyError, ValueError) as e:
        # includes jax's "Unable to initialize backend 'tpu'" (RuntimeError)
        # and a checkout that holds the benchmark but not the program
        import traceback

        traceback.print_exc()
        print(f"benchmark: no result: {type(e).__name__}: {e}", file=sys.stderr)
        return 3
    sys.stdout.flush()
    # each number compared beside its limit: the last lines of standard error
    # (the result line carries the same under `notes`, its last key)
    notes = result.get("notes", {})
    for name, check in (notes.get("checks") or {"check": notes.get("check")}).items():
        print(f"benchmark: correct={result['correct']} {name}: {json.dumps(check)}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
