"""Find the serve cell's knee, once, on the chip (not part of a run).

    python3 benchmark/knee_sweep.py --workload nature-lstm512.serve-steady \\
        --rates 500 1000 2000 3000 4000 5000 6000 --seconds 8 --out chiprun_out/knee.json

One server (the cell's configuration and traffic file, weights from --seed,
all sessions resident), then one open-loop Poisson window per rate, lowest
first, each from its own seed. A rate SUSTAINS when p99 from the due time is
within the traffic file's `slo_ms`, nothing failed, and the backlog did not
grow: the queue is empty at the end and the last request was answered within
`slo_ms` of its due time. The knee is the highest sustaining rate below the
first that does not; the cell's fixed rate is 0.8 x knee, copied BY HAND into
traffic/<mix>.json with the sweep's table in PERF.md.

When an optimisation has moved the knee so far that nearly every request of
the cell meets the limit (p99 at the fixed rate under a third of `slo_ms`,
say), the cell can show no further gain: a later `benchmark` issue reruns
this script with higher rates and writes the new number into a NEW traffic
file and cell (a file that exists is never edited by a PR that claims a gain).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    p.add_argument("--seconds", type=float, default=8.0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)
    os.environ["JAX_PLATFORMS"] = "tpu"
    sys.path.insert(0, ROOT)
    import jax
    import numpy as np

    from benchmark import harness, loadgen
    from benchmark.drivers import serve_open_loop as drv
    from r2d2_tpu.serve import QueueFullError
    from r2d2_tpu.utils.compilation_cache import enable_compilation_cache

    cell = harness.load_cell(ROOT, args.workload)
    tc = cell.traffic
    device = harness.device_info(cell.workload["chips"], require_tpu=True)
    enable_compilation_cache()
    cfg = harness.build_config(cell.config, args.seed, {"serve_pipeline": bool(tc.get("pipeline", True))})
    server, _ = drv.start_server(cfg, tc, args.seed)
    rows = []
    try:
        ids, obs = drv.fill_sessions(server, cfg, int(tc["sessions"]), np.random.default_rng(args.seed))
        submit = lambda i, sess: server.submit(ids[sess], obs[sess], reward=0.0, reset=False)
        slo = float(tc["slo_ms"]) / 1e3
        for k, rate in enumerate(sorted(args.rates)):
            sched = loadgen.poisson_schedule(args.seed + 100 + k, rate, args.seconds, len(ids))
            before = server.stats()
            res = loadgen.run_open_loop(submit, sched, QueueFullError, drain_s=10.0)
            after = server.stats()
            ok = res.status == loadgen.OK
            lat = res.latency_s[ok]
            tail = res.latency_s[-max(len(lat) // 20, 1):]
            row = {
                "rate_per_s": rate, "requests": int(len(ok)), "failed": int((~ok).sum()),
                "p50_ms": float(np.median(lat) * 1e3), "p99_ms": float(np.percentile(lat, 99) * 1e3),
                "max_ms": float(lat.max() * 1e3), "last_5pct_p50_ms": float(np.nanmedian(tail) * 1e3),
                "late_p99_ms": float(np.percentile(res.late_s, 99) * 1e3),
                "queue_depth_end": after["queue_depth"], "drain_s": res.elapsed_s - args.seconds,
                "occupancy": (after["requests"] - before["requests"]) / max(after["batches"] - before["batches"], 1),
                "completed_per_s": float(ok.sum() / res.elapsed_s),
            }
            row["sustains"] = bool(row["failed"] == 0 and row["p99_ms"] <= slo * 1e3
                                   and row["last_5pct_p50_ms"] <= slo * 1e3 and row["queue_depth_end"] == 0)
            rows.append(row)
            print(json.dumps(row), flush=True)
            time.sleep(1.0)  # let an overloaded window's queue drain fully
    finally:
        server.stop()
    knee = None
    for row in rows:
        if not row["sustains"]:
            break
        knee = row["rate_per_s"]
    out = {"workload": args.workload, "device": device, "seconds": args.seconds, "slo_ms": tc["slo_ms"],
           "rows": rows, "knee_per_s": knee, "rate_at_0.8_knee": None if knee is None else 0.8 * knee}
    print(json.dumps(out), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(out, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
