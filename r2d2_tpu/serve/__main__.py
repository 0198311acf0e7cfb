"""`python -m r2d2_tpu.serve` — run the policy service on a TCP port.

Quickstart (after a training run wrote checkpoints):

    python -m r2d2_tpu.serve --preset tiny_test --ckpt /tmp/run/ckpt \\
        --port 9955 --metrics /tmp/serve_metrics.jsonl

Then from any process:

    from r2d2_tpu.serve import PolicyClient
    c = PolicyClient(port=9955)
    c.act("session-1", obs, reward=0.0, reset=True)["action"]

The checkpoint watcher keeps polling `--ckpt`, so a concurrently training
run's new saves go live without a restart.
"""

from __future__ import annotations

import argparse
import sys
import time

from r2d2_tpu.config import PRESETS, parse_overrides
from r2d2_tpu.serve.client import serve_tcp
from r2d2_tpu.serve.multi import MultiDeviceServer
from r2d2_tpu.serve.server import PolicyServer, ServeConfig
from r2d2_tpu.utils.metrics import MetricsLogger


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m r2d2_tpu.serve",
        description="session-stateful batched policy serving",
    )
    p.add_argument("--preset", default="tiny_test", choices=sorted(PRESETS))
    p.add_argument("--set", nargs="*", default=[], metavar="KEY=VALUE",
                   help="R2D2Config overrides, e.g. --set hidden_dim=256")
    p.add_argument("--ckpt", default=None,
                   help="checkpoint series dir; latest step is served and "
                        "new steps hot-reload. Omitted: fresh-init params "
                        "(smoke serving)")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=9955)
    p.add_argument("--buckets", type=int, nargs="+", default=[2, 4, 8, 16, 32],
                   help="padded batch shapes (min 2: batch-1 breaks bitwise "
                        "parity with batched acting)")
    p.add_argument("--max-wait-ms", type=float, default=2.0)
    p.add_argument("--queue-depth", type=int, default=1024)
    p.add_argument("--cache-capacity", type=int, default=4096,
                   help="resident sessions before LRU eviction")
    p.add_argument("--poll-interval", type=float, default=0.5,
                   help="checkpoint watcher poll cadence (seconds)")
    p.add_argument("--epsilon", type=float, default=0.0)
    p.add_argument("--metrics", default=None, help="jsonl metrics path")
    p.add_argument("--devices", type=int, default=None,
                   help="serve replicas over local devices with session-"
                        "affinity routing (serve/multi.py); default "
                        "cfg.serve_devices (1 = single-device server)")
    p.add_argument("--spill", type=int, default=None,
                   help="host-RAM spill slab capacity in sessions "
                        "(default cfg.serve_spill; 0 disables — evicted "
                        "sessions restart fresh)")
    p.add_argument("--autoscale", action="store_true",
                   help="elastic fleet (serve/autoscale.py): grow replicas "
                        "under sustained SLO pressure, drain idle ones "
                        "through session migration. Bounds and dwells via "
                        "--set autoscale_min_replicas=1 "
                        "autoscale_max_replicas=4 ... (config.py)")
    p.add_argument("--dryrun", type=int, default=0, metavar="N",
                   help="serve N synthetic requests in-process (no TCP) "
                        "and exit 0 — the multi-device smoke path")
    args = p.parse_args(argv)

    from r2d2_tpu.utils.compilation_cache import (
        enable_compilation_cache,
        log_compile_cache_stats,
    )

    # amortizes the bucket-warmup compiles across server restarts; the
    # directory rule (JAX_COMPILATION_CACHE_DIR, else the checkout on a
    # TPU) lives in utils/compilation_cache.py
    enable_compilation_cache()
    cfg = PRESETS[args.preset]()
    if args.set:
        cfg = cfg.replace(**parse_overrides(args.set))
    if args.devices is not None:
        cfg = cfg.replace(serve_devices=args.devices)
    if args.spill is not None:
        cfg = cfg.replace(serve_spill=args.spill)
    if args.autoscale:
        cfg = cfg.replace(serve_autoscale=True)
    cfg = cfg.validate()
    from r2d2_tpu.utils.runtime import print_runtime_banner

    print_runtime_banner("serve", cfg)
    serve_cfg = ServeConfig(
        buckets=tuple(args.buckets),
        max_wait_ms=args.max_wait_ms,
        queue_depth=args.queue_depth,
        cache_capacity=args.cache_capacity,
        poll_interval_s=args.poll_interval,
        epsilon=args.epsilon,
    )
    metrics = MetricsLogger(args.metrics) if args.metrics else None
    if cfg.serve_devices > 1 or cfg.serve_autoscale:
        # an elastic fleet of 1 is still a fleet: add_replica/kill_replica
        # and the router only exist on the multi-device server
        server = MultiDeviceServer(cfg, serve_cfg, checkpoint_dir=args.ckpt,
                                   metrics=metrics)
        # the replica -> device map, so a fleet sharing one chip shows
        # (MultiDeviceServer._pick_device co-locates when none is free)
        placed = " ".join(
            f"{r.name}->{d}" for r, d in zip(server.replicas, server.devices)
        )
        shared = len(server.devices) - len(set(server.devices))
        print(f"[serve] {cfg.serve_devices} replicas"
              + (" (elastic, "
                 f"{cfg.autoscale_min_replicas}.."
                 f"{cfg.autoscale_max_replicas})" if cfg.serve_autoscale
                 else "")
              + f": {placed}"
              + (f" ({shared} co-located on an already used device)"
                 if shared else ""), file=sys.stderr)
    else:
        server = PolicyServer(cfg, serve_cfg, checkpoint_dir=args.ckpt,
                              metrics=metrics)
    print(f"[serve] warming up {len(serve_cfg.buckets)} bucket shapes", file=sys.stderr)
    server.warmup()
    log_compile_cache_stats("serve compile-cache")
    server.start()
    if args.dryrun:
        import numpy as np

        from r2d2_tpu.serve.client import LocalClient

        try:
            client = LocalClient(server)
            rng = np.random.default_rng(0)
            for i in range(args.dryrun):
                sid = f"dry-{i % max(args.dryrun // 2, 1)}"
                obs = rng.integers(0, 255, cfg.obs_shape, np.uint8)
                client.act(sid, obs, reward=0.0, reset=False)
            server.check()
            st = server.stats()
            print(f"[serve] dryrun ok: {args.dryrun} requests, "
                  f"ckpt_step={st['ckpt_step']} "
                  f"devices={st.get('serve_devices', 1)}", file=sys.stderr)
            return 0
        finally:
            server.stop()
            if metrics is not None:
                metrics.close()
    tcp, _ = serve_tcp(server, host=args.host, port=args.port)
    host, port = tcp.server_address[:2]
    print(
        f"[serve] listening on {host}:{port} "
        f"(ckpt_step={server.stats()['ckpt_step']})",
        file=sys.stderr,
    )
    try:
        while True:
            time.sleep(5.0)
            server.check()  # raises WorkerFatalError when a worker dies
    except KeyboardInterrupt:
        return 0
    finally:
        tcp.shutdown()
        tcp.server_close()
        server.stop()
        if metrics is not None:
            metrics.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
