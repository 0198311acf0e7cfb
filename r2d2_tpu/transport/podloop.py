"""Pod-loop processes: many serve hosts feed one learner over the
block-stream transport, across REAL process boundaries.

This module is the single definition of both process bodies — the
transport tests spawn the same code paths the module's own CLI exposes:

    python -m r2d2_tpu.transport.podloop --role serve \
        --learner-port P --host-id h0 --spool-dir /tmp/spool --stats s.jsonl
    python -m r2d2_tpu.transport.podloop --role learner \
        --port P --stats s.jsonl

Serve host process: a one-replica `MultiDeviceServer` behind the stock
JSON-lines TCP frontend, with the full liveloop capture stack
(`LiveLoopPlane`) — except the plane's "replay" is a
`BlockStreamPublisher`, so finished Blocks stream to the learner instead
of landing in a local store. Checkpoints arrive back over the same
socket; the CKPT apply reconstructs the param tree against the host's
own template treedef and runs the fleet publish
(`MultiDeviceServer.publish_params`), so hot-reload needs no shared
filesystem.

Learner process: a `LiveLoopTrainer` whose replay store fills from an
`IngestService`; every `save_interval` crossing broadcasts the freshly
trained params to every connected host.

Both processes append one JSON line per second to `--stats` (counters
only, no analysis) and exit cleanly on SIGTERM after draining — the
bench driver owns traffic generation, the SIGKILL drill, and all
assertions.
"""

from __future__ import annotations

import argparse
import json
import signal
import sys
import threading
import time

import numpy as np


def podloop_config(seed: int, checkpoint_dir: str, spool_dir: str = ""):
    """The ONE config both roles build: the serve hosts' network must be
    architecturally identical to the learner's (the CKPT broadcast ships
    leaves only; the treedef is reconstructed locally)."""
    from r2d2_tpu.config import tiny_test

    return tiny_test().replace(
        env_name="catch",
        action_dim=3,
        liveloop=True,
        checkpoint_dir=checkpoint_dir,
        save_interval=20,
        learning_starts=128,
        buffer_capacity=4096,
        training_steps=1_000_000,  # wall clock, not step count, ends the run
        serve_spill=64,
        transport_spool_dir=spool_dir,
        transport_heartbeat_s=0.5,
        transport_dead_peer_s=5.0,
    ).validate()


def _emit_stats(path: str, row: dict) -> None:
    if not path:
        return
    with open(path, "a") as f:
        f.write(json.dumps(row, default=str) + "\n")


def _install_sigterm(stop: threading.Event) -> None:
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    signal.signal(signal.SIGINT, lambda *_: stop.set())


def run_serve_host(
    host_id: str,
    learner_port: int,
    port: int = 0,
    spool_dir: str = "",
    stats_path: str = "",
    seed: int = 0,
    learner_host: str = "127.0.0.1",
    stats_interval_s: float = 1.0,
) -> None:
    import jax

    from r2d2_tpu.liveloop import LiveLoopPlane
    from r2d2_tpu.serve import MultiDeviceServer, ServeConfig
    from r2d2_tpu.serve.client import serve_tcp
    from r2d2_tpu.transport.publisher import BlockStreamPublisher

    cfg = podloop_config(seed, checkpoint_dir="", spool_dir=spool_dir)
    serve_cfg = ServeConfig(
        buckets=(2, 4, 8), max_wait_ms=2.0, cache_capacity=32,
        poll_interval_s=3600.0,  # no fs watcher: reloads arrive over CKPT
        seed=seed,
    )
    d0 = jax.local_devices()[0]
    server = MultiDeviceServer(cfg, serve_cfg, devices=[d0])
    treedef = jax.tree.structure(server._template.params)
    leaf_template = jax.tree.leaves(server._template.params)

    def apply_ckpt(leaves, step, version):
        if len(leaves) != len(leaf_template):
            raise ValueError(
                f"CKPT leaf count {len(leaves)} != template "
                f"{len(leaf_template)} — config drift between learner "
                "and serve host"
            )
        params = jax.tree.unflatten(treedef, leaves)
        server.publish_params(params, step, version=version)

    publisher = BlockStreamPublisher(
        cfg, (learner_host, learner_port), host_id,
        on_checkpoint=apply_ckpt, seed=seed,
    )
    plane = LiveLoopPlane(cfg, server, replay=publisher, seed=seed)
    # the tap appends each block's audit entry immediately before the
    # emit that reaches the publisher, on the same thread — so "freshest
    # audit-tail entry" is exactly the block being offered
    publisher.audit_source = (
        lambda: plane.tap.audit_tail[-1] if plane.tap.audit_tail else None
    )

    server.warmup()
    server.start(watch_checkpoints=False)
    plane.start()
    publisher.start()
    tcp, _ = serve_tcp(server, port=port)

    stop = threading.Event()
    _install_sigterm(stop)
    print(json.dumps({
        "podloop_ready": True, "role": "serve", "host": host_id,
        "serve_port": tcp.server_address[1],
    }), flush=True)

    t0 = time.time()
    while not stop.is_set():
        plane.check()
        publisher.check()
        server.check()
        _emit_stats(stats_path, {
            "t": round(time.time() - t0, 3), "role": "serve",
            "host": host_id,
            **{k: v for k, v in server.stats().items()
               if isinstance(v, (int, float, str, bool))},
            **plane.stats(),
            **publisher.stats(),
        })
        stop.wait(stats_interval_s)

    tcp.shutdown()
    tcp.server_close()
    plane.stop()        # final tap/bridge drains land in the publisher
    publisher.stop()    # flush: spool -> learner, best effort
    server.stop()
    _emit_stats(stats_path, {
        "t": round(time.time() - t0, 3), "role": "serve", "host": host_id,
        "final": True,
        **{k: v for k, v in server.stats().items()
           if isinstance(v, (int, float, str, bool))},
        **plane.stats(), **publisher.stats(),
    })


def run_learner(
    port: int,
    checkpoint_dir: str,
    stats_path: str = "",
    seed: int = 0,
    host: str = "127.0.0.1",
    stats_interval_s: float = 1.0,
) -> None:
    import jax

    from r2d2_tpu.liveloop import LiveLoopTrainer
    from r2d2_tpu.transport.ingest import IngestService

    cfg = podloop_config(seed, checkpoint_dir=checkpoint_dir)
    trainer = LiveLoopTrainer(cfg)
    version = {"n": 0}
    service = IngestService(
        cfg, trainer.replay, host=host, port=port,
        version_source=lambda: version["n"],
    )
    service.start()

    stop = threading.Event()
    _install_sigterm(stop)
    print(json.dumps({
        "podloop_ready": True, "role": "learner",
        "ingest_port": service.port,
    }), flush=True)

    t0 = time.time()
    last_stats = 0.0
    last_ckpt_bucket = 0
    while not stop.is_set():
        service.check()
        if trainer.can_train():
            trainer.train(8, deadline=time.monotonic() + 0.5)
        else:
            stop.wait(0.05)
        bucket = trainer.step // cfg.save_interval
        if bucket > last_ckpt_bucket:
            last_ckpt_bucket = bucket
            version["n"] += 1
            leaves = [
                np.asarray(x)
                for x in jax.tree.leaves(trainer.trainer.state.params)
            ]
            service.broadcast_checkpoint(leaves, trainer.step, version["n"])
        now = time.time()
        if now - last_stats >= stats_interval_s:
            last_stats = now
            _emit_stats(stats_path, {
                "t": round(now - t0, 3), "role": "learner",
                "params_version": version["n"],
                **trainer.stats(), **service.stats(),
            })

    trainer.finish()
    service.stop()
    _emit_stats(stats_path, {
        "t": round(time.time() - t0, 3), "role": "learner", "final": True,
        "params_version": version["n"],
        **trainer.stats(), **service.stats(),
    })


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description="pod-loop process bodies")
    p.add_argument("--role", required=True, choices=["serve", "learner"])
    p.add_argument("--port", type=int, default=0,
                   help="serve: TCP frontend port; learner: ingest port")
    p.add_argument("--learner-port", type=int, default=0,
                   help="serve role: the learner's ingest port")
    p.add_argument("--learner-host", default="127.0.0.1")
    p.add_argument("--host-id", default="h0")
    p.add_argument("--spool-dir", default="")
    p.add_argument("--ckpt-dir", default="")
    p.add_argument("--stats", default="")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    if args.role == "serve":
        if not args.learner_port:
            p.error("--role serve requires --learner-port")
        run_serve_host(
            host_id=args.host_id, learner_port=args.learner_port,
            port=args.port, spool_dir=args.spool_dir,
            stats_path=args.stats, seed=args.seed,
            learner_host=args.learner_host,
        )
    else:
        run_learner(
            port=args.port, checkpoint_dir=args.ckpt_dir,
            stats_path=args.stats, seed=args.seed,
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
