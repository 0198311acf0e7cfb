"""Does the system still start on the chip? The quickest end-to-end proof.

    python chip_smoke.py            # on a machine with a TPU; ~minutes cold

Drives the main path once through the entry points a user calls, at the
full width of the model every chip claim in this repo is about (Nature
conv encoder + LSTM-512 + dueling heads, obs 84x84x1, B=64, T=85, bf16):

  kernels   every pallas_call of ops/pallas_lstm.py at T=85, B=64, H=512,
            fp32 and bf16, against the lax.scan LSTM (models/lstm.py)
  store_bytes  the replay store's obs bytes where no loss can see them: a
            row store filled through replay/block.frames_to_rows (the
            config's block order) with frames of a pattern of (block, row,
            offset), gathered through learner.make_store_gather as canonical
            frames and as stored (the step programs' form), under plain jit
            and, on four chips, inside the sharded plane's shard_map and
            through the GSPMD-partitioned gather of run_with_stores; bit for
            bit against numpy, before, in the same program as, and after an
            in-place slab write the gather reads
  train     python -m r2d2_tpu.train, fused megastep: on-device collection,
            HBM replay ring, K=16 scanned updates, fused sequence kernel
            forward + backward, deferred priorities, orbax save
  resume    the same command with --resume in a NEW process: must continue
            from the saved step and hit the persistent compile cache
  serve     python -m r2d2_tpu.serve --dryrun on that checkpoint (every
            bucket warmed, N requests answered, ckpt_step = trained step)
  serve_tcp the same server behind its TCP frontend, PolicyClient.act calls
  dp4       the train phase with --dp 4 --replay sharded, when four chips
            are present (printed as skipped when not)

It measures nothing: the seconds it prints are set-up observations, not
speeds. It exits non-zero, printing no result line, when no TPU is found
(no CPU fallback), when run outside a checkout, or when ANY phase fails —
and a phase is judged by what it printed (steps, ckpt_step, request
counts, finite losses), never by an exit code alone. On success the last
line of stdout is one JSON object naming the device as jax reports it.

One process holds the chip at a time: this parent never imports jax and
runs the phases as sequential children (the one deliberate pair is the TCP
server, which holds the chip, and its client, which is pinned to the CPU).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

ROOT = os.path.dirname(os.path.abspath(__file__))
DEADLINE_S = 1150.0  # the contract allows 1200 s, compilation included


class Shape(NamedTuple):
    """What the phases run at. FULL is the chip size; a tiny Shape exists
    only so the parent's control flow can be debugged on a CPU through
    run_phases() — main() never uses one."""

    preset: str
    train_sets: Tuple[str, ...]
    k: int                      # updates per dispatch
    kernel_tbh: Tuple[int, int, int]


FULL = Shape(
    preset="atari",
    # examples/catch_demo.py --full at 256 actors. 409,600 transitions =
    # 1,024 block slots: the deferred-priority ring guard (megastep.py
    # _init_protocol) needs more than 2*(2*256-1) = 1,022
    train_sets=(
        "max_episode_steps=82", "num_actors=256", "buffer_capacity=409600",
        "learning_starts=40000",
    ),
    k=16,
    kernel_tbh=(85, 64, 512),
)


class PhaseFailed(Exception):
    pass


# --------------------------------------------------------------------------
# child: the kernel phase (imports jax; holds the chip while it runs)
# --------------------------------------------------------------------------


def _kernels_child(T: int, B: int, H: int) -> int:
    """Each pallas_call at (T, B, H), both precisions, against the scan
    LSTM on identical params, to tests/test_pallas_lstm.py's tolerance
    classes. Prints one `KERNEL {json}` verdict line per call x dtype."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from r2d2_tpu.models.lstm import LSTM

    D = H + 3 + 1  # core input: latent + one-hot action + reward (catch)
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.normal(size=(B, T, D)).astype(np.float32))
    carry = (
        jnp.asarray(rng.normal(size=(B, H)).astype(np.float32) * 0.2),
        jnp.asarray(rng.normal(size=(B, H)).astype(np.float32) * 0.2),
    )
    # collect.py emits seam 0 (first window of a block) or the full burn-in;
    # the leading rows walk the rest of the contract range [0, T-1]
    edge = [0, 1, T // 2 - 1, T // 2 + 1, T - 1, T // 5, 5 % T, (2 * T) // 3]
    burn = jnp.asarray(np.concatenate([
        edge, np.where(np.arange(len(edge), B) % 4 == 0, 0, (T * 40) // 85),
    ])[:B].astype(np.int32))
    dev = jax.devices()[0]
    print("KERNELS_ON " + json.dumps({
        "platform": dev.platform, "device_kind": dev.device_kind,
        "interpreted": jax.default_backend() != "tpu",
    }), flush=True)

    def loss(mod, p, burn_in):
        outs, _ = mod.apply(p, xs, carry, burn_in=burn_in)
        return jnp.sum(jnp.tanh(outs.astype(jnp.float32)))

    cases = [
        # (name, the pallas_call it exercises, fwd|grad, seam)
        ("fwd", "_lstm_fwd_call", "fwd", None),
        ("bwd_step", "_lstm_bwd_call", "grad", None),
        ("seq_bwd", "_lstm_seq_bwd_call", "grad", burn),
    ]
    failed = 0
    for dtype in (jnp.float32, jnp.bfloat16):
        fp32 = dtype == jnp.float32
        scan_mod = LSTM(hidden_dim=H, in_dim=D, dtype=dtype, backend="scan")
        params = scan_mod.init(jax.random.PRNGKey(0), xs, carry)
        # fp32 parity needs true f32 matmuls on BOTH sides (the TPU's
        # default f32 dot is a bf16 pass); bf16 runs as production does
        ctx = (
            jax.default_matmul_precision("highest") if fp32
            else contextlib.nullcontext()
        )
        with ctx:
            ref_fwd = jax.jit(lambda p: scan_mod.apply(p, xs, carry))(params)
            ref_grad = {
                False: jax.jit(jax.grad(lambda p: loss(scan_mod, p, None)))(params),
                True: jax.jit(jax.grad(lambda p: loss(scan_mod, p, burn)))(params),
            }
        mod = LSTM(hidden_dim=H, in_dim=D, dtype=dtype, backend="pallas")
        for name, call, kind, seam in cases:
            row = {"case": name, "call": call, "dtype": jnp.dtype(dtype).name}
            t0 = time.time()
            try:
                with ctx:
                    if kind == "fwd":
                        got = jax.jit(lambda p: mod.apply(p, xs, carry))(params)
                        ref = ref_fwd
                    else:
                        got = jax.jit(jax.grad(lambda p: loss(mod, p, seam)))(params)
                        ref = ref_grad[seam is not None]
                    got = jax.block_until_ready(got)
                worst_abs, worst_l2, finite = 0.0, 0.0, True
                for a, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
                    a = np.asarray(a, np.float32)
                    r = np.asarray(r, np.float32)
                    finite &= bool(np.isfinite(a).all())
                    # error relative to the tensor's own scale: gradients
                    # here are sums over T*B terms reaching 1e3, where an
                    # elementwise atol of 1e-5 is below one f32 ulp
                    worst_abs = max(worst_abs, float(
                        np.max(np.abs(a - r)) / (np.max(np.abs(r)) + 1e-6)
                    ))
                    worst_l2 = max(worst_l2, float(
                        np.linalg.norm(a - r) / (np.linalg.norm(r) + 1e-6)
                    ))
                row.update(max_err_over_scale=worst_abs, rel_l2=worst_l2)
                # the tests' classes at this shape: fp32 within rtol 1e-4
                # of the tensor scale; bf16 forward within atol 3e-2 on
                # O(0.7) values, bf16 grads relative L2 < 0.05
                if fp32:
                    ok = finite and worst_abs <= 1e-4
                elif kind == "fwd":
                    ok = finite and worst_abs <= 5e-2
                else:
                    ok = finite and worst_l2 < 0.05
                row["verdict"] = "ok" if ok else "mismatch"
            except Exception as e:  # noqa: BLE001 — the verdict IS the error
                row["verdict"] = "refused"
                row["error"] = f"{type(e).__name__}: {str(e)[:600]}"
            row["secs"] = round(time.time() - t0, 2)
            failed += row["verdict"] != "ok"
            print("KERNEL " + json.dumps(row), flush=True)
    print(f"KERNELS_DONE {2 * len(cases)}", flush=True)
    return 1 if failed else 0


# --------------------------------------------------------------------------
# child: the store-bytes phase (imports jax; holds the chip while it runs)
# --------------------------------------------------------------------------


def _store_bytes_child(preset: str, sets: List[str], batches: int = 3) -> int:
    """Does the gather hand back the bytes that were stored? `correct` in
    the benchmark and every loss in the tests compare the program with a
    reference on the SAME gathered batch, so neither can see a store that
    holds, or a gather that returns, the wrong bytes. Here the obs store of a
    real replay plane is filled on the device with a byte that encodes
    (generation, global block, slot row, offset in the frame) and gathered
    `batches` times through learner.make_store_gather, as canonical frames
    and as stored (the step programs' form: PR 38 keeps a frame's bytes in
    the encoder's block order), each frame compared with numpy's evaluation
    of the same pattern:

      jit        DeviceReplayBuffer, the gather plainly jitted on one chip
      shard_map  ShardedDeviceReplay on four chips: per-shard LOCAL indices
                 inside the sharded megastep's shard_map
      gspmd      the same store through run_with_stores with GLOBAL indices
                 (what benchmark/drivers/train_fused.py::_sample_batch runs)

    each `before` a slab write, in the `same_program` as a donated in-place
    slab write of generation-1 blocks into slots the gather also reads (the
    step programs' order: the batch must hold the OLD bytes), and `after`
    it. Prints one `STORE_BYTES {json}` verdict line per plane x moment."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from r2d2_tpu.config import PRESETS, parse_overrides
    from r2d2_tpu.learner import make_store_gather
    from r2d2_tpu.megastep import _slab_write
    from r2d2_tpu.replay.block import frames_to_rows, store_field_specs

    base = PRESETS[preset]().replace(**parse_overrides(sets))
    n_bytes, frame_block = math.prod(base.obs_shape), base.resolved_frame_block
    dev = jax.devices()[0]
    print("STORE_BYTES_ON " + json.dumps({
        "platform": dev.platform, "device_kind": dev.device_kind,
        "devices": len(jax.devices()),
    }), flush=True)

    def pattern(gen, block, row, offset):  # numpy or jax.numpy integers alike
        # odd factors: neighbours along any one coordinate differ
        return (gen * 101 + block * 131 + row * 31 + offset * 7 + (offset >> 7) * 3) & 0xFF

    def fill(cfg, blocks: int, block0, gen: int):
        """(blocks, slot, R, 128) uint8 rows: frames of the pattern, written
        as every writer of a device store writes them, through
        frames_to_rows with the config's block (one block of frames at a
        time: the whole store as frames beside its rows would not fit); the
        other fields as a block whose windows all lie inside it."""
        S, L = cfg.seqs_per_block, cfg.learning_steps
        shape = (cfg.block_slot_len, n_bytes)
        row, off = (jax.lax.broadcasted_iota(jnp.int32, shape, d) for d in range(2))

        def rows_of(block):
            frames = pattern(gen, block + block0, row, off).astype(jnp.uint8)
            return frames_to_rows(
                frames.reshape(cfg.block_slot_len, *cfg.obs_shape), cfg.obs_shape, frame_block)

        out = {k: jnp.zeros((blocks, *sh), dt) for k, (sh, dt) in store_field_specs(cfg).items()}
        out["obs"] = jax.lax.map(rows_of, jnp.arange(blocks, dtype=jnp.int32))
        out["burn_in"] = jnp.broadcast_to(
            jnp.minimum(jnp.arange(S, dtype=jnp.int32) * L, cfg.burn_in_steps), (blocks, S))
        out["learning"] = jnp.full((blocks, S), L, jnp.int32)
        out["forward"] = jnp.full((blocks, S), cfg.forward_steps, jnp.int32)
        return out

    def expected(cfg, gen_of_block, b, s, as_stored: bool):
        """numpy: the frames make_store_gather's contract promises for
        GLOBAL blocks b and sequences s of the filled store: canonical, or
        in the store's block order (the permutation stated here on its own,
        not through the program's functions)."""
        L, T, slot = cfg.learning_steps, cfg.seq_len, cfg.block_slot_len
        win = s * L - np.minimum(s * L, cfg.burn_in_steps)
        rows = np.clip(win[:, None] + np.arange(T)[None, :], 0, slot - 1)
        off = np.arange(n_bytes, dtype=np.int32)
        want = pattern(gen_of_block[b][:, None, None], b[:, None, None], rows[:, :, None], off[None, None, :])
        want = want.astype(np.uint8).reshape(len(b), T, *cfg.obs_shape)
        if as_stored and frame_block > 1:
            (H, W, C), k = cfg.obs_shape, frame_block
            want = want.reshape(len(b), T, H // k, k, W // k, k, C).transpose(0, 1, 2, 4, 3, 5, 6)
            want = want.reshape(len(b), T, H // k, W // k, k * k * C)
        return want

    failed = 0

    def verdict(plane: str, moment: str, cfg, gen_of_block, slab, b, s, both) -> None:
        """`slab`: the global blocks the slab write lands on, whenever it
        does; `both`: the canonical gather's obs and the as-stored one's."""
        for got, as_stored in zip(both, (False, True)):
            _verdict(plane, moment, cfg, gen_of_block, slab, b, s, got, as_stored)

    def _verdict(plane, moment, cfg, gen_of_block, slab, b, s, got, as_stored) -> None:
        nonlocal failed
        b, s, got = np.asarray(b).reshape(-1), np.asarray(s).reshape(-1), np.asarray(jax.device_get(got))
        got = got.reshape(len(b), *got.shape[-(1 + len(cfg.obs_shape)):])
        want = expected(cfg, gen_of_block, b, s, as_stored)
        bad = int((got != want).sum()) if got.shape == want.shape else int(want.size)
        row = {"plane": plane, "moment": moment, "order": "stored" if as_stored else "canonical",
               "frame_block": frame_block, "frames": int(got.shape[0] * got.shape[1]),
               "read_from_slab_slots": int(np.isin(b, slab).sum()), "mismatched_bytes": bad,
               "verdict": "ok" if bad == 0 and got.dtype == np.uint8 else "mismatch"}
        failed += row["verdict"] != "ok"
        print("STORE_BYTES " + json.dumps(row), flush=True)

    rng = np.random.default_rng(0)

    def slab_blocks(cfg) -> int:  # per shard: the collector's chunk of blocks
        return cfg.num_actors // max(cfg.dp_size, 1)

    def draw(cfg, blocks: int, shape):
        """Block indices in [0, blocks) and sequences; every other block is
        one of those the slab write lands on (slots 1 .. slab_blocks)."""
        b = rng.integers(0, blocks, shape).astype(np.int32)
        hit = rng.integers(1, 1 + slab_blocks(cfg), shape).astype(np.int32)
        b = np.where(np.arange(b.size).reshape(shape) % 2 == 0, hit, b)
        return b, rng.integers(0, cfg.seqs_per_block, shape).astype(np.int32)

    # ---- one chip, plain jit
    from r2d2_tpu.replay.device_store import DeviceReplayBuffer

    cfg = base
    nb, B, E = cfg.num_blocks, cfg.batch_size, slab_blocks(base)
    replay = DeviceReplayBuffer(cfg)
    with replay.lock:
        replay.stores = jax.jit(lambda: fill(cfg, nb, 0, 0))()
    gens, slab = np.zeros(nb, np.int64), np.arange(1, 1 + E)
    ones = jnp.ones(B, jnp.float32)

    def both_orders(cfg):
        """(stores, b, s, w) -> (canonical obs, obs as stored): the gather
        as everyone calls it and as the step programs do."""
        canonical, stored = make_store_gather(cfg), make_store_gather(cfg, as_stored=True)
        return lambda *a: (canonical(*a).obs, stored(*a).obs)

    gather = jax.jit(both_orders(cfg))

    def read(b, s):
        return replay.run_with_stores(lambda st: gather(st, jnp.asarray(b), jnp.asarray(s), ones))

    def step(stores, chunk, start, b, s, w):  # the step programs' order
        obs = both_orders(cfg)(stores, b, s, w)
        return _slab_write(stores, chunk, start), obs

    step_jit = jax.jit(step, donate_argnums=(0,))
    for _ in range(batches):
        b, s = draw(cfg, nb, (B,))
        verdict("jit", "before", cfg, gens, slab, b, s, read(b, s))
    b, s = draw(cfg, nb, (B,))
    chunk = jax.jit(lambda: fill(cfg, E, 1, 1))()
    with replay.lock:
        replay.stores, got = step_jit(replay.stores, chunk, jnp.int32(1), jnp.asarray(b), jnp.asarray(s), ones)
    verdict("jit", "same_program", cfg, gens, slab, b, s, got)
    gens[slab] = 1
    for _ in range(batches):
        b, s = draw(cfg, nb, (B,))
        verdict("jit", "after", cfg, gens, slab, b, s, read(b, s))
    del replay, chunk, got

    # ---- four chips: the sharded plane
    if len(jax.devices()) >= 4:
        from r2d2_tpu.parallel.jax_compat import shard_map
        from r2d2_tpu.parallel.mesh import dp_manual_axes, make_mesh
        from r2d2_tpu.replay.sharded_store import ShardedDeviceReplay

        dp = 4
        cfg = base.replace(dp_size=dp, replay_plane="sharded", buffer_capacity=base.buffer_capacity * dp)
        nb, per, E = cfg.num_blocks, cfg.num_blocks // dp, slab_blocks(cfg)
        mesh = make_mesh(dp=dp, tp=1, devices=jax.devices()[:dp])
        replay = ShardedDeviceReplay(cfg, mesh)
        shard = jax.sharding.NamedSharding(mesh, P("dp"))
        gather = both_orders(cfg)

        def local(fn):
            """fn over each shard's LOCAL view, as make_sharded_megastep maps its body."""
            return shard_map(fn, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
                             axis_names=dp_manual_axes(mesh), check_vma=False)

        def fill_local(gen, blocks):
            return lambda ids: fill(cfg, blocks, ids[0] * per + (gen > 0), gen)

        shard_ids = jax.device_put(jnp.arange(dp, dtype=jnp.int32), shard)
        with replay.lock:
            replay.stores = jax.jit(local(fill_local(0, per)))(shard_ids)
        gens = np.zeros(nb, np.int64)
        slab = np.concatenate([np.arange(g * per + 1, g * per + 1 + E) for g in range(dp)])
        w_local = jnp.ones((dp, B // dp), jnp.float32)

        def body(stores, b, s, w):
            return tuple(obs[None] for obs in gather(stores, b[0], s[0], w[0]))

        def body_step(stores, chunk, starts, b, s, w):
            return _slab_write(stores, chunk, starts[0]), body(stores, b, s, w)

        read_local = jax.jit(local(body))
        step_local = jax.jit(local(body_step), donate_argnums=(0,))
        gspmd = jax.jit(gather)
        offsets = (np.arange(dp) * per)[:, None]

        def reads(moment: str) -> None:
            for _ in range(batches):
                b, s = draw(cfg, per, (dp, B // dp))  # LOCAL to each shard
                got = replay.run_with_stores(lambda st: read_local(st, jnp.asarray(b), jnp.asarray(s), w_local))
                verdict("shard_map", moment, cfg, gens, slab, b + offsets, s, got)
                # _sample_batch's way: local draws made global, the first n of them
                gb, gs = (b + offsets).reshape(-1)[:8], s.reshape(-1)[:8]
                got = replay.run_with_stores(
                    lambda st: gspmd(st, jnp.asarray(gb), jnp.asarray(gs), jnp.ones(8, jnp.float32)))
                verdict("gspmd", moment, cfg, gens, slab, gb, gs, got)
                gb, gs = draw(cfg, nb, (B,))  # and any global block, a whole batch
                got = replay.run_with_stores(lambda st: gspmd(st, jnp.asarray(gb), jnp.asarray(gs), ones))
                verdict("gspmd", moment, cfg, gens, slab, gb, gs, got)

        reads("before")
        b, s = draw(cfg, per, (dp, B // dp))
        chunk = jax.jit(local(fill_local(1, E)))(shard_ids)
        starts = jax.device_put(jnp.ones(dp, jnp.int32), shard)
        with replay.lock:
            replay.stores, got = step_local(replay.stores, chunk, starts, jnp.asarray(b), jnp.asarray(s), w_local)
        verdict("shard_map", "same_program", cfg, gens, slab, b + offsets, s, got)
        gens[slab] = 1
        reads("after")
    else:
        print(f"STORE_BYTES_SKIPPED sharded plane: {len(jax.devices())} chip(s)", flush=True)
    print(f"STORE_BYTES_DONE failed={failed}", flush=True)
    return 1 if failed else 0


# --------------------------------------------------------------------------
# child: the TCP client (pinned to the CPU — the server holds the chip)
# --------------------------------------------------------------------------


def _client_child(port: int, requests: int, obs_shape: Tuple[int, ...]) -> int:
    import numpy as np

    from r2d2_tpu.serve.client import PolicyClient

    rng = np.random.default_rng(0)
    steps, actions = [], []
    with PolicyClient(port=port, timeout=60.0) as client:
        for i in range(requests):
            resp = client.act(
                f"tcp-{i % 2}", rng.integers(0, 255, obs_shape, np.uint8),
                reward=0.0, reset=(i < 2), want_q=True,
            )
            if not np.isfinite(np.asarray(resp["q"], np.float32)).all():
                print(f"non-finite q in response {i}: {resp}")
                return 1
            steps.append(resp["ckpt_step"])
            actions.append(resp["action"])
    print("CLIENT " + json.dumps({"answered": len(actions), "ckpt_steps": steps}))
    return 0


# --------------------------------------------------------------------------
# parent: runs phases as children, judges them by what they printed
# --------------------------------------------------------------------------


class Runner:
    def __init__(self, platform: str, shape: Shape, work: str, logs: str,
                 extra_env: Optional[Dict[str, str]] = None):
        self.platform = platform
        self.shape = shape
        self.work = work
        self.logs = logs
        self.t_start = time.time()
        self.env = {**os.environ, **(extra_env or {})}
        # jax's own handle: a child that cannot initialise this platform
        # fails at start-up instead of falling back to the CPU (the chip
        # machine itself exports JAX_PLATFORMS=tpu,cpu)
        self.env["JAX_PLATFORMS"] = platform
        self.env["PYTHONPATH"] = os.pathsep.join(
            p for p in (ROOT, self.env.get("PYTHONPATH", "")) if p
        )
        self.env["PYTHONUNBUFFERED"] = "1"
        self.rows: List[dict] = []       # one summary row per phase
        self.runtime: Optional[dict] = None  # first [runtime] banner seen
        self.procs: List[subprocess.Popen] = []

    # ------------------------------------------------------------ processes

    def _remaining(self, cap: float) -> float:
        left = DEADLINE_S - (time.time() - self.t_start)
        if left <= 5:
            raise PhaseFailed("out of time: the 1200 s budget is spent")
        return min(cap, left)

    def _spawn(self, name: str, argv: List[str], env=None) -> Tuple[subprocess.Popen, str]:
        log = os.path.join(self.logs, f"{name}.log")
        f = open(log, "w")
        proc = subprocess.Popen(
            [sys.executable, *argv], cwd=ROOT, env=env or self.env,
            stdout=f, stderr=subprocess.STDOUT, start_new_session=True,
        )
        f.close()  # the child holds its own descriptor
        self.procs.append(proc)
        return proc, log

    @staticmethod
    def _kill(proc: subprocess.Popen) -> None:
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()

    def kill_all(self) -> None:
        for p in self.procs:
            self._kill(p)

    def _run(self, name: str, argv: List[str], cap: float, env=None,
             check: bool = True) -> str:
        """Run one child to its end; returns its combined output. A
        non-zero exit fails the phase unless the caller judges the output
        itself (check=False: the kernel phase prints its own verdicts)."""
        proc, log = self._spawn(name, argv, env)
        try:
            rc = proc.wait(timeout=self._remaining(cap))
        except subprocess.TimeoutExpired:
            self._kill(proc)
            raise PhaseFailed(f"{name}: no end within its time cap; killed "
                              f"(log: {log})\n{_tail(log)}")
        if rc != 0 and check:
            raise PhaseFailed(f"{name}: exit code {rc}\n{_tail(log)}")
        return _read(log)

    # --------------------------------------------------------------- checks

    def _check_runtime(self, name: str, text: str) -> dict:
        info = _runtime_line(text)
        if info is None:
            raise PhaseFailed(f"{name}: printed no [runtime] banner")
        if info["platform"] != self.platform:
            raise PhaseFailed(
                f"{name}: ran on {info['platform']!r}, not {self.platform!r}"
            )
        if self.platform == "tpu" and (
            info["core"] != "pallas" or info["pallas_interpreted"]
        ):
            raise PhaseFailed(
                f"{name}: the recurrent core is not a compiled Pallas kernel "
                f"(core={info['core']}, interpreted={info['pallas_interpreted']})"
            )
        if self.runtime is None:
            self.runtime = info
        return info

    def _train_args(self, ckpt: str, steps: int, metrics: str) -> List[str]:
        sets = [*self.shape.train_sets, f"checkpoint_dir={ckpt}",
                # a save at every dispatch: the run ends on a checkpoint
                f"save_interval={self.shape.k}", "log_interval=0"]
        argv = ["-m", "r2d2_tpu.train", "--preset", self.shape.preset,
                "--env", "catch", "--mode", "fused",
                "--updates-per-dispatch", str(self.shape.k),
                "--steps", str(steps), "--metrics", metrics]
        for s in sets:
            argv += ["--set", s]
        return argv

    def _check_train(self, name: str, text: str, metrics: str, ckpt: str,
                     start: int, steps: int) -> dict:
        info = self._check_runtime(name, text)
        if info["start_step"] != start:
            # train.py starts from scratch, exit 0, when --resume finds nothing
            raise PhaseFailed(
                f"{name}: started at step {info['start_step']}, expected {start}"
            )
        if "replay snapshot failed" in text:
            raise PhaseFailed(f"{name}: snapshot on exit failed (swallowed)")
        recs = [json.loads(l) for l in _read(metrics).splitlines() if l.strip()]
        if not recs:
            raise PhaseFailed(f"{name}: no metrics record was logged")
        bad = [r["step"] for r in recs
               if not (math.isfinite(r["loss"]) and math.isfinite(r["q_mean"]))]
        if bad:
            raise PhaseFailed(f"{name}: non-finite loss/q at steps {bad}")
        first, last = recs[0]["step"], recs[-1]["step"]
        if first != start + self.shape.k or last != steps:
            raise PhaseFailed(
                f"{name}: logged steps {first}..{last}, expected "
                f"{start + self.shape.k}..{steps}"
            )
        if recs[0].get("platform") != self.platform:
            raise PhaseFailed(f"{name}: first metrics record carries no "
                              "runtime stamp")
        if not os.path.exists(
            os.path.join(ckpt, f"step_{steps}", "_CHECKPOINT_METADATA")
        ):
            raise PhaseFailed(f"{name}: no finalized checkpoint step_{steps}")
        return {"steps": f"{first}..{last}", "loss_last": recs[-1]["loss"],
                **_cache_line(name, text)}

    # --------------------------------------------------------------- phases

    def phase(self, name: str, fn) -> None:
        t0 = time.time()
        row = {"phase": name}
        try:
            row.update(fn() or {})
        finally:
            row["wall_s"] = round(time.time() - t0, 1)
            self.rows.append(row)
            print(f"[chip_smoke] {json.dumps(row)}", flush=True)

    def kernels(self) -> dict:
        T, B, H = self.shape.kernel_tbh
        # judged by its verdict lines (exit code 1 = some verdict not "ok")
        text = self._run("kernels", [
            os.path.join(ROOT, "chip_smoke.py"), "--phase", "kernels",
            "--tbh", f"{T},{B},{H}",
        ], 420, check=False)
        on = [json.loads(l[11:]) for l in text.splitlines()
              if l.startswith("KERNELS_ON ")]
        verdicts = [json.loads(l[7:]) for l in text.splitlines()
                    if l.startswith("KERNEL ")]
        if not on or f"KERNELS_DONE {len(verdicts)}" not in text:
            raise PhaseFailed(f"kernels: did not run to its end\n{text[-3000:]}")
        if on[0]["platform"] != self.platform or (
            self.platform == "tpu" and on[0]["interpreted"]
        ):
            raise PhaseFailed(f"kernels: ran as {on[0]}")
        for v in verdicts:
            print(f"[chip_smoke] kernel {v['call']:<26} {v['dtype']:<8} "
                  f"{v['case']:<18} {v['verdict']}"
                  + (f"  {v['error'][:300]}" if "error" in v else
                     f"  err/scale={v['max_err_over_scale']:.2e} "
                     f"rel_l2={v['rel_l2']:.2e}"), flush=True)
        bad = [f"{v['case']}[{v['dtype']}]={v['verdict']}" for v in verdicts
               if v["verdict"] != "ok"]
        if bad:
            raise PhaseFailed(f"kernels: {bad}")
        return {"calls_ok": len(verdicts)}

    def store_bytes(self) -> dict:
        # judged by its verdict lines (exit code 1 = some verdict not "ok")
        text = self._run("store_bytes", [
            os.path.join(ROOT, "chip_smoke.py"), "--phase", "store_bytes",
            "--preset", self.shape.preset, "--sets", *self.shape.train_sets,
        ], 300, check=False)
        on = [json.loads(l[15:]) for l in text.splitlines()
              if l.startswith("STORE_BYTES_ON ")]
        verdicts = [json.loads(l[12:]) for l in text.splitlines()
                    if l.startswith("STORE_BYTES ")]
        if not on or "STORE_BYTES_DONE" not in text or not verdicts:
            raise PhaseFailed(f"store_bytes: did not run to its end\n{text[-3000:]}")
        if on[0]["platform"] != self.platform:
            raise PhaseFailed(f"store_bytes: ran as {on[0]}")
        bad = [v for v in verdicts if v["verdict"] != "ok"]
        if bad:
            raise PhaseFailed(f"store_bytes: {bad}")
        planes = sorted({v["plane"] for v in verdicts})
        if on[0]["devices"] >= 4 and planes != ["gspmd", "jit", "shard_map"]:
            raise PhaseFailed(f"store_bytes: four chips, but only {planes} were read")
        return {"planes": planes, "gathers_ok": len(verdicts),
                "frames": sum(v["frames"] for v in verdicts),
                "read_from_slab_slots": sum(v["read_from_slab_slots"] for v in verdicts)}

    def train(self) -> dict:
        ckpt, m = os.path.join(self.work, "ckpt"), os.path.join(self.work, "m_train.jsonl")
        steps = 3 * self.shape.k
        text = self._run("train", self._train_args(ckpt, steps, m), 500)
        self.trained_step = steps
        return self._check_train("train", text, m, ckpt, 0, steps)

    def resume(self) -> dict:
        ckpt, m = os.path.join(self.work, "ckpt"), os.path.join(self.work, "m_resume.jsonl")
        start, steps = self.trained_step, self.trained_step + 2 * self.shape.k
        text = self._run(
            "resume", [*self._train_args(ckpt, steps, m), "--resume"], 300
        )
        out = self._check_train("resume", text, m, ckpt, start, steps)
        if out["cache_hits"] <= 0:
            raise PhaseFailed(
                "resume: the same programs in a second process hit the "
                f"persistent compile cache 0 times ({out})"
            )
        self.trained_step = steps
        return out

    def _serve_args(self) -> List[str]:
        eps = dict(s.split("=") for s in self.shape.train_sets)["max_episode_steps"]
        return ["-m", "r2d2_tpu.serve", "--preset", self.shape.preset,
                "--set", "env_name=catch", "action_dim=3",
                f"max_episode_steps={eps}",
                "--ckpt", os.path.join(self.work, "ckpt")]

    def serve(self) -> dict:
        n = 24
        text = self._run("serve", [*self._serve_args(), "--dryrun", str(n)], 300)
        self._check_runtime("serve", text)
        m = re.search(r"dryrun ok: (\d+) requests, ckpt_step=(-?\d+)", text)
        if not m:
            raise PhaseFailed(f"serve: no dry-run result line\n{text[-2000:]}")
        answered, step = int(m.group(1)), int(m.group(2))
        if answered != n or step != self.trained_step:
            # ckpt_step=-1 is fresh-init params: an empty --ckpt dir serves
            # those and still exits 0
            raise PhaseFailed(
                f"serve: {answered}/{n} requests at ckpt_step={step}, "
                f"expected {self.trained_step}"
            )
        return {"requests": answered, "ckpt_step": step,
                **_cache_line("serve", text, "serve compile-cache")}

    def serve_tcp(self) -> dict:
        n = 6
        server, log = self._spawn("serve_tcp", [*self._serve_args(), "--port", "0"])
        try:
            port = None
            limit = time.time() + self._remaining(300)
            while port is None:
                m = re.search(r"listening on [\d.]+:(\d+)", _read(log))
                if m:
                    port = int(m.group(1))
                elif server.poll() is not None:
                    raise PhaseFailed(f"serve_tcp: server exited "
                                      f"{server.returncode}\n{_tail(log)}")
                elif time.time() > limit:
                    raise PhaseFailed(f"serve_tcp: never listened\n{_tail(log)}")
                else:
                    time.sleep(0.5)
            self._check_runtime("serve_tcp", _read(log))
            # the client needs no chip, and must not ask for the one the
            # server holds
            client_env = {**self.env, "JAX_PLATFORMS": "cpu"}
            obs = ",".join(map(str, _obs_shape(self.shape.preset)))
            text = self._run("serve_tcp_client", [
                os.path.join(ROOT, "chip_smoke.py"), "--phase", "client",
                "--port", str(port), "--requests", str(n), "--obs-shape", obs,
            ], 120, env=client_env)
            got = [json.loads(l[7:]) for l in text.splitlines()
                   if l.startswith("CLIENT ")]
            if not got or got[0]["answered"] != n or any(
                s != self.trained_step for s in got[0]["ckpt_steps"]
            ):
                raise PhaseFailed(f"serve_tcp: client saw {got}, expected {n} "
                                  f"answers at ckpt_step={self.trained_step}")
            os.killpg(server.pid, signal.SIGINT)  # the CLI's clean-stop path
            try:
                rc = server.wait(timeout=60)
            except subprocess.TimeoutExpired:
                raise PhaseFailed("serve_tcp: server did not stop on SIGINT")
            if rc != 0:
                raise PhaseFailed(f"serve_tcp: server exit {rc}\n{_tail(log)}")
            return {"requests": n, "ckpt_step": self.trained_step, "port": port}
        finally:
            self._kill(server)

    def dp4(self) -> dict:
        count = self.runtime["device_count"]
        if count < 4:
            print(f"[chip_smoke] dp4 skipped: {count} chip(s)", flush=True)
            return {"skipped": f"{count} chip(s)"}
        ckpt, m = os.path.join(self.work, "ckpt_dp4"), os.path.join(self.work, "m_dp4.jsonl")
        steps = 2 * self.shape.k
        text = self._run("dp4", [
            *self._train_args(ckpt, steps, m), "--dp", "4", "--replay", "sharded",
        ], 500)
        out = self._check_train("dp4", text, m, ckpt, 0, steps)
        placed = [json.loads(l[12:]) for l in text.splitlines()
                  if l.startswith("[placement] ")]
        if not placed:
            raise PhaseFailed("dp4: no [placement] line")
        p = placed[0]
        idle = [d for d, b in p["bytes_in_use"].items() if not b]
        if len(p["params"]) < 4 or len(p["replay"]) < 4 or (
            self.platform == "tpu" and idle
        ):
            raise PhaseFailed(f"dp4: work is not on four devices: {p}")
        return {**out, "param_devices": p["params"], "replay_devices": p["replay"],
                "bytes_in_use": p["bytes_in_use"]}


def _read(path: str) -> str:
    try:
        with open(path, errors="replace") as f:
            return f.read()
    except FileNotFoundError:
        return ""


def _tail(path: str, n: int = 3000) -> str:
    return _read(path)[-n:]


def _runtime_line(text: str) -> Optional[dict]:
    for line in text.splitlines():
        if line.startswith("[runtime] "):
            return json.loads(line[len("[runtime] "):])
    return None


def _cache_line(name: str, text: str, prefix: str = "compile-cache") -> dict:
    m = re.search(
        re.escape(f"[{prefix}]") + r" dir=(\S+) source=(\S+) hits=(\d+) misses=(\d+)",
        text,
    )
    if not m:
        raise PhaseFailed(f"{name}: printed no [{prefix}] line")
    return {"cache_dir": m.group(1), "cache_source": m.group(2),
            "cache_hits": int(m.group(3)), "cache_misses": int(m.group(4))}


def _obs_shape(preset: str) -> Tuple[int, ...]:
    from r2d2_tpu.config import PRESETS  # config only: no jax in the parent

    return tuple(PRESETS[preset]().obs_shape)


def run_phases(platform: str, shape: Shape,
               extra_env: Optional[Dict[str, str]] = None) -> Runner:
    """Run every phase in order; raises PhaseFailed at the first failure.
    Scratch (checkpoints, metrics) lives in a git-ignored directory of the
    checkout and is removed; the children's logs stay under chiprun_out/."""
    work = os.path.join(ROOT, ".chip_smoke")
    logs = os.path.join(ROOT, "chiprun_out", "chip_smoke")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    os.makedirs(logs, exist_ok=True)
    r = Runner(platform, shape, work, logs, extra_env)
    try:
        for name in ("kernels", "store_bytes", "train", "resume", "serve", "serve_tcp", "dp4"):
            r.phase(name, getattr(r, name))
    finally:
        r.kill_all()
        shutil.rmtree(work, ignore_errors=True)
    return r


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    # internal: the child roles this file plays for itself
    p.add_argument("--phase", choices=["kernels", "store_bytes", "client"], help=argparse.SUPPRESS)
    p.add_argument("--preset", default="", help=argparse.SUPPRESS)
    p.add_argument("--sets", nargs="*", default=[], help=argparse.SUPPRESS)
    p.add_argument("--tbh", default="", help=argparse.SUPPRESS)
    p.add_argument("--port", type=int, default=0, help=argparse.SUPPRESS)
    p.add_argument("--requests", type=int, default=0, help=argparse.SUPPRESS)
    p.add_argument("--obs-shape", default="", help=argparse.SUPPRESS)
    args = p.parse_args()
    if args.phase == "kernels":
        return _kernels_child(*map(int, args.tbh.split(",")))
    if args.phase == "store_bytes":
        return _store_bytes_child(args.preset, args.sets)
    if args.phase == "client":
        return _client_child(
            args.port, args.requests, tuple(map(int, args.obs_shape.split(",")))
        )

    asked = os.environ.get("JAX_PLATFORMS", "")
    if asked and "tpu" not in asked.split(","):
        print(f"chip_smoke: FAILED: no TPU: JAX_PLATFORMS={asked!r} excludes "
              "it, and this check never falls back to another backend",
              file=sys.stderr)
        return 1
    # a terminated parent must still stop its children (they run in their
    # own sessions): turn SIGTERM into an exit that unwinds run_phases
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    t0 = time.time()
    try:
        r = run_phases("tpu", FULL)
    except PhaseFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    rt = r.runtime
    print(f"[chip_smoke] ran on platform={rt['platform']} "
          f"device_kind={rt['device_kind']!r} devices={rt['device_count']} "
          f"core={rt['core']} pallas_interpreted={rt['pallas_interpreted']} "
          f"replay_core={rt['replay_core']}; "
          f"{len(r.rows)} phases in {time.time() - t0:.0f} s "
          "(set-up observations, not speeds)", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": rt["platform"], "kind": rt["device_kind"],
        "count": rt["device_count"],
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
