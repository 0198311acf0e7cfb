"""Elastic resume: re-split replay snapshots across a changed topology.

A snapshot records the layout it was written under (the topology manifest
snapshot.snapshot_topology embeds); this module restores those files into
a replay built on a DIFFERENT layout — a different dp, a different
process count, even a different plane family — so a preempted dp=4 run
can restart on whatever the scheduler gives back (ROADMAP item 3:
preemption-safety becomes autoscaling).

Two phases, each a registered fault site so the chaos suite can kill
mid-reshard:

1. GATHER (`reshard.gather`): read every snapshot file the old run left
   (one per process for multihost, one otherwise) and reassemble the
   LOGICAL replay — per-global-shard control state + store slabs keyed by
   global shard id, placed by each file's manifest slab ranges. Purely
   read-only: a crash here leaves the files intact and a second resume
   starts over.
2. SCATTER (`reshard.scatter`): re-split the logical state across the new
   layout. Two sub-paths:
   - EXACT: the logical shard set is unchanged (same dp, same capacity) —
     every shard's full ring state (pointer, lap stamp, tree leaves,
     slabs) carries over bit-for-bit, so with the multihost draw streams
     keyed by (seed, GLOBAL shard id, epoch) the resumed sampling —
     and hence the learner loss — is bit-identical to the uninterrupted
     run, regardless of how the shards regroup over processes.
   - RE-DEAL: dp (or capacity) changed — occupied blocks are replayed in
     global arrival order (oldest-first per shard, interleaved the way
     the round-robin writers dealt them) and re-dealt round-robin across
     the new shards, carrying each block's per-sequence tree priorities.
     Counters rebuild from per-block accounting; the remainder that
     per-block accounting cannot attribute (evicted/dropped blocks' env
     steps, episode tallies) lands on shard 0, so GLOBAL totals are
     preserved exactly. Sampling after a re-deal is deterministic but not
     identical to the old layout's — the bounded-drift class
     ARCHITECTURE.md's elasticity section documents.

Cross-family moves (host <-> device stores) cast the action fields
between the host plane's uint8 and the device planes' int32 — lossless,
actions are < 256 by construction.

The returned extras keep only the LAYOUT-FREE carry keys (cut step,
trainer sample RNG, published params); per-host actor/env episode streams
and deferred priority write-backs are dropped — the new layout's
collectors re-split the episode streams by starting fresh ones per local
shard, the same bounded-drift class as a lagging periodic snapshot.

CLI: `python -m r2d2_tpu.replay.reshard CKPT_DIR [--expect-dp N ...]`
prints every snapshot manifest in a checkpoint dir as json and exits
nonzero on an expectation mismatch or incoherent shard coverage — the
runs/ chain scripts call it before trusting `--resume`.
"""

from __future__ import annotations

import glob
import os
import re
import struct
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from r2d2_tpu.replay.block import frames_to_rows
from r2d2_tpu.replay.control_plane import ReplayControlPlane
from r2d2_tpu.replay.device_store import DeviceReplayBuffer
from r2d2_tpu.replay.replay_buffer import ReplayBuffer
from r2d2_tpu.replay.snapshot import (
    STORE_FIELDS,
    _Bf16NpzView,
    _COUNTERS,
    _EXTRA_PREFIX,
    _topology_from,
    read_manifest,
)
from r2d2_tpu.utils.faults import fault_point

# layout-bound carry prefixes (train._carry_payload): per-host episode
# streams and deferred write-backs don't survive a layout change
_LAYOUT_BOUND_CARRY = ("pend_", "actor_", "env_")


def snapshot_paths(ckpt_dir: str) -> List[str]:
    """Every replay snapshot file a run left in `ckpt_dir`, per-process
    files ordered by the saving process index (single-file planes write
    plain replay_snapshot.npz)."""
    out = []
    single = os.path.join(ckpt_dir, "replay_snapshot.npz")
    if os.path.exists(single):
        out.append(single)
    # sorted: glob order is fs-dependent; the _pidx sort below is stable,
    # so a deterministic input order makes the full ordering canonical
    per_proc = sorted(glob.glob(os.path.join(ckpt_dir, "replay_snapshot_p*.npz")))

    def _pidx(p: str) -> int:
        m = re.search(r"replay_snapshot_p(\d+)\.npz$", p)
        return int(m.group(1)) if m else 0

    out.extend(sorted(per_proc, key=_pidx))
    return out


def _read_shard(d, prefix: str, store_prefix: str) -> Dict:
    """One logical shard's control state + stores out of an open npz view,
    in the plane-agnostic schema the scatter side consumes."""
    names = d.files
    out: Dict = {"tree_leaves": np.asarray(d[prefix + "tree_leaves"])}
    for k in _COUNTERS:
        if prefix + k in names:
            v = d[prefix + k][()]
            out[k] = float(v) if "reward" in k else int(v)
        else:  # pre-ptr_advances snapshot
            out[k] = 0.0 if "reward" in k else 0
    for k in ("learning_sum", "occupied", "num_seq_store"):
        out[k] = np.asarray(d[prefix + k])
    out["stores"] = {k: np.asarray(d[store_prefix + k]) for k in STORE_FIELDS}
    return out


def _flatten_disk_tier(d, shard: Dict) -> None:
    """Host snapshots with a disk tier (PR 19) hold two row ranges: slab
    rows in store_* and demoted blocks as encoded segment-record bytes.
    Resharding FLATTENS the hierarchy — decode each record and append its
    per-step rows to the stores, so every later phase sees one plain host
    plane whose store row count matches the (already-extended) occupancy
    arrays. Unoccupied disk slots append zero rows, mirroring an
    unoccupied slab slot."""
    from r2d2_tpu.replay import codec
    from r2d2_tpu.replay.block import DISK_FIELDS

    db = int(d["disk_blocks"][()])
    if db <= 0:
        return
    stores = shard["stores"]
    ext = {
        k: np.zeros((db, *stores[k].shape[1:]), stores[k].dtype)
        for k in DISK_FIELDS
    }
    dir_size = struct.calcsize(f">{len(DISK_FIELDS)}I")
    for i in np.asarray(d["disk_occupied_slots"], np.int64):
        buf = np.asarray(d[f"disk_rec_{int(i)}"], np.uint8).tobytes()
        pos = dir_size  # field payloads are self-describing past the directory
        for name in DISK_FIELDS:
            arr, pos = codec.decode_field(buf, pos)
            ext[name][int(i)] = arr
    for k in DISK_FIELDS:
        stores[k] = np.concatenate([stores[k], ext[k]], axis=0)


def gather_logical(paths: List[str]) -> Tuple[Dict, Dict[int, Dict], Dict]:
    """Phase 1: read every snapshot file and reassemble the LOGICAL replay.

    Returns (meta, shards, extras): meta describes the saved logical
    layout (plane, dp, num_blocks, seqs_per_block, RNG stream state),
    shards maps GLOBAL shard id -> _read_shard schema, extras is the
    carry payload from the lowest-process_index file (the one that held
    the trainer-global carry). Read-only — safe to crash and retry."""
    fault_point("reshard.gather")
    if not paths:
        raise ValueError("no snapshot files to gather")
    shards: Dict[int, Dict] = {}
    meta: Dict = {}
    extras: Dict[str, np.ndarray] = {}
    extras_pidx: Optional[int] = None
    for path in paths:
        with np.load(path, allow_pickle=False) as npz:
            d = _Bf16NpzView(npz)
            kind = str(d["kind"])
            topo = _topology_from(d)
            file_shards: Dict[int, Dict] = {}
            if kind in ("host", "device"):
                file_shards[0] = _read_shard(d, "", "store_")
                if kind == "host" and "disk_blocks" in d.files:
                    _flatten_disk_tier(d, file_shards[0])
                dp = 1
            elif kind == "sharded":
                dp = (
                    topo["dp"] if topo
                    else sum(
                        1 for k in d.files
                        if k.startswith("shard") and k.endswith("_block_ptr")
                    )
                )
                nb_total = d["store_" + STORE_FIELDS[0]].shape[0]
                bps = nb_total // dp
                for i in range(dp):
                    sh = _read_shard(d, f"shard{i}_", "store_")
                    sh["stores"] = {
                        k: np.asarray(d["store_" + k][i * bps:(i + 1) * bps])
                        for k in STORE_FIELDS
                    }
                    file_shards[i] = sh
            elif kind == "multihost":
                dp = topo["dp"] if topo else None
                for g in [int(x) for x in d["local_ids"]]:
                    file_shards[g] = _read_shard(d, f"g{g}_", f"g{g}_store_")
            else:
                raise ValueError(f"unknown snapshot kind {kind!r} in {path}")
            dup = set(file_shards) & set(shards)
            if dup:
                raise ValueError(
                    f"global shard(s) {sorted(dup)} appear in more than one "
                    f"snapshot file (stale per-process files in the dir?)"
                )
            shards.update(file_shards)
            if not meta:
                meta = {
                    "plane": kind,
                    "dp": dp,
                    "seed": topo["rng_seed"] if topo else None,
                    "epoch": topo["rng_epoch"] if topo else 0,
                    "seqs_per_block": (
                        topo["seqs_per_block"] if topo else None
                    ),
                    "topo": topo,
                }
            elif kind != meta["plane"]:
                raise ValueError(
                    f"snapshot files disagree on plane kind: {meta['plane']} "
                    f"vs {kind} ({path})"
                )
            if topo:
                meta["epoch"] = max(meta["epoch"], topo["rng_epoch"])
            pidx = topo["process_index"] if topo else 0
            if extras_pidx is None or pidx < extras_pidx:
                file_extras = {
                    k[len(_EXTRA_PREFIX):]: np.asarray(d[k])
                    for k in d.files
                    if k.startswith(_EXTRA_PREFIX)
                }
                if file_extras or extras_pidx is None:
                    extras = file_extras
                    extras_pidx = pidx
    ids = sorted(shards)
    if meta["dp"] is None:
        meta["dp"] = len(ids)
    if ids != list(range(meta["dp"])):
        raise ValueError(
            f"gathered shards {ids} do not cover the saved dp={meta['dp']} "
            "layout — a per-process snapshot file is missing"
        )
    any_shard = shards[ids[0]]
    bps_old = len(any_shard["occupied"])
    meta["num_blocks"] = bps_old * meta["dp"]
    if meta["seqs_per_block"] is None:
        meta["seqs_per_block"] = len(any_shard["tree_leaves"]) // max(bps_old, 1)
    return meta, shards, extras


# --------------------------------------------------------------- re-deal


def _logical_blocks(meta: Dict, shards: Dict[int, Dict]) -> List[Dict]:
    """Occupied blocks in global arrival order: oldest-first within each
    shard (the ring pointer points at the oldest slot), interleaved
    across shards the way the round-robin writers dealt them."""
    S = meta["seqs_per_block"]
    per_shard: Dict[int, List[Dict]] = {}
    for g in sorted(shards):
        sh = shards[g]
        nb = len(sh["occupied"])
        ptr = sh["block_ptr"] % nb if nb else 0
        blocks = []
        for off in range(nb):
            slot = (ptr + off) % nb
            if not sh["occupied"][slot]:
                continue
            blocks.append({
                "num_seq": int(sh["num_seq_store"][slot]),
                "learning": int(sh["learning_sum"][slot]),
                "leaves": sh["tree_leaves"][slot * S:(slot + 1) * S],
                "stores": {k: sh["stores"][k][slot] for k in STORE_FIELDS},
            })
        per_shard[g] = blocks
    out: List[Dict] = []
    gs = sorted(per_shard)
    depth = max((len(b) for b in per_shard.values()), default=0)
    for j in range(depth):
        for g in gs:
            if j < len(per_shard[g]):
                out.append(per_shard[g][j])
    return out


def _empty_dest(meta: Dict, bps_new: int, with_stores: bool) -> Dict:
    S = meta["seqs_per_block"]
    d: Dict = {
        "tree_leaves": np.zeros(bps_new * S, np.float64),
        "learning_sum": np.zeros(bps_new, np.int64),
        "occupied": np.zeros(bps_new, bool),
        "num_seq_store": np.zeros(bps_new, np.int32),
    }
    for k in _COUNTERS:
        d[k] = 0.0 if "reward" in k else 0
    if with_stores:
        d["stores"] = None  # allocated lazily from the first block's shapes
    return d


def _redeal(
    meta: Dict,
    shards: Dict[int, Dict],
    dp_new: int,
    bps_new: int,
    only: Optional[set] = None,
) -> Tuple[Dict[int, Dict], int]:
    """Deal the logical blocks round-robin across dp_new shards of
    bps_new capacity each. Keeps the NEWEST blocks when the new capacity
    is smaller (the eviction order a live run would have applied).
    `only`: materialize store slabs just for these destination shards
    (a multihost process only owns its local ones); every destination's
    COUNTERS are still computed, so all processes derive the same global
    accounting from the same files. Returns (per_dest, dropped)."""
    S = meta["seqs_per_block"]
    blocks = _logical_blocks(meta, shards)
    cap = dp_new * bps_new
    dropped = max(0, len(blocks) - cap)
    if dropped:
        blocks = blocks[dropped:]
    dest = {i: _empty_dest(meta, bps_new, with_stores=True) for i in range(dp_new)}
    placed = np.zeros(dp_new, np.int64)
    src_sample = shards[sorted(shards)[0]]["stores"]
    for i in range(dp_new):
        if only is None or i in only:
            dest[i]["stores"] = {
                k: np.zeros((bps_new, *v.shape[1:]), v.dtype)
                for k, v in src_sample.items()
            }
    for j, blk in enumerate(blocks):
        i, slot = j % dp_new, j // dp_new
        d = dest[i]
        d["tree_leaves"][slot * S:(slot + 1) * S] = blk["leaves"]
        d["occupied"][slot] = True
        d["learning_sum"][slot] = blk["learning"]
        d["num_seq_store"][slot] = blk["num_seq"]
        d["size"] += blk["learning"]
        placed[i] += 1
        if d["stores"] is not None:
            for k in STORE_FIELDS:
                d["stores"][k][slot] = blk["stores"][k]
    for i in range(dp_new):
        dest[i]["block_ptr"] = int(placed[i]) % bps_new
        dest[i]["ptr_advances"] = int(placed[i])
        dest[i]["env_steps"] = dest[i]["size"]
    # preserve GLOBAL totals exactly: whatever per-block accounting cannot
    # attribute (evicted/dropped blocks' env steps, episode tallies) lands
    # on shard 0 — consumers only ever sum these across shards
    env_total = sum(sh["env_steps"] for sh in shards.values())
    dest[0]["env_steps"] += env_total - sum(d["env_steps"] for d in dest.values())
    for k in ("num_episodes", "total_episodes"):
        dest[0][k] = sum(sh[k] for sh in shards.values())
    for k in ("episode_reward_sum", "total_reward_sum"):
        dest[0][k] = float(sum(sh[k] for sh in shards.values()))
    return dest, dropped


# ---------------------------------------------------------------- scatter


def _apply_plane(plane: ReplayControlPlane, d: Dict) -> None:
    """Load one shard-schema dict into a live control plane. Caller holds
    the plane's lock."""
    plane.tree.load_leaves(np.asarray(d["tree_leaves"], np.float64))
    for k in _COUNTERS:
        setattr(plane, k, d[k])
    plane.learning_sum[:] = d["learning_sum"]
    plane.occupied[:] = d["occupied"]
    plane.num_seq_store[:] = d["num_seq_store"]


def _cast_stores(
    stores: Dict[str, np.ndarray], targets: Dict[str, Tuple], rows_cfg=None
) -> Dict[str, np.ndarray]:
    """Validate shapes against the destination and cast dtypes across the
    host/device family boundary (uint8 <-> int32 action fields; lossless,
    actions < 256). `rows_cfg` says the destination keeps frames as rows, in
    that config's block order (the device planes; snapshot files hold frames
    on every plane): obs is checked against the frame shape and handed back
    as rows. Raises BEFORE the caller mutates anything."""
    out = {}
    for k in STORE_FIELDS:
        shape, dtype = targets[k]
        rows = k == "obs" and rows_cfg is not None
        if rows:
            shape = (*shape[:-2], *rows_cfg.obs_shape)
        v = stores[k]
        if tuple(v.shape) != tuple(shape):
            raise ValueError(
                f"store {k}: snapshot slab {v.shape} != destination {shape} "
                "(incompatible config, not just topology)"
            )
        v = v if v.dtype == dtype else v.astype(dtype)
        if rows:
            v = frames_to_rows(v, rows_cfg.obs_shape, rows_cfg.resolved_frame_block)
        out[k] = v
    return out


def _dest_layout(replay) -> Tuple[str, int, int]:
    """(plane, dp, blocks_per_shard) of the destination replay."""
    from r2d2_tpu.replay.multihost_store import MultiHostShardedReplay
    from r2d2_tpu.replay.sharded_store import ShardedDeviceReplay

    if isinstance(replay, MultiHostShardedReplay):
        return "multihost", replay.dp, replay.blocks_per_shard
    if isinstance(replay, ShardedDeviceReplay):
        return "sharded", replay.dp, replay.blocks_per_shard
    if isinstance(replay, DeviceReplayBuffer):
        return "device", 1, replay.cfg.num_blocks
    if isinstance(replay, ReplayBuffer):
        return "host", 1, replay.cfg.num_blocks
    raise TypeError(f"unknown replay type {type(replay).__name__}")


def reshard_replay(replay, paths: List[str]) -> Dict[str, np.ndarray]:
    """Restore snapshot files written under ANY topology into `replay`.

    Gathers the files' slabs to logical order, then re-splits them across
    `replay`'s layout (exact when the logical shard set is unchanged,
    round-robin re-deal otherwise — see module docstring for what is
    bit-exact vs bounded-drift). Validation happens before any mutation.
    Returns the layout-free subset of the saved carry extras."""
    meta, shards, extras = gather_logical(paths)
    plane_kind, dp_new, bps_new = _dest_layout(replay)
    cfg = replay.cfg
    exact = (
        meta["dp"] == dp_new
        and meta["num_blocks"] == cfg.num_blocks
        and meta["seqs_per_block"] == cfg.seqs_per_block
    )
    fault_point("reshard.scatter")
    if exact:
        per_dest: Dict[int, Dict] = shards
        dropped = 0
    else:
        if plane_kind == "multihost":
            only = set(replay.local_ids)
        else:
            only = set(range(dp_new))
        per_dest, dropped = _redeal(meta, shards, dp_new, bps_new, only=only)
    if dropped:
        print(
            f"[reshard] new layout holds {dp_new * bps_new} blocks < "
            f"{meta['num_blocks']} saved; dropped the {dropped} oldest"
        )
    _scatter(replay, plane_kind, per_dest, meta)
    kept = {
        k: v for k, v in extras.items()
        if not k.startswith(_LAYOUT_BOUND_CARRY)
    }
    return kept


def _scatter(replay, plane_kind: str, per_dest: Dict[int, Dict], meta: Dict) -> None:
    """Phase 2 writer: install per-destination-shard state into the live
    replay. All per-shard payloads are validated (_cast_stores) before the
    first mutation of that shard's plane/stores."""
    if plane_kind == "multihost":
        targets = {
            k: (replay.stores[replay.local_ids[0]][k].shape,
                replay.stores[replay.local_ids[0]][k].dtype)
            for k in STORE_FIELDS
        }
        with replay.lock:
            cast = {
                g: _cast_stores(per_dest[g]["stores"], targets, replay.cfg)
                for g in replay.local_ids
            }
            for g in replay.local_ids:
                shard = replay.shards[g]
                with shard.lock:
                    _apply_plane(shard, per_dest[g])
                    replay.stores[g] = {
                        k: jax.device_put(v, replay._shard_device[g])
                        for k, v in cast[g].items()
                    }
            replay._rr = 0
            replay._epoch = meta["epoch"]
            if meta["seed"] is not None:
                replay._seed = meta["seed"]
            replay._pending = None
    elif plane_kind == "sharded":
        from r2d2_tpu.parallel.mesh import slab_sharding

        bps = replay.blocks_per_shard
        targets = {
            k: ((bps, *replay.stores[k].shape[1:]), replay.stores[k].dtype)
            for k in STORE_FIELDS
        }
        with replay.lock:
            cast = {
                i: _cast_stores(per_dest[i]["stores"], targets, replay.cfg)
                for i in range(replay.dp)
            }
            flat = {
                k: np.concatenate([cast[i][k] for i in range(replay.dp)])
                for k in STORE_FIELDS
            }
            for i, shard in enumerate(replay.shards):
                with shard.lock:
                    _apply_plane(shard, per_dest[i])
            replay.stores = {
                k: jax.device_put(v, slab_sharding(replay.mesh))
                for k, v in flat.items()
            }
            replay._rr = 0
    elif plane_kind == "device":
        targets = {
            k: (replay.stores[k].shape, replay.stores[k].dtype)
            for k in STORE_FIELDS
        }
        with replay.lock:
            cast = _cast_stores(per_dest[0]["stores"], targets, replay.cfg)
            _apply_plane(replay, per_dest[0])
            replay.stores = {k: jax.device_put(v) for k, v in cast.items()}
    else:  # host / tiered
        targets = {
            k: (
                getattr(replay, k + "_store").shape,
                getattr(replay, k + "_store").dtype,
            )
            for k in STORE_FIELDS
        }
        with replay.lock:
            cast = _cast_stores(per_dest[0]["stores"], targets)
            _apply_plane(replay, per_dest[0])
            for k in STORE_FIELDS:
                getattr(replay, k + "_store")[:] = cast[k]


# -------------------------------------------------------------------- CLI


def main(argv=None) -> int:
    """Assert a checkpoint dir's snapshot topology before `--resume`.

    Prints every snapshot file's manifest as json. Exit codes: 0 — no
    snapshot, or manifests coherent (and matching any --expect-* flags);
    2 — mismatch/incoherence. runs/lib.sh assert_snapshot_topology wraps
    this for the recovery chain scripts."""
    import argparse
    import json
    import sys

    p = argparse.ArgumentParser(
        prog="python -m r2d2_tpu.replay.reshard",
        description="inspect/assert replay snapshot topology manifests",
    )
    p.add_argument("ckpt_dir")
    p.add_argument("--expect-dp", type=int, default=None)
    p.add_argument("--expect-tp", type=int, default=None)
    p.add_argument("--expect-process-count", type=int, default=None)
    args = p.parse_args(argv)

    paths = snapshot_paths(args.ckpt_dir)
    manifests = {os.path.basename(q): read_manifest(q) for q in paths}
    print(json.dumps({"ckpt_dir": args.ckpt_dir, "manifests": manifests}, indent=2))
    if not paths:
        return 0  # nothing to assert: --resume refills replay from scratch

    problems = []
    topos = [m for m in manifests.values() if m is not None]
    if len(topos) != len(manifests):
        legacy = [k for k, m in manifests.items() if m is None]
        problems.append(f"pre-manifest snapshot file(s): {legacy}")
    if topos:
        t0 = topos[0]
        for key in ("plane", "dp", "tp", "num_blocks", "process_count"):
            vals = {t.get(key) for t in topos}
            if len(vals) > 1:
                problems.append(f"files disagree on {key}: {sorted(map(str, vals))}")
        covered = sorted(g for t in topos for g in t["local_ids"])
        if covered != list(range(t0["dp"])):
            problems.append(
                f"shard coverage {covered} != saved dp={t0['dp']} layout "
                "(missing or stale per-process files)"
            )
        expects = {
            "dp": args.expect_dp,
            "tp": args.expect_tp,
            "process_count": args.expect_process_count,
        }
        for key, want in expects.items():
            if want is not None and t0.get(key) != want:
                problems.append(
                    f"manifest {key}={t0.get(key)} != expected {want} — "
                    "resume with --reshard or fix the layout"
                )
    elif any(
        v is not None
        for v in (args.expect_dp, args.expect_tp, args.expect_process_count)
    ):
        problems.append("cannot assert expectations against pre-manifest snapshots")
    for prob in problems:
        print(f"topology assert failed: {prob}", file=sys.stderr)
    return 2 if problems else 0


if __name__ == "__main__":
    raise SystemExit(main())
