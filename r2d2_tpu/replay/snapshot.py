"""Replay snapshots: persist the full replay state for true resume.

The reference has no resume path at all (SURVEY.md section 5.4); even this
framework's learner checkpoints (utils/checkpoint.py) restore optimization
exactly but refill replay from fresh experience. For workloads where replay
contents matter across restarts (long warmups, offline analysis, failure
recovery mid-curriculum), these helpers save and restore EVERYTHING the
replay subsystem holds:

- control plane: sum-tree leaf priorities, circular block pointer, size /
  env-step / episode accounting, per-slot sequence counts, staleness state;
- data plane: every store field — host numpy arrays (ReplayBuffer),
  single-chip HBM stores (DeviceReplayBuffer, downloaded/uploaded once),
  or dp-sharded HBM stores (ShardedDeviceReplay, restored with their
  NamedSharding intact).

A restored buffer is bit-identical to the saved one: sampling with the same
RNG stream yields the same batches (pinned by tests/test_snapshot.py).
Consistency: the whole payload is captured under the buffer lock(s), so a
snapshot taken while collection threads are writing is a clean point-in-time
cut; the file write itself happens outside the locks and lands atomically
(temp file + os.replace), so a crash mid-write can never leave a truncated
snapshot that poisons --resume.

Format: one .npz (uncompressed — obs dominate and are incompressible-ish
uint8; write speed matters more). Obs storage dominates the file size:
~7 KB/transition at 84x84, so snapshot cadence is the caller's cost knob —
the Trainer writes one at end-of-run when cfg.snapshot_replay is set and
restores it on --resume. The FILE holds obs as frames, (block, slot,
*obs_shape), on every plane: the device planes' lane-aligned rows
(replay/block.frames_to_rows) are converted on the way out and back in, so
a file is one format whichever plane or release wrote it, and reshard moves
it between host and device planes without knowing the row format.
"""

from __future__ import annotations

import os
from typing import Dict, Optional

import jax
import ml_dtypes
import numpy as np

from r2d2_tpu.replay.block import frames_to_rows, rows_to_frames
from r2d2_tpu.replay.control_plane import ReplayControlPlane
from r2d2_tpu.replay.device_store import DeviceReplayBuffer
from r2d2_tpu.replay.replay_buffer import ReplayBuffer
from r2d2_tpu.utils.faults import fault_point

STORE_FIELDS = (
    "obs", "last_action", "last_reward", "action", "n_step_reward",
    "gamma", "hidden", "burn_in", "learning", "forward",
)

# ptr_advances is the lap-detection stamp deferred write-backs compare
# against; dropping it across a resume would let a stale write-back land
# after a full buffer lap. Old snapshots (pre ptr_advances) restore with 0.
_COUNTERS = (
    "block_ptr", "size", "env_steps", "num_episodes", "episode_reward_sum",
    "total_episodes", "total_reward_sum", "ptr_advances",
)

# extras ride in the same npz under this prefix (mid-run carry: trainer
# RNG / actor / env / pending write-back state), so snapshot + carry land
# or are lost atomically — one os.replace
_EXTRA_PREFIX = "x_"

# topology manifest keys ride under this prefix: every snapshot records
# the (dp, tp, process_count) layout it was written under, the global
# block ranges its slabs cover, and its RNG stream identity, so a resume
# on a DIFFERENT layout can regather the slabs (replay/reshard.py)
# instead of aborting
_TOPO_PREFIX = "topo_"


class TopologyMismatch(ValueError):
    """A snapshot's recorded topology differs from the replay restoring it.

    Carries structured `saved` and `current` dicts (plane, dp, tp,
    process_count, local_ids, ...) so callers — the Trainer's resume path,
    the reshard CLI — can decide programmatically; the message names the
    escape hatch. Subclasses ValueError so pre-elasticity callers that
    caught the bare layout error keep working."""

    def __init__(self, saved: Dict, current: Dict, detail: str = ""):
        self.saved = dict(saved)
        self.current = dict(current)

        def _fmt(t: Dict) -> str:
            return (
                f"plane={t.get('plane')} dp={t.get('dp')} tp={t.get('tp')} "
                f"process_count={t.get('process_count')} "
                f"local_ids={t.get('local_ids')}"
            )

        msg = f"snapshot topology [{_fmt(self.saved)}] != current [{_fmt(self.current)}]"
        if detail:
            msg += f" ({detail})"
        msg += (
            " — pass --reshard (cfg.reshard_on_resume) to regather the "
            "replay slabs and re-split them across the new layout"
        )
        super().__init__(msg)


def snapshot_topology(replay, tp: int = 1) -> Dict[str, np.ndarray]:
    """The topology manifest a snapshot embeds: which layout wrote it.

    Records the logical shard structure (dp, blocks per shard), the
    process layout (process_count/index, the global shard ids THIS file
    holds), the per-slab partition map rows this host owns (global block
    ranges, mirroring parallel/mesh.slab_partition_map), and the
    per-logical-shard RNG stream identity (the multihost draw stream is
    keyed (seed, GLOBAL shard id, epoch) — layout-independent by design,
    which is exactly what makes elastic resume deterministic per logical
    shard). `tp` is the mesh's tensor-parallel degree; the replay object
    alone cannot know it, so snapshot writers pass it explicitly (the
    snapshot-missing-topology lint keeps them honest)."""
    from r2d2_tpu.replay.multihost_store import MultiHostShardedReplay
    from r2d2_tpu.replay.sharded_store import ShardedDeviceReplay

    cfg = replay.cfg
    nb = cfg.num_blocks
    if isinstance(replay, MultiHostShardedReplay):
        plane, dp = "multihost", replay.dp
        local_ids = list(replay.local_ids)
        bps = replay.blocks_per_shard
        seed, epoch = replay._seed, replay._epoch
    elif isinstance(replay, ShardedDeviceReplay):
        plane, dp = "sharded", replay.dp
        local_ids = list(range(replay.dp))
        bps = replay.blocks_per_shard
        seed = epoch = 0
    elif isinstance(replay, DeviceReplayBuffer):
        plane, dp, local_ids, bps, seed, epoch = "device", 1, [0], nb, 0, 0
    elif isinstance(replay, ReplayBuffer):
        plane, dp, local_ids, bps, seed, epoch = "host", 1, [0], nb, 0, 0
    else:
        raise TypeError(f"unknown replay type {type(replay).__name__}")
    return {
        "plane": np.asarray(plane),
        "dp": np.asarray(dp, np.int64),
        "tp": np.asarray(tp, np.int64),
        "process_count": np.asarray(jax.process_count(), np.int64),
        "process_index": np.asarray(jax.process_index(), np.int64),
        "num_blocks": np.asarray(nb, np.int64),
        "blocks_per_shard": np.asarray(bps, np.int64),
        "seqs_per_block": np.asarray(cfg.seqs_per_block, np.int64),
        "local_ids": np.asarray(local_ids, np.int64),
        "slab_ranges": np.asarray(
            [[g * bps, (g + 1) * bps] for g in local_ids], np.int64
        ).reshape(len(local_ids), 2),
        "rng_streams": np.asarray(local_ids, np.int64),
        "rng_seed": np.asarray(seed, np.int64),
        "rng_epoch": np.asarray(epoch, np.int64),
        # disk tier below the host slab (0 = no tier): reshard's
        # gather_logical flattens these records into plain store rows
        "disk_blocks": np.asarray(
            getattr(getattr(replay, "disk", None), "disk_blocks", 0), np.int64
        ),
    }


def _plain(topo: Dict) -> Dict:
    """A manifest as plain python scalars/lists (json-able, error-printable)."""
    out = {}
    for k, v in topo.items():
        v = np.asarray(v)
        if v.dtype.kind in ("U", "S"):
            out[k] = str(v)
        elif v.ndim == 0:
            out[k] = int(v)
        else:
            out[k] = v.tolist()
    return out


def _topology_from(d) -> Optional[Dict]:
    """Extract the plain-form manifest from an open npz (view); None for
    pre-manifest snapshots."""
    names = getattr(d, "files", None) or list(d)
    if _TOPO_PREFIX + "plane" not in names:
        return None
    return _plain({
        k[len(_TOPO_PREFIX):]: d[k]
        for k in names
        if k.startswith(_TOPO_PREFIX)
    })


def read_manifest(path: str) -> Optional[Dict]:
    """The topology manifest embedded in a snapshot file, as plain python
    values; None for pre-manifest snapshots."""
    with np.load(path, allow_pickle=False) as npz:
        return _topology_from(npz)


def _plane_state(plane: ReplayControlPlane, prefix: str = "") -> Dict[str, np.ndarray]:
    d = {prefix + "tree_leaves": plane.tree.leaves()}
    if plane.dtree is not None:
        # priority_plane="device": the float32 HBM tree is AUTHORITATIVE
        # for sampling and carries the learner's write-backs (the host
        # tree only sees ingestion there) — snapshot its leaves so
        # --resume continues from the same priority distribution
        d[prefix + "dtree_leaves"] = np.asarray(plane.dtree.leaves(), np.float32)
    for k in _COUNTERS:
        d[prefix + k] = np.asarray(getattr(plane, k))
    d[prefix + "learning_sum"] = plane.learning_sum.copy()
    d[prefix + "occupied"] = plane.occupied.copy()
    d[prefix + "num_seq_store"] = plane.num_seq_store.copy()
    return d


def _restore_plane(plane: ReplayControlPlane, d, prefix: str = "") -> None:
    plane.tree.load_leaves(d[prefix + "tree_leaves"])
    names = getattr(d, "files", None) or list(d)
    if plane.dtree is not None:
        if prefix + "dtree_leaves" in names:
            plane.dtree.load_leaves(d[prefix + "dtree_leaves"])
        else:
            # host-plane snapshot restored under priority_plane="device":
            # seed the device tree from the host leaves (f64 -> f32, the
            # parity-bounded drift class, ARCHITECTURE.md)
            plane.dtree.load_leaves(
                np.asarray(d[prefix + "tree_leaves"], np.float32)
            )
    for k in _COUNTERS:
        if prefix + k not in names:  # pre-ptr_advances snapshot
            setattr(plane, k, 0)
            continue
        v = d[prefix + k][()]
        setattr(plane, k, float(v) if "reward" in k else int(v))
    plane.learning_sum[:] = d[prefix + "learning_sum"]
    plane.occupied[:] = d[prefix + "occupied"]
    plane.num_seq_store[:] = d[prefix + "num_seq_store"]


def _check_kind(kind: str, want: str, replay, saved_topo: Optional[Dict]) -> None:
    if kind != want:
        raise TopologyMismatch(
            saved_topo or {"plane": kind},
            _plain(snapshot_topology(replay)),
            f"snapshot kind {kind!r} != replay plane {want!r}",
        )


def _validated_stores(
    d, current: Dict[str, np.ndarray], prefix: str = "store_", rows_cfg=None
) -> Dict[str, np.ndarray]:
    """Load every store field from the npz ONCE (NpzFile re-parses per
    access, and obs dominate the file), checking shape/dtype against the
    live buffer BEFORE the caller mutates anything — a mismatched snapshot
    must leave the buffer untouched. `rows_cfg` says the live store keeps
    frames as rows in that config's block order (the device planes): the
    file's frames are checked against the frame shape and handed back as
    rows."""
    out = {}
    for k in STORE_FIELDS:
        cur = current[k]
        val = d[prefix + k]
        rows = k == "obs" and rows_cfg is not None
        want = (*cur.shape[:-2], *rows_cfg.obs_shape) if rows else cur.shape
        if val.shape != want or val.dtype != cur.dtype:
            raise ValueError(
                f"store {prefix}{k}: snapshot {val.shape}/{val.dtype} != "
                f"buffer {want}/{cur.dtype}"
            )
        if rows:
            val = frames_to_rows(val, rows_cfg.obs_shape, rows_cfg.resolved_frame_block)
        out[k] = val
    return out


def _download_stores(cfg, stores) -> Dict[str, np.ndarray]:
    """A device plane's stores as the file holds them: host arrays, obs
    back as frames."""
    out = {k: np.asarray(stores[k]) for k in STORE_FIELDS}
    out["obs"] = rows_to_frames(out["obs"], cfg.obs_shape, cfg.resolved_frame_block)
    return out


# bfloat16 stores (precision="bf16" carry slabs, and actor carries in the
# extras payload under bf16 compute) cannot ride npz directly: np.savez
# writes the ml_dtypes extension dtype but np.load hands it back as raw
# void bytes. Round-trip them as uint16 bit-views plus a key manifest —
# the restore side views them back, so _validated_stores still sees the
# exact storage dtype and `--resume` stays bit-exact per plane.
_BF16 = np.dtype(ml_dtypes.bfloat16)
_BF16_KEYS = "bf16_keys"


def _encode_bf16(payload: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    keys = sorted(k for k, v in payload.items() if v.dtype == _BF16)
    if not keys:
        return payload
    out = dict(payload)
    for k in keys:
        out[k] = payload[k].view(np.uint16)
    out[_BF16_KEYS] = np.asarray(keys)
    return out


class _Bf16NpzView:
    """Read-side counterpart of _encode_bf16: an NpzFile facade that hands
    back bfloat16 arrays with their dtype restored."""

    def __init__(self, npz):
        self._npz = npz
        self._bf16 = (
            {str(k) for k in npz[_BF16_KEYS]} if _BF16_KEYS in npz.files else set()
        )
        self.files = [k for k in npz.files if k != _BF16_KEYS]

    def __getitem__(self, k):
        v = self._npz[k]
        return v.view(_BF16) if k in self._bf16 else v


def _atomic_savez(path: str, payload: Dict[str, np.ndarray]) -> None:
    # keep the .npz suffix on the temp name: np.savez APPENDS .npz to
    # filenames without it, which would break the rename
    fault_point("snapshot.write")
    tmp = path + ".tmp.npz"
    np.savez(tmp, **_encode_bf16(payload))
    os.replace(tmp, path)


def save_replay(
    replay,
    path: str,
    extra: Optional[Dict[str, np.ndarray]] = None,
    topology: Optional[Dict[str, np.ndarray]] = None,
) -> None:
    """Snapshot any replay plane (host / device / sharded) to `path`.

    The payload (control state + a copy of every store) is captured under
    the buffer lock; the npz write happens after release. `extra` carries
    caller state (trainer RNG / actor / env / pending write-backs) in the
    same file under a reserved prefix — restore_replay hands it back.
    `topology` is the snapshot_topology manifest; callers that know the
    mesh pass snapshot_topology(replay, tp=...) explicitly (enforced by
    the snapshot-missing-topology lint), None derives a tp=1 manifest —
    either way EVERY snapshot embeds one."""
    from r2d2_tpu.replay.multihost_store import MultiHostShardedReplay
    from r2d2_tpu.replay.sharded_store import ShardedDeviceReplay

    if isinstance(replay, MultiHostShardedReplay):
        # PER-HOST snapshot: each process saves only the shards it owns
        # (keyed by GLOBAL shard id), to its own path — restore requires
        # the same process layout, which is validated, not assumed
        with replay.lock:
            payload = {"kind": np.asarray("multihost")}
            payload["local_ids"] = np.asarray(replay.local_ids, np.int64)
            payload["rr"] = np.asarray(replay._rr)
            for g in replay.local_ids:
                shard = replay.shards[g]
                with shard.lock:
                    payload.update(_plane_state(shard, prefix=f"g{g}_"))
                    for k, v in _download_stores(replay.cfg, replay.stores[g]).items():
                        payload[f"g{g}_store_{k}"] = v
    elif isinstance(replay, ShardedDeviceReplay):
        with replay.lock:
            payload: Dict[str, np.ndarray] = {"kind": np.asarray("sharded")}
            payload["rr"] = np.asarray(replay._rr)
            for i, shard in enumerate(replay.shards):
                with shard.lock:
                    payload.update(_plane_state(shard, prefix=f"shard{i}_"))
            for k, v in _download_stores(replay.cfg, replay.stores).items():
                payload["store_" + k] = v
    elif isinstance(replay, DeviceReplayBuffer):
        with replay.lock:
            payload = {"kind": np.asarray("device")}
            payload.update(_plane_state(replay))
            for k, v in _download_stores(replay.cfg, replay.stores).items():
                payload["store_" + k] = v
    elif isinstance(replay, ReplayBuffer):
        with replay.lock:
            payload = {"kind": np.asarray("host")}
            payload.update(_plane_state(replay))
            for k in STORE_FIELDS:
                # copy under the lock: np.savez runs after release, and the
                # live stores keep mutating under collection threads
                payload["store_" + k] = getattr(replay, k + "_store").copy()
            disk = getattr(replay, "disk", None)
            if disk is not None:
                # disk tier manifest: occupied records ride VERBATIM as
                # their encoded segment bytes (no decode/re-encode round
                # trip), so --resume rewrites segments bit-exactly — and a
                # torn segment left by a kill mid-demotion is healed by the
                # rewrite rather than trusted
                payload["disk_blocks"] = np.asarray(disk.disk_blocks, np.int64)
                payload["disk_ptr"] = np.asarray(replay._disk_ptr, np.int64)
                payload["slot_stamp"] = replay.slot_stamp.copy()
                occ = np.nonzero(replay.occupied[replay.cfg.num_blocks:])[0]
                payload["disk_occupied_slots"] = occ.astype(np.int64)
                for i in occ:
                    payload[f"disk_rec_{int(i)}"] = disk.record_bytes(int(i))
    else:
        raise TypeError(f"unknown replay type {type(replay).__name__}")
    for k, v in (extra or {}).items():
        payload[_EXTRA_PREFIX + k] = np.asarray(v)
    topo = topology if topology is not None else snapshot_topology(replay)
    for k, v in topo.items():
        payload[_TOPO_PREFIX + k] = np.asarray(v)
    _atomic_savez(path, payload)


def restore_replay(replay, path: str) -> Dict[str, np.ndarray]:
    """Restore a snapshot into a freshly built replay of the SAME config.

    Mismatches raise BEFORE any state is touched — a failed restore leaves
    the buffer exactly as constructed. Layout mismatches (plane kind, dp,
    process/shard ownership) raise TopologyMismatch, which the Trainer's
    --reshard path catches to regather the slabs (replay/reshard.py);
    content mismatches (capacity, obs shape, hidden dim) stay plain
    ValueErrors. Returns the `extra` dict the snapshot was saved with
    (empty for plain snapshots), fully materialized."""
    from r2d2_tpu.replay.multihost_store import MultiHostShardedReplay
    from r2d2_tpu.replay.sharded_store import ShardedDeviceReplay

    with np.load(path, allow_pickle=False) as npz:
        d = _Bf16NpzView(npz)
        kind = str(d["kind"])
        saved_topo = _topology_from(d)
        # materialize extras before the NpzFile closes
        extras = {
            k[len(_EXTRA_PREFIX):]: np.asarray(d[k])
            for k in d.files
            if k.startswith(_EXTRA_PREFIX)
        }
        if isinstance(replay, MultiHostShardedReplay):
            _check_kind(kind, "multihost", replay, saved_topo)
            with replay.lock:
                saved_ids = [int(x) for x in d["local_ids"]]
                if saved_ids != list(replay.local_ids):
                    raise TopologyMismatch(
                        saved_topo or {"plane": kind, "local_ids": saved_ids},
                        _plain(snapshot_topology(replay)),
                        f"snapshot owns global shards {saved_ids}, this "
                        f"process owns {list(replay.local_ids)}",
                    )
                # validate EVERY shard before mutating anything (the
                # validated arrays are reused below — one npz read each)
                vals_by_shard = {}
                for g in replay.local_ids:
                    if len(d[f"g{g}_tree_leaves"]) != replay.shards[g].tree.capacity:
                        raise ValueError(f"shard {g}: tree size mismatch")
                    vals_by_shard[g] = _validated_stores(
                        d, replay.stores[g], prefix=f"g{g}_store_",
                        rows_cfg=replay.cfg,
                    )
                replay._rr = int(d["rr"][()])
                for g in replay.local_ids:
                    shard = replay.shards[g]
                    with shard.lock:
                        _restore_plane(shard, d, prefix=f"g{g}_")
                        replay.stores[g] = {
                            k: jax.device_put(v, replay._shard_device[g])
                            for k, v in vals_by_shard[g].items()
                        }
        elif isinstance(replay, ShardedDeviceReplay):
            _check_kind(kind, "sharded", replay, saved_topo)
            saved_dp = (
                saved_topo["dp"] if saved_topo
                else sum(
                    1 for k in d.files
                    if k.startswith("shard") and k.endswith("_block_ptr")
                )
            )
            if saved_dp != replay.dp:
                raise TopologyMismatch(
                    saved_topo or {"plane": kind, "dp": saved_dp},
                    _plain(snapshot_topology(replay)),
                    f"snapshot holds {saved_dp} dp shards, replay has {replay.dp}",
                )
            with replay.lock:
                vals = _validated_stores(
                    d, replay.stores, rows_cfg=replay.cfg
                )
                for i in range(len(replay.shards)):  # leaf-count pre-check
                    if len(d[f"shard{i}_tree_leaves"]) != replay.shards[i].tree.capacity:
                        raise ValueError(f"shard {i}: tree size mismatch")
                replay._rr = int(d["rr"][()])
                for i, shard in enumerate(replay.shards):
                    with shard.lock:
                        _restore_plane(shard, d, prefix=f"shard{i}_")
                replay.stores = {
                    k: jax.device_put(v, replay.stores[k].sharding)
                    for k, v in vals.items()
                }
        elif isinstance(replay, DeviceReplayBuffer):
            _check_kind(kind, "device", replay, saved_topo)
            with replay.lock:
                vals = _validated_stores(
                    d, replay.stores, rows_cfg=replay.cfg
                )
                if len(d["tree_leaves"]) != replay.tree.capacity:
                    raise ValueError("tree size mismatch")
                _restore_plane(replay, d)
                replay.stores = {k: jax.device_put(v) for k, v in vals.items()}
        elif isinstance(replay, ReplayBuffer):
            _check_kind(kind, "host", replay, saved_topo)
            with replay.lock:
                current = {k: getattr(replay, k + "_store") for k in STORE_FIELDS}
                vals = _validated_stores(d, current)
                if len(d["tree_leaves"]) != replay.tree.capacity:
                    raise ValueError("tree size mismatch")
                disk = getattr(replay, "disk", None)
                saved_db = (
                    int(d["disk_blocks"][()]) if "disk_blocks" in d.files else 0
                )
                live_db = disk.disk_blocks if disk is not None else 0
                if saved_db != live_db:
                    raise ValueError(
                        f"disk tier mismatch: snapshot holds {saved_db} disk "
                        f"blocks, replay configured for {live_db}"
                    )
                _restore_plane(replay, d)
                for k in STORE_FIELDS:
                    current[k][:] = vals[k]
                if disk is not None:
                    replay._disk_ptr = int(d["disk_ptr"][()])
                    replay.slot_stamp[:] = d["slot_stamp"]
                    replay._disk_cache.clear()
                    for i in d["disk_occupied_slots"]:
                        disk.write_record_bytes(int(i), d[f"disk_rec_{int(i)}"])
                    disk.flush()
        else:
            raise TypeError(f"unknown replay type {type(replay).__name__}")
    return extras
