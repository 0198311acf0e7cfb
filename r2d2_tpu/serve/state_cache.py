"""Device-resident session-state cache with a host-RAM spill tier.

R2D2's policy is stateful: every user session carries an LSTM carry plus
its last action and last reward across requests (models/r2d2.py `act`).
Shipping that state to the client and back would add two host<->device
round trips of 2*H floats per request; instead the state lives HERE, in
fixed-capacity device arrays, and requests carry only a session id. Batch
formation gathers the rows for the sessions in the batch, the jitted serve
step advances them, and the updated rows scatter back — recurrent state
never leaves the device between requests.

Host side this is an LRU map session_id -> slot index (an OrderedDict —
hits move to the back, evictions pop the front). The device arrays hold
one extra scratch row at index `capacity`: padding rows of a bucketed
batch gather from and scatter into it, so partially-full batches need no
masking inside the jitted step.

Session tiers (the million-session shape — the HBM hot set is one tier of
a larger session population):

    HBM rows (capacity)  <-- promote --  host spill slab (spill_capacity)
          |  evict                              |  spill-LRU full
          +------------- demote --------------->+---- drop (fresh on
                                                       return)

With `spill_capacity > 0`, LRU eviction DEMOTES the victim's
(h, c, last_action, last_reward) into a preallocated host-RAM slab — the
same pinned-slab discipline as the tiered replay store
(replay/tiered_store.py): one preallocated array per field, np.zeros'
lazy allocation on Linux means a multi-million-row slab costs physical
pages only for the filled prefix, and bytes move tier-to-tier as one
vectorized gather/scatter per batch, never per session. A returning
spilled session is PROMOTED back with its carry intact: the slab stores
the cache dtype verbatim (fp32 or bf16), so the round trip is bit-exact
and the session continues as if it had never been evicted. Only sessions
the slab has never seen (or has itself LRU-dropped) start fresh.

`spill_capacity == 0` keeps the original semantics: an evicted session
that returns is re-admitted FRESH (zero carry, NOOP last action, zero
last reward — exactly the training episode-start state, models/core.py
`zero_carry`), which is also what per-session reset produces.

Array mutation (`arrays` / `commit` / the demote readback / the promote
scatter) is single-writer by contract — only the serve loop touches the
device rows, and `assign` is only ever called from that loop. The
host-side maps (slots, spill index, counters) are lock-protected so
`reset` / `evict` / `stats` may be called from any thread.

Under the depth-2 serve pipeline (config.serve_pipeline) both halves of
a batch's cache interaction — `assign` at STAGE time and `commit` at
DISPATCH time — still run back-to-back on the one serve thread, so the
single-writer contract is untouched: batch k+1's assign happens strictly
after batch k's commit in program order, and the arrays handed to step
k+1 already reference batch k's (possibly still-executing) donated
outputs — the device stream, not the host, orders the actual row
updates. The completion worker never calls into this class; it only
reads host copies materialized from step outputs.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from r2d2_tpu.models.core import check_two_row_state


class RecurrentStateCache:
    """Fixed-capacity device store: session_id -> (carry, last_action,
    last_reward) with LRU eviction into an optional host spill tier."""

    def __init__(self, capacity: int, hidden_dim: int, dtype=jnp.float32,
                 spill_capacity: int = 0, device=None,
                 state_shape: Optional[Tuple[int, ...]] = None, core: str = ""):
        # h / c / _spill_h / _spill_c below were written for a two-row
        # state: a core that stores another shape (models/core.py
        # state_spec, passed by the server) is refused HERE, not in a
        # scatter (ROADMAP D1b)
        if state_shape is not None:
            check_two_row_state(state_shape, hidden_dim, core, "RecurrentStateCache")
        if capacity < 1:
            raise ValueError("cache capacity must be >= 1")
        if spill_capacity < 0:
            raise ValueError("spill_capacity must be >= 0 (0 disables)")
        self.capacity = capacity
        self.hidden_dim = hidden_dim
        # carry storage dtype: float32, or bfloat16 under the bf16
        # precision policy (cfg.state_dtype) — halves per-session HBM
        self.dtype = jnp.dtype(dtype)
        # replica placement (serve/multi.py): the rows live on exactly one
        # device; None keeps jax's default placement (single-device serve)
        self.device = device
        # +1 scratch row for bucket padding (gathered/scattered harmlessly)
        self.h = self._device_zeros((capacity + 1, hidden_dim), self.dtype)
        self.c = self._device_zeros((capacity + 1, hidden_dim), self.dtype)
        self.last_action = self._device_zeros((capacity + 1,), jnp.int32)
        self.last_reward = self._device_zeros((capacity + 1,), jnp.float32)
        self._slots: "OrderedDict[str, int]" = OrderedDict()
        self._free: List[int] = list(range(capacity))
        self._lock = threading.Lock()
        # ---- host spill tier (preallocated slab, tiered_store discipline)
        self.spill_capacity = spill_capacity
        if spill_capacity > 0:
            np_state = _bf16_np() if self.dtype.name == "bfloat16" \
                else np.dtype(self.dtype.name)
            self._spill_h = np.zeros((spill_capacity, hidden_dim), np_state)
            self._spill_c = np.zeros((spill_capacity, hidden_dim), np_state)
            self._spill_la = np.zeros((spill_capacity,), np.int32)
            self._spill_lr = np.zeros((spill_capacity,), np.float32)
        self._spill_slots: "OrderedDict[str, int]" = OrderedDict()
        self._spill_free: List[int] = list(range(spill_capacity))
        self._promote_fn = None  # jitted scatter, built on first promote
        # ---- counters (all under self._lock)
        self.evictions = 0        # HBM slots reclaimed (spilled or dropped)
        self.admissions = 0       # sessions granted an HBM slot on a miss
        self.hits = 0             # assign found the session resident
        self.misses = 0           # assign did not
        self.spills = 0           # sessions demoted into the host slab
        self.promotes = 0         # sessions promoted back, carry intact
        self.readmits = 0         # misses that found host-spilled state
        self.spill_evictions = 0  # slab-LRU drops (session state lost)
        self.imports = 0          # sessions migrated IN from another replica
        self.spill_sheds = 0      # slab rows dropped by pressure shedding

    def _device_zeros(self, shape, dtype):
        z = jnp.zeros(shape, dtype)
        return jax.device_put(z, self.device) if self.device is not None else z

    @property
    def pad_slot(self) -> int:
        """The scratch row index padding gathers/scatters target."""
        return self.capacity

    def __len__(self) -> int:
        with self._lock:
            return len(self._slots)

    def __contains__(self, session_id: str) -> bool:
        with self._lock:
            return session_id in self._slots

    def spilled(self, session_id: str) -> bool:
        with self._lock:
            return session_id in self._spill_slots

    # ------------------------------------------------------------ admission

    def assign(self, session_ids: Sequence[str]) -> Tuple[np.ndarray, np.ndarray]:
        """Map session ids to slot indices, admitting unknown sessions
        (evicting the LRU session when full — into the spill tier when one
        is configured). Returns (slots, fresh) where fresh[i] marks
        sessions that must start from zero state (never seen, or whose
        spilled state was dropped); a promoted session is NOT fresh — its
        carry is already back in its device row when this returns. Ids
        must be unique within one call — the batcher guarantees at most
        one request per session per batch.

        Serve-loop thread only: demotion reads and promotion scatters
        touch the device rows. In the pipelined server this is the STAGE
        half of the batch's cache interaction — it runs after the
        previous batch's dispatch-time commit on the same thread, so the
        slots it hands out gather that batch's committed (possibly
        still-executing) arrays.
        """
        if len(set(session_ids)) != len(session_ids):
            raise ValueError("duplicate session ids in one batch")
        slots = np.empty(len(session_ids), np.int32)
        fresh = np.zeros(len(session_ids), bool)
        demote: List[Tuple[str, int]] = []   # (sid, hbm slot) victims
        promote: List[Tuple[int, int]] = []  # (hbm slot, spill row)
        with self._lock:
            for i, sid in enumerate(session_ids):
                slot = self._slots.get(sid)
                if slot is None:
                    self.misses += 1
                    self.admissions += 1
                    if self._free:
                        slot = self._free.pop()
                    else:
                        # evict the least-recently-used session NOT part of
                        # this batch (batch members were just admitted to
                        # the back of the order, so the front is safe)
                        victim, slot = self._slots.popitem(last=False)
                        self.evictions += 1
                        if self.spill_capacity > 0:
                            demote.append((victim, slot))
                    row = self._spill_slots.pop(sid, None)
                    if row is not None:
                        # returning spilled session: carry comes back
                        self.readmits += 1
                        self.promotes += 1
                        promote.append((slot, row))
                    else:
                        fresh[i] = True
                else:
                    self.hits += 1
                self._slots[sid] = slot
                self._slots.move_to_end(sid)
                slots[i] = slot
        # Device IO OUTSIDE the lock: reset/evict/stats callers never wait
        # on a transfer. Safe because assign is single-threaded (serve
        # loop) and the demoted slots are re-gathered before any step runs.
        # Ordering when one batch both promotes and demotes:
        #   1. stage the promoted rows OUT of the slab (host copy) and free
        #      them — before any demotion writes, so a demotion may reuse a
        #      promoted row without clobbering data still to be lifted;
        #   2. demote: read the victims' device rows, write the slab;
        #   3. promote: scatter the staged rows into the device slots —
        #      after the demote read, since a victim's freed slot may be
        #      exactly where a promoted session lands.
        staged = self._stage_promotions(promote) if promote else None
        if promote:
            with self._lock:
                self._spill_free.extend(row for _, row in promote)
        if demote:
            self._demote(demote)
        if staged is not None:
            self._promote(promote, staged)
        return slots, fresh

    # ------------------------------------------------------ tier movement

    def _demote(self, victims: List[Tuple[str, int]]) -> None:
        """Copy the victims' device rows into the host slab — ONE
        vectorized gather + readback for the whole batch's evictions, not
        one transfer per session (the tiered-store rule: bytes cross the
        host boundary in slabs)."""
        idx = jnp.asarray(np.array([s for _, s in victims], np.int32))
        h_rows = np.asarray(jnp.take(self.h, idx, axis=0))
        c_rows = np.asarray(jnp.take(self.c, idx, axis=0))
        la_rows = np.asarray(jnp.take(self.last_action, idx, axis=0))
        lr_rows = np.asarray(jnp.take(self.last_reward, idx, axis=0))
        with self._lock:
            for j, (sid, _) in enumerate(victims):
                row = self._spill_slots.pop(sid, None)
                if row is None:
                    if self._spill_free:
                        row = self._spill_free.pop()
                    else:
                        # slab full: drop the LRU spilled session for good
                        _, row = self._spill_slots.popitem(last=False)
                        self.spill_evictions += 1
                self._spill_h[row] = h_rows[j]
                self._spill_c[row] = c_rows[j]
                self._spill_la[row] = la_rows[j]
                self._spill_lr[row] = lr_rows[j]
                self._spill_slots[sid] = row
                self._spill_slots.move_to_end(sid)
                self.spills += 1

    def _stage_promotions(self, moves: List[Tuple[int, int]]):
        """Host-side gather of the promoted sessions' slab rows, taken
        BEFORE any of this batch's demotions write the slab (numpy fancy
        indexing copies, so the rows are immediately reusable)."""
        # host-list -> index array: pure host work, no device handle in
        # sight — the serve-step rule's _stage* net is wider than this
        # r2d2: disable=blocking-host-sync-in-serve-step
        rows = np.array([r for _, r in moves], np.int64)
        return (self._spill_h[rows], self._spill_c[rows],
                self._spill_la[rows], self._spill_lr[rows])

    def _promote(self, moves: List[Tuple[int, int]], staged) -> None:
        """Scatter staged spill rows back into their new device slots: one
        H2D lift of the gathered host rows + one jitted scatter for the
        whole batch's promotions. The scatter donates the old stores
        (non-CPU) so XLA updates the rows in place — the same donation
        discipline as the serve step itself."""
        slots = np.array([s for s, _ in moves], np.int32)
        h_rows, c_rows, la_rows, lr_rows = map(jnp.asarray, staged)
        if self._promote_fn is None:
            donate = () if jax.default_backend() == "cpu" else (0, 1, 2, 3)

            def scatter(h, c, la, lr, slots_, rh, rc, rla, rlr):
                return (
                    h.at[slots_].set(rh),
                    c.at[slots_].set(rc),
                    la.at[slots_].set(rla),
                    lr.at[slots_].set(rlr),
                )

            # single-writer contract: the device stores (and this lazily
            # compiled scatter) are only ever touched by the thread driving
            # batches — the serve loop in production, the main thread in
            # warmup/tests, never both at once (warmup completes before
            # start()). Taking _lock here would put jit dispatch inside a
            # critical section for no real race.
            # r2d2: disable=cross-thread-unguarded-write
            self._promote_fn = jax.jit(scatter, donate_argnums=donate)
        # r2d2: disable=cross-thread-unguarded-write  (same single-writer contract)
        self.h, self.c, self.last_action, self.last_reward = self._promote_fn(
            self.h, self.c, self.last_action, self.last_reward,
            jnp.asarray(slots), h_rows, c_rows, la_rows, lr_rows,
        )

    def export_sessions(self) -> "OrderedDict[str, tuple]":
        """Drain every tracked session's carry to host memory for
        migration (replica drain/kill, serve/multi.py): resident rows come
        back in ONE vectorized D2H gather, spilled rows as host copies.
        Returns sid -> (h, c, last_action, last_reward) rows in the cache
        dtype verbatim, LRU-oldest first, so importing in order preserves
        recency on the target. Call ONLY with this cache's serve loop
        stopped — the export reads the device rows (single-writer
        contract, same as _demote)."""
        with self._lock:
            resident = list(self._slots.items())
            spilled = list(self._spill_slots.items())
        out: "OrderedDict[str, tuple]" = OrderedDict()
        # spilled sessions are by construction colder than resident ones:
        # emit them first so the LRU-oldest-first ordering holds fleetwide
        for sid, row in spilled:
            out[sid] = (self._spill_h[row].copy(), self._spill_c[row].copy(),
                        self._spill_la[row].copy(), self._spill_lr[row].copy())
        if resident:
            idx = jnp.asarray(np.array([s for _, s in resident], np.int32))
            h_rows = np.asarray(jnp.take(self.h, idx, axis=0))
            c_rows = np.asarray(jnp.take(self.c, idx, axis=0))
            la_rows = np.asarray(jnp.take(self.last_action, idx, axis=0))
            lr_rows = np.asarray(jnp.take(self.last_reward, idx, axis=0))
            for j, (sid, _) in enumerate(resident):
                out[sid] = (h_rows[j], c_rows[j], la_rows[j], lr_rows[j])
        return out

    def import_spilled(self, session_id: str, h, c, last_action,
                       last_reward) -> bool:
        """Admit a migrated session's carry into THIS cache's host slab
        (bit-exact: rows are stored in the cache dtype verbatim, so the
        session's next request promotes exactly the carry it left the dead
        replica with). Returns False when there is no slab, no free row
        (a migrant never evicts a session already here), or the session is
        already tracked."""
        with self._lock:
            if self.spill_capacity == 0:
                return False
            if session_id in self._slots or session_id in self._spill_slots:
                return False
            if not self._spill_free:
                return False
            row = self._spill_free.pop()
            self._spill_h[row] = h
            self._spill_c[row] = c
            self._spill_la[row] = last_action
            self._spill_lr[row] = last_reward
            self._spill_slots[session_id] = row
            self._spill_slots.move_to_end(session_id)
            self.imports += 1
            return True

    def shed_spill(self, keep_fraction: float) -> int:
        """Pressure-shed the spill slab down to `keep_fraction` of its
        capacity, dropping the LRU spilled sessions for good (they restart
        fresh if they return) — the degrade ladder's host-memory relief
        valve. Returns the number of sessions dropped."""
        target = int(self.spill_capacity * max(min(keep_fraction, 1.0), 0.0))
        dropped = 0
        with self._lock:
            while len(self._spill_slots) > target:
                _, row = self._spill_slots.popitem(last=False)
                self._spill_free.append(row)
                self.spill_evictions += 1
                self.spill_sheds += 1
                dropped += 1
        return dropped

    def reset(self, session_id: str) -> None:
        """Forget a session's state ENTIRELY — resident slot and any
        spilled copy: the next request re-runs admission-fresh semantics
        via the reset flag, so dropping the mappings is enough (and
        cheaper than touching device rows from a foreign thread). Without
        the spill drop, a promoted stale carry would resurrect the
        session the client explicitly reset."""
        self.evict(session_id)

    def evict(self, session_id: str) -> bool:
        """Explicitly free a session's resources (client disconnect):
        resident slot AND spill row. Unlike LRU pressure this does NOT
        demote — a disconnected session has no future request to promote
        for. Returns True if anything was freed."""
        with self._lock:
            slot = self._slots.pop(session_id, None)
            if slot is not None:
                self._free.append(slot)
            row = self._spill_slots.pop(session_id, None)
            if row is not None:
                self._spill_free.append(row)
            return slot is not None or row is not None

    # ------------------------------------------------------------ device IO

    def arrays(self):
        """The device arrays the jitted serve step reads and rewrites."""
        return self.h, self.c, self.last_action, self.last_reward

    def commit(self, h, c, last_action, last_reward) -> None:
        """Install the serve step's updated arrays (serve-loop thread
        only). The old arrays may have been donated into the step.
        Single-writer contract: only the batch-driving thread (serve loop,
        or main during warmup — never concurrently) calls commit, so these
        swaps deliberately take no lock; guarding them would serialize the
        serve loop against stats() for device-array pointer writes that
        nothing else mutates. In the pipelined server this is the
        DISPATCH half: it runs right after the async step dispatch and
        BEFORE the next batch stages, with the arrays still futures — the
        device stream orders the in-place update, the completion worker
        never touches these references."""
        # r2d2: disable=cross-thread-unguarded-write  (single-writer contract above)
        self.h, self.c = h, c
        # r2d2: disable=cross-thread-unguarded-write  (single-writer contract above)
        self.last_action, self.last_reward = last_action, last_reward

    @property
    def session_carry_bytes(self) -> int:
        """Device bytes of recurrent state per session: h + c rows."""
        return 2 * self.hidden_dim * self.dtype.itemsize

    def stats(self) -> dict:
        with self._lock:
            lookups = self.hits + self.misses
            return {
                "cache_sessions": len(self._slots),
                "cache_capacity": self.capacity,
                "cache_evictions": self.evictions,
                "cache_admissions": self.admissions,
                "cache_hits": self.hits,
                "cache_misses": self.misses,
                "cache_hit_rate": self.hits / lookups if lookups else 0.0,
                "cache_readmits": self.readmits,
                "cache_spills": self.spills,
                "cache_promotes": self.promotes,
                "cache_spill_evictions": self.spill_evictions,
                "cache_imports": self.imports,
                "cache_spill_sheds": self.spill_sheds,
                "spill_sessions": len(self._spill_slots),
                "spill_capacity": self.spill_capacity,
                "cache_dtype": self.dtype.name,
                "session_carry_bytes": self.session_carry_bytes,
            }


def _bf16_np():
    """numpy-side bfloat16 (ml_dtypes, a jax dependency) — the same byte
    layout config.state_dtype hands every replay plane's host slab."""
    import ml_dtypes

    return np.dtype(ml_dtypes.bfloat16)
