#!/bin/bash
# Round-9 device-priority-plane bench chain: the measurement side of the
# priority-plane PR (HBM sum tree, in-jit sampling + write-back, N×K
# superstep). Four rungs, each one JSON line appended to
# runs/bench_priority_r9.jsonl:
#
#   1. priority-plane gate — the sum-tree three-way parity + superstep
#      equivalence tests (tests/test_sum_tree.py, tests/test_superstep.py)
#      plus the static analysis CLI (the superstep jaxpr is traced at
#      fp32 AND bf16 by scan_entry_points). A parity or equivalence
#      regression aborts the chain: a wrong tree's throughput is noise.
#   2. breakdown          — per-phase step timing, now carrying the
#      host_ms_per_update pair: the host-thread cost of the priority
#      plane per update under priority_plane=host (numpy sample +
#      write-back on the critical path) vs =device (dispatch-only).
#   3. learner headline   — best-of-matrix, unchanged machinery: the
#      synthetic-feed ceiling the system rows are read against.
#   4. system A/B         — the full system (concurrent on-device
#      collection + learning) three ways: priority_plane=host (the
#      per-update host fence), =device N=1 (fence in-jit), =device N=4
#      (host re-enters every 64 updates). Each row carries
#      priority_plane/superstep_dispatches.
#
# PRE-REGISTERED read: rung 4's device rows beating its host row is the
# tentpole's claim on real hardware, and the device N=4 row passing
# rung 3's synthetic-feed learner rate (which pays no replay fence at
# all) is the headline read.
# Rung 2's host_ms_per_update["priority_plane=device"] collapsing to
# dispatch cost (~0.1ms-class vs the host arm's tree walk) is the
# mechanism check behind that read.
cd /root/repo

. runs/lib.sh

OUT=runs/bench_priority_r9.jsonl
: > "$OUT"

echo "=== RUNG 1: priority-plane gate ==="
python -m pytest tests/test_sum_tree.py tests/test_superstep.py -q -p no:cacheprovider
RC=$?
echo "=== PRIORITY_PYTEST EXIT: $RC ==="
python -m r2d2_tpu.analysis.cli --jaxpr
RCA=$?
echo "=== ANALYSIS EXIT: $RCA ==="
if [ $RC -ne 0 ] || [ $RCA -ne 0 ]; then
  echo "=== ABORT: priority gate failed; bench rows would be noise ==="
  exit 1
fi

echo "=== RUNG 2: per-phase breakdown (host_ms_per_update pair) ==="
python bench.py --mode breakdown | tee -a "$OUT"
echo "=== BREAKDOWN EXIT: $? ==="

echo "=== RUNG 3: learner headline ==="
python bench.py --mode learner --precision both | tee -a "$OUT"
echo "=== LEARNER EXIT: $? ==="

echo "=== RUNG 4: system A/B (host fence vs in-jit tree) ==="
python bench.py --mode system --priority-plane host | tee -a "$OUT"
echo "=== SYSTEM_HOST EXIT: $? ==="
python bench.py --mode system --priority-plane device | tee -a "$OUT"
echo "=== SYSTEM_DEVICE_N1 EXIT: $? ==="
python bench.py --mode system --priority-plane device --superstep 4 | tee -a "$OUT"
echo "=== SYSTEM_DEVICE_N4 EXIT: $? ==="

echo R9_PRIORITY_ALL_DONE
