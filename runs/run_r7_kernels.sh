#!/bin/bash
# Round-7 kernel-pass bench chain: the measurement side of the raw-speed
# PR (fused Pallas sequence kernel, fused act tail, int8 serve arm).
# Four rungs, each one JSON line appended to runs/bench_kernels_r7.jsonl:
#
#   1. kernel-plane gate  — `pytest -m kernels` (interpret-mode parity +
#      launch counts) plus the static analysis CLI. A parity or
#      launch-count regression aborts the chain: a wrong kernel's
#      throughput number is noise.
#   2. breakdown          — per-phase step timing (unroll / head /
#      loss+grad / optimizer), the denominator map kernel rows cite.
#   3. learner headline   — best-of-matrix.
#   4. serve 3-arm        — fp32 -> bf16 -> int8; the serve_int8 sub-row
#      carries vs_fp32 and the q_drift_vs_fp32 bounded-parity column.
#
# PRE-REGISTERED read: rung 4's q_drift_vs_fp32 staying ~1e-2 of the
# Q scale is the int8 arm's bounded-parity claim at full network size.
cd /root/repo

. runs/lib.sh

OUT=runs/bench_kernels_r7.jsonl
: > "$OUT"

echo "=== RUNG 1: kernel-plane gate ==="
python -m pytest tests/ -q -m kernels -p no:cacheprovider
RC=$?
echo "=== KERNELS_PYTEST EXIT: $RC ==="
python -m r2d2_tpu.analysis.cli --jaxpr
RCA=$?
echo "=== ANALYSIS EXIT: $RCA ==="
if [ $RC -ne 0 ] || [ $RCA -ne 0 ]; then
  echo "=== ABORT: kernel gate failed; bench rows would be noise ==="
  exit 1
fi

echo "=== RUNG 2: per-phase breakdown ==="
python bench.py --mode breakdown | tee -a "$OUT"
echo "=== BREAKDOWN EXIT: $? ==="

echo "=== RUNG 3: learner headline ==="
python bench.py --mode learner --precision both | tee -a "$OUT"
echo "=== LEARNER EXIT: $? ==="

echo "=== RUNG 4: serve three-arm (fp32/bf16/int8) ==="
python bench.py --mode serve --precision both | tee -a "$OUT"
echo "=== SERVE EXIT: $? ==="

echo R7_KERNELS_ALL_DONE
