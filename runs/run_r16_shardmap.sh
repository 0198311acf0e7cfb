#!/bin/bash
# Round-16 manual-partitioning chain: the measurement side of the
# shard_map tp x fsdp PR. Four rungs, each one JSON line appended to
# runs/bench_shardmap_r16.jsonl:
#
#   1. shardmap gate — the manual-partition parity suites (tp2 x fsdp2 x
#      dp2 step vs the unsharded reference at fp32 AND bf16; ZeRO-2
#      moment shards + update equality vs replicated Adam; resume across
#      a CHANGED tp x fsdp layout) and the static analysis CLI (the
#      shard_mapped step is traced at fp32+bf16; raw shard_map imports
#      outside parallel/jax_compat.py are an AST error). A parity
#      regression aborts the chain: a wrong collective's speedup is
#      noise.
#   2. breakdown — per-phase step timing with the vs_r14 column
#      (per-phase deltas against BENCH_r14.json) and the
#      largest-model-that-fits table per mesh shape (model_fits).
#   3. breakdown (grown presets) — the same timing at --model-preset
#      wide/deep: the "grow the brain" rung. TPU-gated: on CPU the
#      grown shapes crawl and the timings say nothing (rung 2's
#      model_fits rows already size every preset analytically on any
#      host).
#   4. tp x fsdp smoke — one short train.py run on the dp2 x tp2 x
#      fsdp2 cell over faked host devices (the exact mesh shape PR 14's
#      validate() used to block), then resume under a DIFFERENT
#      tp x fsdp layout: orbax restores onto the new layout's shardings
#      through the sharded restore template.
#
# PRE-REGISTERED read: rung 2's model_fits.largest_fit growing
# monotonically with tp x fsdp (more shards -> bigger largest model)
# and rung 4's resume crossing the layout change with
# training continuing from the saved step — the BENCH_r16 headline.
cd /root/repo

. runs/lib.sh

OUT=runs/bench_shardmap_r16.jsonl
: > "$OUT"

echo "=== RUNG 1: shardmap gate ==="
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
python -m pytest tests/test_sharding_map.py tests/test_pallas_lstm.py \
  tests/test_analysis.py -q -p no:cacheprovider
RC=$?
echo "=== SHARDMAP_PYTEST EXIT: $RC ==="
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
python -m r2d2_tpu.analysis.cli --jaxpr
RCA=$?
echo "=== ANALYSIS EXIT: $RCA ==="
if [ $RC -ne 0 ] || [ $RCA -ne 0 ]; then
  echo "=== ABORT: shardmap gate failed; bench rows would be noise ==="
  exit 1
fi

echo "=== RUNG 2: breakdown (vs_r14 + model_fits) ==="
python bench.py --mode breakdown --batch 8 | tee -a "$OUT"
echo "=== BREAKDOWN EXIT: $? ==="

if python -c 'import jax, sys; sys.exit(0 if jax.default_backend() == "tpu" else 1)'; then
  echo "=== RUNG 3: breakdown, grown model presets ==="
  python bench.py --mode breakdown --batch 8 --model-preset wide | tee -a "$OUT"
  echo "=== BREAKDOWN_WIDE EXIT: $? ==="
  python bench.py --mode breakdown --batch 8 --model-preset deep | tee -a "$OUT"
  echo "=== BREAKDOWN_DEEP EXIT: $? ==="
else
  echo "=== RUNG 3 SKIPPED: no TPU (grown presets crawl on CPU) ==="
fi

echo "=== RUNG 4: tp x fsdp smoke (save/resume across the layout) ==="
CKPT=runs/r16_shardmap_smoke
rm -rf "$CKPT"
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
python -m r2d2_tpu.train --preset tiny_test --env catch --mode inline \
  --dp 2 --tp 2 --fsdp 2 --steps 30 \
  --set checkpoint_dir="$CKPT" --set save_interval=15
echo "=== TPFSDP_TRAIN EXIT: $? ==="
# resume under a DIFFERENT tp x fsdp layout: the sharded restore
# template places every leaf per the NEW mesh, so the step count
# continues and no TopologyMismatch fires
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
python -m r2d2_tpu.train --preset tiny_test --env catch --mode inline \
  --dp 4 --tp 1 --fsdp 2 --steps 60 --resume \
  --set checkpoint_dir="$CKPT" --set save_interval=15
echo "=== TPFSDP_RESUME EXIT: $? ==="

echo R16_SHARDMAP_ALL_DONE
