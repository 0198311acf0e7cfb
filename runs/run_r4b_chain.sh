#!/bin/bash
# Round-4 chain B: after chain A drains.
#  1. MFU + LRU-breakdown measurements (verdict items 6 & 8) while the
#     chip is otherwise idle — minutes each.
#  2. The long-context stabilization attack (verdict item 1 follow-up):
#     BOTH round-3 long-context runs (LSTM chain F, LRU chain A) climbed
#     clearly above chance (~-0.19 vs random ~-0.9) then REGRESSED under
#     constant lr. Retry the LRU run with lr_schedule=cosine (decay to
#     0.1x by 36k) — the single-variable change aimed at the late-run
#     instability; n=64 eval for tighter error bars. If the final
#     checkpoints still regress below -0.35, a second arm adds the
#     slower target sync (500).
#  3. The 8x8 procmaze confirmation eval at n=256 (verdict item 5).
#  4. The procmaze ladder with transfer (verdict item 4): measure the
#     12x12 random baseline, warm-start from the solved 8x8 policy
#     (runs/procmaze_small step_30000, the curriculum pattern that
#     cracked memory catch), train 30k more, eval the series. If the
#     final eval clears the measured baseline, climb to 16x16 the same
#     way.
cd /root/repo
while ! grep -q R4A_CHAIN_ALL_DONE runs/r4a_chain.log 2>/dev/null; do sleep 60; done

run_with_retry() {
  local tries=0
  "$@"
  local rc=$?
  while [ $rc -eq 86 ] && [ $tries -lt 3 ]; do
    tries=$((tries+1)); echo "=== stall 86; resume (try $tries) ==="
    "$@" --resume; rc=$?
  done
  return $rc
}

last_eval() { python - "$1" <<'PY'
import json, sys
rows = [json.loads(l) for l in open(sys.argv[1]) if l.strip()]
print(rows[-1]["mean_reward"] if rows else -9)
PY
}

# runs/measure_mfu.py (which wrote runs/mfu.json here) left at PR 51:
# benchmark/flops.py owns the FLOP count, the benchmark's mfu reader the rate
python runs/bench_lru_breakdown.py --out runs/lru_breakdown.jsonl
echo "=== LRU_BREAKDOWN EXIT: $? ==="
python runs/bench_core_unroll.py --out runs/core_unroll_r4.jsonl
echo "=== CORE_UNROLL_R4 EXIT: $? ==="

run_with_retry python examples/long_context_demo.py --out runs/long_context_mid_lru2 \
  --env memory_catch:10:12 --steps 36000 --eval-episodes 4 \
  --set obs_shape=26,26,1 --set encoder=impala --set impala_channels=8,16 \
  --set hidden_dim=128 --set max_episode_steps=288 \
  --set learning_steps=256 --set block_length=512 \
  --set buffer_capacity=102400 --set learning_starts=40000 \
  --set recurrent_core=lru --set lr_schedule=cosine
echo "=== LONG_CONTEXT_MID_LRU2 EXIT: $? ==="
EV=$(last_eval runs/long_context_mid_lru2/eval.jsonl)
echo "=== LONG_CONTEXT_MID_LRU2 EVAL: $EV ==="
if ! python -c "import sys; sys.exit(0 if float('$EV') >= -0.35 else 1)"; then
  run_with_retry python examples/long_context_demo.py --out runs/long_context_mid_lru3 \
    --env memory_catch:10:12 --steps 36000 --eval-episodes 4 \
    --set obs_shape=26,26,1 --set encoder=impala --set impala_channels=8,16 \
    --set hidden_dim=128 --set max_episode_steps=288 \
    --set learning_steps=256 --set block_length=512 \
    --set buffer_capacity=102400 --set learning_starts=40000 \
    --set recurrent_core=lru --set lr_schedule=cosine \
    --set target_net_update_interval=500
  echo "=== LONG_CONTEXT_MID_LRU3 EXIT: $? ==="
fi

python -m r2d2_tpu.evaluate --preset procgen_impala --env procmaze_shaped:8 --episodes 16 \
  --out runs/procmaze_small/eval_n256.jsonl --plot runs/procmaze_small/curve_n256.jpg \
  --set checkpoint_dir=runs/procmaze_small/ckpt
echo "=== PROCMAZE8_N256 EXIT: $? ==="

mkdir -p runs/procmaze12_warm/ckpt
python runs/measure_random_baseline.py --env procmaze_shaped:12 --episodes 2048 \
  --out runs/procmaze12_warm/baseline.json
echo "=== PROCMAZE12_BASELINE EXIT: $? ==="
if [ ! -d runs/procmaze12_warm/ckpt/step_30000 ]; then
  cp -r runs/procmaze_small/ckpt/step_30000 runs/procmaze12_warm/ckpt/step_30000
fi
run_with_retry python -m r2d2_tpu.train --preset procgen_impala --env procmaze_shaped:12 \
  --mode fused --steps 60000 --updates-per-dispatch 16 --resume \
  --set checkpoint_dir=runs/procmaze12_warm/ckpt \
  --set metrics_path=runs/procmaze12_warm/metrics.jsonl \
  --set buffer_capacity=200000 --set learning_starts=30000 \
  --set samples_per_insert=15.0 --set save_interval=3750 \
  --set target_net_update_interval=500 --set forward_steps=20 --set num_actors=16
echo "=== PROCMAZE12 TRAIN EXIT: $? ==="
python -m r2d2_tpu.evaluate --preset procgen_impala --env procmaze_shaped:12 --episodes 4 \
  --out runs/procmaze12_warm/eval.jsonl --plot runs/procmaze12_warm/curve.jpg \
  --set checkpoint_dir=runs/procmaze12_warm/ckpt
echo "=== PROCMAZE12 EVAL EXIT: $? ==="

EV12=$(last_eval runs/procmaze12_warm/eval.jsonl)
BASE12=$(python -c "import json; print(json.load(open('runs/procmaze12_warm/baseline.json'))['random_mean_reward'])" 2>/dev/null || echo 9)
echo "=== PROCMAZE12 EVAL: $EV12 BASELINE: $BASE12 ==="
if python -c "import sys; sys.exit(0 if float('$EV12') > float('$BASE12') + 0.05 else 1)"; then
  mkdir -p runs/procmaze16_warm/ckpt
  python runs/measure_random_baseline.py --env procmaze_shaped:16 --episodes 2048 \
    --out runs/procmaze16_warm/baseline.json
  if [ ! -d runs/procmaze16_warm/ckpt/step_60000 ]; then
    cp -r runs/procmaze12_warm/ckpt/step_60000 runs/procmaze16_warm/ckpt/step_60000
  fi
  run_with_retry python -m r2d2_tpu.train --preset procgen_impala --env procmaze_shaped:16 \
    --mode fused --steps 90000 --updates-per-dispatch 16 --resume \
    --set checkpoint_dir=runs/procmaze16_warm/ckpt \
    --set metrics_path=runs/procmaze16_warm/metrics.jsonl \
    --set buffer_capacity=200000 --set learning_starts=30000 \
    --set samples_per_insert=15.0 --set save_interval=3750 \
    --set target_net_update_interval=500 --set forward_steps=20 --set num_actors=16
  echo "=== PROCMAZE16 TRAIN EXIT: $? ==="
  python -m r2d2_tpu.evaluate --preset procgen_impala --env procmaze_shaped:16 --episodes 4 \
    --out runs/procmaze16_warm/eval.jsonl --plot runs/procmaze16_warm/curve.jpg \
    --set checkpoint_dir=runs/procmaze16_warm/ckpt
  echo "=== PROCMAZE16 EVAL EXIT: $? ==="
fi

echo R4B_CHAIN_ALL_DONE
