#!/usr/bin/env bash
# Example smoke runs (reference run_ci_examples.sh runs the dataset and
# torch_dataset __main__ smoke tests; here the end-to-end DLRM trainer on a
# tiny workload, CPU backend, plus the multi-chip dry run).
set -euo pipefail
cd "$(dirname "$0")"
export JAX_PLATFORMS=cpu
python examples/train_dlrm.py --smoke
python examples/train_dlrm.py --smoke --loader resident --model transformer
# 2 devices: one full butterfly round + the bf16 wire path at a fraction
# of the 8-device cost (8 virtual devices on shared cores is ~6 min).
XLA_FLAGS="--xla_force_host_platform_device_count=2" \
    python examples/train_dlrm.py --smoke --grad-reduce adasum --grad-bf16
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python examples/train_long_context.py --dp 2 --sp 4 --steps 8 \
    --seq-len 256
python examples/train_lfm2_moe.py
python examples/train_dlrm_multirank.py --num-trainers 2 \
    --num-rows 50000 --num-files 4 --batch-size 5000 --epochs 2
python -m ray_shuffling_data_loader_tpu.dataset
python -m ray_shuffling_data_loader_tpu.torch_dataset
python examples/train_dlrm_pod.py --simulate-pod 2 \
    --num-rows 30000 --num-files 8 --batch-size 3000 --epochs 1 \
    --rendezvous-dir "$(mktemp -d)"
python __graft_entry__.py 8
