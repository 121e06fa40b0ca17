#!/usr/bin/env bash
# Multi-host TPU-pod example: the TPU-native analogue of the reference's
# Slurm launcher (examples/slurm_example.sub:70-118, srun --mpi=pmix over
# 128 tasks/node).
#
# On a TPU pod there is no MPI: one framework process runs per TPU-VM
# host, jax.distributed supplies rank/world (the JaxProcessBackend
# bootstraps it when --comm jax is selected), host-level collectives ride
# ICI/DCN, and per-host CPU parallelism comes from the preprocess
# executor's local worker pool. Bulk data still moves through a shared
# filesystem (GCS fuse or NFS), exactly like the reference.
#
# Run this script ON EVERY HOST of the pod slice, e.g.:
#
#   gcloud compute tpus tpu-vm ssh "${TPU_NAME}" --worker=all \
#     --command="bash lddl_tpu/examples/tpu_pod_example.sh gs-mounted/workdir"
#
# jax.distributed auto-detects the pod topology from the TPU metadata
# server; on CPU clusters set LDDL_COORDINATOR_ADDRESS /
# LDDL_NUM_PROCESSES / LDDL_PROCESS_ID instead (see
# lddl_tpu/comm/backend.py:ensure_jax_distributed).

set -euo pipefail

readonly repo="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
readonly workdir="${1:?usage: tpu_pod_example.sh <shared-workdir>}"
export PYTHONPATH="${repo}:${PYTHONPATH:-}"

readonly bin_size=64
readonly target_seq_length=512
# One output shard per (data-parallel rank x loader stream) is the usual
# choice; 4096 matches the reference example's scale.
readonly num_blocks=4096
readonly num_shards=4096

# 1. Download + extract Wikipedia on host 0 only (shared filesystem).
#    Other hosts wait for the sentinel. TPU_WORKER_ID is set by the TPU-VM
#    runtime on every host of a pod slice.
if [[ "${TPU_WORKER_ID:-0}" == "0" ]]; then
  python -m lddl_tpu.cli download_wikipedia --outdir "${workdir}/wikipedia"
  # A BERT WordPiece vocab; the NVIDIA Deep Learning Examples copy is the
  # one the reference example fetches too (local_example.sh:44-48).
  wget -O "${workdir}/vocab.txt" \
    https://raw.githubusercontent.com/NVIDIA/DeepLearningExamples/master/PyTorch/LanguageModeling/BERT/vocab/vocab
  touch "${workdir}/wikipedia/.done"
fi
until [[ -f "${workdir}/wikipedia/.done" ]]; do sleep 10; done

# 2. Preprocess across all hosts: rank-strided partition ownership via
#    --comm jax; each host additionally fans out over its local cores.
python -m lddl_tpu.cli preprocess_bert_pretrain \
  --comm jax \
  --wikipedia "${workdir}/wikipedia/source" \
  --sink "${workdir}/pretrain" \
  --vocab-file "${workdir}/vocab.txt" \
  --target-seq-length ${target_seq_length} \
  --num-blocks ${num_blocks} \
  --bin-size ${bin_size} \
  --masking

# 3. Balance across all hosts (same modulo-ownership parallelism as the
#    reference's MPI balancer, collectives over ICI/DCN).
python -m lddl_tpu.cli balance_shards \
  --comm jax \
  --indir "${workdir}/pretrain" \
  --outdir "${workdir}/balanced" \
  --num-shards ${num_shards}

# 4. Mock training: every host consumes its dp shard of the global batch
#    once and dumps its sequence lengths (benchmarks/validate_binning.py
#    --in-dir "${workdir}/seqlens" checks them once every host has written).
python "${repo}/benchmarks/train_bench.py" \
  --path "${workdir}/balanced" \
  --vocab-file "${workdir}/vocab.txt" \
  --bin-size ${bin_size} \
  --max-seq-length ${target_seq_length} \
  --masking static \
  --seq-len-dir "${workdir}/seqlens"

# 5. Train: the product's loop on the same shards; every host feeds its
#    dp shard of the global batch, the mesh spans all chips of the slice.
python -m lddl_tpu.training.pretrain \
  --comm jax \
  --path "${workdir}/balanced" \
  --vocab-file "${workdir}/vocab.txt" \
  --model base \
  --bin-size ${bin_size} \
  --max-seq-length ${target_seq_length} \
  --masking static \
  --checkpoint-dir "${workdir}/checkpoints"
