#!/usr/bin/env bash
# End-to-end long-context example: corpus -> packed preprocess (8k-token
# document-packed id rows) -> balance -> BERT pretraining with flash
# attention on those rows. No reference counterpart — the reference's
# data path tops out at seq-512 NSP pairs; this is the workflow behind
# the s=8k-32k single-chip and ring-attention capabilities (the
# benchmark's cell bert-base-pos8k.packed-s8k-longdoc measures it on a
# v5e: PERF.md).
#
# Usage:
#   bash examples/long_context_example.sh [workdir]
#
# Offline by default (synthetic corpus + the repo's committed vocab).
# For real data, point --source at any one-document-per-line corpus
# (e.g. download_wikipedia output).

set -euo pipefail

readonly repo="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
readonly workdir="${1:-$(mktemp -d -t lddl_tpu_longctx_XXXX)}"
export PYTHONPATH="${repo}:${PYTHONPATH:-}"

readonly target_seq_length=8192
readonly bin_size=2048
readonly vocab="${repo}/benchmarks/assets/bench_vocab_30522.txt"

echo "== workdir: ${workdir}"

echo '== 1. corpus (synthetic stand-in for a real document corpus)'
python - "${workdir}" <<'EOF'
import sys
from lddl_tpu.core.synth import write_corpus
print('MB written:', round(write_corpus(sys.argv[1] + '/source', 8,
                                        num_shards=4, seed=7), 1))
EOF

echo '== 2. packed preprocess (greedy document packing to 8192 tokens)'
LDDL_PROGRESS=stderr python -m lddl_tpu.cli preprocess_packed_pretrain \
  --source "${workdir}/source" \
  --sink "${workdir}/packed" \
  --vocab-file "${vocab}" \
  --target-seq-length "${target_seq_length}" \
  --bin-size "${bin_size}" \
  --num-workers 2

echo '== 3. balance'
python -m lddl_tpu.cli balance_shards \
  --indir "${workdir}/packed" \
  --outdir "${workdir}/balanced" \
  --num-shards 4

echo '== 4. long-context pretraining (flash attention, masked-only head)'
# On a real chip drop --model tiny and raise --steps; batch 1 x 8192
# tokens trains BERT-base on a single 16 GB v5e (PERF.md long-context
# section). --sp N sequence-shards over N chips via ring_flash.
python -m lddl_tpu.cli pretrain_bert \
  --path "${workdir}/balanced" \
  --vocab-file "${vocab}" \
  --data-format packed \
  --model tiny \
  --attention flash \
  --max-seq-length "${target_seq_length}" \
  --bin-size "${bin_size}" \
  --batch-size 1 \
  --steps 3 --warmup-steps 1 --log-every 1 \
  --max-predictions 1359 \
  --checkpoint-dir "${workdir}/ckpt"

echo "== done; artifacts under ${workdir}"
