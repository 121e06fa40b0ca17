#!/usr/bin/env bash
# End-to-end local example: corpus -> preprocess -> balance -> loader
# pass + binning validation -> a few steps of the trainer, on one machine
# with zero network access.
#
# Capability parity with the reference's examples/local_example.sh:36-92
# (download -> mpirun preprocess -> balance -> torch.distributed mock
# train), re-expressed for the TPU stack: no MPI/docker — the preprocess
# executor fans out over local cores by itself, and the train step is the
# product's own loop (pretrain_bert) over the local device(s).
#
# Usage:
#   bash examples/local_example.sh [workdir]
#
# By default a small synthetic corpus is generated so the example runs
# offline and in seconds. To run on real Wikipedia instead, replace the
# "generate corpus" step with:
#   python -m lddl_tpu.cli download_wikipedia --outdir "${workdir}/wikipedia"
# and point --source at "${workdir}/wikipedia/source", with a real BERT
# vocab file.

set -euo pipefail

readonly repo="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
readonly workdir="${1:-$(mktemp -d -t lddl_tpu_example_XXXX)}"
# Append (never overwrite) PYTHONPATH: the environment may already use it.
export PYTHONPATH="${repo}:${PYTHONPATH:-}"

readonly bin_size=64
readonly target_seq_length=512
readonly num_blocks=8
readonly num_shards=8
readonly batch_size=8

echo "== workdir: ${workdir}"
mkdir -p "${workdir}"

echo '== 1/6 generate a synthetic one-document-per-line corpus + vocab'
python - "$workdir" <<'EOF'
import sys, os
workdir = sys.argv[1]
from lddl_tpu.core.synth import write_corpus
mb = write_corpus(os.path.join(workdir, 'source'), 2, num_shards=4,
                  seed=1234)
print(f'generated {mb:.1f} MB under {workdir}/source')
EOF
cp "${repo}/benchmarks/assets/bench_vocab_30522.txt" "${workdir}/vocab.txt"

echo '== 2/6 preprocess (static masking + sequence binning)'
python -m lddl_tpu.cli preprocess_bert_pretrain \
  --source "${workdir}/source" \
  --sink "${workdir}/pretrain" \
  --vocab-file "${workdir}/vocab.txt" \
  --target-seq-length ${target_seq_length} \
  --num-blocks ${num_blocks} \
  --bin-size ${bin_size} \
  --masking

echo '== 3/6 balance the binned shards'
python -m lddl_tpu.cli balance_shards \
  --indir "${workdir}/pretrain" \
  --outdir "${workdir}/balanced" \
  --num-shards ${num_shards}

echo '== 4/6 mock training: one pass of the loader, sequence lengths dumped'
python "${repo}/benchmarks/train_bench.py" \
  --path "${workdir}/balanced" \
  --vocab-file "${workdir}/vocab.txt" \
  --batch-size ${batch_size} \
  --bin-size ${bin_size} \
  --max-seq-length ${target_seq_length} \
  --masking static \
  --iters-per-epoch 8 --warmup 2 --log-freq 4 \
  --seq-len-dir "${workdir}/seqlens"

echo '== 5/6 validate the binning contract from the run dumps'
python "${repo}/benchmarks/validate_binning.py" \
  --in-dir "${workdir}/seqlens" \
  --bin-size ${bin_size}

echo '== 6/6 train: the same shards through the jitted train step'
python -m lddl_tpu.training.pretrain \
  --path "${workdir}/balanced" \
  --vocab-file "${workdir}/vocab.txt" \
  --model tiny \
  --batch-size ${batch_size} \
  --bin-size ${bin_size} \
  --max-seq-length ${target_seq_length} \
  --masking static \
  --steps 8 --warmup-steps 2 --log-every 4

echo "== done; artifacts in ${workdir}"
