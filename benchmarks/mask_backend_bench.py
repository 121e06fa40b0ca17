"""Host vs device MLM-masking on the attached accelerator: parity + timing.

Produces the evidence PERF.md's device-masking claims rest on, as three
JSON lines (tee to ``benchmarks/results/mask_backend_<chip>.txt``):

  1. ``link``: measured host->device and device->host bandwidth of the
     attached chip;
  2. ``parity``: the full fast-engine preprocess run twice on the same
     corpus — ``--mask-backend host`` vs ``device`` — asserting the
     non-masking columns are byte-identical and the device-masked rows
     satisfy the masking invariants (positions strictly inside rows,
     k = max(1, round(len*ratio)) per row, labels = original tokens);
  3. ``timing``: wall-clock of the host path (assemble + vectorized
     Philox masking) vs the device path (fused gather+mask kernel,
     including transfers, post-compile) over a partition-sized batch
     sweep, with the implied winner per size.

Usage: python benchmarks/mask_backend_bench.py [--rows 2048 8192 32768]
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_VOCAB = os.path.join(os.path.dirname(os.path.abspath(__file__)), 'assets',
                      'bench_vocab_30522.txt')
SEQ_LEN = 128
RATIO = 0.15


def measure_link(mb=4):
  import jax
  x = np.zeros((mb * 1024 * 1024 // 4,), np.int32)
  d = jax.device_put(x)
  d.block_until_ready()  # warm connection + allocator
  t0 = time.perf_counter()
  d = jax.device_put(x)
  d.block_until_ready()
  up = x.nbytes / (time.perf_counter() - t0) / 1e6
  t0 = time.perf_counter()
  np.asarray(d)
  down = x.nbytes / (time.perf_counter() - t0) / 1e6
  return {
      'metric': 'link',
      'device': jax.devices()[0].device_kind,
      'host_to_device_mb_per_s': round(up, 1),
      'device_to_host_mb_per_s': round(down, 1),
  }


def check_parity(corpus_mb=2):
  """Full preprocess under both backends; non-mask columns must match."""
  import pyarrow.parquet as pq

  from lddl_tpu.core.synth import write_corpus
  from lddl_tpu.core.utils import get_all_parquets_under
  from lddl_tpu.pipeline.executor import Executor
  from lddl_tpu.preprocess.bert import BertPretrainConfig, run
  from lddl_tpu.preprocess.readers import read_corpus

  work = tempfile.mkdtemp(prefix='lddl_maskbench_')
  try:
    src = os.path.join(work, 'src')
    write_corpus(src, corpus_mb, num_shards=2, seed=99)
    sinks = {}
    for backend in ('host', 'device'):
      cfg = BertPretrainConfig(
          vocab_file=_VOCAB, target_seq_length=SEQ_LEN, bin_size=32,
          duplicate_factor=1, masking=True, masked_lm_ratio=RATIO,
          sentence_backend='rules', seed=42, engine='fast',
          tokenizer_backend='native', mask_backend=backend)
      sink = os.path.join(work, backend)
      run(read_corpus([src], num_blocks=2, sample_ratio=1.0), sink, cfg,
          executor=Executor(num_local_workers=1))
      sinks[backend] = sink

    # A/B columns store POST-masking tokens (reference semantics:
    # ``create_masked_lm_predictions`` returns the masked sequence and
    # masked_lm_labels holds the originals). The backends draw independent
    # RNG streams, so A/B may differ at picked positions — the invariant
    # is that *un-masking* both outputs (labels applied back at their
    # positions) reconstructs the identical original pairs.
    structure_equal = True
    originals_equal = True
    rows_checked = 0
    invariants_ok = True
    hf = get_all_parquets_under(sinks['host'])
    df = get_all_parquets_under(sinks['device'])
    assert [os.path.basename(p) for p in hf] == \
        [os.path.basename(p) for p in df]
    from lddl_tpu.core.utils import deserialize_np_array

    def reconstruct(row):
      toks = (['[CLS]'] + row['A'].split() + ['[SEP]'] + row['B'].split() +
              ['[SEP]'])
      pos = deserialize_np_array(row['masked_lm_positions'])
      for p, lab in zip(pos, row['masked_lm_labels'].split()):
        toks[p] = lab
      return toks, pos

    for a, b in zip(hf, df):
      ta, tb = pq.read_table(a), pq.read_table(b)
      for col in ('is_random_next', 'num_tokens'):
        if not ta.column(col).equals(tb.column(col)):
          structure_equal = False
      for hrow, drow in zip(ta.to_pylist(), tb.to_pylist()):
        h_orig, _ = reconstruct(hrow)
        d_orig, pos = reconstruct(drow)
        originals_equal = originals_equal and h_orig == d_orig
        labels = drow['masked_lm_labels'].split()
        na = len(drow['A'].split())
        want_k = max(1, round(len(d_orig) * RATIO))
        ok = len(pos) == len(labels) == want_k
        if len(pos) > 1:
          ok = ok and bool((np.diff(pos) > 0).all())
        ok = ok and all(0 < p < len(d_orig) - 1 and p != 1 + na for p in pos)
        invariants_ok = invariants_ok and ok
        rows_checked += 1
    if rows_checked == 0:
      # Zero rows must not read as vacuous success.
      structure_equal = originals_equal = invariants_ok = False
    return {
        'metric': 'parity',
        'corpus_mb': corpus_mb,
        'structure_equal': structure_equal,
        'reconstructed_originals_equal': originals_equal,
        'device_rows_checked': rows_checked,
        'device_invariants_ok': invariants_ok,
    }
  finally:
    shutil.rmtree(work, ignore_errors=True)


def timing_sweep(row_counts):
  from lddl_tpu.ops.masking import (assemble_pair_matrix, mask_batch_host,
                                    mask_partition_device)
  rng = np.random.default_rng(7)
  out = []
  for n in row_counts:
    # Synthetic ragged pairs: na,nb uniform in [8, 60] over a flat pool.
    na = rng.integers(8, 61, n)
    nb = rng.integers(8, 61, n)
    total = int((na + nb).sum())
    flat = rng.integers(5, 30000, total).astype(np.int32)
    bounds = np.zeros(2 * n + 1, np.int64)
    np.cumsum(np.stack([na, nb], 1).ravel(), out=bounds[1:])
    a_ranges = np.stack([bounds[0:-1:2], bounds[1::2]], 1)
    b_ranges = np.stack([bounds[1::2], bounds[2::2]], 1)

    def host_path():
      mat, row_len, na_out = assemble_pair_matrix(
          flat, a_ranges, b_ranges, cls_id=2, sep_id=3, max_len=SEQ_LEN)
      np_rng = np.random.Generator(np.random.Philox(key=np.uint64(11)))
      mask_batch_host(mat, row_len, na_out, masked_lm_ratio=RATIO,
                      vocab_size=30522, mask_id=4, np_rng=np_rng)

    def device_path():
      mask_partition_device(
          flat, a_ranges, b_ranges, seq_len=SEQ_LEN, masked_lm_ratio=RATIO,
          vocab_size=30522, mask_id=4, cls_id=2, sep_id=3, seed=11)

    device_path()  # compile + first-transfer warmup outside the timing
    host_s = min(_time(host_path) for _ in range(3))
    dev_s = min(_time(device_path) for _ in range(3))
    out.append({
        'metric': 'timing',
        'rows': int(n),
        'host_ms': round(host_s * 1e3, 2),
        'device_ms': round(dev_s * 1e3, 2),
        'host_mrows_per_s': round(n / host_s / 1e6, 3),
        'device_mrows_per_s': round(n / dev_s / 1e6, 3),
        'winner': 'device' if dev_s < host_s else 'host',
    })
  return out


def _time(fn):
  t0 = time.perf_counter()
  fn()
  return time.perf_counter() - t0


def main(argv=None):
  ap = argparse.ArgumentParser(description=__doc__)
  ap.add_argument('--rows', type=int, nargs='+', default=[2048, 8192, 32768])
  ap.add_argument('--corpus-mb', type=float, default=2.0)
  args = ap.parse_args(argv)
  print(json.dumps(measure_link()), flush=True)
  print(json.dumps(check_parity(args.corpus_mb)), flush=True)
  for line in timing_sweep(args.rows):
    print(json.dumps(line), flush=True)


if __name__ == '__main__':
  main()
