"""Mock-training harness: consume the loader, measure, verify.

What the reference's mock trainer is for
(``/root/reference/benchmarks/torch_train.py:97-252``), without a model:

  - pure data-pipeline consumption — per-step latency (avg/min/max after
    ``--warmup``), samples/s, shape/dtype asserts every step, ``--debug``
    raw-batch eyeballing with id→token decoding;
  - per-rank sequence-length stats dumped to ``<seq-len-dir>/lens_<rank>.npz``
    (min/max/batch-size/padded-len per iteration + seq-len and padded-zero
    histograms), the input contract of ``benchmarks/validate_binning.py``
    (reference ``make_training_seqlen_plots.py``).

The train step is the product's: ``python -m lddl_tpu.training.pretrain``
runs the same shards through ``TrainLoop.run``.

Run from the repo root, e.g.::

  python benchmarks/train_bench.py --path balanced/ --vocab-file vocab.txt \
      --bin-size 64 --batch-size 16 --epochs 1 --seq-len-dir seqlens/
"""

import argparse
import json
import logging
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class StepMeter:
  """Streaming latency stats; the first ``warmup`` updates are excluded
  from the aggregates (compile steps would swamp them) but still counted
  in ``iters``."""

  def __init__(self, warmup=0):
    self._warmup = warmup
    self.reset()

  def reset(self):
    self.iters = 0
    self.count = 0
    self.total = 0.0
    self.min = float('inf')
    self.max = float('-inf')
    self.last = 0.0

  def update(self, seconds):
    self.iters += 1
    self.last = seconds
    if self.iters > self._warmup:
      self.count += 1
      self.total += seconds
      self.min = min(self.min, seconds)
      self.max = max(self.max, seconds)

  @property
  def avg(self):
    return self.total / max(self.count, 1)


class SeqlenStats:
  """Per-iteration min/max/batch/padded-len arrays + token histograms —
  the ``lens_<rank>.npz`` payload the binning validator consumes."""

  def __init__(self, epochs, iters):
    shape = (epochs, iters)
    self.min_lens = np.zeros(shape, dtype=np.uint16)
    self.max_lens = np.zeros(shape, dtype=np.uint16)
    self.batch_sizes = np.zeros(shape, dtype=np.uint16)
    self.padded_lens = np.zeros(shape, dtype=np.uint16)
    self._seq_len_counts = {}
    self._padded_zero_counts = {}

  def record(self, epoch, it, batch):
    lens = np.asarray(batch['attention_mask']).sum(axis=1).astype(np.int64)
    padded = batch['input_ids'].shape[1]
    self.min_lens[epoch, it] = lens.min()
    self.max_lens[epoch, it] = lens.max()
    self.batch_sizes[epoch, it] = batch['input_ids'].shape[0]
    self.padded_lens[epoch, it] = padded
    for v, c in zip(*np.unique(lens, return_counts=True)):
      self._seq_len_counts[int(v)] = self._seq_len_counts.get(int(v), 0) + int(c)
    for v, c in zip(*np.unique(padded - lens, return_counts=True)):
      self._padded_zero_counts[int(v)] = (
          self._padded_zero_counts.get(int(v), 0) + int(c))

  @staticmethod
  def _to_hist(counts):
    hist = np.zeros((max(counts) + 1 if counts else 1,), dtype=np.uint64)
    for v, c in counts.items():
      hist[v] = c
    return hist

  def save(self, path):
    np.savez_compressed(
        path,
        min_lens=self.min_lens,
        max_lens=self.max_lens,
        batch_sizes=self.batch_sizes,
        padded_lens=self.padded_lens,
        seq_len_hist=self._to_hist(self._seq_len_counts),
        padded_zero_hist=self._to_hist(self._padded_zero_counts))


def check_batch(batch):
  """The reference's per-step invariant asserts (torch_train.py:170-175)."""
  ids = batch['input_ids']
  assert ids.dtype == np.int32 or str(ids.dtype) == 'int32', ids.dtype
  for k in ('token_type_ids', 'attention_mask', 'labels'):
    assert batch[k].shape == ids.shape, (k, batch[k].shape, ids.shape)
  nsp = batch['next_sentence_labels']
  assert nsp.ndim == 1 and nsp.shape[0] == ids.shape[0]


def debug_print(batch, tokenizer):
  from lddl_tpu.loader.bert import IGNORE_INDEX
  ids = np.asarray(batch['input_ids'][0]).tolist()
  print('input_ids[0] =', ids)
  print('tokens[0]    =', ' '.join(tokenizer.convert_ids_to_tokens(ids)))
  print('token_type_ids[0] =', np.asarray(batch['token_type_ids'][0]).tolist())
  print('attention_mask[0] =', np.asarray(batch['attention_mask'][0]).tolist())
  print('next_sentence_labels[0] =', int(batch['next_sentence_labels'][0]))
  labels = np.asarray(batch['labels'][0])
  mask = labels != IGNORE_INDEX
  restored = np.asarray(batch['input_ids'][0]).copy()
  restored[mask] = labels[mask]
  print('original[0]  =',
        ' '.join(tokenizer.convert_ids_to_tokens(restored.tolist())))


def run(args):
  import lddl_tpu  # noqa: F401  (PYTHONPATH check before heavy imports)
  from lddl_tpu.loader import get_bert_pretrain_data_loader
  from lddl_tpu.tokenization.wordpiece import load_bert_tokenizer

  if args.dp_world_size == 1:
    # Multi-host pod run with defaults: each process feeds its own dp
    # shard and dumps its own lens_<rank>.npz (the reference derives the
    # same from the launcher env; torch_train.py:98-104): a pod run
    # otherwise duplicates data per host.
    import jax
    if jax.process_count() > 1:
      args.dp_rank = jax.process_index()
      args.dp_world_size = jax.process_count()

  tokenizer = load_bert_tokenizer(
      vocab_file=args.vocab_file, hub_name=args.tokenizer, backend='hf')
  loader = get_bert_pretrain_data_loader(
      args.path,
      dp_rank=args.dp_rank,
      dp_world_size=args.dp_world_size,
      batch_size_per_rank=args.batch_size,
      # Worker processes rebuild the tokenizer from the file/name args; a
      # live tokenizer is only passed for the in-process path.
      tokenizer=None if args.num_workers else tokenizer,
      vocab_file=args.vocab_file,
      tokenizer_name=args.tokenizer,
      num_workers=args.num_workers,
      masking=args.masking,
      mlm_probability=args.mlm_probability,
      max_seq_length=args.max_seq_length,
      bin_size=args.bin_size,
      sequence_length_alignment=args.sequence_length_alignment,
      shuffle_buffer_size=args.shuffle_buffer_size,
      shuffle_buffer_warmup_factor=args.shuffle_buffer_warmup_factor,
      base_seed=args.seed,
      start_epoch=args.start_epoch,
      log_dir=args.log_dir,
      log_level=getattr(logging, args.log_level))

  iters_per_epoch = min(len(loader), args.iters_per_epoch)
  stats = SeqlenStats(args.epochs, iters_per_epoch)
  meter = StepMeter(warmup=args.warmup)
  data_meter = StepMeter(warmup=args.warmup)

  summary = {}
  for epoch in range(args.epochs):
    total_samples = 0
    total_tokens = 0
    epoch_start = time.perf_counter()
    epoch_before = loader.epoch
    stream = iter(loader)

    t0 = time.perf_counter()
    for i in range(iters_per_epoch):
      t_data = time.perf_counter()
      try:
        batch = next(stream)
      except StopIteration:
        break
      data_meter.update(time.perf_counter() - t_data)
      check_batch(batch)
      stats.record(epoch, i, batch)
      b, s = batch['input_ids'].shape
      elapsed = time.perf_counter() - t0
      t0 = time.perf_counter()
      meter.update(elapsed)
      if meter.iters <= args.warmup:
        # Keep the rate numerators aligned with the measured denominator
        # (meter.total excludes warmup steps).
        total_samples = 0
        total_tokens = 0
      else:
        total_samples += b
        total_tokens += b * s
      if (i + 1) % args.log_freq == 0:
        print(f'epoch={epoch} iter={i + 1}/{iters_per_epoch} '
              f'latency(ms) last={elapsed * 1e3:.1f} '
              f'avg={meter.avg * 1e3:.1f} min={meter.min * 1e3:.1f} '
              f'max={meter.max * 1e3:.1f} '
              f'samples/s={total_samples / max(meter.total, 1e-9):.1f}')
        if args.debug:
          debug_print(batch, tokenizer)

    # An --iters-per-epoch cutoff can leave the loader generator short of
    # its final yield, where it advances its epoch counter. Pin the epoch
    # to exactly before+1 — an unconditional assignment, so it is correct
    # whether or not the generator got to its own increment.
    loader.epoch = epoch_before + 1

    epoch_elapsed = time.perf_counter() - epoch_start
    measured = max(meter.total, 1e-9)
    summary = {
        'mode': 'loader',
        'epoch': epoch,
        'iters': meter.iters,
        'epoch_seconds': round(epoch_elapsed, 3),
        'avg_latency_ms': round(meter.avg * 1e3, 3),
        'min_latency_ms': round(meter.min * 1e3, 3) if meter.count else 0.0,
        'max_latency_ms': round(meter.max * 1e3, 3) if meter.count else 0.0,
        'avg_data_wait_ms': round(data_meter.avg * 1e3, 3),
        'samples_per_sec': round(total_samples / measured, 2),
        'tokens_per_sec': round(total_tokens / measured, 1),
    }
    print(json.dumps(summary))
    meter.reset()
    data_meter.reset()

  if args.seq_len_dir:
    os.makedirs(args.seq_len_dir, exist_ok=True)
    out = os.path.join(args.seq_len_dir, f'lens_{args.dp_rank}.npz')
    stats.save(out)
    print(f'wrote {out}')
  return summary


def attach_args(parser):
  parser.add_argument('--path', required=True,
                      help='balanced shard directory')
  parser.add_argument('--vocab-file', default=None)
  parser.add_argument('--tokenizer', default=None,
                      help='hub tokenizer name when no --vocab-file')
  parser.add_argument('--batch-size', type=int, default=64,
                      help='per-rank samples per step')
  parser.add_argument('--bin-size', type=int, default=None)
  parser.add_argument('--max-seq-length', type=int, default=512)
  parser.add_argument('--sequence-length-alignment', type=int, default=8)
  parser.add_argument('--masking', choices=['dynamic', 'static'],
                      default='dynamic')
  parser.add_argument('--mlm-probability', type=float, default=0.15)
  parser.add_argument('--epochs', type=int, default=1)
  parser.add_argument('--iters-per-epoch', type=int, default=10**9)
  parser.add_argument('--warmup', type=int, default=2,
                      help='steps excluded from latency aggregates')
  parser.add_argument('--num-workers', type=int, default=0,
                      help='collate in this many worker processes '
                           '(byte-identical output; 0 = in-process)')
  parser.add_argument('--shuffle-buffer-size', type=int, default=16384)
  parser.add_argument('--shuffle-buffer-warmup-factor', type=int, default=16)
  parser.add_argument('--seed', type=int, default=127)
  parser.add_argument('--start-epoch', type=int, default=0)
  parser.add_argument('--dp-rank', type=int, default=0)
  parser.add_argument('--dp-world-size', type=int, default=1)
  parser.add_argument('--log-freq', type=int, default=50)
  parser.add_argument('--log-dir', default=None)
  parser.add_argument('--log-level', default='WARNING',
                      choices=['CRITICAL', 'ERROR', 'WARNING', 'INFO',
                               'DEBUG'])
  parser.add_argument('--seq-len-dir', default=None,
                      help='dump per-rank lens_<rank>.npz here')
  parser.add_argument('--debug', action='store_true',
                      help='decode + print raw batches at each log step')
  return parser


def main(argv=None):
  args = attach_args(argparse.ArgumentParser(
      description=__doc__,
      formatter_class=argparse.RawDescriptionHelpFormatter)).parse_args(argv)
  return run(args)


if __name__ == '__main__':
  main()
