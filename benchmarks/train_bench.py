"""Mock-training benchmark harness: consume the loader, measure, verify.

Capability parity with the reference's de-facto integration test
(``/root/reference/benchmarks/torch_train.py:97-252``) plus the TPU-native
additions the reference could not have:

  - ``--mode loader``: pure data-pipeline consumption — per-step latency
    (avg/min/max after ``--warmup``), samples/s, shape/dtype asserts every
    step, ``--debug`` raw-batch eyeballing with id→token decoding;
  - ``--mode train``: the same loader feeding the real
    :func:`lddl_tpu.parallel.make_train_step` over a device mesh — step
    latency, samples/s, tokens/s, and **MFU** (analytic model FLOPs from
    :mod:`lddl_tpu.models.flops` / measured step time / chip peak);
  - per-rank sequence-length stats dumped to ``<seq-len-dir>/lens_<rank>.npz``
    (min/max/batch-size/padded-len per iteration + seq-len and padded-zero
    histograms), the input contract of ``benchmarks/validate_binning.py``
    (reference ``make_training_seqlen_plots.py``).

Run from the repo root, e.g.::

  python benchmarks/train_bench.py --path balanced/ --vocab-file vocab.txt \
      --bin-size 64 --batch-size 16 --mode train --model tiny --epochs 1 \
      --seq-len-dir seqlens/
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


MODEL_PRESETS = {
    # hidden, layers, heads, intermediate
    'tiny': (128, 2, 2, 512),      # CI / smoke
    'base': (768, 12, 12, 3072),
    'large': (1024, 24, 16, 4096),
}


def build_train_state(args, tokenizer):
  """Model + optimizer + sharded params + jitted step over the mesh."""
  import jax
  import optax
  if getattr(args, 'prng', 'threefry') != 'threefry':
    jax.config.update('jax_default_prng_impl', args.prng)

  from lddl_tpu.core.compile_cache import use_compile_cache
  from lddl_tpu.models import BertConfig, BertForPretraining
  from lddl_tpu.parallel import make_mesh, make_train_step, mesh_summary
  from lddl_tpu.parallel.train import init_params

  use_compile_cache()
  hidden, layers, heads, inter = MODEL_PRESETS[args.model]
  vocab = ((tokenizer.vocab_size + 63) // 64) * 64  # pad for the MXU
  cfg = BertConfig(
      vocab_size=vocab,
      hidden_size=hidden,
      num_layers=layers,
      num_heads=heads,
      intermediate_size=inter,
      max_position_embeddings=max(args.max_seq_length, 512),
      attention_impl=args.attention,
      dropout_rate=args.dropout,
      fused_qkv=args.fused_qkv,
      remat=args.remat)
  model = BertForPretraining(cfg)
  mesh = make_mesh(data=args.dp, fsdp=args.fsdp, tensor=args.tp,
                   seq=args.sp)
  print(f'mesh: {mesh_summary(mesh)}; devices={len(jax.devices())} '
        f'({jax.devices()[0].device_kind})')
  if args.max_predictions is not None:
    from lddl_tpu.parallel.train import check_max_predictions
    check_max_predictions(args.max_predictions, args.max_seq_length,
                          args.masking,
                          mlm_probability=args.mlm_probability)
  tx = optax.adamw(1e-4)
  params = init_params(model, mesh, jax.random.key(args.seed),
                       seq_len=min(128, args.max_seq_length))
  opt_state = jax.jit(
      tx.init, out_shardings=None)(params)
  step = make_train_step(model, tx, mesh,
                         max_predictions=args.max_predictions)
  return cfg, mesh, model, tx, step, params, opt_state


def run_scan(args, loader, tokenizer):
  """``--scan-steps K``: jit K train steps into ONE program (``lax.scan``
  over a device-resident batch window) so per-step dispatch and host
  sync cost is paid once per window. Collects K same-shape batches from
  the real loader, stacks them on device, then times ``--scan-windows``
  window executions.
  """
  import jax

  from lddl_tpu.models.flops import (bert_pretrain_flops_per_step,
                                     peak_flops_per_device)
  from lddl_tpu.parallel import make_scan_train_step, stack_batch_window

  cfg, mesh, model, tx, _, params, opt_state = build_train_state(
      args, tokenizer)
  k = args.scan_steps
  # K batches of one static shape (whichever bin shape fills first wins,
  # unless --scan-seq-len pins a specific bin's padded length — e.g. 512
  # for a phase-2 datapoint, which short-pair bins would otherwise
  # outrace).
  by_shape = {}
  batches = None
  for batch in loader:
    check_batch(batch)
    if (args.scan_seq_len and
        batch['input_ids'].shape[1] != args.scan_seq_len):
      continue
    group = by_shape.setdefault(batch['input_ids'].shape, [])
    group.append(batch)
    if len(group) == k:
      batches = group
      break
  if batches is None:
    best = max(by_shape.values(), key=len, default=[])
    hint = ('no batch matched --scan-seq-len '
            f'{args.scan_seq_len} (check the dataset has that bin); '
            if args.scan_seq_len and not by_shape else '')
    raise SystemExit(
        f'no bin yielded {k} batches (best: {len(best)}); {hint}lower '
        '--scan-steps or use a bigger dataset')
  shape = batches[0]['input_ids'].shape
  window = stack_batch_window(batches, mesh)
  b, s = shape
  scan = make_scan_train_step(model, tx, mesh,
                              max_predictions=args.max_predictions)
  rng = jax.random.key(args.seed + 1)

  t0 = time.perf_counter()
  params, opt_state, metrics = scan(params, opt_state, rng, window)
  loss = float(metrics['loss'])  # device->host read: waits for the window
  compile_s = time.perf_counter() - t0

  n_dev = len(jax.devices())
  peak = (args.peak_tflops * 1e12 if args.peak_tflops else
          peak_flops_per_device())
  flops_per_step = bert_pretrain_flops_per_step(
      cfg, b, s, max_predictions=args.max_predictions)
  times = []
  # Shared capture path with the live /profile endpoint (same output
  # layout); no-op when --profile-dir is unset.
  from lddl_tpu.telemetry.profiling import trace_capture
  with trace_capture(args.profile_dir):
    for _ in range(args.scan_windows):
      t0 = time.perf_counter()
      params, opt_state, metrics = scan(params, opt_state, rng, window)
      loss = float(metrics['loss'])
      times.append(time.perf_counter() - t0)
  # Median window: robust against an outlier window in either direction.
  med_step = sorted(times)[len(times) // 2] / k
  avg_step = sum(times) / len(times) / k
  summary = {
      'mode': 'train-scan',
      'model': args.model,
      'batch': b,
      'seq_len': s,
      'scan_steps': k,
      'windows': args.scan_windows,
      'compile_seconds': round(compile_s, 2),
      'avg_latency_ms': round(avg_step * 1e3, 3),
      'median_latency_ms': round(med_step * 1e3, 3),
      'min_latency_ms': round(min(times) / k * 1e3, 3),
      'samples_per_sec': round(b / med_step, 2),
      'tokens_per_sec': round(b * s / med_step, 1),
      'model_tflops_per_sec': round(flops_per_step / med_step / 1e12, 3),
      'remat': bool(args.remat),
      'devices': n_dev,
      'loss': round(loss, 4),
  }
  if peak:  # None only on the CPU backend without --peak-tflops
    summary['mfu'] = round(flops_per_step / med_step / (peak * n_dev), 6)
  print(json.dumps(summary))
  return summary


def run(args):
  import lddl_tpu  # noqa: F401  (PYTHONPATH check before heavy imports)
  from lddl_tpu.loader import get_bert_pretrain_data_loader
  from lddl_tpu.tokenization.wordpiece import load_bert_tokenizer

  if args.dp_world_size == 1:
    # Multi-host pod run with defaults: each process feeds its own dp
    # shard and dumps its own lens_<rank>.npz (the reference derives the
    # same from the launcher env; torch_train.py:98-104). Applies to both
    # modes — a loader-mode pod run otherwise duplicates data per host.
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

  if args.mode == 'train' and args.scan_steps:
    return run_scan(args, loader, tokenizer)

  iters_per_epoch = min(len(loader), args.iters_per_epoch)
  stats = SeqlenStats(args.epochs, iters_per_epoch)
  meter = StepMeter(warmup=args.warmup)
  data_meter = StepMeter(warmup=args.warmup)

  train = args.mode == 'train'
  if train:
    import jax

    from lddl_tpu.loader.device import prefetch_to_device
    from lddl_tpu.models.flops import (bert_pretrain_flops_per_step,
                                       peak_flops_per_device)
    cfg, mesh, _, _, step, params, opt_state = build_train_state(
        args, tokenizer)
    rng = jax.random.key(args.seed + 1)
    peak = (args.peak_tflops * 1e12 if args.peak_tflops else
            peak_flops_per_device())
    n_dev = len(jax.devices())

  summary = {}
  for epoch in range(args.epochs):
    total_samples = 0
    total_tokens = 0
    total_model_flops = 0.0
    epoch_start = time.perf_counter()
    epoch_before = loader.epoch
    it = iter(loader)
    stream = enumerate(it)
    if train:
      # Overlap host collate with device compute; stats/checks run on the
      # host copy before transfer.
      def _tee(src):
        for i, b in src:
          check_batch(b)
          if i < iters_per_epoch:  # prefetch may read past the cutoff
            stats.record(epoch, i, b)
          yield b

      device_stream = prefetch_to_device(
          _tee(stream), mesh=mesh, size=args.prefetch)

    t0 = time.perf_counter()
    for i in range(iters_per_epoch):
      if train:
        t_data = time.perf_counter()
        try:
          batch = next(device_stream)
        except StopIteration:
          break
        data_meter.update(time.perf_counter() - t_data)
        params, opt_state, metrics = step(params, opt_state, rng, batch)
        jax.block_until_ready(metrics['loss'])
        b, s = batch['input_ids'].shape
        total_model_flops += bert_pretrain_flops_per_step(
            cfg, b, s, max_predictions=args.max_predictions)
      else:
        t_data = time.perf_counter()
        try:
          _, batch = next(stream)
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
        # (meter.total excludes warmup/compile steps).
        if train:
          total_model_flops = 0.0
        total_samples = 0
        total_tokens = 0
      else:
        total_samples += b
        total_tokens += b * s
      if (i + 1) % args.log_freq == 0:
        line = (f'epoch={epoch} iter={i + 1}/{iters_per_epoch} '
                f'latency(ms) last={elapsed * 1e3:.1f} '
                f'avg={meter.avg * 1e3:.1f} min={meter.min * 1e3:.1f} '
                f'max={meter.max * 1e3:.1f} '
                f'samples/s={total_samples / max(meter.total, 1e-9):.1f}')
        if train:
          line += f" loss={float(metrics['loss']):.4f}"
        print(line)
        if args.debug:
          debug_print(batch, tokenizer)

    # An --iters-per-epoch cutoff can leave the loader generator short of
    # its final yield, where it advances its epoch counter. Quiesce the
    # prefetch producer (close() joins it), then pin the epoch to exactly
    # before+1 — an unconditional assignment, so it is correct whether or
    # not the generator got to its own increment.
    if train:
      device_stream.close()
    loader.epoch = epoch_before + 1

    epoch_elapsed = time.perf_counter() - epoch_start
    measured = max(meter.total, 1e-9)
    summary = {
        'mode': args.mode,
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
    if train:
      summary['model_tflops_per_sec'] = round(
          total_model_flops / measured / 1e12, 6)
      if peak:
        summary['mfu'] = round(total_model_flops / measured / (peak * n_dev),
                               6)
      summary['devices'] = n_dev
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
  parser.add_argument('--mode', choices=['loader', 'train'],
                      default='loader')
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
                      help='steps excluded from latency aggregates '
                           '(compile steps)')
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
  parser.add_argument('--profile-dir', default=None,
                      help='write a jax.profiler trace of the measured '
                           'scan windows here (view with TensorBoard or '
                           'xprof) — device-time ground truth for the '
                           'MFU numbers')
  parser.add_argument('--seq-len-dir', default=None,
                      help='dump per-rank lens_<rank>.npz here')
  parser.add_argument('--debug', action='store_true',
                      help='decode + print raw batches at each log step')
  # train mode
  parser.add_argument('--model', choices=sorted(MODEL_PRESETS),
                      default='base')
  parser.add_argument('--dp', type=int, default=1)
  parser.add_argument('--fsdp', type=int, default=1)
  parser.add_argument('--tp', type=int, default=1)
  parser.add_argument('--sp', type=int, default=1)
  parser.add_argument('--prefetch', type=int, default=2)
  parser.add_argument('--scan-steps', type=int, default=0,
                      help='train mode: jit this many steps into one '
                           'program (lax.scan over a device-resident '
                           'window) so dispatch cost amortizes; 0 = '
                           'one program per step')
  parser.add_argument('--scan-windows', type=int, default=8,
                      help='timed window executions in --scan-steps mode')
  parser.add_argument('--scan-seq-len', type=int, default=None,
                      help='collect the scan window from the bin with this '
                           'padded sequence length instead of the first '
                           'bin to fill (e.g. 512 for a phase-2 row)')
  parser.add_argument('--peak-tflops', type=float, default=None,
                      help='override per-chip peak bf16 TFLOP/s for MFU')
  parser.add_argument('--attention', default='dense',
                      choices=['dense', 'flash', 'ring', 'ring_flash'],
                      help='attention implementation (flash: Pallas '
                           'blockwise kernel, no s^2 score tensor)')
  parser.add_argument('--max-predictions', type=int, default=None,
                      help='masked-only MLM head: gather this many MLM '
                           'positions per row before the vocab projection '
                           '(honest FLOPs accounting follows); None = '
                           'full-sequence head')
  parser.add_argument('--fused-qkv', action='store_true',
                      help='single [d,3d] QKV projection (see '
                      'BertConfig.fused_qkv)')
  parser.add_argument('--prng', default='threefry',
                      choices=['threefry', 'rbg'],
                      help="jax PRNG impl; 'rbg' makes per-step dropout "
                      'draws ~free on TPU (weaker statistical guarantees '
                      'than threefry, fine for dropout)')
  parser.add_argument('--dropout', type=float, default=0.1,
                      help='model dropout rate (0 disables the per-step '
                      'RNG draws entirely)')
  parser.add_argument('--remat', action='store_true',
                      help='rematerialize layer activations (trade FLOPs '
                           'for HBM; lets bigger batches fit)')
  return parser


def main(argv=None):
  args = attach_args(argparse.ArgumentParser(
      description=__doc__,
      formatter_class=argparse.RawDescriptionHelpFormatter)).parse_args(argv)
  return run(args)


if __name__ == '__main__':
  main()
