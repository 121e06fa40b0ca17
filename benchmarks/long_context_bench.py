"""Long-context single-chip training: real BERT train steps at s >= 8192.

The grid-blocked flash kernel removed the sequence-length cap on
attention memory; this bench shows what that buys in-model: full
BERT-base training steps (fwd + bwd + adamw update) at sequence lengths
the dense path cannot represent at all (its [b, h, s, s] score tensors
stop compiling past 4k — see attention_bench). Configuration per step:
``attention_impl='flash'``, remat on, masked-only MLM head (the b*s*V
logits chain would otherwise dominate memory at long s).

Batches default to synthetic (uniform ids, 15% masked positions); with
``--packed-data DIR --vocab-file V`` they instead come from the real
long-context pipeline — :mod:`lddl_tpu.preprocess.packed` shards through
:func:`lddl_tpu.loader.get_packed_pretrain_data_loader` (token ids,
dynamic Philox masking) — so the s>=8k steps train on real preprocessed
data end-to-end. The model, sharding, scan-window dispatch amortization,
and optimizer are the real training stack
(`lddl_tpu.parallel.make_scan_train_step`) either way. Writes one line
per sequence length; OOM is recorded as the datapoint.
"""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def _synthetic_batch(rng, batch, seq_len, vocab, max_predictions,
                     docs_per_row=None):
  from lddl_tpu.loader.bert import IGNORE_INDEX
  n_mask = max_predictions
  ids = rng.integers(5, vocab, (batch, seq_len), dtype=np.int32)
  labels = np.full((batch, seq_len), IGNORE_INDEX, np.int32)
  for b in range(batch):
    pos = rng.choice(np.arange(1, seq_len - 1), size=n_mask, replace=False)
    labels[b, pos] = ids[b, pos]
    ids[b, pos] = 4  # [MASK]
  out = {
      'input_ids': ids,
      'token_type_ids': np.zeros((batch, seq_len), np.int32),
      'attention_mask': np.ones((batch, seq_len), np.int32),
      'labels': labels,
      'next_sentence_labels': rng.integers(0, 2, (batch,), dtype=np.int32),
  }
  if docs_per_row is not None:
    from attention_bench import ragged_segments
    out['segment_ids'] = ragged_segments(batch, seq_len, docs_per_row,
                                         seed=int(rng.integers(1 << 30)))
  return out


def _drain_packed(args, s, block_diagonal=False):
  """scan_steps real batches of exactly width s from the packed loader.

  Full-width rows live in the top bin; the loader streams raw rows and
  only top-bin batches (max num_tokens inside the last bin's range) pay
  the collate — lower bins are skipped without deserializing ids or
  drawing masks."""
  from lddl_tpu.loader import get_packed_pretrain_data_loader
  from lddl_tpu.loader.packed import PackedCollate
  from lddl_tpu.pipeline.parquet_io import read_samples
  from lddl_tpu.core import get_all_parquets_under
  from lddl_tpu.tokenization.wordpiece import load_bert_tokenizer
  # One packed dir serves exactly one target length: validate s against
  # the shards up front instead of crashing mid-drain (too-short s) or
  # silently replaying 8 full epochs (too-long s).
  longest = max(
      (int(r['num_tokens']) for p_ in get_all_parquets_under(args.packed_data)
       for r in read_samples(p_, columns=['num_tokens'])), default=0)
  if longest == 0 or not (s - args.bin_size < longest <= s):
    raise RuntimeError(
        f'--packed-data rows top out at {longest} tokens, which does not '
        f'fill the top bin of s={s} (expected ({s - args.bin_size}, {s}]); '
        'regenerate with --target-seq-length matching --seqs')
  tok = load_bert_tokenizer(vocab_file=args.vocab_file, backend='hf')
  collate = PackedCollate(tok, base_seed=17, block_diagonal=block_diagonal)
  batches = []
  for epoch in range(8):
    dl = get_packed_pretrain_data_loader(
        args.packed_data, vocab_file=args.vocab_file,
        batch_size_per_rank=args.batch, bin_size=args.bin_size,
        max_seq_length=s, sequence_length_alignment=128, base_seed=17,
        start_epoch=epoch, return_raw_samples=True)
    for step, rows in enumerate(dl):
      if max(r['num_tokens'] for r in rows) <= s - args.bin_size:
        continue  # lower bin: batch width would not be s
      batches.append(collate(rows, s, epoch, step))
      if len(batches) == args.scan_steps:
        return batches
  raise RuntimeError(
      f'packed dataset yielded only {len(batches)} width-{s} batches; '
      'regenerate with a matching --target-seq-length')


def main(argv=None):
  p = argparse.ArgumentParser(description=__doc__)
  p.add_argument('--seqs', default='8192,16384,32768')
  p.add_argument('--batch', type=int, default=1)
  p.add_argument('--model', default='base')
  p.add_argument('--scan-steps', type=int, default=4)
  p.add_argument('--windows', type=int, default=3)
  p.add_argument('--max-predictions', type=int, default=None,
                 help='default: ceil(0.15 * seq_len)')
  p.add_argument('--out', default=None)
  p.add_argument('--packed-data', default=None,
                 help='balanced packed-shard dir (preprocess_packed_'
                 'pretrain at the matching target length); real rows '
                 'instead of synthetic')
  p.add_argument('--vocab-file', default=None)
  p.add_argument('--bin-size', type=int, default=2048,
                 help='bin width of the packed shards')
  p.add_argument('--block-diagonal', action='store_true',
                 help='attach per-doc segment ids to every batch: '
                 'block-diagonal attention (cross-doc flash tiles skipped) '
                 'plus per-doc MLM loss normalization; synthetic batches '
                 'sweep --docs-per-row, packed data decodes doc_offsets')
  p.add_argument('--docs-per-row', default='1,4,16',
                 help='--block-diagonal synthetic mode: comma list of docs '
                 'packed per row')
  args = p.parse_args(argv)

  import jax
  import optax
  from attention_bench import is_oom

  from lddl_tpu.core.compile_cache import use_compile_cache
  from lddl_tpu.models import BertConfig, BertForPretraining
  from lddl_tpu.parallel import make_mesh
  from lddl_tpu.parallel.train import (init_params, make_scan_train_step,
                                       stack_batch_window)

  use_compile_cache()
  sizes = {'base': (768, 12, 12, 3072), 'large': (1024, 24, 16, 4096)}
  hidden, layers, heads, inter = sizes[args.model]
  vocab = 30528
  mesh = make_mesh()
  rng = np.random.default_rng(0)
  mode = ' block-diagonal' if args.block_diagonal else ''
  lines = [('# long-context single-chip train steps: '
            f'{args.model}, batch={args.batch}, flash+remat+masked-only '
            f'head{mode}, scan={args.scan_steps}, median of {args.windows} '
            'windows'),
           '# s | k docs | max_pred | ms/step | tokens/s | tiles skipped | '
           'result']
  print('\n'.join(lines), flush=True)
  doc_counts = ([int(x) for x in args.docs_per_row.split(',')]
                if args.block_diagonal and not args.packed_data else [None])

  for s in [int(x) for x in args.seqs.split(',')]:
    if args.max_predictions:
      max_pred = args.max_predictions
    elif args.packed_data:
      # dynamic masking has a binomial tail: +4sd headroom, the same
      # budget check_max_predictions (parallel/train.py) enforces —
      # an undersized P silently drops overflow MLM targets.
      sd = (s * 0.15 * 0.85) ** 0.5
      max_pred = int(s * 0.15 + 4 * sd) + 1
    else:
      max_pred = int(np.ceil(0.15 * s))
    cfg = BertConfig(
        vocab_size=vocab, hidden_size=hidden, num_layers=layers,
        num_heads=heads, intermediate_size=inter,
        max_position_embeddings=s, attention_impl='flash', remat=True)
    model = BertForPretraining(cfg)
    tx = optax.adamw(1e-4)
    for docs in doc_counts:
      kcol = f'{docs:6d}' if docs is not None else '     -'
      skipcol = '            -'
      try:
        params = init_params(model, mesh, jax.random.key(7), seq_len=128)
        opt_state = jax.jit(tx.init, out_shardings=None)(params)
        scan = make_scan_train_step(model, tx, mesh,
                                    max_predictions=max_pred)
        if args.packed_data:
          batches = _drain_packed(args, s,
                                  block_diagonal=args.block_diagonal)
        else:
          batches = [
              _synthetic_batch(rng, args.batch, s, vocab, max_pred,
                               docs_per_row=docs)
              for _ in range(args.scan_steps)
          ]
        if 'segment_ids' in batches[0]:
          from lddl_tpu.ops.flash_attention import count_skippable_tiles
          total = skipped = 0
          for bb in batches:
            t_, sk_ = count_skippable_tiles(bb['segment_ids'])
            total += t_
            skipped += sk_
          skipcol = f'{skipped}/{total} ({skipped / total:.0%})'
        window = stack_batch_window(batches, mesh)
        key = jax.random.key(11)
        params2, opt2, metrics = scan(params, opt_state, key, window)
        float(metrics['loss'])  # sync (compile + first window)
        times = []
        for _ in range(args.windows):
          t0 = time.perf_counter()
          params2, opt2, metrics = scan(params2, opt2, key, window)
          float(metrics['loss'])  # device->host sync
          times.append(time.perf_counter() - t0)
        ms = float(np.median(times)) * 1000 / args.scan_steps
        toks = args.batch * s / (ms / 1000)
        row = (f'{s:6d} | {kcol} | {max_pred:6d} | {ms:9.1f} | '
               f'{toks:9.0f} | {skipcol} | ok')
      except Exception as e:  # noqa: BLE001 — OOM is the datapoint
        if not is_oom(e):
          raise
        row = (f'{s:6d} | {kcol} | {max_pred:6d} |       OOM |       OOM '
               f'| {skipcol} | oom')
      lines.append(row)
      print(row, flush=True)
      if args.out:
        # Rewrite after every row so a hard process kill at a later size
        # (an HBM abort) keeps the finished datapoints.
        with open(args.out, 'w', encoding='utf-8') as f:
          f.write('\n'.join(lines) + '\n')


if __name__ == '__main__':
  main()
