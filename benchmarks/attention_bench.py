"""Flash vs dense attention timings and the long-context memory crossover.

Backs PERF.md's flash-attention section with a committed artifact: for a
BERT-base-shaped head layout ([batch, 12 heads, s, 64], bf16) this times
the Pallas flash kernel (`lddl_tpu/ops/flash_attention.py`) against the
dense einsum path — forward and forward+backward — across sequence
lengths, and records where the dense path stops fitting on the chip
while flash keeps going (no O(s^2) score materialization in either
pass). Run on the attached TPU; results land in
``benchmarks/results/attention_v5e.txt`` with ``--out``.
"""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _dense_attention(q, k, v):
  import jax.numpy as jnp
  d = q.shape[-1]
  scores = jnp.einsum('bhqd,bhkd->bhqk', q, k) / np.sqrt(d).astype(q.dtype)
  probs = jnp.asarray(
      jnp.exp(scores - scores.max(axis=-1, keepdims=True)), q.dtype)
  probs = probs / probs.sum(axis=-1, keepdims=True)
  return jnp.einsum('bhqk,bhkd->bhqd', probs, v)


def is_oom(exc):
  """Running out of device memory is a datapoint of these benches; any
  other exception is a failed run and must propagate."""
  msg = str(exc)
  return ('RESOURCE_EXHAUSTED' in msg or 'Ran out of memory' in msg
          or 'hbm capacity' in msg)


def _sync(out):
  # A device->host scalar read: returns only once the program has run.
  import jax
  leaf = jax.tree_util.tree_leaves(out)[0]
  np.asarray(leaf.ravel()[0])


def _make_scanned_fwd(fn, n):
  """Chain n applications (each output feeds the next query) inside one
  jit program, so dispatch and the host sync are paid once per n calls.
  The data dependency between iterations prevents XLA from removing or
  parallelizing the repeats."""
  import jax
  from jax import lax

  @jax.jit
  def run(q, k, v):
    def body(c, _):
      return fn(c, k, v), ()
    out, _ = lax.scan(body, q, None, length=n)
    return out
  return run


def _make_scanned_bwd(fn, n):
  import jax
  import jax.numpy as jnp
  from jax import lax

  def loss(q, k, v):
    return jnp.sum(fn(q, k, v).astype(jnp.float32))
  g = jax.grad(loss, argnums=(0, 1, 2))

  @jax.jit
  def run(q, k, v):
    def body(c, _):
      dq, dk, dv = g(c, k, v)
      # Chain through all three grads (same shape here since s_q == s_kv)
      # so XLA cannot dead-code-eliminate any part of the backward pass,
      # and the data dependency serializes iterations.
      return c + (dq + dk + dv).astype(c.dtype) * 1e-6, ()
    out, _ = lax.scan(body, q, None, length=n)
    return out
  return run


def _time_per_step(run, n, q, k, v, trials=5):
  _sync(run(q, k, v))  # compile + warm
  times = []
  for _ in range(trials):
    t0 = time.perf_counter()
    _sync(run(q, k, v))
    times.append(time.perf_counter() - t0)
  return float(np.median(times) * 1000 / n)


def ragged_segments(batch, s, k, seed=0):
  """``[batch, s]`` doc ids: k docs per row with ragged boundaries —
  jittered around the equal split so none lands on a kernel block edge
  alignment by construction (the skip logic must not depend on it)."""
  rng = np.random.default_rng(seed * 1000003 + s * 31 + k)
  seg = np.zeros((batch, s), np.int32)
  for b in range(batch):
    cuts = []
    for i in range(1, k):
      base = i * s // k
      cuts.append(int(np.clip(base + rng.integers(-s // (4 * k),
                                                  s // (4 * k) + 1),
                              1, s - 1)))
    bounds = [0] + sorted(set(cuts)) + [s]
    for d in range(len(bounds) - 1):
      seg[b, bounds[d]:bounds[d + 1]] = d
  return seg


def _run_block_diagonal(args):
  """--block-diagonal: packed-row attention at docs-per-row k ∈ {1,4,16}
  vs full attention at the same (b, s); reports per-step time and the
  skipped-tile fraction (also fed into the ``train.attn_tiles_*``
  telemetry counters so the live/offline goodput meters see it)."""
  import jax
  import jax.numpy as jnp

  from lddl_tpu.ops.flash_attention import (count_skippable_tiles,
                                            flash_attention)
  from lddl_tpu.telemetry import get_telemetry

  tele = get_telemetry()
  dev = jax.devices()[0]
  header = (f'# block-diagonal attention bench on {dev.device_kind}: '
            f'batch={args.batch} heads={args.heads} '
            f'head_dim={args.head_dim} bf16, median of {args.trials} scan '
            'windows; "full" = flash over the whole packed row, "bdiag" = '
            'flash with segment ids (cross-doc tiles skipped)\n'
            '# s | k docs | n | full fwd ms | bdiag fwd ms | '
            'full fwd+bwd ms | bdiag fwd+bwd ms | tiles skipped')
  lines = [header]
  print(header, flush=True)
  for s in [int(x) for x in args.seqs.split(',')]:
    key = jax.random.key(s)
    kq, kk, kv = jax.random.split(key, 3)
    shape = (args.batch, args.heads, s, args.head_dim)
    q = jax.random.normal(kq, shape, jnp.bfloat16)
    k = jax.random.normal(kk, shape, jnp.bfloat16)
    v = jax.random.normal(kv, shape, jnp.bfloat16)
    n = max(8, min(256, (4096 * 32) // s))
    for docs in [int(x) for x in args.docs_per_row.split(',')]:
      seg_np = ragged_segments(args.batch, s, docs)
      seg = jnp.asarray(seg_np)
      total, skipped = count_skippable_tiles(seg_np)
      if tele.enabled:
        tele.counter('train.attn_tiles_total').add(total)
        tele.counter('train.attn_tiles_skipped').add(skipped)

      def bdiag(q, k, v, _seg=seg):
        return flash_attention(q, k, v, None, _seg, _seg)

      cells = []
      for make, fn in ((_make_scanned_fwd, flash_attention),
                       (_make_scanned_fwd, bdiag),
                       (_make_scanned_bwd, flash_attention),
                       (_make_scanned_bwd, bdiag)):
        try:
          run = make(fn, n)
          cells.append(
              f'{_time_per_step(run, n, q, k, v, trials=args.trials):8.2f}')
        except Exception as e:  # noqa: BLE001 — OOM is the datapoint here
          if not is_oom(e):
            raise
          cells.append('     OOM')
      row = (f'{s:6d} | {docs:2d} | {n:3d} | ' + ' | '.join(cells) +
             f' | {skipped}/{total} ({skipped / total:.1%})')
      lines.append(row)
      print(row, flush=True)
  text = '\n'.join(lines) + '\n'
  if args.out:
    with open(args.out, 'w', encoding='utf-8') as f:
      f.write(text)


def main(argv=None):
  p = argparse.ArgumentParser(description=__doc__)
  p.add_argument('--batch', type=int, default=8)
  p.add_argument('--heads', type=int, default=12)
  p.add_argument('--head-dim', type=int, default=64)
  p.add_argument('--seqs', default='512,1024,2048,4096,8192,16384')
  p.add_argument('--trials', type=int, default=5)
  p.add_argument('--block-diagonal', action='store_true',
                 help='time packed-row block-diagonal attention (segment-id '
                 'tile skipping) vs full attention at the same shapes')
  p.add_argument('--docs-per-row', default='1,4,16',
                 help='--block-diagonal: comma list of docs packed per row')
  p.add_argument('--out', default=None)
  args = p.parse_args(argv)

  from lddl_tpu.core.compile_cache import use_compile_cache
  use_compile_cache()
  if args.block_diagonal:
    return _run_block_diagonal(args)

  import jax
  import jax.numpy as jnp

  from lddl_tpu.ops.flash_attention import flash_attention

  dev = jax.devices()[0]
  header = (f'# attention bench on {dev.device_kind}: batch={args.batch} '
            f'heads={args.heads} head_dim={args.head_dim} bf16, median of '
            f'{args.trials} scan windows, per-step = window/n (dispatch '
            'amortized inside one jit program)\n'
            '# s | n | dense fwd ms | flash fwd ms | dense fwd+bwd ms | '
            'flash fwd+bwd ms')
  lines = [header]
  print(header, flush=True)

  for s in [int(x) for x in args.seqs.split(',')]:
    key = jax.random.key(s)
    kq, kk, kv = jax.random.split(key, 3)
    shape = (args.batch, args.heads, s, args.head_dim)
    q = jax.random.normal(kq, shape, jnp.bfloat16)
    k = jax.random.normal(kk, shape, jnp.bfloat16)
    v = jax.random.normal(kv, shape, jnp.bfloat16)
    # Deeper scans at short s, where per-step work is smallest relative
    # to the per-window dispatch and sync.
    n = max(8, min(256, (4096 * 32) // s))

    cells = []
    for make, fn in ((_make_scanned_fwd, _dense_attention),
                     (_make_scanned_fwd, flash_attention),
                     (_make_scanned_bwd, _dense_attention),
                     (_make_scanned_bwd, flash_attention)):
      try:
        run = make(fn, n)
        cells.append(f'{_time_per_step(run, n, q, k, v, trials=args.trials):8.2f}')
      except Exception as e:  # noqa: BLE001 — OOM is the datapoint here
        if not is_oom(e):
          raise
        cells.append('     OOM')
    row = f'{s:6d} | {n:3d} | ' + ' | '.join(cells)
    lines.append(row)
    print(row, flush=True)

  text = '\n'.join(lines) + '\n'
  if args.out:
    with open(args.out, 'w', encoding='utf-8') as f:
      f.write(text)


if __name__ == '__main__':
  main()
