"""Long rows go through the reference's attention in blocks of (row, head)
pairs and, inside a block, a tile of queries at a time; the answer and
every gradient are the whole-block path's to float32 rounding, with and
without documents; the fp8 control runs through the same tiles."""

import numpy as np
import pytest


def attention_and_grads(reference, precision, packed):
  import jax
  import jax.numpy as jnp
  rows, s, heads, d = 2, 64, 2, 16
  keys = jax.random.split(jax.random.key(3), 10)
  x = jax.random.normal(keys[0], (rows, s, d), jnp.float32)
  lp = {}
  for i, p in enumerate('qkvo'):
    lp[f'{p}_w'] = 0.3 * jax.random.normal(keys[1 + i], (d, d), jnp.float32)
    lp[f'{p}_b'] = 0.1 * jax.random.normal(keys[5 + i], (d,), jnp.float32)
  seg = np.stack([np.repeat([0, 1, 2, -1], [20, 30, 10, 4]),
                  np.repeat([0, 1, -1], [40, 16, 8])]).astype(np.int32)
  key_real = jnp.asarray(seg >= 0)
  segs = jnp.asarray(seg) if packed else None
  weight = jax.random.normal(keys[9], (rows, s, d), jnp.float32)

  def loss(x, lp):
    out = reference._attention(x, lp, key_real, segs, heads, precision)
    return jnp.sum(out * weight), out

  (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(
      x, lp)
  return [out, grads[0], *(grads[1][k] for k in sorted(lp))]


@pytest.mark.parametrize('packed', [True, False])
@pytest.mark.parametrize('precision', ['float32', 'fp8'])
def test_blocks_and_tiles_give_what_the_whole_block_gives(
    monkeypatch, precision, packed):
  from chipbench import reference
  whole = attention_and_grads(reference, precision, packed)
  # Two (row, head) pairs a block, sixteen queries a tile.
  monkeypatch.setattr(reference, '_SCORE_BLOCK_BYTES', 2 * 4 * 64 * 64)
  monkeypatch.setattr(reference, '_QUERY_TILE', 16)
  tiled = attention_and_grads(reference, precision, packed)
  if precision == 'fp8':
    # The control scales each fp8 operand by its own largest element, a
    # tile's or a block's: another rounding, so only that it runs.
    assert all(np.all(np.isfinite(a)) for a in tiled)
    return
  largest = max(float(np.max(np.abs(b))) for b in whole)
  for a, b in zip(tiled, whole):
    # The key's bias has a gradient of nought under softmax: what is left
    # of it is the rounding of sums of the largest leaves' size.
    scale = max(float(np.max(np.abs(b))), 0.05 * largest)
    assert float(np.max(np.abs(a - b))) <= 5e-6 * scale
  # ... and the tiles were really taken.
  assert any(float(np.max(np.abs(a - b))) > 0 for a, b in zip(tiled, whole))
