"""The flash kernels under causality (interpreted on the CPU): causal
attention, alone and with same-document packing, with grouped key/value
heads read in place, against a dense masked reference, on tiles that lie
below, on and above the diagonal; and the host's count of the tiles the
grid skips."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lddl_tpu.ops import flash_attention as fa
from lddl_tpu.ops.attention import attend


def _dense(q, k, v, mask, seg, causal):
  group = q.shape[1] // k.shape[1]
  k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
  s = jnp.einsum('bhqd,bhkd->bhqk', q, k) / q.shape[-1] ** 0.5
  s = s + jnp.where(mask, 0.0, -1e9)[:, None, None, :]
  if seg is not None:
    s = s + jnp.where(seg[:, None, :, None] == seg[:, None, None, :], 0.0,
                      -1e9)
  if causal:
    s = s + jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), 0.0, -1e9)
  return jnp.einsum('bhqk,bhkd->bhqd', jax.nn.softmax(s, axis=-1), v)


def _inputs(b, h, kvh, s, d, seed=0):
  rng = np.random.default_rng(seed)
  q, k, v = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
             for shape in ((b, h, s, d), (b, kvh, s, d), (b, kvh, s, d)))
  lens = np.array([s, s - 37][:b])
  mask = np.arange(s)[None] < lens[:, None]
  seg = np.full((b, s), -1, np.int32)
  for i in range(b):
    cuts = np.sort(rng.choice(np.arange(1, lens[i]), 3, replace=False))
    seg[i, :lens[i]] = np.searchsorted(cuts, np.arange(lens[i]),
                                       side='right')
  return q, k, v, jnp.asarray(mask), jnp.asarray(seg)


# Caps of 128 on 384 tokens: a 3 x 3 grid, so tiles stand below, on and
# above the diagonal.
@pytest.mark.parametrize('segmented', [False, True])
@pytest.mark.parametrize('heads', [(4, 2), (2, 2)])
def test_causal_flash_matches_dense(monkeypatch, segmented, heads):
  monkeypatch.setattr(fa, '_BLOCK_Q', 128)
  monkeypatch.setattr(fa, '_BLOCK_KV', 128)
  q, k, v, mask, seg = _inputs(2, *heads, 384, 16)
  seg = seg if segmented else None
  real = np.asarray(mask)[:, None, :, None]
  cot = jnp.asarray(np.random.default_rng(9).standard_normal(q.shape),
                    jnp.float32) * real

  def flash(q, k, v):
    return fa.flash_attention(q, k, v, mask, seg, seg, causal=True)

  def dense(q, k, v):
    return _dense(q, k, v, mask, seg, True)

  # float32 on both sides: the kernel's running softmax rescales its sums
  # tile by tile, the dense path sums 384 keys at once (round-off, 2e-5);
  # the gradients' dS = P * (dP - delta) is a difference of near-equal
  # terms, ten times that relative to its size (1e-4).
  np.testing.assert_allclose(np.where(real, flash(q, k, v), 0),
                             np.where(real, dense(q, k, v), 0),
                             rtol=2e-5, atol=2e-5)
  got = jax.grad(lambda *a: jnp.sum(flash(*a) * cot), (0, 1, 2))(q, k, v)
  want = jax.grad(lambda *a: jnp.sum(dense(*a) * cot), (0, 1, 2))(q, k, v)
  for g, w in zip(got, want):
    np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)
  # XLA's path of ``attend`` takes the same term and the same groups.
  np.testing.assert_allclose(
      np.where(real, attend(q, k, v, mask, seg, impl='dense', mesh=None,
                            dtype=jnp.float32, causal=True), 0),
      np.where(real, dense(q, k, v), 0), rtol=2e-5, atol=2e-5)


def test_count_skippable_tiles_counts_the_tiles_above_the_diagonal():
  # One document: of a 3 x 3 grid the three tiles above the diagonal skip.
  seg = np.zeros((1, 384), np.int32)
  assert fa.count_skippable_tiles(seg, 128, 128, causal=True) == (9, 3)
  assert fa.count_skippable_tiles(seg, 128, 128) == (9, 0)
  # Documents [0, 200) and [200, 384): tiles (q 0, k 2) and (q 2, k 0)
  # hold no same-document pair; (q 0, k 1) and (q 1, k 2) are above the
  # diagonal besides.
  seg[0, 200:] = 1
  assert fa.count_skippable_tiles(seg, 128, 128, causal=True) == (9, 4)
  assert fa.count_skippable_tiles(seg, 128, 128) == (9, 2)


def test_ring_refuses_what_it_cannot_rotate():
  q = jnp.zeros((1, 4, 8, 8))
  kv = jnp.zeros((1, 2, 8, 8))
  mask = jnp.ones((1, 8), bool)
  with pytest.raises(NotImplementedError):
    attend(q, kv, kv, mask, None, impl='ring', mesh=object(),
           dtype=jnp.float32)
