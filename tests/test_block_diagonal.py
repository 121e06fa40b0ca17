"""Block-diagonal packed attention: segment-id tile skipping in the
flash/ring kernels vs the dense block-diagonal reference (interpret
mode on CPU — the same kernel code the TPU runs compiled), the packed
loader's doc_offsets -> segment_ids decode, and the packing-aware
per-document MLM loss normalization (arXiv:2107.02027)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lddl_tpu.ops.flash_attention as fa
from lddl_tpu.ops.flash_attention import (count_skippable_tiles,
                                          flash_attention)


def _ragged_segments(b, s, k, seed=0, pad_tail=True):
  """k docs per row, boundaries deliberately NOT multiples of any kernel
  block size; optionally a padded tail (ids -1, mask 0) on row 0."""
  rng = np.random.default_rng(seed)
  seg = np.zeros((b, s), np.int32)
  mask = np.ones((b, s), np.int32)
  for row in range(b):
    cuts = sorted(
        set(int(np.clip(i * s // k + rng.integers(-s // (4 * k), s //
                                                  (4 * k) + 1), 1, s - 1))
            for i in range(1, k)))
    bounds = [0] + cuts + [s]
    for d in range(len(bounds) - 1):
      seg[row, bounds[d]:bounds[d + 1]] = d
  if pad_tail:
    tail = s - max(1, s // 13)  # odd split: never block-aligned
    mask[0, tail:] = 0
    seg[0, tail:] = -1
  return seg, mask


def _inputs(b, h, s, d, seed=0):
  rng = np.random.default_rng(seed)
  mk = lambda: jnp.asarray(rng.standard_normal((b, h, s, d),
                                               dtype=np.float32))
  return mk(), mk(), mk()


def _dense_block_diagonal(q, k, v, mask, seg):
  scale = 1.0 / (q.shape[-1] ** 0.5)
  s = jnp.einsum('bhqd,bhkd->bhqk', q.astype(jnp.float32),
                 k.astype(jnp.float32)) * scale
  s = s + jnp.where(mask, 0.0, -1e9)[:, None, None, :]
  same = seg[:, None, :, None] == seg[:, None, None, :]
  s = s + jnp.where(same, 0.0, -1e9)
  p = jax.nn.softmax(s, axis=-1)
  return jnp.einsum('bhqk,bhkd->bhqd', p, v.astype(jnp.float32))


def _real_mask(mask, h, d):
  return np.asarray(mask, bool)[:, None, :, None]


@pytest.mark.parametrize('s,k', [(512, 4), (2048, 16)])
def test_forward_matches_dense_block_diagonal(s, k):
  b, h, d = 2, 2, 32
  q, kk, v = _inputs(b, h, s, d, seed=s)
  seg, mask = _ragged_segments(b, s, k, seed=s + 1)
  segj, maskj = jnp.asarray(seg), jnp.asarray(mask)
  out = flash_attention(q, kk, v, maskj, segj, segj)
  ref = _dense_block_diagonal(q, kk, v, maskj, segj)
  # Padding rows carry no contract (sliced away in the model); compare
  # real rows only.
  keep = _real_mask(mask, h, d)
  np.testing.assert_allclose(np.asarray(out) * keep, np.asarray(ref) * keep,
                             rtol=2e-5, atol=2e-5)
  assert not np.isnan(np.asarray(out)).any()


@pytest.mark.parametrize('s,k', [(512, 4), (2048, 16)])
def test_gradients_match_dense_block_diagonal(s, k):
  b, h, d = 1, 2, 32
  q, kk, v = _inputs(b, h, s, d, seed=7 * s)
  seg, mask = _ragged_segments(b, s, k, seed=s + 3)
  segj, maskj = jnp.asarray(seg), jnp.asarray(mask)
  cot = jnp.asarray(
      np.random.default_rng(9).standard_normal((b, h, s, d),
                                               dtype=np.float32))
  cot = cot * jnp.asarray(_real_mask(mask, h, d))  # no cotangent on pads

  def loss_flash(q, kv_k, kv_v):
    return jnp.sum(flash_attention(q, kv_k, kv_v, maskj, segj, segj) * cot)

  def loss_dense(q, kv_k, kv_v):
    return jnp.sum(_dense_block_diagonal(q, kv_k, kv_v, maskj, segj) * cot)

  gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, kk, v)
  gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, kk, v)
  for a, b_, name in zip(gf, gd, 'qkv'):
    assert not np.isnan(np.asarray(a)).any(), f'd{name} has NaNs'
    np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=2e-4,
                               atol=2e-4, err_msg=f'd{name}')


@pytest.mark.parametrize('block_q', [64, 128, 256])
def test_multiblock_skip_grid_parity(monkeypatch, block_q):
  """Force tiny blocks so the grid really has skippable cross-doc tiles
  in forward AND both backward kernels, and verify the skipped result
  still matches the dense reference exactly — the tile-skip predicate
  must be conservative, never lossy. With four documents of ~128 tokens
  the three q blocks give interior tiles (64: whole tiles inside one
  document, no segment bias), boundary tiles and skipped ones."""
  monkeypatch.setattr(fa, '_BLOCK_Q', block_q)
  monkeypatch.setattr(fa, '_BLOCK_KV', 128)
  b, h, s, d = 2, 2, 512, 32
  seg, mask = _ragged_segments(b, s, 4, seed=11)
  total, skipped = count_skippable_tiles(seg)
  assert (total, skipped) == count_skippable_tiles(seg, block_q=block_q,
                                                    block_k=128)
  assert skipped > 0  # the point of the test: skips actually happen
  q, kk, v = _inputs(b, h, s, d, seed=13)
  segj, maskj = jnp.asarray(seg), jnp.asarray(mask)
  cot = jnp.asarray(
      np.random.default_rng(5).standard_normal((b, h, s, d),
                                               dtype=np.float32))
  cot = cot * jnp.asarray(_real_mask(mask, h, d))

  out = flash_attention(q, kk, v, maskj, segj, segj)
  ref = _dense_block_diagonal(q, kk, v, maskj, segj)
  keep = _real_mask(mask, h, d)
  np.testing.assert_allclose(np.asarray(out) * keep, np.asarray(ref) * keep,
                             rtol=2e-5, atol=2e-5)

  gf = jax.grad(lambda *a: jnp.sum(flash_attention(*a, maskj, segj, segj) *
                                   cot), argnums=(0, 1, 2))(q, kk, v)
  gd = jax.grad(lambda *a: jnp.sum(_dense_block_diagonal(*a, maskj, segj) *
                                   cot), argnums=(0, 1, 2))(q, kk, v)
  for a, b_, name in zip(gf, gd, 'qkv'):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=2e-4,
                               atol=2e-4, err_msg=f'd{name}')


def test_all_pad_rows_stay_finite():
  """A row that is entirely padding has every tile skipped: its output
  must be exact zeros (0/0 guarded), never NaN — NaN here would poison
  delta in the backward pass of real rows via global reductions."""
  b, h, s, d = 2, 2, 256, 32
  q, kk, v = _inputs(b, h, s, d, seed=17)
  seg = np.zeros((b, s), np.int32)
  mask = np.ones((b, s), np.int32)
  seg[1, :] = -1
  mask[1, :] = 0
  out = flash_attention(q, kk, v, jnp.asarray(mask), jnp.asarray(seg),
                        jnp.asarray(seg))
  arr = np.asarray(out)
  assert not np.isnan(arr).any()
  np.testing.assert_array_equal(arr[1], 0.0)


def test_bf16_segmented():
  b, h, s, d = 1, 2, 384, 64
  q, kk, v = _inputs(b, h, s, d, seed=23)
  seg, mask = _ragged_segments(b, s, 3, seed=29, pad_tail=False)
  qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, kk, v))
  out = flash_attention(qb, kb, vb, jnp.asarray(mask), jnp.asarray(seg),
                        jnp.asarray(seg))
  ref = _dense_block_diagonal(q, kk, v, jnp.asarray(mask), jnp.asarray(seg))
  np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                             rtol=2e-2, atol=2e-2)


def test_interior_tiles_leave_out_the_segment_bias(monkeypatch):
  """A row of two documents cut on a block edge has only interior and
  skipped tiles: no tile runs the elementwise bias, and the result is
  still the dense block-diagonal one (forward and gradients); with a
  padded tail inside the last interior tile, too."""
  monkeypatch.setattr(fa, '_BLOCK_Q', 128)
  monkeypatch.setattr(fa, '_BLOCK_KV', 128)
  b, h, s, d = 1, 2, 512, 32
  seg = np.repeat(np.arange(s)[None, :] // 256, b, 0).astype(np.int32)
  mask = np.ones((b, s), np.int32)
  seg[:, 490:], mask[:, 490:] = -1, 0
  total, skipped = count_skippable_tiles(seg)
  assert (total, skipped) == (16, 8)
  q, kk, v = _inputs(b, h, s, d, seed=31)
  segj, maskj = jnp.asarray(seg), jnp.asarray(mask)
  keep = _real_mask(mask, h, d)
  cot = jnp.asarray(np.random.default_rng(32).standard_normal(
      (b, h, s, d), dtype=np.float32)) * jnp.asarray(keep)
  out = flash_attention(q, kk, v, maskj, segj, segj)
  ref = _dense_block_diagonal(q, kk, v, maskj, segj)
  np.testing.assert_allclose(np.asarray(out) * keep, np.asarray(ref) * keep,
                             rtol=2e-5, atol=2e-5)
  gf = jax.grad(lambda *a: jnp.sum(flash_attention(*a, maskj, segj, segj) *
                                   cot), argnums=(0, 1, 2))(q, kk, v)
  gd = jax.grad(lambda *a: jnp.sum(_dense_block_diagonal(*a, maskj, segj) *
                                   cot), argnums=(0, 1, 2))(q, kk, v)
  for a, b_, name in zip(gf, gd, 'qkv'):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=2e-4,
                               atol=2e-4, err_msg=f'd{name}')


def test_segment_ids_require_pairing():
  q, kk, v = _inputs(1, 1, 64, 32)
  seg = jnp.zeros((1, 64), jnp.int32)
  with pytest.raises(ValueError, match='together'):
    flash_attention(q, kk, v, None, seg, None)


def _two_documents(b, s):
  """Two documents a row, the boundary on no block edge."""
  return np.repeat(np.where(np.arange(s)[None, :] < int(s * 0.574), 0, 1),
                   b, 0).astype(np.int32)


def _tiles_by_pairs(seg, block_q, block_k):
  """(total, skipped) from the definition: a tile is live if it holds
  one same-document pair of real tokens. No intervals, no kernel code."""
  b, s = seg.shape
  assert s % block_q == 0 and s % block_k == 0
  q = seg.reshape(b, s // block_q, 1, block_q, 1)
  k = seg.reshape(b, 1, s // block_k, 1, block_k)
  live = ((q == k) & (q >= 0)).any(axis=(3, 4))
  return live.size, int(live.size - live.sum())


@pytest.mark.parametrize('case', [
    'one-document-s2048', 'sixteen-documents-s8192', 'all-padding',
    'one-document-s8192', 'two-documents-s8192'])
def test_count_skippable_tiles(case):
  """The host's count is the grid's: blocks from the kernels' own
  ``_tile_blocks``, and on whole rows equal to the count by pairs."""
  if case == 'all-padding':
    # All-padding rows skip everything.
    total, skipped = count_skippable_tiles(np.full((1, 512), -1, np.int32))
    assert total > 0 and skipped == total
    return
  s = 2048 if case.endswith('s2048') else 8192
  if case.startswith('one-document'):
    # One doc per row: every tile overlaps itself -> nothing skips.
    seg = np.zeros((2, s), np.int32)
  elif case.startswith('two-documents'):
    seg = _two_documents(2, s)
  else:
    seg, _ = _ragged_segments(2, s, 16, seed=3, pad_tail=False)
  total, skipped = count_skippable_tiles(seg)
  (block_q, padded_q), (block_k, padded_k) = fa._tile_blocks(s, s)
  assert padded_q == padded_k == s
  assert total == 2 * (s // block_q) * (s // block_k)
  assert (total, skipped) == _tiles_by_pairs(seg, block_q, block_k)
  if case.startswith('one-document'):
    assert skipped == 0
  elif case.startswith('two-documents'):
    # Each document's q blocks skip the other document's kv blocks; the
    # one q block and the one kv block on the boundary skip nothing.
    assert 0.25 < skipped / total < 0.5
  else:
    # 16 docs per row at the segmented default blocking: most of the grid
    # is provably cross-document (the acceptance bar for the packed path).
    assert skipped / total > 0.5


def test_ring_flash_matches_dense_block_diagonal():
  from jax.sharding import PartitionSpec as P

  from lddl_tpu.parallel import make_mesh
  from lddl_tpu.parallel.ring import make_ring_attention
  mesh = make_mesh(data=1, fsdp=1, tensor=1, seq=4,
                   devices=jax.devices()[:4])
  b, h, s, d = 2, 2, 64, 32
  q, kk, v = _inputs(b, h, s, d, seed=2)
  # 4 docs over 4 ring shards, ragged boundaries: some rotated shards
  # are whole-shard skips, others straddle and fall through to flash.
  seg, mask = _ragged_segments(b, s, 4, seed=41)
  fn = make_ring_attention(mesh, q_spec=P(None, None, 'seq', None),
                           mask_spec=P(None, 'seq'), block_impl='flash')
  out = fn(q, kk, v, jnp.asarray(mask), jnp.asarray(seg))
  ref = _dense_block_diagonal(q, kk, v, jnp.asarray(mask), jnp.asarray(seg))
  keep = _real_mask(mask, h, d)
  np.testing.assert_allclose(np.asarray(out) * keep, np.asarray(ref) * keep,
                             rtol=2e-4, atol=2e-4)


def test_ring_dense_matches_dense_block_diagonal():
  from jax.sharding import PartitionSpec as P

  from lddl_tpu.parallel import make_mesh
  from lddl_tpu.parallel.ring import make_ring_attention
  mesh = make_mesh(data=1, fsdp=1, tensor=1, seq=2,
                   devices=jax.devices()[:2])
  b, h, s, d = 2, 2, 64, 32
  q, kk, v = _inputs(b, h, s, d, seed=4)
  seg, mask = _ragged_segments(b, s, 3, seed=43)
  fn = make_ring_attention(mesh, q_spec=P(None, None, 'seq', None),
                           mask_spec=P(None, 'seq'), block_impl='dense')
  out = fn(q, kk, v, jnp.asarray(mask), jnp.asarray(seg))
  ref = _dense_block_diagonal(q, kk, v, jnp.asarray(mask), jnp.asarray(seg))
  keep = _real_mask(mask, h, d)
  np.testing.assert_allclose(np.asarray(out) * keep, np.asarray(ref) * keep,
                             rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# loader: doc_offsets -> segment_ids


class TestPackedCollateSegmentIds:

  def _rows(self, specs, seq_len):
    """Synthetic wire rows: specs = list of per-row doc-piece lengths
    (token counts excluding [CLS]/[SEP] overhead — we fabricate the row
    as [CLS] p0 [SEP] p1 [SEP] ... exactly like preprocess/packed.py,
    marking each piece's first token)."""
    from lddl_tpu.core.utils import serialize_np_array
    rows = []
    for pieces in specs:
      ids, marks = [101], []
      for plen in pieces:
        marks.append(len(ids))
        ids.extend([1000 + i for i in range(plen)])
        ids.append(102)
      assert len(ids) <= seq_len
      rows.append({
          'input_ids': serialize_np_array(np.asarray(ids, np.uint16)),
          'doc_offsets': serialize_np_array(np.asarray(marks, np.uint16)),
          'num_tokens': len(ids),
      })
    return rows

  def _collate(self, block_diagonal=True):
    from lddl_tpu.loader.packed import PackedCollate

    class Tok:
      cls_token_id = 101
      sep_token_id = 102
      mask_token_id = 103
      pad_token_id = 0
      vocab_size = 30000

    return PackedCollate(Tok(), block_diagonal=block_diagonal)

  def test_segment_ids_roundtrip(self):
    seq_len = 64
    batch = self._collate()(self._rows([[10, 7, 20], [40]], seq_len),
                            seq_len, epoch=0, step=0)
    assert 'segment_ids' in batch
    seg = batch['segment_ids']
    assert seg.shape == (2, seq_len) and seg.dtype == np.int32
    # Row 0: [CLS] d0(10) [SEP] d1(7) [SEP] d2(20) [SEP] -> lengths
    # incl. trailing SEP: 1+10+1=12 cols of doc0 (CLS joins doc 0),
    # then 8 of doc1, then 21 of doc2, then -1 padding.
    expect0 = np.full(seq_len, -1, np.int32)
    expect0[:12] = 0
    expect0[12:20] = 1
    expect0[20:41] = 2
    np.testing.assert_array_equal(seg[0], expect0)
    # Row 1: single doc -> all real cols are doc 0.
    n1 = 1 + 40 + 1
    assert (seg[1, :n1] == 0).all() and (seg[1, n1:] == -1).all()
    # segment_ids agree with the attention mask about what is padding.
    np.testing.assert_array_equal(seg >= 0, batch['attention_mask'] == 1)

  def test_split_document_chunks_get_own_segments(self):
    """A document split across rows re-marks each chunk (preprocess
    appends a mark per *piece*): every chunk is its own attention
    segment in its row — chunk rows never see a mark-less remainder."""
    seq_len = 32
    # Two rows as the packer would emit for one long split doc: each
    # row's piece list has exactly one entry starting at index 1.
    batch = self._collate()(self._rows([[30], [14, 10]], seq_len),
                            seq_len, epoch=0, step=0)
    seg = batch['segment_ids']
    assert (seg[0][seg[0] >= 0] == 0).all()
    # Second row: continuation chunk is doc 0, next doc is 1.
    assert (seg[1, :16] == 0).all() and (seg[1, 16:27] == 1).all()

  def test_flag_off_omits_key(self):
    batch = self._collate(block_diagonal=False)(
        self._rows([[10]], 32), 32, epoch=0, step=0)
    assert 'segment_ids' not in batch


# ---------------------------------------------------------------------------
# per-document MLM loss normalization


class TestPerDocLossNorm:

  def test_matches_hand_computation(self):
    from lddl_tpu.parallel.train import per_doc_mlm_loss
    ce = jnp.asarray([[1.0, 2.0, 3.0, 4.0, 5.0, 6.0]])
    masked = jnp.asarray([[True, True, False, True, False, True]])
    seg = jnp.asarray([[0, 0, 0, 1, 1, 2]], jnp.int32)
    # doc0 mean = (1+2)/2, doc1 mean = 4, doc2 mean = 6 -> mean over 3.
    got = float(per_doc_mlm_loss(ce, np.asarray(masked), seg, 6))
    assert got == pytest.approx((1.5 + 4.0 + 6.0) / 3)

  def test_docs_without_targets_are_excluded(self):
    from lddl_tpu.parallel.train import per_doc_mlm_loss
    ce = jnp.asarray([[2.0, 8.0, 99.0]])
    masked = jnp.asarray([[True, True, False]])
    seg = jnp.asarray([[0, 0, 1]], jnp.int32)  # doc1 has no MLM targets
    got = float(per_doc_mlm_loss(ce, np.asarray(masked), seg, 3))
    assert got == pytest.approx(5.0)

  def test_packed_equals_unpacked_mean(self):
    """The 2107.02027 property: a packed row of two docs yields the
    same loss as averaging the two docs' standalone (per-sequence
    normalized) losses — which the naive masked-token mean violates
    whenever the docs have different mask counts."""
    from lddl_tpu.parallel.train import per_doc_mlm_loss
    rng = np.random.default_rng(0)
    ce_a, ce_b = rng.random(8).astype(np.float32), rng.random(
        2).astype(np.float32)
    packed_ce = jnp.asarray(np.concatenate([ce_a, ce_b])[None])
    masked = jnp.ones((1, 10), bool)
    seg = jnp.asarray(np.r_[np.zeros(8), np.ones(2)][None].astype(np.int32))
    got = float(per_doc_mlm_loss(packed_ce, np.asarray(masked), seg, 10))
    want = (ce_a.mean() + ce_b.mean()) / 2
    assert got == pytest.approx(want, rel=1e-6)
    naive = float(packed_ce.mean())
    assert abs(naive - want) > 1e-3  # the bias the normalization removes

  def test_pretrain_loss_consumes_segment_ids(self):
    """End-to-end: a batch carrying segment_ids runs block-diagonal
    attention + per-doc normalization through the real loss, finite and
    differentiable."""
    from lddl_tpu.loader.bert import IGNORE_INDEX
    from lddl_tpu.models import BertConfig, BertForPretraining
    from lddl_tpu.parallel.train import pretrain_loss
    rng = np.random.default_rng(3)
    b, s = 2, 64
    cfg = BertConfig(vocab_size=128, hidden_size=32, num_layers=2,
                     num_heads=2, intermediate_size=64,
                     max_position_embeddings=s, dtype=jnp.float32,
                     attention_impl='flash')
    model = BertForPretraining(cfg)
    seg, mask = _ragged_segments(b, s, 3, seed=51)
    labels = np.full((b, s), IGNORE_INDEX, np.int32)
    labels[:, 2:20:3] = rng.integers(5, 128, labels[:, 2:20:3].shape)
    batch = {
        'input_ids': jnp.asarray(rng.integers(5, 128, (b, s)), jnp.int32),
        'token_type_ids': jnp.zeros((b, s), jnp.int32),
        'attention_mask': jnp.asarray(mask),
        'labels': jnp.asarray(labels),
        'next_sentence_labels': jnp.zeros((b,), jnp.int32),
        'segment_ids': jnp.asarray(seg),
    }
    params = model.init(jax.random.key(0), batch['input_ids'],
                        batch['token_type_ids'], batch['attention_mask'],
                        segment_ids=batch['segment_ids'])['params']

    def loss_fn(p):
      return pretrain_loss(model, p, batch, max_predictions=16)[0]

    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert np.isfinite(float(loss))
    flat = jax.tree_util.tree_leaves(grads)
    assert all(np.isfinite(np.asarray(g)).all() for g in flat)


# ---------------------------------------------------------------------------
# telemetry plumbing


def test_goodput_meter_reports_skip_fraction():
  from lddl_tpu.telemetry.live import goodput_meters
  merged = {'metrics': {
      'train.attn_tiles_total': {'kind': 'counter', 'total': 200},
      'train.attn_tiles_skipped': {'kind': 'counter', 'total': 150},
  }}
  meters = goodput_meters(merged)
  assert meters['attn_tile_skip_fraction'] == pytest.approx(0.75)
  assert goodput_meters({'metrics': {}})['attn_tile_skip_fraction'] is None
