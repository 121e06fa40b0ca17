"""Fast (columnar/device) preprocess engine: mirror fidelity vs the
reference-style python engine, masking backends, and end-to-end parity."""

import os
import random

import numpy as np
import pyarrow.parquet as pq
import pytest

from lddl_tpu.core import deserialize_np_array, get_all_parquets_under
from lddl_tpu.core.random import rng_from_key
from lddl_tpu.pipeline.executor import Executor
from lddl_tpu.preprocess import bert
from lddl_tpu.preprocess.pairing import (
    TokenizedDocs,
    plan_pairs_partition,
)
from lddl_tpu.preprocess.readers import read_corpus
from lddl_tpu.tokenization.wordpiece import load_bert_tokenizer


@pytest.fixture()
def tokenizer(tiny_vocab):
  return load_bert_tokenizer(vocab_file=tiny_vocab, backend='hf')


def _doc_lines(n=8, sentences=5, words=8, seed=9):
  from tests.conftest import WORDS
  r = random.Random(seed)
  lines = []
  for d in range(n):
    sents = [
        (' '.join(r.choice(WORDS) for _ in range(words)) + '.').capitalize()
        for _ in range(sentences)
    ]
    lines.append(f'doc-{d} ' + ' '.join(sents))
  return lines


def _tokenized_docs(tokenizer, lines):
  texts = [line.split(None, 1)[1] for line in lines]
  return bert.encode_documents(texts, tokenizer, sentence_backend='rules')


class TestPlanMirrorsSlowPath:
  """plan_pairs_partition must be draw-for-draw identical to
  create_pairs_from_document given the same rng."""

  def _slow(self, tokenizer, lines, seed, dup=2, max_seq=32):
    docs = bert.documents_from_lines(lines, tokenizer,
                                     sentence_backend='rules')
    rng = rng_from_key(seed, 'mirror')
    out = []
    for _ in range(dup):
      for di in range(len(docs)):
        out.extend(
            bert.create_pairs_from_document(docs, di, rng,
                                            max_seq_length=max_seq))
    return out

  def _fast(self, tokenizer, lines, seed, dup=2, max_seq=32):
    docs = _tokenized_docs(tokenizer, lines)
    rng = rng_from_key(seed, 'mirror')
    a_r, b_r, isr = plan_pairs_partition(docs, rng, max_seq_length=max_seq,
                                         duplicate_factor=dup)
    flat = docs.flat_ids
    words = tokenizer.vocab_words
    out = []
    for i in range(len(isr)):
      a = ' '.join(words[t] for t in flat[a_r[i, 0]:a_r[i, 1]])
      b = ' '.join(words[t] for t in flat[b_r[i, 0]:b_r[i, 1]])
      out.append({
          'A': a, 'B': b, 'is_random_next': bool(isr[i]),
          'num_tokens': (a_r[i, 1] - a_r[i, 0]) + (b_r[i, 1] - b_r[i, 0]) + 3,
      })
    return out

  @pytest.mark.parametrize('seed', [1, 7, 23])
  def test_mirror(self, tokenizer, seed):
    lines = _doc_lines(seed=seed)
    assert self._fast(tokenizer, lines, seed) == \
        self._slow(tokenizer, lines, seed)

  def test_mirror_single_sentence_docs(self, tokenizer):
    lines = [f'doc-{d} Alpha bravo charlie delta.' for d in range(5)]
    assert self._fast(tokenizer, lines, 3) == self._slow(tokenizer, lines, 3)

  def test_zero_sentence_docs_rejected(self, tokenizer):
    with pytest.raises(ValueError):
      TokenizedDocs(np.zeros(0, np.int32), np.zeros(1, np.int64), [2, 0, 1])


def _run_engine(tmp_corpus, tiny_vocab, sink, engine, masking=False,
                mask_backend='host', tok='hf', seed=42):
  cfg = bert.BertPretrainConfig(
      vocab_file=tiny_vocab,
      target_seq_length=32,
      duplicate_factor=2,
      masking=masking,
      bin_size=8,
      seed=seed,
      sentence_backend='rules',
      engine=engine,
      tokenizer_backend=tok,
      mask_backend=mask_backend,
  )
  corpus = read_corpus(tmp_corpus, num_blocks=4, sample_ratio=1.0)
  bert.run(corpus, sink, cfg, executor=Executor(num_local_workers=1))
  return sink


class TestEndToEndParity:

  def test_fast_equals_python_unmasked(self, tmp_corpus, tiny_vocab,
                                       tmp_path):
    fast = _run_engine(tmp_corpus, tiny_vocab, str(tmp_path / 'f'), 'fast')
    slow = _run_engine(tmp_corpus, tiny_vocab, str(tmp_path / 'p'), 'python')
    pf, ps = get_all_parquets_under(fast), get_all_parquets_under(slow)
    assert [os.path.basename(p) for p in pf] == \
        [os.path.basename(p) for p in ps]
    for a, b in zip(pf, ps):
      assert pq.read_table(a).equals(pq.read_table(b)), a

  def test_fast_bit_identical_reruns(self, tmp_corpus, tiny_vocab, tmp_path):
    s1 = _run_engine(tmp_corpus, tiny_vocab, str(tmp_path / 'a'), 'fast',
                     masking=True)
    s2 = _run_engine(tmp_corpus, tiny_vocab, str(tmp_path / 'b'), 'fast',
                     masking=True)
    for a, b in zip(get_all_parquets_under(s1), get_all_parquets_under(s2)):
      assert pq.read_table(a).equals(pq.read_table(b))


def _check_masked_rows(sink, sep_required=True):
  n_rows = 0
  tot_pos = tot_tok = 0
  for p in get_all_parquets_under(sink):
    t = pq.read_table(p)
    delta = 'mask_delta_positions' in t.schema.names
    for r in t.to_pylist():
      a, b = r['A'].split(), r['B'].split()
      n = len(a) + len(b) + 3
      assert r['num_tokens'] == n
      if delta:
        # delta format: one base row packs duplicate_factor mask copies
        pos_all = deserialize_np_array(r['mask_delta_positions'])
        ks = deserialize_np_array(r['mask_delta_k'])
        assert pos_all.dtype == np.uint16
        assert ks.dtype == np.uint16 and len(ks) >= 1
        copies = []
        s = 0
        for k in ks:
          copies.append(pos_all[s:s + int(k)])
          s += int(k)
        assert s == len(pos_all)
      else:
        pos = deserialize_np_array(r['masked_lm_positions'])
        labels = r['masked_lm_labels'].split()
        assert pos.dtype == np.uint16
        assert len(pos) == len(labels)
        copies = [pos]
      for pos in copies:
        assert len(pos) >= 1
        assert list(pos) == sorted(pos)
        for p_ in pos:
          # structural: picked positions are never the [CLS]/[SEP] slots
          assert 0 < p_ < n - 1 and p_ != len(a) + 1
        tot_pos += len(pos)
        tot_tok += n
        n_rows += 1
  assert n_rows > 0
  return n_rows, tot_pos / tot_tok


class TestMaskingBackends:

  def test_host_masked_invariants(self, tmp_corpus, tiny_vocab, tmp_path):
    sink = _run_engine(tmp_corpus, tiny_vocab, str(tmp_path / 'm'), 'fast',
                       masking=True, mask_backend='host')
    n, ratio = _check_masked_rows(sink)
    assert 0.08 < ratio < 0.25

  def test_device_masked_invariants(self, tmp_corpus, tiny_vocab, tmp_path):
    # 'device' exercises the fused jit kernel; under tests JAX runs on the
    # CPU backend, same code path as a real TPU.
    sink = _run_engine(tmp_corpus, tiny_vocab, str(tmp_path / 'd'), 'fast',
                       masking=True, mask_backend='device')
    n, ratio = _check_masked_rows(sink)
    assert 0.08 < ratio < 0.25

  def test_host_device_same_structure(self, tmp_corpus, tiny_vocab,
                                      tmp_path):
    h = _run_engine(tmp_corpus, tiny_vocab, str(tmp_path / 'h'), 'fast',
                    masking=True, mask_backend='host')
    d = _run_engine(tmp_corpus, tiny_vocab, str(tmp_path / 'dv'), 'fast',
                    masking=True, mask_backend='device')
    for a, b in zip(get_all_parquets_under(h), get_all_parquets_under(d)):
      ta, tb = pq.read_table(a), pq.read_table(b)
      # masking bits differ across backends; pair structure must not
      assert ta.column('num_tokens').equals(tb.column('num_tokens'))
      assert ta.column('is_random_next').equals(tb.column('is_random_next'))


def test_auto_mask_backend_is_host_without_probing(monkeypatch):
  """'auto' decides which RNG stream writes the shards, so it must be a
  constant: no clock, no device probe, no jax import can change it."""
  import sys
  import time

  from lddl_tpu.ops.masking import resolve_mask_backend

  def no_clock(*a, **k):
    raise AssertionError('resolve_mask_backend read a clock')

  for name in ('time', 'perf_counter', 'monotonic'):
    monkeypatch.setattr(time, name, no_clock)
  monkeypatch.setitem(sys.modules, 'jax', None)  # `import jax` would raise
  assert resolve_mask_backend('auto') == 'host'
  assert resolve_mask_backend('host') == 'host'
  assert resolve_mask_backend('device') == 'device'


class TestMaskingOps:

  def test_mask_batch_host_exact_k(self):
    from lddl_tpu.ops import mask_batch_host
    rng = np.random.Generator(np.random.Philox(key=np.uint64(7)))
    n, l = 64, 32
    na = np.full(n, 10, np.int32)
    row_len = np.full(n, 25, np.int32)
    ids = np.full((n, l), 6, np.int32)
    masked, picked = mask_batch_host(
        ids, row_len, na, masked_lm_ratio=0.15, vocab_size=30, mask_id=4,
        np_rng=rng)
    k = picked.sum(axis=1)
    assert (k == max(1, round(25 * 0.15))).all()
    # specials and padding never picked
    assert not picked[:, 0].any()
    assert not picked[:, 11].any()
    assert not picked[:, 24].any()
    assert not picked[:, 25:].any()
    # unpicked positions unchanged
    assert (masked[~picked] == ids[~picked]).all()

  def test_mask_partition_device_counts(self):
    from lddl_tpu.ops import mask_partition_device
    flat = np.arange(100, dtype=np.int32) % 30
    a_ranges = np.array([[0, 10], [20, 35]], np.int64)
    b_ranges = np.array([[40, 52], [60, 70]], np.int64)
    pos, new_ids, k = mask_partition_device(
        flat, a_ranges, b_ranges, seq_len=64, masked_lm_ratio=0.15,
        vocab_size=30, mask_id=4, cls_id=2, sep_id=3, seed=11)
    row_len = np.array([10 + 12 + 3, 15 + 10 + 3])
    assert (k == np.maximum(1, np.rint(row_len * 0.15))).all()
    for i in range(2):
      p = pos[i, :k[i]]
      assert (np.diff(p) > 0).all()
      na = a_ranges[i, 1] - a_ranges[i, 0]
      assert (p != 0).all() and (p != 1 + na).all() and \
          (p != row_len[i] - 1).all()
      assert (p < row_len[i] - 1).all()

  def test_max_predictions_cap(self):
    from lddl_tpu.ops import mask_batch
    ids = np.full((8, 64), 5, np.int32)
    row_len = np.full(8, 60, np.int32)
    na = np.full(8, 20, np.int32)
    _, picked = mask_batch(
        ids, row_len, na, masked_lm_ratio=0.5, vocab_size=30, mask_id=4,
        seed=3, backend='host', max_predictions=7)
    assert (picked.sum(axis=1) == 7).all()


class TestRaggedMaskParity:

  def test_native_matches_numpy_bitwise(self):
    """The fused C++ partition masking (lddl_mask_partition) and its
    numpy fallback implement one shared Philox/Fisher-Yates draw spec;
    all five outputs must be bit-identical, or shard bits would depend
    on toolchain availability."""
    from lddl_tpu.ops import masking as M
    rng = np.random.default_rng(77)
    for trial in range(10):
      flat = rng.integers(5, 30000, 4000).astype(np.int32)
      n = int(rng.integers(1, 120))
      a0 = rng.integers(0, 3000, n)
      b0 = rng.integers(0, 3000, n)
      a_ranges = np.stack([a0, a0 + rng.integers(1, 80, n)], 1)
      b_ranges = np.stack([b0, b0 + rng.integers(1, 80, n)], 1)
      kw = dict(masked_lm_ratio=0.15, vocab_size=30000, mask_id=4,
                seed=int(rng.integers(0, 2**63)),
                max_predictions=None if trial % 2 else 12)
      old = M._TOPK_NATIVE
      try:
        M._TOPK_NATIVE = None
        nat = M.mask_partition_host(flat, a_ranges, b_ranges, **kw)
        if not M._TOPK_NATIVE:
          pytest.skip('native toolchain unavailable')
        M._TOPK_NATIVE = False
        fb = M.mask_partition_host(flat, a_ranges, b_ranges, **kw)
      finally:
        M._TOPK_NATIVE = old
      for name, x, y in zip(('flat_a', 'flat_b', 'pos', 'labels', 'k'),
                            nat, fb):
        assert x.dtype == y.dtype, name
        assert np.array_equal(x, y), name

  def test_bogus_offsets_rejected(self):
    """Caller-supplied offs_a/offs_b feed the native kernel's scatter
    unchecked, so anything that is not the exact cumsum of the segment
    lengths must raise instead of silently writing out of bounds."""
    from lddl_tpu.ops import mask_partition_host
    flat = (np.arange(500, dtype=np.int32) * 3) % 20000 + 10
    a_ranges = np.array([[0, 20], [50, 80]], np.int64)
    b_ranges = np.array([[100, 130], [200, 210]], np.int64)
    kw = dict(masked_lm_ratio=0.15, vocab_size=20000, mask_id=4, seed=9)
    na = a_ranges[:, 1] - a_ranges[:, 0]
    nb = b_ranges[:, 1] - b_ranges[:, 0]
    good_a = np.zeros(3, np.int64)
    np.cumsum(na, out=good_a[1:])
    good_b = np.zeros(3, np.int64)
    np.cumsum(nb, out=good_b[1:])
    baseline = mask_partition_host(flat, a_ranges, b_ranges, **kw)
    # correct explicit offsets reproduce the default path bit-for-bit
    explicit = mask_partition_host(flat, a_ranges, b_ranges,
                                   offs_a=good_a, offs_b=good_b, **kw)
    for x, y in zip(baseline, explicit):
      assert np.array_equal(x, y)
    with pytest.raises(ValueError, match='offs_a'):
      mask_partition_host(flat, a_ranges, b_ranges,
                          offs_a=good_a[:-1], offs_b=good_b, **kw)
    bad = good_a.copy()
    bad[1] += 1  # not the cumsum of na
    with pytest.raises(ValueError, match='offs_a'):
      mask_partition_host(flat, a_ranges, b_ranges,
                          offs_a=bad, offs_b=good_b, **kw)
    with pytest.raises(ValueError, match='offs_b'):
      mask_partition_host(flat, a_ranges, b_ranges,
                          offs_a=good_a, offs_b=good_b + 1, **kw)

  def test_structure_and_determinism(self):
    from lddl_tpu.ops import mask_partition_host
    flat = (np.arange(2000, dtype=np.int32) * 7) % 25000 + 10
    a_ranges = np.array([[0, 30], [100, 160], [500, 505]], np.int64)
    b_ranges = np.array([[700, 740], [900, 910], [1200, 1260]], np.int64)
    kw = dict(masked_lm_ratio=0.15, vocab_size=25000, mask_id=4, seed=3)
    fa1, fb1, pos1, lab1, k1 = mask_partition_host(flat, a_ranges, b_ranges,
                                                   **kw)
    fa2, fb2, pos2, lab2, k2 = mask_partition_host(flat, a_ranges, b_ranges,
                                                   **kw)
    assert np.array_equal(fa1, fa2) and np.array_equal(pos1, pos2)
    na = a_ranges[:, 1] - a_ranges[:, 0]
    nb = b_ranges[:, 1] - b_ranges[:, 0]
    row_len = na + nb + 3
    assert np.array_equal(
        k1, np.minimum(np.maximum(1, np.rint(row_len * 0.15)), na + nb))
    offs = np.zeros(4, np.int64)
    np.cumsum(k1, out=offs[1:])
    for r in range(3):
      p = pos1[offs[r]:offs[r + 1]].astype(np.int64)
      assert (np.diff(p) > 0).all()  # sorted, unique
      assert (p > 0).all() and (p != 1 + na[r]).all() \
          and (p < row_len[r] - 1).all()
    # unpicked positions keep their original ids
    offs_a = np.zeros(4, np.int64)
    np.cumsum(na, out=offs_a[1:])
    orig_a = np.concatenate(
        [flat[a_ranges[r, 0]:a_ranges[r, 1]] for r in range(3)])
    changed = np.nonzero(orig_a != fa1)[0]
    picked_a = []
    ri = np.repeat(np.arange(3), k1)
    in_a = pos1.astype(np.int64) - 1 < na[ri]
    picked_a = offs_a[ri[in_a]] + pos1[in_a].astype(np.int64) - 1
    assert set(changed) <= set(picked_a.tolist())


class TestPositionsSerialization:

  def test_binary_parts_match_serialize_u16_batch(self):
    from lddl_tpu.core.utils import serialize_u16_batch, u16_batch_binary_parts
    rng = np.random.default_rng(3)
    for _ in range(5):
      n = int(rng.integers(1, 40))
      counts = rng.integers(0, 30, n)
      offs = np.zeros(n + 1, np.int64)
      np.cumsum(counts, out=offs[1:])
      vals = rng.integers(0, 512, int(offs[-1])).astype('<u2')
      expected = serialize_u16_batch(vals, offs)
      boffs, data = u16_batch_binary_parts(vals, offs)
      raw = data.tobytes()
      got = [raw[boffs[i]:boffs[i + 1]] for i in range(n)]
      assert got == expected

  def test_empty(self):
    from lddl_tpu.core.utils import u16_batch_binary_parts
    boffs, data = u16_batch_binary_parts(np.zeros(0, '<u2'),
                                         np.zeros(1, np.int64))
    assert len(boffs) == 1 and len(data) == 0

  def test_sub_span_offsets(self):
    """Offsets describing a sub-span of values (like serialize_u16_batch
    supports) must serialize that span, not crash or shift."""
    from lddl_tpu.core.utils import serialize_u16_batch, u16_batch_binary_parts
    vals = np.arange(10).astype('<u2')
    offs = np.array([2, 5, 9], np.int64)
    expected = serialize_u16_batch(vals, offs)
    boffs, data = u16_batch_binary_parts(vals, offs)
    raw = data.tobytes()
    assert [raw[boffs[i]:boffs[i + 1]] for i in range(2)] == expected


class TestTopkSelection:

  def test_native_matches_numpy(self):
    """The C++ per-row top-k (native/src/masking.cpp) must emit exactly
    what the numpy argpartition path emits — same picked set, same
    row-major order — or the downstream decide/replacement RNG draws
    would shift and masked outputs would differ by backend."""
    from lddl_tpu.ops import masking as M
    rng = np.random.default_rng(123)
    for _ in range(30):
      n = int(rng.integers(1, 300))
      l = int(rng.choice([16, 64, 128, 131, 200]))
      u = rng.random((n, l))
      lane_bits = max(1, (l - 1)).bit_length()
      keys = (u.view(np.uint64) & ~np.uint64((1 << lane_bits) - 1)
              | np.arange(l, dtype=np.uint64)[None, :])
      k = rng.integers(0, l + 1, n)
      old = M._TOPK_NATIVE
      try:
        M._TOPK_NATIVE = None
        pr1, pc1, p1 = M._select_topk(keys, k, n, l)
        if not M._TOPK_NATIVE:
          pytest.skip('native toolchain unavailable')
        M._TOPK_NATIVE = False
        pr2, pc2, p2 = M._select_topk(keys, k, n, l)
      finally:
        M._TOPK_NATIVE = old
      assert np.array_equal(pr1, pr2)
      assert np.array_equal(pc1, pc2)
      assert np.array_equal(p1, p2)
