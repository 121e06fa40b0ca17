"""Native C++ tokenizer: build, HF parity, sentence-split parity, decode."""

import random

import numpy as np
import pytest

pytest.importorskip('transformers')


@pytest.fixture(scope='module')
def native_mod():
  try:
    from lddl_tpu.native import build_library
    build_library()
  except Exception as e:  # no compiler on this host
    pytest.skip(f'native library unavailable: {e}')
  from lddl_tpu import native
  return native


@pytest.fixture(scope='module')
def rich_vocab(tmp_path_factory):
  words = ['[PAD]', '[UNK]', '[CLS]', '[SEP]', '[MASK]']
  words += ['run', 'walk', 'talk', 'read', 'dog', 'cat', 'house', 'tree',
            'the', 'a', 'and', 'cafe', 'francais', 'uber', 'strasse',
            'naive', 'zurich', 'fast', 'slow', 'kind']
  words += ['##' + s for s in ('ing', 'ed', 'er', 's', 'ly', 'ness', 'able')]
  words += list('.,!?;:()[]"\'-0123456789')
  words += ['##' + c for c in '0123456789']
  words += ['中', '国', '人', '日', '本']
  path = tmp_path_factory.mktemp('vocab') / 'rich_vocab.txt'
  path.write_text('\n'.join(dict.fromkeys(words)) + '\n', encoding='utf-8')
  return str(path)


@pytest.fixture(scope='module')
def hf_and_native(native_mod, rich_vocab):
  from transformers import BertTokenizerFast
  hf = BertTokenizerFast(vocab_file=rich_vocab, do_lower_case=True)
  return hf, native_mod.NativeWordPiece.from_hf(hf)


_SAMPLE_WORDS = [
    'running', 'walked', 'dogs', 'cats', 'faster', 'slowly', 'kindness',
    'readable', 'café', 'Français', 'Über', 'Straße', 'naïve', 'Zürich',
    'xyzzy', 'qwerty123', '中国', '日本人', 'U.S.', 'Mr.', 'e.g.', '3.14',
    'hello-world', '"quote"', "it's", 'the', 'a', 'and', 'ОЧЕНЬ', 'Δοκιμή',
]


class TestHfParity:

  def test_tokenize_matches_hf(self, hf_and_native):
    hf, nat = hf_and_native
    r = random.Random(0)
    for _ in range(500):
      text = ' '.join(r.choice(_SAMPLE_WORDS) for _ in range(r.randrange(1, 12)))
      if r.random() < 0.3:
        text = text.capitalize() + r.choice('.!?')
      assert nat.tokenize(text) == hf.tokenize(text), repr(text)

  def test_batch_ids_match_hf(self, hf_and_native):
    hf, nat = hf_and_native
    texts = [' '.join(_SAMPLE_WORDS[i:i + 5]) for i in range(20)]
    ids, offsets = nat.encode_batch_ids(texts)
    encs = hf.backend_tokenizer.encode_batch(texts, add_special_tokens=False)
    hf_flat = [i for e in encs for i in e.ids]
    assert ids.tolist() == hf_flat
    assert offsets.tolist() == list(
        np.cumsum([0] + [len(e.ids) for e in encs]))

  def test_max_tokens_truncation(self, hf_and_native):
    _, nat = hf_and_native
    toks = nat.tokenize('the dog and the cat and the tree', max_length=3)
    assert len(toks) == 3

  def test_empty_and_whitespace(self, hf_and_native):
    hf, nat = hf_and_native
    for text in ('', '   ', '\t\n', 'the'):
      assert nat.tokenize(text) == hf.tokenize(text)

  def test_unk_for_long_word(self, hf_and_native):
    hf, nat = hf_and_native
    w = 'x' * 150
    assert nat.tokenize(w) == hf.tokenize(w) == ['[UNK]']

  def test_threading_invariant(self, native_mod, rich_vocab):
    from transformers import BertTokenizerFast
    hf = BertTokenizerFast(vocab_file=rich_vocab, do_lower_case=True)
    one = native_mod.NativeWordPiece.from_hf(hf, num_threads=1)
    four = native_mod.NativeWordPiece.from_hf(hf, num_threads=4)
    texts = [' '.join(_SAMPLE_WORDS) for _ in range(64)]
    i1, o1 = one.encode_batch_ids(texts)
    i4, o4 = four.encode_batch_ids(texts)
    assert np.array_equal(i1, i4) and np.array_equal(o1, o4)


class TestSentenceSplit:

  def test_matches_python_rules(self, hf_and_native):
    from lddl_tpu.tokenization.sentences import _rule_based_split
    _, nat = hf_and_native
    r = random.Random(1)
    words = _SAMPLE_WORDS + ['Dr.', 'etc.', 'vs.', 'No.', '(A)', 'i.e.']
    for _ in range(500):
      parts = []
      for _ in range(r.randrange(1, 5)):
        k = r.randrange(2, 9)
        parts.append(' '.join(r.choice(words) for _ in range(k)).capitalize()
                     + r.choice('..!?'))
      text = ' '.join(parts)
      assert nat.split_sentences(text) == _rule_based_split(text), repr(text)

  def test_encode_docs_matches_split_then_encode(self, hf_and_native):
    _, nat = hf_and_native
    docs = [
        'The dog ran. The cat walked fast!',
        'Kindness read the tree. Naïve café. Xyzzy!',
        '',
        '中国 the 日本人.',
    ]
    flat, sent_offsets, doc_counts = nat.encode_docs(docs)
    # manual: split + encode + drop empties
    exp_ids, exp_counts = [], []
    for d in docs:
      kept = 0
      for s in nat.split_sentences(d):
        ids, _ = nat.encode_batch_ids([s])
        if len(ids):
          exp_ids.append(ids.tolist())
          kept += 1
      exp_counts.append(kept)
    assert doc_counts.tolist() == exp_counts
    got = [
        flat[sent_offsets[i]:sent_offsets[i + 1]].tolist()
        for i in range(len(sent_offsets) - 1)
    ]
    assert got == exp_ids


class TestDecode:

  def test_decode_join_roundtrip(self, hf_and_native):
    _, nat = hf_and_native
    texts = ['the dog ran.', 'kindness readable café', '中国 3.14']
    ids, offsets = nat.encode_batch_ids(texts)
    joined = nat.decode_join(ids, offsets)
    for text, j in zip(texts, joined):
      assert j.split() == nat.tokenize(text)

  def test_decode_join_buffers_arrow(self, hf_and_native):
    import pyarrow as pa
    _, nat = hf_and_native
    ids, offsets = nat.encode_batch_ids(['the dog', 'cat ran fast'])
    out_offsets, data = nat.decode_join_buffers(ids, offsets)
    arr = pa.StringArray.from_buffers(
        len(out_offsets) - 1, pa.py_buffer(out_offsets.tobytes()),
        pa.py_buffer(data.tobytes()))
    assert arr.to_pylist() == nat.decode_join(ids, offsets)

  def test_not_picklable(self, hf_and_native):
    import pickle
    _, nat = hf_and_native
    with pytest.raises(TypeError):
      pickle.dumps(nat)


class TestColumnarEmit:
  """The fused encode->columnar entry point must reproduce the separate
  decode_join_buffers + numpy-framing path byte for byte."""

  def test_string_columns_match_decode_join_buffers(self, hf_and_native):
    _, nat = hf_and_native
    cols = []
    for texts in (['the dog ran.', '', 'cat ran fast'],
                  ['kindness readable café', '中国 3.14']):
      cols.append(nat.encode_batch_ids(texts))
    # An out-of-range id must size and decode as [UNK] on both paths.
    bad_ids = np.array([0, 99999, 1], np.int32)
    bad_offs = np.array([0, 3], np.int64)
    cols.append((bad_ids, bad_offs))
    string_parts, pos_parts = nat.columnar_emit(cols)
    assert pos_parts is None
    assert len(string_parts) == len(cols)
    for (ids, offs), (oo, data) in zip(cols, string_parts):
      ref_oo, ref_data = nat.decode_join_buffers(ids, offs)
      np.testing.assert_array_equal(oo, ref_oo)
      assert data.tobytes() == ref_data.tobytes()

  def test_positions_match_numpy_framing(self, hf_and_native):
    from lddl_tpu.core.utils import u16_batch_binary_parts
    _, nat = hf_and_native
    ids, offs = nat.encode_batch_ids(['the dog', 'cat ran'])
    vals = np.array([3, 0, 65535, 7, 9], np.uint16)
    # Includes a zero-length row and non-zero-based sub-span offsets.
    poffs = np.array([1, 3, 3, 5], np.int64) + 0
    string_parts, pos_parts = nat.columnar_emit([(ids, offs)],
                                                positions=(vals, poffs))
    boffs, bdata = pos_parts
    ref_boffs, ref_bdata = u16_batch_binary_parts(vals, poffs)
    np.testing.assert_array_equal(boffs, np.asarray(ref_boffs))
    assert bdata.tobytes() == np.asarray(ref_bdata).tobytes()

  def test_empty_and_zero_columns(self, hf_and_native):
    _, nat = hf_and_native
    empty = (np.zeros(0, np.int32), np.zeros(1, np.int64))
    string_parts, pos_parts = nat.columnar_emit([empty])
    assert pos_parts is None
    oo, data = string_parts[0]
    assert list(oo) == [0] and len(data) == 0


def test_pairing_falls_back_without_toolchain(monkeypatch):
  """A host without g++ must degrade to the Python planner with a warning,
  not crash at first use (the build runs lazily inside the probe)."""
  import warnings
  import numpy as np
  from lddl_tpu.preprocess import pairing
  from lddl_tpu.native import build

  def boom():
    raise FileNotFoundError('g++')

  # native.pairing binds `load_library` at import time; import it first so
  # the patch below cannot be captured permanently by a first-time import
  # happening inside this test (which would leak `boom` into later tests).
  from lddl_tpu.native import pairing as native_pairing

  monkeypatch.setattr(pairing, '_NATIVE_PLANNER', None)
  monkeypatch.setattr(build, 'load_library', boom)
  monkeypatch.setattr(native_pairing, 'load_library', boom)
  docs = pairing.TokenizedDocs(
      np.arange(40, dtype=np.int32),
      np.array([0, 10, 25, 40], dtype=np.int64), [2, 1])
  import random
  with warnings.catch_warnings(record=True) as w:
    warnings.simplefilter('always')
    a, b, ir = pairing.plan_pairs_partition(docs, random.Random(3),
                                            backend='auto')
  assert any('native pair planner unavailable' in str(x.message) for x in w)
  a2, b2, ir2 = pairing.plan_pairs_partition(docs, random.Random(3),
                                             backend='python')
  assert np.array_equal(a, a2) and np.array_equal(b, b2)
  monkeypatch.setattr(pairing, '_NATIVE_PLANNER', None)  # re-probe later


def test_library_name_covers_flags_and_target_cpu(monkeypatch):
  """The .so is compiled with -march=native, so its name must change
  with the CPU (and the flags), not with the sources alone: a checkout
  copied to another machine must not load this machine's binary."""
  from lddl_tpu.native import build
  here = build._lib_path()
  assert build._lib_path() == here  # stable on one machine
  monkeypatch.setattr(build, '_target_cpu', lambda: 'another cpu')
  elsewhere = build._lib_path()
  assert elsewhere != here
  monkeypatch.setattr(build, '_FLAGS', build._FLAGS + ('-g',))
  assert build._lib_path() not in (here, elsewhere)
