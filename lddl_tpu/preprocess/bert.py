"""BERT pretraining preprocessor.

Turns one-document-per-line corpora into next-sentence-prediction pairs with
optional static MLM masking and sequence-length binning, written as Parquet
shards. Output schema and on-disk naming are interoperable with the
reference (``lddl/dask/bert/pretrain.py:444-498``):

  A: str                     space-joined WordPiece tokens of segment A
  B: str                     space-joined WordPiece tokens of segment B
  is_random_next: bool       NSP label
  num_tokens: uint16         len(A) + len(B) + 3 ([CLS] + 2x[SEP])
  [masked_lm_positions: binary   serialized uint16 positions into the
                                 assembled [CLS] A [SEP] B [SEP] sequence]
  [masked_lm_labels: str         space-joined original tokens]
  [bin_id: int64             when binned]

Pairing follows the standard BERT recipe (segment chunks to a target
length, 50% random-next B, random front/back truncation; reference
``pretrain.py:241-365``) — but every random draw here threads an explicit
per-partition RNG, so unlike the reference (which uses the unseeded global
``random`` inside Dask workers) the whole pipeline is deterministic given
(seed, corpus): identical reruns produce identical shards.
"""

import argparse
import dataclasses
import functools
import time

import numpy as np
import pyarrow as pa

from ..core import attach_bool_arg, serialize_np_array
from ..core.random import rng_from_key
from ..core.utils import (binary_column_from_parts, npy_batch_binary_parts,
                          u16_batch_binary_parts)
from ..pipeline.executor import Executor
from ..pipeline.parquet_io import write_samples_partition, write_table_partition
from ..pipeline.pool import current_writer
from ..pipeline.shard_format import (DELTA, DELTA_COLUMNS, MATERIALIZED,
                                     tag_table)
from ..pipeline.shuffle import gather_partition
from ..tokenization import split_sentences
from .common import run_shuffled
from .readers import read_corpus, split_id_text


@dataclasses.dataclass(frozen=True)
class Document:
  doc_id: str
  sentences: tuple  # tuple of tuples of tokens

  def __len__(self):
    return len(self.sentences)

  def __getitem__(self, i):
    return self.sentences[i]


def documents_from_lines(lines, tokenizer, max_length=512,
                         sentence_backend='auto'):
  """Parse raw document lines into tokenized Documents.

  All sentences of all documents are tokenized in a single batched backend
  call, then redistributed — the partition-level equivalent of the
  reference's per-sentence ``tokenizer.tokenize`` loop
  (``lddl/dask/bert/pretrain.py:77-97``).
  """
  doc_ids, doc_sentence_strs = [], []
  for line in lines:
    doc_id, text = split_id_text(line)
    if not text:
      continue
    sents = [s.strip() for s in split_sentences(text, backend=sentence_backend)]
    sents = [s for s in sents if s]
    if sents:
      doc_ids.append(doc_id)
      doc_sentence_strs.append(sents)
  flat = [s for sents in doc_sentence_strs for s in sents]
  flat_tokens = tokenizer.batch_tokenize(flat, max_length=max_length)
  documents = []
  pos = 0
  for doc_id, sents in zip(doc_ids, doc_sentence_strs):
    toks = [tuple(t) for t in flat_tokens[pos:pos + len(sents)]]
    pos += len(sents)
    toks = [t for t in toks if t]
    if toks:
      documents.append(Document(doc_id, tuple(toks)))
  return documents


def truncate_seq_pair(tokens_a, tokens_b, max_num_tokens, rng):
  """Randomly trim the longer segment from the front or back until the pair
  fits (reference ``pretrain.py:161-176``)."""
  while len(tokens_a) + len(tokens_b) > max_num_tokens:
    trunc = tokens_a if len(tokens_a) > len(tokens_b) else tokens_b
    if rng.random() < 0.5:
      del trunc[0]
    else:
      trunc.pop()


def create_masked_lm_predictions(tokens_a, tokens_b, masked_lm_ratio,
                                 vocab_words, rng, max_predictions=None):
  """Static MLM masking over the assembled [CLS] A [SEP] B [SEP] sequence.

  Standard 80/10/10 recipe (reference ``pretrain.py:182-238``). Positions
  index the assembled sequence. Returns the masked A/B token lists plus
  sorted (positions, labels).
  """
  n_a, n_b = len(tokens_a), len(tokens_b)
  tokens = ['[CLS]'] + list(tokens_a) + ['[SEP]'] + list(tokens_b) + ['[SEP]']
  cand = [i for i, t in enumerate(tokens) if t not in ('[CLS]', '[SEP]')]
  rng.shuffle(cand)
  num_to_predict = max(1, int(round(len(tokens) * masked_lm_ratio)))
  if max_predictions is not None:
    num_to_predict = min(num_to_predict, max_predictions)
  picked = sorted(cand[:num_to_predict])
  labels = [tokens[i] for i in picked]
  for i in picked:
    r = rng.random()
    if r < 0.8:
      tokens[i] = '[MASK]'
    elif r < 0.9:
      pass  # keep original
    else:
      tokens[i] = vocab_words[rng.randrange(len(vocab_words))]
  return (
      tokens[1:1 + n_a],
      tokens[2 + n_a:2 + n_a + n_b],
      picked,
      labels,
  )


def create_masked_lm_predictions_np(tokens_a, tokens_b, masked_lm_ratio,
                                    vocab_words, np_rng,
                                    max_predictions=None):
  """Vectorized 80/10/10 masking: one ``Generator.choice`` + one uniform
  draw per instance instead of a Python shuffle over every candidate
  position (the reference's per-token loop, ``pretrain.py:182-238``, is
  the second-hottest preprocess cost after tokenization)."""
  n_a, n_b = len(tokens_a), len(tokens_b)
  tokens = ['[CLS]'] + list(tokens_a) + ['[SEP]'] + list(tokens_b) + ['[SEP]']
  cand = np.concatenate(
      [np.arange(1, 1 + n_a), np.arange(2 + n_a, 2 + n_a + n_b)])
  num_to_predict = max(1, int(round(len(tokens) * masked_lm_ratio)))
  if max_predictions is not None:
    num_to_predict = min(num_to_predict, max_predictions)
  num_to_predict = min(num_to_predict, cand.size)
  picked = np.sort(np_rng.choice(cand, size=num_to_predict, replace=False))
  labels = [tokens[i] for i in picked]
  decide = np_rng.random(num_to_predict)
  rand_ids = np_rng.integers(0, len(vocab_words), num_to_predict)
  for j, i in enumerate(picked):
    if decide[j] < 0.8:
      tokens[i] = '[MASK]'
    elif decide[j] < 0.9:
      pass  # keep original
    else:
      tokens[i] = vocab_words[rand_ids[j]]
  return (
      tokens[1:1 + n_a],
      tokens[2 + n_a:2 + n_a + n_b],
      picked.tolist(),
      labels,
  )


def create_pairs_from_document(
    all_documents,
    document_index,
    rng,
    max_seq_length=128,
    short_seq_prob=0.1,
    masking=False,
    masked_lm_ratio=0.15,
    vocab_words=None,
    np_rng=None,
):
  """NSP pair construction for one document (reference
  ``pretrain.py:241-365``): accumulate sentence chunks up to a target
  length, split at a random point into A, and with probability 0.5 replace
  the continuation by sentences from a random other document in the
  partition."""
  document = all_documents[document_index]
  max_num_tokens = max_seq_length - 3
  target_seq_length = max_num_tokens
  if rng.random() < short_seq_prob:
    target_seq_length = rng.randint(2, max_num_tokens)

  instances = []
  chunk = []
  chunk_len = 0
  i = 0
  while i < len(document):
    chunk.append(document[i])
    chunk_len += len(document[i])
    if i == len(document) - 1 or chunk_len >= target_seq_length:
      if chunk:
        a_end = 1 if len(chunk) < 2 else rng.randint(1, len(chunk) - 1)
        tokens_a = [t for seg in chunk[:a_end] for t in seg]
        tokens_b = []
        if len(chunk) == 1 or rng.random() < 0.5:
          # Random next: fill B from a random other document.
          is_random_next = True
          target_b_length = target_seq_length - len(tokens_a)
          random_document_index = document_index
          for _ in range(10):
            candidate = rng.randint(0, len(all_documents) - 1)
            if candidate != document_index:
              random_document_index = candidate
              break
          if random_document_index == document_index:
            is_random_next = False
          random_document = all_documents[random_document_index]
          start = rng.randint(0, len(random_document) - 1)
          for j in range(start, len(random_document)):
            tokens_b.extend(random_document[j])
            if len(tokens_b) >= target_b_length:
              break
          # Unused trailing segments of the chunk are replayed.
          i -= len(chunk) - a_end
        else:
          is_random_next = False
          tokens_b = [t for seg in chunk[a_end:] for t in seg]
        truncate_seq_pair(tokens_a, tokens_b, max_num_tokens, rng)
        if tokens_a and tokens_b:
          if masking:
            if np_rng is not None:
              tokens_a, tokens_b, positions, labels = (
                  create_masked_lm_predictions_np(tokens_a, tokens_b,
                                                  masked_lm_ratio,
                                                  vocab_words, np_rng))
            else:
              tokens_a, tokens_b, positions, labels = (
                  create_masked_lm_predictions(tokens_a, tokens_b,
                                               masked_lm_ratio, vocab_words,
                                               rng))
          instance = {
              'A': ' '.join(tokens_a),
              'B': ' '.join(tokens_b),
              'is_random_next': is_random_next,
              'num_tokens': len(tokens_a) + len(tokens_b) + 3,
          }
          if masking:
            instance['masked_lm_positions'] = serialize_np_array(
                np.asarray(positions, dtype=np.uint16))
            instance['masked_lm_labels'] = ' '.join(labels)
          instances.append(instance)
      chunk = []
      chunk_len = 0
    i += 1
  return instances


def encode_documents(doc_texts, tokenizer, sentence_backend='rules',
                     max_length=512):
  """Raw document texts -> :class:`~lddl_tpu.preprocess.pairing.TokenizedDocs`.

  With the native tokenizer and the 'rules' sentence backend the whole
  front end (segmentation + WordPiece) is one multithreaded C call;
  otherwise sentences are split in Python and encoded via the tokenizer's
  batched id path. Zero-sentence documents are dropped (mirror of
  ``documents_from_lines``).
  """
  from .pairing import TokenizedDocs
  if tokenizer.native is not None and sentence_backend == 'rules':
    flat, sent_offsets, doc_counts = tokenizer.native.encode_docs(
        doc_texts, max_tokens_per_sent=max_length)
  else:
    sents_per_doc = []
    for text in doc_texts:
      sents = [s.strip() for s in split_sentences(text,
                                                  backend=sentence_backend)]
      sents_per_doc.append([s for s in sents if s])
    flat_sents = [s for sents in sents_per_doc for s in sents]
    flat, offsets = tokenizer.encode_batch_ids(flat_sents,
                                               max_tokens=max_length)
    lens = np.diff(offsets)
    keep = lens > 0
    sent_offsets = np.concatenate(
        [[0], np.cumsum(lens[keep])]).astype(np.int64)
    doc_counts = np.zeros(len(doc_texts), dtype=np.int64)
    pos = 0
    for d, sents in enumerate(sents_per_doc):
      doc_counts[d] = int(keep[pos:pos + len(sents)].sum())
      pos += len(sents)
  nonempty = doc_counts > 0
  return TokenizedDocs(flat, sent_offsets, doc_counts[nonempty])


def _string_column(tokenizer, flat_ids, offsets):
  """Ragged id ranges -> Arrow string column of space-joined tokens
  (zero-copy from native buffers when available)."""
  bufs = tokenizer.decode_join_buffers(flat_ids, offsets)
  if bufs is not None:
    out_offsets, data = bufs
    return pa.StringArray.from_buffers(
        len(out_offsets) - 1, pa.py_buffer(out_offsets.tobytes()),
        pa.py_buffer(data.tobytes()))
  return pa.array(tokenizer.decode_join(flat_ids, offsets), type=pa.string())


def resolve_shard_format(cfg):
  """Resolve ``cfg.shard_format`` ('auto' | 'materialized' | 'delta').

  'auto' picks delta exactly where it wins: fast-engine static masking
  with ``duplicate_factor > 1`` (the dup copies of a pair differ only by
  their mask, so storing the base once plus per-copy deltas cuts write
  bytes ~duplicate_factor×). Explicit 'delta' is validated loudly: an
  unmasked run has no mask delta to store (unmasked dup copies differ by
  their *pairing*, which delta cannot represent), and the python engine
  materializes per-document instances with no columnar delta path.
  """
  fmt = cfg.shard_format
  if fmt == 'auto':
    if cfg.masking and cfg.duplicate_factor > 1 and cfg.engine == 'fast':
      return DELTA
    return MATERIALIZED
  if fmt == DELTA:
    if not cfg.masking:
      raise ValueError(
          '--shard-format delta requires --masking: unmasked duplicate '
          'copies differ by pairing, not by a mask delta')
    if cfg.engine != 'fast':
      raise ValueError(
          "--shard-format delta requires the fast engine (engine='fast')")
  elif fmt != MATERIALIZED:
    raise ValueError(f'unknown shard format {fmt!r}')
  return fmt


def _fused_string_col(parts):
  """(offsets, utf8 data) from the native fused assembler -> Arrow column."""
  out_offsets, data = parts
  return pa.StringArray.from_buffers(
      len(out_offsets) - 1, pa.py_buffer(out_offsets), pa.py_buffer(data))


def process_partition_columnar(doc_texts, tokenizer, cfg, rng, mask_seed):
  """The fast path: tokenize -> plan pairs -> batched (device) masking ->
  Arrow table. Returns a ``pyarrow.Table`` matching :func:`bert_schema`
  for the resolved shard format, tagged via
  :func:`~lddl_tpu.pipeline.shard_format.tag_table`.

  This is the TPU-first redesign of the reference's per-partition hot loop
  (``lddl/dask/bert/pretrain.py:77-97,182-238``): token ids end-to-end,
  contiguous-range pair planning, one batched masking call on the
  accelerator, and zero-copy Arrow column assembly.

  Masked runs with ``duplicate_factor > 1`` plan the base pairs ONCE and
  tile the ranges copy-adjacent (p0c0, p0c1, ..., p0c{dup-1}, p1c0, ...);
  the counter-based Philox mask stream is keyed by row index, so each
  tiled copy draws an independent mask for free. This holds for BOTH
  shard formats, which is what makes them logically equivalent
  row-for-row — the delta format just stores each base once plus the
  per-copy (positions, new_ids, label_ids, k) deltas instead of
  materializing dup masked rows.
  """
  from ..ops import masking as _masking_ops
  from .pairing import plan_pairs_partition

  from ..ops.masking import (mask_partition_device, mask_partition_host,
                             resolve_mask_backend)

  shard_format = resolve_shard_format(cfg)
  delta = shard_format == DELTA

  docs = encode_documents(doc_texts, tokenizer,
                          sentence_backend=cfg.sentence_backend)
  if len(docs) == 0:
    return tag_table(
        bert_schema(cfg.masking, shard_format).empty_table(),
        shard_format, cfg.duplicate_factor)
  # Masked dup>1: plan base pairs once and tile copy-adjacent (see
  # docstring). Unmasked dup>1 keeps the legacy per-copy planning passes
  # (one continuing rng stream), matching the python engine pass-for-pass.
  plan_once = cfg.masking and cfg.duplicate_factor > 1
  base_a, base_b, base_irn = plan_pairs_partition(
      docs, rng, max_seq_length=cfg.target_seq_length,
      short_seq_prob=cfg.short_seq_prob,
      duplicate_factor=1 if plan_once else cfg.duplicate_factor)
  dup = cfg.duplicate_factor if plan_once else 1
  nbase = len(base_a)
  if plan_once and dup > 1:
    a_ranges = np.repeat(base_a, dup, axis=0)
    b_ranges = np.repeat(base_b, dup, axis=0)
    is_random_next = np.repeat(np.asarray(base_irn), dup)
  else:
    a_ranges, b_ranges, is_random_next = base_a, base_b, base_irn
  flat_ids = docs.flat_ids
  n = len(a_ranges)
  na = (a_ranges[:, 1] - a_ranges[:, 0]).astype(np.int64)
  nb = (b_ranges[:, 1] - b_ranges[:, 0]).astype(np.int64)
  row_len = na + nb + 3
  if n and int(row_len.max()) > cfg.target_seq_length:
    # Fail loudly at preprocess time (the padded-matrix path used to
    # enforce this in assemble_pair_matrix): oversized rows would break
    # downstream binning/collate shape assumptions silently.
    raise ValueError(f'pair of {int(row_len.max())} tokens exceeds '
                     f'target_seq_length {cfg.target_seq_length}')
  mask_mode = resolve_mask_backend(cfg.mask_backend) if cfg.masking else None
  offs_a = np.zeros(n + 1, dtype=np.int64)
  np.cumsum(na, out=offs_a[1:])
  offs_b = np.zeros(n + 1, dtype=np.int64)
  np.cumsum(nb, out=offs_b[1:])

  newv = None
  if mask_mode == 'host':
    # Fused ragged path: one native pass gathers A/B, draws k Fisher-
    # Yates picks per row from a counter-based Philox stream, applies
    # 80/10/10, and emits sorted positions + label ids — no padded id
    # matrix, no dense [N, L] uniform draws (see ops/masking.py
    # mask_partition_host; numpy fallback is bit-identical).
    flat_a, flat_b, ci, label_ids, k = mask_partition_host(
        flat_ids, a_ranges, b_ranges, masked_lm_ratio=cfg.masked_lm_ratio,
        vocab_size=tokenizer.vocab_size, mask_id=tokenizer.mask_token_id,
        seed=mask_seed, offs_a=offs_a, offs_b=offs_b)
    if delta:
      # The host kernel applies the delta in place; re-read the post-mask
      # ids at the picked positions so the delta columns can store them.
      ri = np.repeat(np.arange(n, dtype=np.int64), k)
      ci64 = ci.astype(np.int64)
      in_a = ci64 < 1 + na[ri]
      idx_a = offs_a[ri] + ci64 - 1
      idx_b = offs_b[ri] + ci64 - 2 - na[ri]
      newv = np.where(in_a, flat_a[np.where(in_a, idx_a, 0)],
                      flat_b[np.where(in_a, 0, idx_b)])
  else:
    if not delta:
      # Ragged gather straight from the flat partition ids (no id matrix).
      ra, ca = _masking_ops.ragged_indices(na)
      flat_a = flat_ids[a_ranges[ra, 0] + ca]
      rb, cb = _masking_ops.ragged_indices(nb)
      flat_b = flat_ids[b_ranges[rb, 0] + cb]
    if mask_mode == 'device':
      positions, new_ids, kk = mask_partition_device(
          flat_ids, a_ranges, b_ranges, seq_len=cfg.target_seq_length,
          masked_lm_ratio=cfg.masked_lm_ratio,
          vocab_size=tokenizer.vocab_size,
          mask_id=tokenizer.mask_token_id,
          cls_id=tokenizer.cls_token_id, sep_id=tokenizer.sep_token_id,
          seed=mask_seed)
      k = kk.astype(np.int64)
      pm = np.arange(positions.shape[1])[None, :] < k[:, None]
      ri = np.nonzero(pm)[0]
      ci = positions[pm].astype(np.int64)  # sorted within each row
      in_a = ci < 1 + na[ri]
      if not delta:
        # Original (label) ids, read from the flat array via the ranges
        # (the delta format stores no labels — collate recovers them).
        idx_a = a_ranges[ri, 0] + ci - 1
        idx_b = b_ranges[ri, 0] + ci - 2 - na[ri]
        label_ids = np.where(
            in_a, flat_ids[np.where(in_a, idx_a, 0)],
            flat_ids[np.where(in_a, 0, idx_b)]).astype(np.int32)
      newv = new_ids[pm].astype(flat_ids.dtype)
      if not delta:
        # Apply the post-masking ids into the ragged A/B columns.
        tgt_a = offs_a[ri] + ci - 1
        flat_a[tgt_a[in_a]] = newv[in_a]
        tgt_b = offs_b[ri] + ci - 2 - na[ri]
        flat_b[tgt_b[~in_a]] = newv[~in_a]

  offs_l = None
  if cfg.masking:
    offs_l = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(k, out=offs_l[1:])

  from .common import fused_string_columns

  if delta:
    # Delta format: one physical row per BASE pair. The A/B strings come
    # from the unmasked base ids; the dup per-copy mask deltas are packed
    # ragged into four binary columns. Tiled rows are copy-adjacent, so
    # each base row's delta span is a pure stride view: offs_l[::dup].
    base_na = na[::dup]
    base_nb = nb[::dup]
    boffs_a = np.zeros(nbase + 1, dtype=np.int64)
    np.cumsum(base_na, out=boffs_a[1:])
    boffs_b = np.zeros(nbase + 1, dtype=np.int64)
    np.cumsum(base_nb, out=boffs_b[1:])
    ra, ca = _masking_ops.ragged_indices(base_na)
    base_flat_a = flat_ids[base_a[ra, 0] + ca]
    rb, cb = _masking_ops.ragged_indices(base_nb)
    base_flat_b = flat_ids[base_b[rb, 0] + cb]
    fused = fused_string_columns(
        tokenizer, [(base_flat_a, boffs_a), (base_flat_b, boffs_b)])
    if fused is not None:
      string_parts, _ = fused
      col_a = _fused_string_col(string_parts[0])
      col_b = _fused_string_col(string_parts[1])
    else:
      col_a = _string_column(tokenizer, base_flat_a, boffs_a)
      col_b = _string_column(tokenizer, base_flat_b, boffs_b)
    cols = {
        'A': col_a,
        'B': col_b,
        'is_random_next': pa.array(np.asarray(base_irn)),
        'num_tokens': pa.array((base_na + base_nb + 3).astype(np.uint16),
                               type=pa.uint16()),
    }
    doffs = offs_l[::dup]
    koffs = np.arange(nbase + 1, dtype=np.int64) * dup
    # No label column: the label at a masked position is the original
    # token, which the collate reads out of input_ids before applying
    # the delta. Post-mask ids fit u2 whenever the vocab does.
    new_dt = '<u2' if tokenizer.vocab_size <= 1 << 16 else '<i4'
    for name, vals, offs, dt in (
        ('mask_delta_positions', ci, doffs, '<u2'),
        ('mask_delta_new_ids', newv, doffs, new_dt),
        ('mask_delta_k', k, koffs, '<u2')):
      bo, bd = npy_batch_binary_parts(vals, offs, dt)
      cols[name] = binary_column_from_parts(bo, bd, nbase, name)
    return tag_table(pa.table(cols), DELTA, dup)

  # Fused native columnar assembly (LDDL_NATIVE_COLUMNAR, default on):
  # every string column and the npy-framed positions column in one native
  # round trip — no numpy capacity/framing passes, no buffer re-copies.
  # Bytes are identical to the per-column fallback below (tested), so the
  # shard contract f(task, global_index) is unchanged.
  emit_cols = [(flat_a, offs_a), (flat_b, offs_b)]
  if cfg.masking:
    emit_cols.append((label_ids, offs_l))
  fused = fused_string_columns(
      tokenizer, emit_cols,
      positions=(ci, offs_l) if cfg.masking else None)
  if fused is not None:
    string_parts, pos_parts = fused
    cols = {
        'A': _fused_string_col(string_parts[0]),
        'B': _fused_string_col(string_parts[1]),
        'is_random_next': pa.array(is_random_next),
        'num_tokens': pa.array(row_len.astype(np.uint16), type=pa.uint16()),
    }
    if cfg.masking:
      boffs, bdata = pos_parts
      cols['masked_lm_positions'] = binary_column_from_parts(
          boffs, bdata, n, 'masked_lm_positions')
      cols['masked_lm_labels'] = _fused_string_col(string_parts[2])
    return tag_table(pa.table(cols), MATERIALIZED, cfg.duplicate_factor)

  cols = {
      'A': _string_column(tokenizer, flat_a, offs_a),
      'B': _string_column(tokenizer, flat_b, offs_b),
      'is_random_next': pa.array(is_random_next),
      'num_tokens': pa.array(row_len.astype(np.uint16), type=pa.uint16()),
  }
  if cfg.masking:
    boffs, bdata = u16_batch_binary_parts(ci, offs_l)
    cols['masked_lm_positions'] = binary_column_from_parts(
        boffs, bdata, n, 'masked_lm_positions')
    cols['masked_lm_labels'] = _string_column(tokenizer, label_ids, offs_l)
  return tag_table(pa.table(cols), MATERIALIZED, cfg.duplicate_factor)


def bert_schema(masking, shard_format=MATERIALIZED):
  fields = [
      ('A', pa.string()),
      ('B', pa.string()),
      ('is_random_next', pa.bool_()),
      ('num_tokens', pa.uint16()),
  ]
  if shard_format == DELTA:
    if not masking:
      raise ValueError('delta shard format requires masking')
    fields += [(name, pa.binary()) for name in DELTA_COLUMNS]
  elif masking:
    fields += [
        ('masked_lm_positions', pa.binary()),
        ('masked_lm_labels', pa.string()),
    ]
  return pa.schema(fields)


@dataclasses.dataclass(frozen=True)
class BertPretrainConfig:
  vocab_file: str = None
  tokenizer_name: str = None
  lowercase: bool = True
  tokenizer_backend: str = 'auto'
  sentence_backend: str = 'auto'
  engine: str = 'fast'  # 'fast' (columnar/device) | 'python' (reference-style)
  mask_backend: str = 'auto'  # 'host' | 'device'; 'auto' means 'host'
  target_seq_length: int = 128
  short_seq_prob: float = 0.1
  duplicate_factor: int = 5
  masking: bool = False
  masked_lm_ratio: float = 0.15
  bin_size: int = None
  seed: int = 12345
  output_format: str = 'parquet'
  # 'auto' resolves to 'delta' for fast-engine masked duplicate_factor>1
  # runs (see resolve_shard_format), 'materialized' otherwise.
  shard_format: str = 'auto'

  @property
  def nbins(self):
    if self.bin_size is None:
      return None
    if self.target_seq_length % self.bin_size != 0:
      raise ValueError('bin_size must divide target_seq_length')
    return self.target_seq_length // self.bin_size


def _get_tokenizer(cfg):
  from .common import get_cached_tokenizer
  return get_cached_tokenizer(
      vocab_file=cfg.vocab_file,
      hub_name=cfg.tokenizer_name,
      lowercase=cfg.lowercase,
      backend=cfg.tokenizer_backend)


def _warmup_worker(cfg):
  """Persistent-pool warmup hook: build (and cache) the tokenizer in the
  worker before its first task, so vocab load + native-encoder
  construction are paid once per worker per pool lifetime instead of
  inside task one of every phase."""
  tokenizer = _get_tokenizer(cfg)
  tokenizer.batch_tokenize(['warmup'])


def _mask_seed(seed, tgt_idx):
  """Per-partition masking seed, independent of the pairing rng stream."""
  return int(
      np.random.SeedSequence([seed, tgt_idx, 0x6d61736b]).generate_state(1)[0])


def _process_partition(tgt_idx, global_idx, spill_dir, out_dir, cfg):
  """Worker task: shuffled lines of one partition -> pair instances ->
  (binned) Parquet. Returns {bin_id_or_None: num_samples}."""
  del global_idx
  tokenizer = _get_tokenizer(cfg)
  lines = gather_partition(tgt_idx, spill_dir, cfg.seed)
  rng = rng_from_key(cfg.seed, 'pairs', tgt_idx)

  if cfg.engine == 'fast':
    doc_texts = []
    for line in lines:
      _, text = split_id_text(line)
      if text:
        doc_texts.append(text)
    table = process_partition_columnar(doc_texts, tokenizer, cfg, rng,
                                       _mask_seed(cfg.seed, tgt_idx))
    out = write_table_partition(
        table,
        out_dir,
        tgt_idx,
        bin_size=cfg.bin_size,
        nbins=cfg.nbins,
        output_format=cfg.output_format,
        writer=current_writer(),
    )
    return {b: nrows for b, (_, nrows) in out.items()}

  documents = documents_from_lines(
      lines, tokenizer, sentence_backend=cfg.sentence_backend)
  np_rng = np.random.Generator(
      np.random.Philox(key=[np.uint64(cfg.seed),
                            np.uint64(tgt_idx)]))
  instances = []
  for _ in range(cfg.duplicate_factor):
    for di in range(len(documents)):
      instances.extend(
          create_pairs_from_document(
              documents,
              di,
              rng,
              max_seq_length=cfg.target_seq_length,
              short_seq_prob=cfg.short_seq_prob,
              masking=cfg.masking,
              masked_lm_ratio=cfg.masked_lm_ratio,
              vocab_words=tokenizer.vocab_words,
              np_rng=np_rng,
          ))
  out = write_samples_partition(
      instances,
      bert_schema(cfg.masking),
      out_dir,
      tgt_idx,
      bin_size=cfg.bin_size,
      nbins=cfg.nbins,
      output_format=cfg.output_format,
      writer=current_writer(),
  )
  return {b: n for b, (_, n) in out.items()}


def run(corpus, sink_dir, cfg, executor=None, num_shuffle_partitions=None):
  """Execute the full preprocess: global doc shuffle -> pair/mask/bin ->
  Parquet shards under ``sink_dir``. Returns per-partition sample counts."""
  executor = executor or Executor()
  if cfg.sentence_backend == 'auto':
    # Resolve once and broadcast so segmentation (and thus shard content)
    # never depends on which worker host has nltk data installed.
    from ..tokenization.sentences import resolve_backend
    resolved = executor.comm.broadcast_object(resolve_backend(), root=0)
    cfg = dataclasses.replace(cfg, sentence_backend=resolved)
  if cfg.tokenizer_backend == 'auto':
    # Same principle: 'auto' must not resolve per worker (native needs a
    # compiler; a heterogeneous fleet would silently emit mixed token
    # streams for exotic scripts). Probe once on root, broadcast the
    # decision; a worker that then cannot honor it fails loudly.
    local = None
    if executor.comm.rank == 0:
      local = 'native' if _get_tokenizer(cfg).native is not None else 'hf'
    resolved = executor.comm.broadcast_object(local, root=0)
    cfg = dataclasses.replace(cfg, tokenizer_backend=resolved)
  # Masking backends have independent RNG streams, so which one runs is
  # part of the output contract: 'auto' is resolved here, once, to the
  # same answer on every host (never per pool worker, never by probing).
  from ..ops.masking import resolve_mask_backend
  cfg = dataclasses.replace(
      cfg, mask_backend=resolve_mask_backend(cfg.mask_backend))
  # Resolve the shard format once up front: it is part of the output
  # contract (and invalid combinations — delta without masking, delta on
  # the python engine — must fail loudly before any worker starts).
  cfg = dataclasses.replace(cfg, shard_format=resolve_shard_format(cfg))
  if executor.comm.rank == 0:
    mask = (cfg.mask_backend
            if cfg.masking and cfg.engine == 'fast' else 'off')
    print(f'preprocess backends: tokenizer={cfg.tokenizer_backend} '
          f'sentences={cfg.sentence_backend} mask={mask} '
          f'format={cfg.shard_format}')
  return run_shuffled(
      corpus,
      sink_dir,
      functools.partial(_process_partition, out_dir=sink_dir, cfg=cfg),
      cfg.seed,
      executor=executor,
      num_shuffle_partitions=num_shuffle_partitions,
      warmup=functools.partial(_warmup_worker, cfg),
      warmup_key=('bert-warmup', cfg))


def attach_args(parser):
  parser.add_argument('--wikipedia', type=str, default=None)
  parser.add_argument('--books', type=str, default=None)
  parser.add_argument('--common-crawl', type=str, default=None)
  parser.add_argument('--open-webtext', type=str, default=None)
  parser.add_argument('--source', type=str, default=None,
                      help='generic one-doc-per-line source dir')
  parser.add_argument('--sink', type=str, required=True)
  parser.add_argument('--num-blocks', type=int, default=None)
  parser.add_argument('--block-size', type=str, default=None,
                      help='bytes per partition, accepts n[KMG]')
  parser.add_argument('--sample-ratio', type=float, default=0.9)
  parser.add_argument('--seed', type=int, default=12345)
  parser.add_argument('--vocab-file', type=str, default=None)
  parser.add_argument('--tokenizer', type=str, default=None,
                      help='HF hub tokenizer name (needs egress)')
  parser.add_argument('--tokenizer-backend', type=str, default='auto',
                      choices=['auto', 'hf', 'native'])
  parser.add_argument('--engine', type=str, default='fast',
                      choices=['fast', 'python'],
                      help='fast: columnar ids + batched/device masking; '
                      'python: reference-style per-document loop')
  parser.add_argument('--mask-backend', type=str, default='auto',
                      choices=['auto', 'device', 'host'],
                      help='where batched MLM masking runs (fast engine); '
                      "'auto' means 'host' — 'device' (a different RNG "
                      'stream, so different shard bytes) only by request')
  parser.add_argument('--sentence-backend', type=str, default='auto',
                      choices=['auto', 'punkt', 'rules'])
  parser.add_argument('--target-seq-length', type=int, default=128)
  parser.add_argument('--short-seq-prob', type=float, default=0.1)
  parser.add_argument('--duplicate-factor', type=int, default=5)
  parser.add_argument('--bin-size', type=int, default=None)
  parser.add_argument('--masked-lm-ratio', type=float, default=0.15)
  parser.add_argument('--shard-format', type=str, default='auto',
                      choices=['auto', 'materialized', 'delta'],
                      help='on-disk shard layout: materialized stores every '
                      'masked duplicate row in full; delta stores each base '
                      'pair once plus per-copy mask deltas (~duplicate_factor'
                      'x fewer write bytes). auto: delta for fast-engine '
                      'masked duplicate_factor>1 runs, else materialized')
  attach_bool_arg(parser, 'masking', default=False,
                  help_str='store static MLM masks')
  attach_bool_arg(parser, 'lowercase', default=True)
  parser.add_argument('--output-format', type=str, default='parquet',
                      choices=['parquet', 'txt'])
  parser.add_argument('--num-workers', type=int, default=None,
                      help='local worker processes (default: all cores)')
  parser.add_argument('--comm', type=str, default='null',
                      choices=['null', 'file', 'jax'])
  return parser


def main(args=None):
  parser = attach_args(
      argparse.ArgumentParser(
          description=__doc__,
          formatter_class=argparse.ArgumentDefaultsHelpFormatter))
  args = parser.parse_args(args)
  from ..core.utils import parse_str_of_num_bytes
  from ..comm import get_backend

  dirs = [
      d for d in (args.wikipedia, args.books, args.common_crawl,
                  args.open_webtext, args.source) if d is not None
  ]
  if not dirs:
    parser.error('need at least one source dir')
  if not args.vocab_file and not args.tokenizer:
    parser.error('need --vocab-file or --tokenizer')
  comm = get_backend(args.comm)
  executor = Executor(comm=comm, num_local_workers=args.num_workers)
  block_size = (parse_str_of_num_bytes(args.block_size)
                if args.block_size else None)
  corpus = read_corpus(
      dirs,
      num_blocks=args.num_blocks or 4 * executor.num_local_workers *
      comm.world_size,
      block_size=block_size,
      sample_ratio=args.sample_ratio,
      sample_seed=args.seed,
  )
  cfg = BertPretrainConfig(
      vocab_file=args.vocab_file,
      tokenizer_name=args.tokenizer,
      lowercase=args.lowercase,
      tokenizer_backend=args.tokenizer_backend,
      sentence_backend=args.sentence_backend,
      engine=args.engine,
      mask_backend=args.mask_backend,
      target_seq_length=args.target_seq_length,
      short_seq_prob=args.short_seq_prob,
      duplicate_factor=args.duplicate_factor,
      masking=args.masking,
      masked_lm_ratio=args.masked_lm_ratio,
      bin_size=args.bin_size,
      seed=args.seed,
      output_format=args.output_format,
      shard_format=args.shard_format,
  )
  t0 = time.perf_counter()
  with executor:
    counts = run(corpus, args.sink, cfg, executor=executor)
  if comm.rank == 0:
    total = sum(n for c in counts for n in c.values())
    print(f'preprocessed {total} samples into {len(counts)} partitions '
          f'in {time.perf_counter() - t0:.1f}s')


if __name__ == '__main__':
  main()
