"""Headline benchmark: BERT pretrain preprocessing throughput.

Prints ONE JSON line:
  {"metric": "bert_preprocess_mb_per_sec_per_chip", "value": N,
   "unit": "MB/s/chip", "vs_baseline": N,
   "dup1_mb_per_sec_per_chip": N}

``value`` is MB of raw one-document-per-line text turned into binned,
masked NSP-pair Parquet shards per second per accelerator chip (the
BASELINE.json north-star metric) at the **reference's default recipe**:
``duplicate_factor=5`` (five masked instances per pair, reference
``lddl/dask/bert/pretrain.py:377,693``). The lighter dup=1 rate is
reported as ``dup1_mb_per_sec_per_chip`` in the same line, the headline
repeats as ``dup5_mb_per_sec_per_chip`` (so `lddl-perf --gate` judges
the recipe by name), and ``shard_format`` / ``shard_formats`` stamp
which shard format produced the headline plus a same-run
materialized-format write-bytes comparison (README "Shard formats").
Both rates are
measured with the **real-scale tokenizer model**: a 30,522-entry trained
WordPiece vocabulary (``benchmarks/assets/bench_vocab_30522.txt``, 4,754
``##`` continuations — see ``benchmarks/make_bench_vocab.py``) over
realistic text (Zipfian ~50k-type word distribution, English-like
morphology, punctuation / digits / non-ASCII at prose rates —
:mod:`lddl_tpu.core.synth`). A toy vocab overstates throughput; this
configuration makes longest-match do the same work Wikipedia+Books
would (VERDICT r2 item 1).

``vs_baseline`` compares against a faithful reimplementation of the
reference's per-partition hot loop (per-sentence ``tokenizer.tokenize``
calls + per-token Python masking, reference
``lddl/dask/bert/pretrain.py:77-97,182-238``) run at the same
``duplicate_factor=5`` on a slice of the same corpus with the same vocab
in the same process, so the ratio isolates the framework's pipeline
improvements from hardware differences.

Corpus size: LDDL_BENCH_MB (default 64 — a measurement window long
enough that one-time process costs amortize as they do on a real
multi-GB run). The baseline runs on LDDL_BENCH_BASELINE_MB (default 1)
and is scaled.
"""

import json
import os
import shutil
import tempfile
import time

_VOCAB = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      'benchmarks', 'assets', 'bench_vocab_30522.txt')


def _telemetry_artifacts():
  """Export telemetry/trace artifacts for this bench run, when enabled.

  With ``LDDL_TELEMETRY=1`` and/or ``LDDL_TRACE=1`` the run's metric
  snapshot and trace buffer are written under ``LDDL_TELEMETRY_DIR`` (a
  fresh persistent temp dir when unset) and the bottleneck verdict is
  embedded in the printed JSON line — BENCH captures carry their own
  attribution instead of needing a manual telemetry run. Returns the
  extra JSON fields ({} when both are off).
  """
  from lddl_tpu.telemetry import get_telemetry, rank_file_name
  from lddl_tpu.telemetry.trace import get_tracer, trace_file_name
  tele = get_telemetry()
  tracer = get_tracer()
  if not (tele.enabled or tracer.enabled):
    return {}
  out_dir = os.environ.get('LDDL_TELEMETRY_DIR') or tempfile.mkdtemp(
      prefix='lddl_bench_telemetry_')
  extra = {'telemetry_dir': out_dir}
  if tele.enabled:
    tele.write_jsonl(rank_file_name(out_dir, 0))
    from lddl_tpu.telemetry.report import (merge_metric_lines,
                                           summarize_stages)
    merged = merge_metric_lines([tele.snapshot_lines(rank=0)])
    verdict = summarize_stages(merged)
    extra['bottleneck'] = verdict['bottleneck']
    if verdict.get('detail'):
      extra['bottleneck_detail'] = verdict['detail']
    # Device bound-class over the run's cumulative counters. Only the
    # class is stamped, and it depends on ratios (arithmetic intensity,
    # wait fraction), not rates, so the window length is arbitrary.
    from lddl_tpu.telemetry.roofline import bound_class
    extra['roofline_bound'] = bound_class(merged, 1.0)
  if tracer.enabled:
    tracer.write_jsonl(trace_file_name(out_dir, 0))
  return extra


def _lint_status():
  """Stamp the analyzer's verdict onto the BENCH JSON line.

  Perf artifacts assume the determinism invariants lddl-analyze guards
  (LDA001/LDA002: identical plans and seeded randomness — see PERF.md);
  recording clean/dirty makes every captured number traceable to a
  lint-clean tree. Never fails the bench: an import/analysis error just
  omits the fields.
  """
  try:
    from lddl_tpu.analysis import (CONCURRENCY_RULE_IDS,
                                   LINT_SCHEMA_VERSION, analyze_package)
    unsuppressed, suppressed = analyze_package()
    conc = [f for f in unsuppressed if f.rule_id in CONCURRENCY_RULE_IDS]
    conc_sup = [f for f in suppressed if f.rule_id in CONCURRENCY_RULE_IDS]
    return {
        'lint_schema': LINT_SCHEMA_VERSION,
        'lint_clean': not unsuppressed,
        'lint_findings': len(unsuppressed),
        'lint_suppressed': len(suppressed),
        # the thread-graph rules broken out: a bench number captured on
        # a tree with an open race/deadlock finding is not trustworthy
        'lint_concurrency_findings': len(conc),
        'lint_concurrency_suppressed': len(conc_sup),
    }
  except Exception:
    return {}


def _ledger_enabled():
  """Whether the determinism ledger will fingerprint this run's batches.

  Resolved through :func:`get_ledger` (not a raw env check) so the stamp
  reflects the same gate the pipeline consults — including programmatic
  ``enable_ledger()`` use that never touches ``LDDL_LEDGER``.
  """
  try:
    from lddl_tpu.telemetry.ledger import get_ledger
    return get_ledger().enabled
  except Exception:
    return False


def _sentinel_stamp():
  """Streaming-sentinel stamp: whether anomaly detection was armed
  during the measurement and with which detectors. A BENCH line taken
  with sentinels on carries their (small) per-step cost — see PERF.md
  "Sentinel & flight recorder overhead"."""
  try:
    from lddl_tpu.telemetry.sentinel import get_sentinel
    sent = get_sentinel()
    return {'enabled': bool(sent.enabled),
            'detectors': list(getattr(sent, 'detectors', ()) or ())}
  except Exception:
    return {'enabled': False, 'detectors': []}


def _replay_stamp():
  """Replay-capability stamp: whether this build can rematerialize a
  recorded coordinate (lddl-replay present) and the repro-bundle format
  version it writes — a BENCH line names the bundle format its ledger
  coordinates are replayable under."""
  try:
    from lddl_tpu.replay import BUNDLE_VERSION
    return {'available': True, 'bundle_version': BUNDLE_VERSION}
  except Exception:
    return {'available': False, 'bundle_version': None}


def _sink_bytes(sink):
  """(compressed, uncompressed) bytes of the Parquet shards under ``sink``.

  Compressed is the on-disk file size; uncompressed is the in-memory
  Arrow table size — the volume the write-back path actually serializes
  (the "dup=5 write-back wall"). lz4's 64 KB window dedupes the
  copy-adjacent duplicated text of materialized shards almost entirely,
  so the on-disk ratio understates the write-back work by design.
  """
  import pyarrow.parquet as pq
  disk = table = 0
  for root, _, names in os.walk(sink):
    for n in names:
      if '.parquet' in n:
        p = os.path.join(root, n)
        disk += os.path.getsize(p)
        table += pq.read_table(p).nbytes
  return disk, table


def _reference_style_partition(lines, hf_tok, vocab_words, seed,
                               duplicate_factor=5):
  """The reference's per-partition hot loop, reimplemented faithfully:
  per-sentence tokenize (``pretrain.py:79-91``), per-document pairing,
  per-token masking RNG loop (``pretrain.py:182-238``)."""
  import random

  from lddl_tpu.preprocess.bert import Document, create_pairs_from_document
  from lddl_tpu.preprocess.readers import split_id_text
  from lddl_tpu.tokenization import split_sentences

  rng = random.Random(seed)
  docs = []
  for line in lines:
    doc_id, text = split_id_text(line)
    sents = []
    for s in split_sentences(text, backend='rules'):
      toks = hf_tok.tokenize(s, max_length=512, truncation=True)  # 1 call/sent
      if toks:
        sents.append(tuple(toks))
    if sents:
      docs.append(Document(doc_id, tuple(sents)))
  instances = []
  for _ in range(duplicate_factor):  # reference default: 5 (pretrain.py:377)
    for di in range(len(docs)):
      instances.extend(
          create_pairs_from_document(
              docs, di, rng, masking=True, vocab_words=vocab_words))
  return instances


def main():
  corpus_mb = float(os.environ.get('LDDL_BENCH_MB', '64'))
  baseline_mb = float(os.environ.get('LDDL_BENCH_BASELINE_MB', '1'))
  work = tempfile.mkdtemp(prefix='lddl_bench_')
  try:
    src = os.path.join(work, 'source')
    from lddl_tpu.core.synth import write_corpus
    actual_mb = write_corpus(src, corpus_mb, num_shards=8, seed=1234)

    import jax
    num_chips = max(1, len(jax.devices()))

    from lddl_tpu.comm import comm_heartbeat_interval
    from lddl_tpu.loader.workers import _resolve_transport, _resolve_zero_copy
    from lddl_tpu.pipeline.executor import Executor, lease_timeout
    from lddl_tpu.preprocess.bert import BertPretrainConfig, run
    from lddl_tpu.preprocess.common import native_columnar_enabled
    from lddl_tpu.preprocess.readers import read_corpus
    from lddl_tpu.training.elastic import (async_ckpt_enabled,
                                           elastic_train_enabled)

    import dataclasses
    cfg = BertPretrainConfig(
        vocab_file=_VOCAB,
        target_seq_length=128,
        bin_size=32,
        duplicate_factor=5,  # the reference's default recipe
        masking=True,
        sentence_backend='rules',
        seed=42,
        engine='fast',
        tokenizer_backend='auto',
        mask_backend=os.environ.get('LDDL_BENCH_MASK', 'auto'))
    cfg1 = dataclasses.replace(cfg, duplicate_factor=1)
    executor = Executor()
    corpus = read_corpus([src], num_blocks=4 * executor.num_local_workers)
    # One-time warmups outside the timed region (multi-GB runs amortize
    # them): tokenizer construction (builds the native .so on first use)
    # and, when LDDL_BENCH_MASK=device, the jit masking kernel compile.
    from lddl_tpu.ops import mask_partition_device, resolve_mask_backend
    from lddl_tpu.preprocess.bert import _get_tokenizer
    try:  # pyarrow lazily imports pandas (when present) on first table
      import pandas  # noqa: F401
    except ImportError:
      pass
    tok = _get_tokenizer(cfg)
    tok.batch_tokenize(['warm up'])
    if resolve_mask_backend(cfg.mask_backend) == 'device':
      import numpy as _np
      mask_partition_device(
          _np.arange(64, dtype=_np.int32) % tok.vocab_size,
          _np.array([[0, 5]], _np.int64), _np.array([[10, 20]], _np.int64),
          seq_len=cfg.target_seq_length, masked_lm_ratio=cfg.masked_lm_ratio,
          vocab_size=tok.vocab_size, mask_id=tok.mask_token_id,
          cls_id=tok.cls_token_id, sep_id=tok.sep_token_id, seed=0)
    # One untimed pass first: the steady state a multi-GB run sits in
    # (page cache holding the sources, warmed allocator/branch history)
    # is reached only after the first tens of MB — measuring from cold
    # start made round-2 numbers swing ~20% run to run.
    run(corpus, os.path.join(work, 'sink_warm'), cfg1, executor=executor)
    shutil.rmtree(os.path.join(work, 'sink_warm'), ignore_errors=True)
    corpus = read_corpus([src], num_blocks=4 * executor.num_local_workers)
    t0 = time.perf_counter()
    run(corpus, os.path.join(work, 'sink1'), cfg1, executor=executor)
    dup1_s = time.perf_counter() - t0
    dup1_mbps = actual_mb / dup1_s / num_chips
    shutil.rmtree(os.path.join(work, 'sink1'), ignore_errors=True)
    corpus = read_corpus([src], num_blocks=4 * executor.num_local_workers)
    t0 = time.perf_counter()
    run(corpus, os.path.join(work, 'sink'), cfg, executor=executor)
    ours_s = time.perf_counter() - t0
    ours_mbps = actual_mb / ours_s / num_chips
    dup5_bytes, dup5_table_bytes = _sink_bytes(os.path.join(work, 'sink'))

    # dup=5 with the legacy materialized format, timed on the same corpus:
    # the delta-format write-back win (bytes and rate) is evidenced inside
    # every BENCH line instead of needing a cross-round comparison.
    from lddl_tpu.preprocess.bert import resolve_shard_format
    dup5_format = resolve_shard_format(cfg)
    cfg_mat = dataclasses.replace(cfg, shard_format='materialized')
    corpus = read_corpus([src], num_blocks=4 * executor.num_local_workers)
    t0 = time.perf_counter()
    run(corpus, os.path.join(work, 'sink_mat'), cfg_mat, executor=executor)
    mat_s = time.perf_counter() - t0
    mat_mbps = actual_mb / mat_s / num_chips
    mat_bytes, mat_table_bytes = _sink_bytes(os.path.join(work, 'sink_mat'))
    shutil.rmtree(os.path.join(work, 'sink_mat'), ignore_errors=True)

    # Reference-style hot loop (dup=5, like the timed headline run) on a
    # corpus slice, scaled.
    from lddl_tpu.tokenization.wordpiece import load_bert_tokenizer
    tok = load_bert_tokenizer(vocab_file=_VOCAB)
    lines, nbytes = [], 0
    budget = int(baseline_mb * 1024 * 1024)
    for name in sorted(os.listdir(src)):
      with open(os.path.join(src, name), encoding='utf-8') as f:
        for line in f:
          if nbytes >= budget:
            break
          lines.append(line.rstrip('\n'))
          nbytes += len(line.encode('utf-8'))
    t0 = time.perf_counter()
    _reference_style_partition(lines, tok.hf, tok.vocab_words, seed=42)
    ref_s = time.perf_counter() - t0
    ref_mbps = (nbytes / (1024 * 1024)) / ref_s / num_chips

    result = {
        'metric': 'bert_preprocess_mb_per_sec_per_chip',
        'value': round(ours_mbps, 3),
        'unit': 'MB/s/chip',
        'vs_baseline': round(ours_mbps / ref_mbps, 3),
        'dup1_mb_per_sec_per_chip': round(dup1_mbps, 3),
        # Explicit gated series for the dup=5 recipe (same number as
        # 'value'; named so `lddl-perf --gate` judges it by recipe), plus
        # the shard format that produced it.
        'dup5_mb_per_sec_per_chip': round(ours_mbps, 3),
        'shard_format': dup5_format,
        # Delta-format write-back evidence: bytes and rate of the same
        # dup=5 recipe under both formats, measured in this very run.
        # Nested on purpose — raw byte counts must not become auto-gated
        # history series (their direction heuristic would be wrong).
        'shard_formats': {
            'dup5': dup5_format,
            'dup5_sink_bytes': dup5_bytes,
            'dup5_materialized_sink_bytes': mat_bytes,
            'dup5_disk_reduction':
                round(mat_bytes / dup5_bytes, 3) if dup5_bytes else None,
            # Uncompressed Arrow table bytes = the volume the write-back
            # path serializes; this is the "write-back wall" number (lz4
            # hides most of the duplicated text on disk, see _sink_bytes).
            'dup5_table_bytes': dup5_table_bytes,
            'dup5_materialized_table_bytes': mat_table_bytes,
            'dup5_write_reduction':
                round(mat_table_bytes / dup5_table_bytes, 3)
                if dup5_table_bytes else None,
            'dup5_materialized_mb_per_sec_per_chip': round(mat_mbps, 3),
        },
        # The scheduler the numbers were measured under (workers, start
        # method, LPT+stealing, async write-back) — a BENCH line is not
        # comparable across scheduler configs without this.
        'scheduler': executor.scheduler_info(),
        # Feed-path knobs in effect (loader batch transport, zero-copy slot
        # views, fused native columnar shard assembly) — same
        # comparability rule as 'scheduler'.
        'transport': _resolve_transport(None),
        # Endpoint of the network data service when transport=network
        # (None otherwise): wire numbers are only comparable against
        # other wire numbers, and the endpoint says whose wire it was.
        'data_service': os.environ.get('LDDL_DATA_SERVER') or None,
        'zero_copy': _resolve_zero_copy(None),
        'native_columnar': native_columnar_enabled(),
        # Whether the LDDL_MONITOR live endpoint was serving during the
        # measurement (its thread shares the host CPU with the pipeline).
        'monitor': os.environ.get('LDDL_MONITOR', '') not in
                   ('', '0', 'false', 'off', 'no'),
        # Whether the determinism ledger was fingerprinting batches during
        # the measurement (per-batch xxh64/blake2b + O_APPEND write — see
        # PERF.md "Determinism ledger overhead"). A BENCH line captured
        # with the ledger on is not comparable against one with it off.
        'ledger': _ledger_enabled(),
        # Deterministic-replay capability of this build (lddl-replay +
        # bundle format version): names the replay contract the ledger
        # coordinates in this line are executable under.
        'replay': _replay_stamp(),
        # Whether streaming anomaly sentinels (LDDL_SENTINEL) were armed
        # during the measurement, and which detectors.
        'sentinel': _sentinel_stamp(),
        # Attention masking regime of the training stack this build feeds:
        # 'full' (whole packed row attends to itself) vs 'block_diagonal'
        # (per-doc segment ids, cross-doc tiles skipped) — LDDL_BENCH_
        # BLOCK_DIAGONAL mirrors the trainer's --block-diagonal flag.
        'attention_mask_mode':
            'block_diagonal'
            if os.environ.get('LDDL_BENCH_BLOCK_DIAGONAL', '') not in
            ('', '0', 'false', 'off', 'no') else 'full',
        # Fault-tolerance/resume regime during the measurement: the
        # elastic lease-claimed scheduler pays a (tiny) heartbeat +
        # claim-CAS cost the static stride does not, so a BENCH line is
        # not comparable across these settings either.
        'fault_tolerance': {
            'elastic': executor.scheduler_info().get('elastic', False),
            'lease_timeout_sec': lease_timeout(),
            'heartbeat_sec': comm_heartbeat_interval(),
            # Train-side elastic regime: membership polling and the
            # background checkpoint lane both shift the measured step
            # cadence, so the BENCH line records them (PERF.md keys its
            # async-ckpt overlap note off this stamp).
            'elastic_train': elastic_train_enabled(executor.comm),
            'async_ckpt': async_ckpt_enabled(),
        },
        'resume': {
            'resumable': executor.scheduler_info().get('elastic', False),
            'run_id': getattr(executor.comm, '_run_id', None),
        },
    }
    result.update(_telemetry_artifacts())
    result.update(_lint_status())
    # Append this run to the bench-history JSONL that `lddl-perf --gate`
    # judges (LDDL_BENCH_HISTORY overrides; never fails the bench).
    history = os.environ.get('LDDL_BENCH_HISTORY') or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), 'bench_history.jsonl')
    try:
      from lddl_tpu.telemetry.perf import append_history
      append_history(history, dict(result, unix_time=time.time()))
      result['bench_history'] = history
    except OSError:
      result['bench_history'] = None
    print(json.dumps(result))
    executor.close()
  finally:
    shutil.rmtree(work, ignore_errors=True)


if __name__ == '__main__':
  main()
