"""End-to-end tests for the mock-training harness + binning validator."""

import importlib.util
import json
import os
import random

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from test_loader import BIN_SIZE, _make_sample, _schema

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
  spec = importlib.util.spec_from_file_location(
      name, os.path.join(_ROOT, 'benchmarks', f'{name}.py'))
  mod = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(mod)
  return mod


train_bench = _load('train_bench')
validate_binning = _load('validate_binning')


@pytest.fixture(scope='module')
def shards(tmp_path_factory):
  root = tmp_path_factory.mktemp('bench_shards')
  r = random.Random(7)
  for bin_id in (0, 1):
    for shard in range(2):
      rows = [_make_sample(r, bin_id) for _ in range(32)]
      cols = {k: [row[k] for row in rows] for k in rows[0]}
      pq.write_table(
          pa.table(cols, schema=_schema(False)),
          root / f'part.{shard}.parquet_{bin_id}')
  return str(root)


def _run(shards, tiny_vocab, seq_dir, extra=()):
  return train_bench.main([
      '--path', shards, '--vocab-file', tiny_vocab, '--bin-size',
      str(BIN_SIZE), '--max-seq-length', '128', '--batch-size', '8',
      '--shuffle-buffer-size', '16', '--seq-len-dir', str(seq_dir),
      '--log-freq', '4', '--warmup', '1', *extra,
  ])


def test_loader_mode_and_validator(shards, tiny_vocab, tmp_path, capsys):
  seq_dir = tmp_path / 'lens'
  summary = _run(shards, tiny_vocab, seq_dir)
  assert summary['mode'] == 'loader'
  assert summary['iters'] == 16  # 2 bins * 64 samples / batch 8
  assert summary['samples_per_sec'] > 0
  npz = seq_dir / 'lens_0.npz'
  assert npz.exists()
  with np.load(npz) as z:
    assert z['padded_lens'].shape == (1, 16)
    assert set(np.unique(z['padded_lens'])) <= {64, 128}
    # every real length fits its batch's padded length
    assert (z['max_lens'] <= z['padded_lens']).all()

  rc = validate_binning.main(
      ['--in-dir', str(seq_dir), '--bin-size', str(BIN_SIZE)])
  assert rc == 0
  out = capsys.readouterr().out
  report = json.loads(out.strip().splitlines()[-1])
  assert report['cross_rank_bin_agreement'] is True
  assert report['worst_batch_spread'] <= BIN_SIZE
  assert report['padding_waste_ratio'] >= 0


def test_validator_catches_rank_divergence(shards, tiny_vocab, tmp_path):
  seq_dir = tmp_path / 'lens'
  _run(shards, tiny_vocab, seq_dir)
  # Forge a second rank that drew a different bin at iteration 3.
  with np.load(seq_dir / 'lens_0.npz') as z:
    forged = {k: z[k].copy() for k in z.files}
  forged['padded_lens'][0, 3] = 999
  np.savez_compressed(seq_dir / 'lens_1.npz', **forged)
  rc = validate_binning.main(
      ['--in-dir', str(seq_dir), '--bin-size', str(BIN_SIZE)])
  assert rc == 1


def test_validator_catches_loose_bins(tmp_path):
  np.savez_compressed(
      tmp_path / 'lens_0.npz',
      min_lens=np.array([[10]], dtype=np.uint16),
      max_lens=np.array([[200]], dtype=np.uint16),  # spread 190 > bin 64
      batch_sizes=np.array([[8]], dtype=np.uint16),
      padded_lens=np.array([[256]], dtype=np.uint16),
      seq_len_hist=np.zeros(4, dtype=np.uint64),
      padded_zero_hist=np.zeros(4, dtype=np.uint64))
  rc = validate_binning.main(
      ['--in-dir', str(tmp_path), '--bin-size', '64'])
  assert rc == 1


def _example_commands(script):
  """(program, argv) of every command an example script gives to the
  trainer, the mock trainer or the binning validator, its ``${...}``
  filled with a stand-in that is a path and a number alike."""
  import re
  import shlex
  with open(os.path.join(_ROOT, 'examples', script)) as f:
    text = f.read().replace('\\\n', ' ')
  for line in text.splitlines():
    words = shlex.split(re.sub(r'\$\{[^}]*\}', '1', line), comments=True)
    if words[:3] == ['python', '-m', 'lddl_tpu.training.pretrain']:
      yield 'pretrain', words[3:]
    elif (len(words) > 1 and words[0] == 'python' and
          words[1].startswith('1/benchmarks/')):
      yield words[1][len('1/benchmarks/'):], words[2:]


@pytest.mark.parametrize('script', ['local_example.sh', 'tpu_pod_example.sh'])
def test_examples_name_programs_and_flags_that_exist(script, monkeypatch):
  """Step 4 on of the two examples: every script they run is there and
  every flag they pass parses under that script's own parser (the local
  example runs the product's loop, ``lddl_tpu.training.pretrain``)."""
  import argparse

  from lddl_tpu.training import pretrain
  seen = set()
  for program, argv in _example_commands(script):
    seen.add(program)
    if program == 'pretrain':
      args = pretrain.attach_args(argparse.ArgumentParser()).parse_args(argv)
      assert args.model in pretrain.MODEL_SIZES
    elif program == 'train_bench.py':
      train_bench.attach_args(argparse.ArgumentParser()).parse_args(argv)
    else:
      assert program == 'validate_binning.py', program
      # Its parser lives in main(): parse, then stop before it reads.
      monkeypatch.setattr(validate_binning, 'collect', lambda d: 1 / 0)
      with pytest.raises(ZeroDivisionError):
        validate_binning.main(argv)
  assert {'pretrain', 'train_bench.py'} <= seen
  if script == 'local_example.sh':
    assert 'validate_binning.py' in seen


def test_bart_loader_bench_smoke(tiny_vocab, tmp_path, capsys):
  """The committed BART-loader artifact must stay reproducible: the
  bench drains balanced sentences shards and prints one JSON line."""
  bench = _load('bart_loader_bench')
  root = tmp_path / 'bart'
  root.mkdir()
  r = random.Random(3)
  words = ['alpha', 'bravo', 'charlie', 'delta', 'echo']
  for shard in range(2):
    sents = [' '.join(r.choice(words) for _ in range(12)) + '.'
             for _ in range(24)]
    pq.write_table(pa.table({'sentences': sents}),
                   root / f'shard-{shard}.parquet')
  import sys
  argv = sys.argv
  try:
    sys.argv = ['x', '--path', str(root), '--vocab-file', tiny_vocab,
                '--batch-size', '4', '--iters', '4', '--warmup', '1']
    bench.main()
  finally:
    sys.argv = argv
  out = capsys.readouterr().out.strip().splitlines()[-1]
  payload = json.loads(out)
  assert payload['metric'] == 'bart_loader_samples_per_sec'
  assert payload['batches'] == 4 and payload['value'] > 0


def test_loader_bench_smoke(tmp_path, capsys):
  """loader_bench sweeps num_workers x transport in both modes, prints
  one JSON line per cell + a summary with shm-vs-pickle speedups, and
  self-attaches per-cell telemetry artifacts."""
  import glob
  bench = _load('loader_bench')
  result = bench.main([
      '--mode', 'both', '--batch-size', '4', '--max-seq-length', '64',
      '--iters', '6', '--e2e-iters', '4', '--warmup', '1',
      '--workers', '1', '--bin-size', '64', '--bin-id', '0',
      '--num-files', '2', '--samples-per-file', '16',
      '--telemetry-dir', str(tmp_path / 'tele'),
  ])
  cells = result['cells']
  assert {c['mode'] for c in cells} == {'transport', 'e2e'}
  for mode in ('transport', 'e2e'):
    assert {c['transport'] for c in cells
            if c['mode'] == mode and c['num_workers'] == 1} \
        == {'pickle', 'shm'}
  for c in cells:
    assert c['batches_per_sec'] > 0 and c['mb_per_sec'] > 0
    assert glob.glob(
        os.path.join(c['telemetry_dir'], 'telemetry.rank*.jsonl'))
  assert 'w1' in result['summary']['shm_speedup']['transport']
  lines = capsys.readouterr().out.strip().splitlines()
  assert json.loads(lines[-1])['metric'] == 'loader_bench_summary'


def test_h2d_bench_smoke(capsys):
  """h2d_bench feeds a synthetic loader through prefetch_to_device and
  derives the overlap fraction from the same train.h2d/train.compute
  trace spans a real run exports."""
  bench = _load('h2d_bench')
  result = bench.main(
      ['--iters', '6', '--batch-size', '8', '--seq-length', '64'])
  assert result['metric'] == 'h2d_overlap_fraction'
  assert 0.0 <= result['value'] <= 1.0
  assert result['h2d_spans'] == 6
  assert result['batches_per_sec'] > 0
  assert result['donation_contract_held'] is True
  line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
  assert line['metric'] == 'h2d_overlap_fraction'


def test_h2d_overlap_fraction_math():
  bench = _load('h2d_bench')
  f = bench.overlap_fraction
  # fully covered, half covered, disjoint
  assert f([(0.0, 1.0)], [(0.0, 2.0)]) == pytest.approx(1.0)
  assert f([(0.0, 1.0)], [(0.5, 2.0)]) == pytest.approx(0.5)
  assert f([(0.0, 1.0)], [(2.0, 1.0)]) == 0.0
  # overlapping compute spans must not double-count coverage
  assert f([(0.0, 1.0)], [(0.0, 0.8), (0.2, 0.8)]) == pytest.approx(1.0)
  assert f([], [(0.0, 1.0)]) == 0.0


def test_loader_bench_committed_artifact_meets_speedup_floor():
  """The committed sweep artifact must demonstrate the shm transport's
  reason to exist: >= 1.5x batches/s over the pickling queue for
  num_workers >= 2 at batch 64 x seq 512 (transport-isolated mode)."""
  path = os.path.join(_ROOT, 'benchmarks', 'results',
                      'loader_transport_sweep.txt')
  summary = None
  with open(path) as f:
    for line in f:
      if line.startswith('{'):
        payload = json.loads(line)
        if payload.get('metric') == 'loader_bench_summary':
          summary = payload
  assert summary is not None
  assert summary['batch_size'] == 64 and summary['max_seq_length'] == 512
  assert summary['shm_speedup']['transport']['w2'] >= 1.5


def test_real_text_corpus_harvest(tmp_path):
  """real_text_bench's harvester yields real prose documents in the
  one-doc-per-line source format with markup stripped."""
  bench = _load('real_text_bench')
  mb = bench.build_corpus(str(tmp_path / 'src'), 0.2, num_shards=2)
  assert mb >= 0.1
  lines = []
  for name in os.listdir(tmp_path / 'src'):
    with open(tmp_path / 'src' / name, encoding='utf-8') as f:
      lines += f.readlines()
  assert len(lines) > 10
  for ln in lines[:50]:
    doc_id, text = ln.split(None, 1)
    assert doc_id.startswith('real-')
    assert len(text) >= 200
    assert '`' not in text and '_' not in text  # markup stripped


def test_flops_accounting_scales():
  from lddl_tpu.models import BertConfig
  from lddl_tpu.models.flops import bert_pretrain_flops_per_step
  cfg = BertConfig()
  f1 = bert_pretrain_flops_per_step(cfg, 8, 128)
  assert f1 == 2 * bert_pretrain_flops_per_step(cfg, 4, 128)
  # attention term makes doubling seq more than double the cost
  assert bert_pretrain_flops_per_step(cfg, 8, 256) > 2 * f1
  # BERT-base @ seq 512 is ~0.3-0.5 TFLOP/sample forward; sanity window.
  per_sample_fwd = bert_pretrain_flops_per_step(cfg, 1, 512) / 3
  assert 1e11 < per_sample_fwd < 1e12


def test_epoch_cutoff_still_advances_epoch(shards, tiny_vocab, tmp_path,
                                           capsys):
  # With an --iters-per-epoch cutoff the loader generator never reaches its
  # natural end; the harness must still advance the epoch so epoch 1 is not
  # a byte-identical replay of epoch 0.
  seq_dir = tmp_path / 'lens'
  _run(shards, tiny_vocab, seq_dir,
       extra=['--epochs', '2', '--iters-per-epoch', '8', '--seed', '3'])
  with np.load(seq_dir / 'lens_0.npz') as z:
    row0 = np.stack([z['min_lens'][0], z['max_lens'][0], z['padded_lens'][0]])
    row1 = np.stack([z['min_lens'][1], z['max_lens'][1], z['padded_lens'][1]])
  assert not np.array_equal(row0, row1)
