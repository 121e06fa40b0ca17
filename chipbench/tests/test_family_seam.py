"""The family seam, and a rehearsal of the PR that brings a new family.

A copy of the benchmark (``BENCHMARK.json`` and ``chipbench/``; the
program and the vocabulary by symlink) gets *only new files* laid over
it, ``fixtures/next_pr/``: a family the harness did not know (BERT under
another module name, with a key of its own for the feed-forward width), a
configuration file that names it, a rehearsal entry and a limits file.
``run.py`` then runs that cell whole, on the CPU, and no file that was
there differs. A family that lacks a part of the interface is refused
with exit 2 and the part's name.
"""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
NEXT_PR = os.path.join(HERE, 'fixtures', 'next_pr')


def digests(root):
  out = {}
  for top in ('BENCHMARK.json', 'chipbench'):
    path = os.path.join(root, top)
    files = [path] if os.path.isfile(path) else [
        os.path.join(d, name) for d, _, names in os.walk(path)
        for name in names if '__pycache__' not in d]
    for name in files:
      with open(name, 'rb') as f:
        out[os.path.relpath(name, root)] = hashlib.sha256(
            f.read()).hexdigest()
  return out


@pytest.fixture()
def checkout(tmp_path):
  """The benchmark's own files as a later PR finds them, the new files of
  ``fixtures/next_pr`` laid over them; ``(root, digests before)``."""
  shutil.copy(os.path.join(REPO, 'BENCHMARK.json'), tmp_path)
  shutil.copytree(os.path.join(REPO, 'chipbench'), tmp_path / 'chipbench',
                  ignore=shutil.ignore_patterns('__pycache__'))
  # Shards made once serve every test of this tree.
  os.makedirs(os.path.join(REPO, '.chipbench_work'), exist_ok=True)
  for name in ('lddl_tpu', 'benchmarks', '.chipbench_work'):
    os.symlink(os.path.join(REPO, name), tmp_path / name)
  before = digests(tmp_path)
  for d, _, names in os.walk(NEXT_PR):
    for name in names:
      new = os.path.relpath(os.path.join(d, name), NEXT_PR)
      assert new not in before, f'{new} is there already: not a new file'
  shutil.copytree(NEXT_PR, tmp_path, dirs_exist_ok=True)
  return tmp_path, before


def run_cell(root, workload):
  return subprocess.run(
      [sys.executable, 'chipbench/run.py', '--workload', workload, '--seed',
       '1', '--seconds', '2', '--trace', '0'],
      cwd=root, capture_output=True, text=True, timeout=600,
      env=dict(os.environ, JAX_PLATFORMS='cpu'))


def test_a_new_family_runs_from_new_files_only(checkout):
  root, before = checkout
  done = run_cell(root, 'widthkey.pairs')
  assert done.returncode == 0, done.stderr[-2000:]
  result = json.loads(done.stdout.strip().splitlines()[-1])
  assert result['correct'] is True
  assert result['attempted'] > 0 and result['failed'] == 0
  assert result['compared']['compiles_in_window'] == {'value': 0, 'limit': 0}
  after = digests(root)
  assert {k: after[k] for k in before} == before
  assert sorted(set(after) - set(before)) == [
      'chipbench/configs/widthkey-tiny.json',
      'chipbench/families/widthkey.py',
      'chipbench/limits/widthkey.pairs.json',
      'chipbench/rehearsal/widthkey.pairs.json']


def test_a_family_that_lacks_a_part_is_refused_by_name(checkout):
  root, _ = checkout
  bench = root / 'chipbench'
  (bench / 'families' / 'lacking.py').write_text(
      'from chipbench.families.widthkey import *  # noqa: F401,F403\n'
      'del follow, padded_flops  # noqa: F821\n')
  config = json.loads((bench / 'configs' / 'widthkey-tiny.json').read_text())
  (bench / 'configs' / 'lacking-tiny.json').write_text(
      json.dumps(dict(config, family='lacking')))
  entry = json.loads((bench / 'rehearsal' / 'widthkey.pairs.json').read_text())
  (bench / 'rehearsal' / 'lacking.pairs.json').write_text(json.dumps(dict(
      entry, name='lacking.pairs', config='lacking-tiny',
      config_file='chipbench/configs/lacking-tiny.json')))
  shutil.copy(bench / 'limits' / 'widthkey.pairs.json',
              bench / 'limits' / 'lacking.pairs.json')
  done = run_cell(root, 'lacking.pairs')
  assert done.returncode == 2
  assert done.stdout == ''
  assert "lacks ['follow', 'padded_flops']" in done.stderr


def test_a_family_that_is_not_there_is_refused():
  from chipbench import families
  assert families.load({}).__name__ == 'chipbench.families.bert'
  for name in ('no_such_family', 'not-a-module', '../bert', 7):
    with pytest.raises(families.Refused):
      families.load({'family': name})


def test_the_bert_family_bills_what_required_work_bills():
  from chipbench import families, required_work, run
  config = run.load_json(os.path.join(REPO, 'chipbench', 'configs',
                                      'bert-base.json'))
  train = run.load_json(os.path.join(
      REPO, 'chipbench', 'traffic', 'pairs-s128.json'))['train']
  family = families.load(config)
  facts = {'rows': [40, 128], 'units': [40, 128], 'masked': [6, 19]}
  flops = family.required_flops(config, train, facts)
  assert flops == required_work.step_required_flops(
      config, [40, 128], [40, 128], [6, 19], train['max_predictions'])
  full = {'rows': [128] * 64, 'units': [128] * 64, 'masked': [20] * 64}
  assert flops < family.required_flops(config, train, full) == (
      family.padded_flops(config, train, 128))
  assert family.flash_required(config, train, facts) == (
      required_work.flash_required(config, [40, 128]))


def test_batch_facts_of_pairs_and_of_packed_rows():
  import numpy as np

  from chipbench import families
  family = families.load({})
  train = {'batch_size': 2, 'block_diagonal': False}
  batch = family.fake_batch(train, 16)
  batch['attention_mask'][1, 10:] = 0
  assert family.batch_facts(batch) == {
      'rows': [16, 10], 'units': [16, 10], 'masked': [3, 3]}
  packed = family.fake_batch(dict(train, block_diagonal=True), 16)
  packed['attention_mask'][0, 12:] = 0
  packed['segment_ids'][0] = np.array([0] * 5 + [1] * 7 + [-1] * 4)
  assert family.batch_facts(packed) == {
      'rows': [12, 16], 'units': [5, 7, 16], 'masked': [3, 3]}
