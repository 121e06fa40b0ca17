"""The fifteen readers of the program's capture summary: nothing to read
gives None (an untraced run, a CPU rehearsal, a program without the
summary), and the recorded steps of the s=128 cell give the numbers
worked out here from the summary's nanoseconds."""

import gzip
import json
import os
import statistics

import pytest

from chipbench import run

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
FIXTURE = os.path.join(REPO, 'tests', 'fixtures',
                       'capture_pairs_s128_three_steps.json.gz')

GAP_READERS = {
    'loop.gap_sync_ms': 'train.loss_read',
    'loop.gap_after_step_ms': 'train.after_step',
    'loop.gap_data_wait_ms': 'train.data_wait',
    'loop.gap_dispatch_ms': 'train.dispatch',
}
SHARE_READERS = {
    'train_step.attention_pct': ('classes', 'attention'),
    'train_step.ffn_pct': ('classes', 'ffn'),
    'train_step.dropout_pct': ('classes', 'dropout'),
    'train_step.norms_pct': ('classes', 'norms'),
    'train_step.head_loss_pct': ('classes', 'head_loss'),
    'train_step.optimizer_pct': ('classes', 'optimizer'),
    'train_step.unscoped_pct': ('classes', 'unscoped'),
    'train_step.recompute_pct': ('passes', 'recompute'),
    'train_step.backward_pct': ('passes', 'backward'),
}
ALL = [*GAP_READERS, 'loop.gap_unattributed_pct', 'loop.epoch_turn_ms',
       *SHARE_READERS]


@pytest.fixture()
def profiler():
  """The program's profiler singleton, as new, and telemetry off."""
  import lddl_tpu.telemetry as telemetry
  import lddl_tpu.telemetry.profiling as profiling
  profiling._reset_for_tests()
  telemetry.disable()
  yield profiling.get_step_profiler()
  profiling._reset_for_tests()
  telemetry.disable()


@pytest.fixture()
def summary(profiler):
  from lddl_tpu.telemetry import capture
  with gzip.open(FIXTURE, 'rt') as f:
    found = capture.summarize(json.load(f)['events'])
  profiler.last_summary = found
  return found


def test_the_benchmark_declares_each_of_these_readers():
  # Found by name: a later PR appends its own entries after them.
  bench = run.load_json(os.path.join(REPO, 'BENCHMARK.json'))
  declared = {m['name']: m for m in bench['per_layer']}
  assert len(declared) == len(bench['per_layer'])
  assert set(ALL) <= set(declared)
  for m in (declared[name] for name in ALL):
    assert m['moves'] == 'tokens_per_s' and 'workloads' not in m
    assert os.path.exists(os.path.join(REPO, 'chipbench', 'metrics',
                                       m['name'] + '.py'))


@pytest.mark.parametrize('name', ALL)
def test_nothing_to_read_gives_none(profiler, name):
  assert run.read_per_layer([name], {}) == {}
  # A capture that found no device (a CPU trace) is nothing to read.
  profiler.last_summary = {'devices': [], 'phases_seen': []}
  assert run.read_per_layer([name], {}) == {}


@pytest.mark.parametrize('name', ALL)
def test_a_program_without_the_summary_gives_none(monkeypatch, name):
  # The parent of the PR that added the summary: its profiler has no
  # such attribute, its registry no such histogram.
  import lddl_tpu.telemetry.profiling as profiling

  class Old:
    pass

  monkeypatch.setattr(profiling, 'get_step_profiler', Old)
  assert run.read_per_layer([name], {}) == {}


@pytest.mark.parametrize('name', GAP_READERS)
def test_gap_readers(summary, name):
  phase = GAP_READERS[name]
  parts = [g['phases'][phase] for g in summary['devices'][0]['gaps']]
  got = run.read_per_layer([name], {})[name]
  assert got == pytest.approx(statistics.median(parts) / 1e6)
  assert 0.01 < got < 5.0  # ms: a part of a gap of a few ms


def test_unattributed_reader(summary):
  gaps = summary['devices'][0]['gaps']
  want = (100.0 * sum(g['phases']['unattributed'] for g in gaps) /
          sum(g['ns'] for g in gaps))
  got = run.read_per_layer(['loop.gap_unattributed_pct'], {})
  assert got == {'loop.gap_unattributed_pct': pytest.approx(want)}
  assert 0 <= want < 10


@pytest.mark.parametrize('name', SHARE_READERS)
def test_share_readers(summary, name):
  key, entry = SHARE_READERS[name]
  d = summary['devices'][0]
  got = run.read_per_layer([name], {})[name]
  assert got == pytest.approx(100.0 * d[key][entry] / d['busy_ns'])
  assert 0 <= got < 100


def test_the_shares_of_all_classes_sum_to_the_busy_time(summary):
  d = summary['devices'][0]
  shares = run.read_per_layer(
      [n for n, (key, _) in SHARE_READERS.items() if key == 'classes'], {})
  # The seven classes with a metric, plus the two without one.
  rest = 100.0 * (d['classes']['embed'] + d['classes']['scan_carry']) / (
      d['busy_ns'])
  assert sum(shares.values()) + rest == pytest.approx(100.0)


def test_epoch_turn_reader(profiler):
  import lddl_tpu.telemetry as telemetry
  name = 'loop.epoch_turn_ms'
  tele = telemetry.enable()
  assert run.read_per_layer([name], {}) == {}  # no epoch turned yet
  tele.histogram('train.epoch_turn_seconds').observe(0.010)
  tele.histogram('train.epoch_turn_seconds').observe(0.030)
  assert run.read_per_layer([name], {}) == {name: pytest.approx(20.0)}
