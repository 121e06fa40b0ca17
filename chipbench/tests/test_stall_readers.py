"""The three readers of the program's per-step stall record
(``loop.stalled_steps``, ``loop.stall_pct``, ``loop.gc_ms_per_step``):
nothing to read gives None; a fabricated record gives the numbers worked
out here over the window's steps, found by matching the record's
intervals to the window's, on either stop path; a record that matches
the window on neither gives None."""

import os
import sys

import pytest

from chipbench import run

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
NAMES = ('loop.stalled_steps', 'loop.stall_pct', 'loop.gc_ms_per_step')


@pytest.fixture()
def record():
  from lddl_tpu.telemetry import stalls
  rows = stalls.step_record()
  saved = list(rows)
  rows.clear()
  yield rows
  rows.clear()
  rows.extend(saved)


def interval(step):
  """Two step shapes, 50 and 70 ms, in no simple order."""
  return 0.070 if step % 3 == 0 or step % 7 == 0 else 0.050


def fabricated(rows, first, last, stalled=()):
  """Steps ``first`` to ``last``, judged against their shape's interval,
  with 1 ms of the collector; a stalled step takes 200 ms more and 100
  ms of the collector."""
  for step in range(first, last + 1):
    if step in stalled:
      rows.append((step, interval(step) + 0.200, interval(step), 0.100, True))
    else:
      rows.append((step, interval(step), interval(step), 0.001, False))


def window(rows, first, last, lag_ms=0.3):
  """The benchmark's view of steps ``first`` to ``last``: each interval
  off the record's by the host work between a loss read and the next step
  call (``lag_ms``, one way or the other)."""
  by_step = {row[0]: row for row in rows}
  ms = [1e3 * by_step[s][1] + (lag_ms if s % 2 else -lag_ms)
        for s in range(first, last + 1)]
  return {'intervals_ms': ms, 'wall_s': 1e-3 * sum(ms)}


def test_the_benchmark_declares_the_three_readers():
  bench = run.load_json(os.path.join(REPO, 'BENCHMARK.json'))
  declared = {m['name']: m for m in bench['per_layer']}
  for name in NAMES:
    m = declared[name]
    assert (m['layer'], m['moves'], m['better']) == (
        'train loop', 'tokens_per_s', 'lower')
    assert 'workloads' not in m  # read in every cell
    assert os.path.exists(os.path.join(REPO, 'chipbench', 'metrics',
                                       name + '.py'))


@pytest.mark.parametrize('name', NAMES)
def test_no_record_gives_none(record, monkeypatch, name):
  ctx = {'intervals_ms': [50.0] * 4, 'wall_s': 0.200}
  assert run.read_per_layer([name], ctx) == {}  # telemetry was off
  # A program without the record: the module cannot be imported.
  fabricated(record, 1, 12)
  monkeypatch.setitem(sys.modules, 'lddl_tpu.telemetry.stalls', None)
  assert run.read_per_layer([name], ctx) == {}


def expected(rows, first, last):
  got = [row for row in rows if first <= row[0] <= last]
  return {
      'loop.stalled_steps': float(sum(row[4] for row in got)),
      'loop.stall_pct': pytest.approx(
          100.0 * sum(row[1] - row[2] for row in got if row[4]) /
          sum(row[1] for row in got)),
      'loop.gc_ms_per_step': pytest.approx(
          1e3 * sum(row[3] for row in got) / len(got))}


def test_the_window_is_the_steps_before_the_stop_path(record):
  # Steps 1-20 found finished; the window is 11-18; 19 and 20 are the
  # stop path's. Stalls at 5 (before the window), 12 and 19 (after).
  fabricated(record, 1, 20, stalled=(5, 12, 19))
  got = run.read_per_layer(list(NAMES), window(record, 11, 18))
  assert got == expected(record, 11, 18)
  assert got['loop.stalled_steps'] == 1.0


def test_where_the_host_lags_the_stop_path_has_one_step(record):
  # The tap found step 18 finished one call early: the loop then finds
  # only step 19 after it. Stalls at 11 and 18, the window's two ends.
  fabricated(record, 1, 19, stalled=(10, 11, 18))
  got = run.read_per_layer(list(NAMES), window(record, 11, 18))
  assert got == expected(record, 11, 18)
  assert got['loop.stalled_steps'] == 2.0


def test_a_freeze_after_the_last_find_is_the_next_steps(record):
  # The host froze for 150 ms after the record found step 78 finished and
  # before the tap did: the tap reads it in step 78, inside the window,
  # the record in step 79, the stop path's.
  fabricated(record, 1, 80, stalled=(79,))
  ctx = window(record, 31, 78)
  ctx['intervals_ms'][-1] += 150.0
  ctx['wall_s'] += 0.150
  got = run.read_per_layer(list(NAMES), ctx)
  assert got == expected(record, 31, 78)
  assert got['loop.stalled_steps'] == 0.0


def test_a_record_without_every_window_step_gives_none(record):
  fabricated(record, 1, 20, stalled=(5, 12, 19))
  ctx = window(record, 11, 18)
  del record[12]  # the row of step 13
  assert run.read_per_layer(list(NAMES), ctx) == {}


def test_a_record_that_is_not_the_window_gives_none(record):
  # Every step a stall in the record, none in the window's own timing:
  # the summed intervals part by more than the ends allow.
  fabricated(record, 1, 20, stalled=range(1, 21))
  ctx = window([(s, interval(s)) for s in range(1, 21)], 11, 18)
  assert run.read_per_layer(list(NAMES), ctx) == {}


def test_a_window_without_stalls_reads_nought(record):
  fabricated(record, 1, 20)
  got = run.read_per_layer(list(NAMES), window(record, 11, 18))
  assert got['loop.stalled_steps'] == 0.0 and got['loop.stall_pct'] == 0.0
  assert got['loop.gc_ms_per_step'] == pytest.approx(1.0)
