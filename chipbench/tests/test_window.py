"""The window holds whole epochs, the same work for every seed.

``run.Window`` is fed hand-made call times, epoch starts and the moments
at which each step is found finished (the loop one or two steps ahead of
the device): it opens and closes on epoch starts only, when the last step
before one is found finished; closes at the first one ``--seconds`` after
it opened (one epoch where an epoch is longer); and leaves ``setup_s`` at
the first call after warm-up. Then whole runs on the CPU: a loop that
ends before an epoch start closes the window gives exit 2 and no result;
``rehearsal.pairs`` on two seeds fills its window with the same steps per
shape, and with the same real tokens up to the loader's drop-last tail;
the packed traffic at the tiny preset still reads ``correct``.
"""

import json
import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class Loss:
  """A step's loss, ready once the device has finished the step."""
  ready = False

  def is_ready(self):
    return self.ready


def drive(monkeypatch, epoch_starts, seconds, calls=60, trace=False,
          ahead=1):
  """A ``Window`` fed one call a second by a loop that keeps ``ahead``
  steps in flight (call ``i`` is made once step ``i - ahead - 1`` is
  finished); ``(window, calls at which it asked the loop to stop)``."""
  from chipbench import run
  from lddl_tpu.telemetry import profiling
  family = types.SimpleNamespace(
      first_gradient_norms=lambda opt_state: 'grad',
      change_norms=lambda config, seed, params: 'change')
  cell = {'family': family, 'config_data': {},
          'traffic_data': {'window': {'warmup_steps': 5, 'trace_steps': 4}}}
  armed = []
  monkeypatch.setattr(
      profiling, 'get_step_profiler',
      lambda: types.SimpleNamespace(arm=lambda n, out_dir: armed.append(n)))
  window = run.Window(cell, seed=1, seconds=seconds,
                      trace_dir='somewhere' if trace else None)
  window.epoch_starts = epoch_starts
  stops = []
  monkeypatch.setattr(run.os, 'kill',
                      lambda pid, sig: stops.append(len(window.calls) - 1))
  tap = types.SimpleNamespace(misses=0)
  losses = []
  for i in range(calls):
    for loss in losses[:max(i - ahead, 0)]:
      loss.ready = True
    window.on_call(100.0 + i, None, None, tap)
    losses.append(Loss())
    window.pending.append(losses[-1])
  # Step j was found finished at call j + ahead + 1, a second a call.
  assert window.done == [100.0 + j + ahead + 1
                         for j in range(calls - ahead - 1)]
  assert armed == ([4] if trace else [])
  return window, stops


@pytest.mark.parametrize('ahead', [1, 2])
@pytest.mark.parametrize('seconds, starts, trace, opened, closed', [
    (12, [0, 10, 20, 30, 40, 50], False, 10, 30),  # 20 is only 10 s later
    (10, [0, 10, 20, 30, 40, 50], False, 10, 20),  # exactly --seconds later
    (3, [0, 10, 20, 30, 40, 50], False, 10, 20),   # an epoch is longer
    (12, [0, 5, 17, 29, 41], False, 5, 17),        # a start at open_at opens
    (12, [0, 10, 20, 30, 40, 50], True, 20, 40),   # traced: open_at is 11
    (25, [0, 7, 14, 21, 28, 35, 42, 49], False, 7, 35),
])
def test_opens_and_closes_on_epoch_starts_only(monkeypatch, seconds, starts,
                                               trace, opened, closed, ahead):
  window, stops = drive(monkeypatch, starts, seconds, trace=trace,
                        ahead=ahead)
  assert (window.open_index, window.close_index) == (opened, closed)
  assert opened in starts and closed in starts
  # Asked to stop once: at the call that found the window's last step
  # finished. The clock was read when the step before each of the two
  # epoch starts was found finished, whatever the loop's lead.
  assert stops == [closed + ahead]
  assert window.done[closed - 1] - window.done[opened - 1] == closed - opened
  # Set-up ends at the first call after warm-up (in a traced run after the
  # traced steps and two more), not at the epoch start that opens.
  assert window.open_at == (11 if trace else 5)
  assert window.calls[window.open_at] == 100.0 + window.open_at
  assert (window.grad_norms, window.change_norms) == ('grad', 'change')


def test_no_epoch_start_no_window(monkeypatch):
  window, stops = drive(monkeypatch, [0], seconds=3)
  assert (window.open_index, window.close_index, stops) == (None, None, [])
  window, stops = drive(monkeypatch, [0, 40], seconds=30)
  assert (window.open_index, window.close_index, stops) == (40, None, [])


def run_main(capsys, *args):
  from chipbench import run
  run.main(['--workload', 'rehearsal.pairs', '--seconds', '1', '--trace',
            '0', *args])
  return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_a_loop_that_ends_before_the_window_closes_gives_exit_2(
    monkeypatch, capsys):
  from chipbench import run
  find_cell = run.find_cell

  def short(name):
    cell = find_cell(name)
    cell['traffic_data']['window']['max_steps'] = 12  # under one epoch
    return cell

  monkeypatch.setattr(run, 'find_cell', short)
  with pytest.raises(SystemExit) as stop:
    run_main(capsys, '--seed', '3')
  assert stop.value.code == 2
  assert capsys.readouterr().out == ''


def test_two_seeds_fill_the_window_with_the_same_work(capsys):
  a = run_main(capsys, '--seed', '1')['window']
  b = run_main(capsys, '--seed', '2147483777')['window']
  assert a['epochs'] == b['epochs'] == 1
  assert a['steps'] == b['steps'] == sum(a['steps_per_shape'].values())
  assert a['steps_per_shape'] == b['steps_per_shape']
  assert len(a['steps_per_shape']) == 2          # the two bins of 64
  # Which samples the loader's drop-last tail leaves out (up to
  # batch_size - 1 of each bin) is the seed's: the real tokens agree up to
  # that tail, 7 samples of at most 64 and of at most 128 tokens.
  assert abs(a['real_tokens'] - b['real_tokens']) <= 7 * (64 + 128)
  assert abs(a['real_tokens'] - b['real_tokens']) < 1e-3 * a['real_tokens']


def test_the_packed_traffic_at_the_tiny_preset_is_correct():
  done = subprocess.run(
      [sys.executable, 'chipbench/run.py', '--workload',
       'rehearsal.packed-x4', '--seed', '4', '--seconds', '1', '--trace',
       '0'], cwd=REPO, capture_output=True, text=True, timeout=900,
      env=dict(os.environ, JAX_PLATFORMS='cpu',
               XLA_FLAGS='--xla_force_host_platform_device_count=4'))
  assert done.returncode == 0, done.stderr[-2000:]
  result = json.loads(done.stdout.strip().splitlines()[-1])
  assert result['correct'] is True and result['failed'] == 0
  window = result['window']
  assert window['epochs'] == 1
  assert list(window['steps_per_shape']) == ['4x512']
  assert result['attempted'] == window['steps']
