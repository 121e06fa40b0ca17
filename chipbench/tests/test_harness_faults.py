"""The whole of a run with the timed path broken underneath.

The harness's look for a chip is skipped (a rehearsal cell runs on any
backend); everything else is ``run.main`` as the driver calls it. Once for
each fault a one-chip training cell can have, ``correct`` has to come out
false; on the program as it is, true.
"""

import json

import pytest

ARGS = ['--workload', 'rehearsal.pairs', '--seed', '2', '--seconds', '1.5',
        '--trace', '0']


def run_main(capsys):
  from chipbench import run
  run.main(ARGS)
  return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def break_step(monkeypatch, broken):
  """Put ``broken(step)`` in the place of every train step the program
  builds (``TrainLoop.build`` imports the factory from the package)."""
  import lddl_tpu.parallel as parallel
  make = parallel.make_train_step

  def make_broken(*args, **kwargs):
    return broken(make(*args, **kwargs))

  monkeypatch.setattr(parallel, 'make_train_step', make_broken)


def test_the_program_as_it_is_is_correct(capsys):
  result = run_main(capsys)
  assert result['correct'] is True
  assert result['attempted'] > 0 and result['failed'] == 0
  assert result['metrics'] == {}          # not a chip: no device metric
  assert result['device']['platform'] == 'cpu'
  assert list(result)[-1] == 'compared'   # the numbers compared come last
  assert set(result['compared']) == {
      'loss_gap', 'loss_gap_1', 'loss_gap_2', 'loss_gap_3', 'grad_gap',
      'grad_gap_median', 'grad_gap_global', 'change_gap',
      'change_gap_median', 'change_gap_global', 'compiles_in_window'}


def test_a_step_that_returns_its_state_unchanged(monkeypatch, capsys):
  import jax

  def broken(step):
    def unchanged(params, opt_state, rng, batch):
      _, _, metrics = step.__wrapped__(params, opt_state, rng, batch)
      return params, opt_state, metrics
    return jax.jit(unchanged)

  break_step(monkeypatch, broken)
  result = run_main(capsys)
  assert result['correct'] is False
  compared = result['compared']
  assert compared['change_gap']['value'] == pytest.approx(1.0)
  assert compared['grad_gap']['value'] == pytest.approx(1.0)


def test_half_of_the_batch_left_out(monkeypatch, capsys):
  import jax

  def broken(step):
    def half(params, opt_state, rng, batch):
      n = batch['input_ids'].shape[0] // 2
      return step.__wrapped__(params, opt_state, rng,
                              {k: v[:n] for k, v in batch.items()})
    return jax.jit(half, donate_argnums=(0, 1))

  break_step(monkeypatch, broken)
  result = run_main(capsys)
  assert result['correct'] is False
  compared = result['compared']
  assert (compared['grad_gap']['value'] > compared['grad_gap']['limit'] or
          compared['change_gap']['value'] > compared['change_gap']['limit'])


def test_a_cell_of_the_benchmark_refuses_the_cpu(capsys):
  from chipbench import run
  with pytest.raises(SystemExit) as stop:
    run.main(['--workload', 'bert-base.pairs-s128', '--seed', '1',
              '--seconds', '1', '--trace', '0'])
  assert stop.value.code == 3
  assert capsys.readouterr().out == ''     # and prints no result


def test_a_directory_without_the_program_gives_no_result(tmp_path):
  import os
  import shutil
  import subprocess
  import sys
  repo = os.path.dirname(os.path.dirname(os.path.dirname(
      os.path.abspath(__file__))))
  shutil.copy(os.path.join(repo, 'BENCHMARK.json'), tmp_path)
  shutil.copytree(os.path.join(repo, 'chipbench'), tmp_path / 'chipbench',
                  ignore=shutil.ignore_patterns('__pycache__'))
  done = subprocess.run(
      [sys.executable, 'chipbench/run.py', '--workload', 'rehearsal.pairs',
       '--seed', '1', '--seconds', '1', '--trace', '0'],
      cwd=tmp_path, capture_output=True, text=True, timeout=300,
      env=dict(os.environ, JAX_PLATFORMS='cpu'))
  assert done.returncode != 0
  assert done.stdout == ''
