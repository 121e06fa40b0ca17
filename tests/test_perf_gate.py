"""``lddl-perf``: the robust perf-regression gate over bench history.

The load-bearing contracts:

  - the trajectory of the driver's five recorded ``BENCH_r01..r05.json``
    rounds (values inlined below; the records themselves were deleted
    with the plug-in they were captured through) passes the gate — its
    swings are growth noise, not cliffs — while a fixture history with
    an injected cliff exits non-zero and benign MAD-scale noise does not;
  - median ± MAD statistics with the min-rel-drop floor: a single
    outlier in the baseline cannot poison the scale, and near-constant
    series never flag measurement jitter;
  - direction inference: throughput-ish names are higher-is-better
    (``_sec`` inside ``per_sec`` must not flip them), latency-ish names
    lower-is-better — improvements never gate;
  - loaders ingest all three sources (BENCH rounds, MULTICHIP rounds,
    the bench-history JSONL ``bench.py`` appends) and the CLI is wired
    into ``python -m lddl_tpu.cli``.
"""

import json
import os

import pytest

from lddl_tpu.telemetry.perf import (append_history, gather_series,
                                     judge_series, load_bench_rounds,
                                     load_history_jsonl,
                                     load_multichip_rounds, main,
                                     metric_direction, robust_stats)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# The five driver rounds recorded before PR 1 (dup=5 host preprocess).
RECORDED_ROUNDS = [0.801, 8.28, 10.433, 16.049, 6.913]


@pytest.fixture
def recorded_rounds_root(tmp_path):
  """A root dir holding the recorded trajectory as ``BENCH_r*.json``."""
  for i, v in enumerate(RECORDED_ROUNDS):
    (tmp_path / f'BENCH_r0{i + 1}.json').write_text(json.dumps(
        {'n': i + 1, 'rc': 0,
         'parsed': {'metric': 'bert_preprocess_mb_per_sec_per_chip',
                    'value': v, 'unit': 'MB/s/chip'}}))
  return str(tmp_path)


def _write_history(path, values, metric='tput_rows_per_sec'):
  with open(path, 'w') as f:
    for v in values:
      f.write(json.dumps({'metric': metric, 'value': v}) + '\n')
  return str(path)


# ---------------------------------------------------------------------------
# the statistics


class TestJudgeSeries:

  def test_cliff_flags(self):
    v = judge_series('tput_rows_per_sec', [10.0, 10.1, 9.9, 10.05, 3.0])
    assert v['status'] == 'regression'
    assert v['robust_z'] < -4.0

  def test_benign_mad_scale_noise_passes(self):
    v = judge_series('tput_rows_per_sec', [10.0, 10.4, 9.6, 10.2, 9.7])
    assert v['status'] == 'ok'

  def test_wide_growth_trajectory_passes(self):
    # The shape of the repo's real rounds: orders-of-magnitude growth
    # with a final value below the median. Robust scale must absorb it.
    v = judge_series('mb_per_sec_per_chip', RECORDED_ROUNDS)
    assert v['status'] == 'ok'

  def test_improvement_never_flags(self):
    v = judge_series('tput_rows_per_sec', [10.0, 10.1, 9.9, 10.05, 30.0])
    assert v['status'] == 'ok'
    # ...and for lower-is-better metrics a drop is the improvement.
    v = judge_series('step_latency_ms', [10.0, 10.1, 9.9, 10.05, 3.0])
    assert v['status'] == 'ok'
    v = judge_series('step_latency_ms', [10.0, 10.1, 9.9, 10.05, 30.0])
    assert v['status'] == 'regression'

  def test_short_series_insufficient(self):
    v = judge_series('x_per_sec', [10.0, 3.0])
    assert v['status'] == 'insufficient-data'

  def test_constant_series_ignores_jitter(self):
    # MAD = 0; the min-rel-drop floor keeps a 2% wobble from flagging.
    v = judge_series('tput_rows_per_sec', [10.0, 10.0, 10.0, 10.0, 9.8])
    assert v['status'] == 'ok'
    v = judge_series('tput_rows_per_sec', [10.0, 10.0, 10.0, 10.0, 5.0])
    assert v['status'] == 'regression'

  def test_robust_stats(self):
    med, mad = robust_stats([1.0, 2.0, 3.0, 4.0, 100.0])
    assert med == 3.0
    assert mad == 1.0  # the outlier does not poison the scale

  def test_direction_inference(self):
    assert metric_direction('bert_preprocess_mb_per_sec_per_chip') == 1
    assert metric_direction('train_samples_per_sec') == 1
    assert metric_direction('multichip_smoke_ok') == 1
    assert metric_direction('step_latency_ms') == -1
    assert metric_direction('data_wait_seconds') == -1
    assert metric_direction('hbm_bytes_in_use') == -1


# ---------------------------------------------------------------------------
# loaders


class TestLoaders:

  def test_recorded_bench_rounds_load(self, recorded_rounds_root):
    series = load_bench_rounds(recorded_rounds_root)
    assert series['bert_preprocess_mb_per_sec_per_chip'] == \
        pytest.approx(RECORDED_ROUNDS)

  def test_real_multichip_rounds_load(self):
    series = load_multichip_rounds(REPO_ROOT)
    assert all(v in (0.0, 1.0)
               for v in series.get('multichip_smoke_ok', []))

  def test_history_roundtrip(self, tmp_path):
    path = str(tmp_path / 'hist.jsonl')
    append_history(path, {'metric': 'm_per_sec', 'value': 1.5, 'n': 1})
    append_history(path, {'metric': 'm_per_sec', 'value': 2.5, 'n': 2,
                          'parsed': {'extra_per_sec': 7.0}})
    series = load_history_jsonl(path)
    assert series['m_per_sec'] == [1.5, 2.5]
    assert series['extra_per_sec'] == [7.0]
    assert 'n' not in series  # round counters are not metrics

  def test_history_tolerates_garbage_lines(self, tmp_path):
    path = tmp_path / 'hist.jsonl'
    path.write_text('not json\n{"metric": "x_per_sec", "value": 1.0}\n\n')
    assert load_history_jsonl(str(path)) == {'x_per_sec': [1.0]}
    assert load_history_jsonl(str(tmp_path / 'missing.jsonl')) == {}

  def test_gather_merges_rounds_and_history(self, tmp_path):
    for i, v in enumerate([1.0, 2.0]):
      (tmp_path / f'BENCH_r0{i + 1}.json').write_text(json.dumps(
          {'n': i + 1, 'parsed': {'metric': 'm_per_sec', 'value': v}}))
    _write_history(tmp_path / 'bench_history.jsonl', [3.0, 4.0],
                   metric='m_per_sec')
    series = gather_series(str(tmp_path))
    assert series['m_per_sec'] == [1.0, 2.0, 3.0, 4.0]


# ---------------------------------------------------------------------------
# the CLI gate


class TestGateCli:

  def test_recorded_trajectory_passes_gate(self, recorded_rounds_root,
                                           capsys):
    assert main(['--root', recorded_rounds_root, '--gate']) == 0
    out = capsys.readouterr().out
    assert 'bert_preprocess_mb_per_sec_per_chip' in out

  def test_injected_cliff_fails_gate(self, tmp_path, capsys):
    _write_history(tmp_path / 'bench_history.jsonl',
                   [10.0, 10.1, 9.9, 10.05, 3.0])
    assert main(['--root', str(tmp_path), '--gate']) == 1
    assert 'regression' in capsys.readouterr().out

  def test_benign_noise_passes_gate(self, tmp_path):
    _write_history(tmp_path / 'bench_history.jsonl',
                   [10.0, 10.4, 9.6, 10.2, 9.7])
    assert main(['--root', str(tmp_path), '--gate']) == 0

  def test_without_gate_regressions_report_but_exit_zero(self, tmp_path):
    _write_history(tmp_path / 'bench_history.jsonl',
                   [10.0, 10.1, 9.9, 10.05, 3.0])
    assert main(['--root', str(tmp_path)]) == 0

  def test_no_inputs_exits_two(self, tmp_path, capsys):
    assert main(['--root', str(tmp_path)]) == 2
    assert 'no bench history' in capsys.readouterr().err

  def test_json_output(self, tmp_path, capsys):
    _write_history(tmp_path / 'bench_history.jsonl',
                   [10.0, 10.1, 9.9, 10.05, 3.0])
    assert main(['--root', str(tmp_path), '--json']) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload['regressions'] == 1
    assert payload['verdicts'][0]['metric'] == 'tput_rows_per_sec'

  def test_cli_wiring(self):
    from lddl_tpu.cli import _COMMANDS
    assert 'lddl_perf' in _COMMANDS
    assert 'lddl-perf' in _COMMANDS

  def test_console_entry_registered(self):
    with open(os.path.join(REPO_ROOT, 'setup.py')) as f:
      setup_src = f.read()
    assert 'lddl-perf=lddl_tpu.telemetry.perf:main' in setup_src


# ---------------------------------------------------------------------------
# --audit: one CI command gating perf + determinism


def _write_ledger(directory, rank, streams):
  """streams: [(boundary, payloads)] — fingerprint each payload and
  record it under a lineage key, via the real Ledger writer so the file
  format stays honest."""
  from lddl_tpu.telemetry.ledger import Ledger, fingerprint_bytes
  led = Ledger(directory=str(directory), rank=rank)
  for boundary, payloads in streams:
    for i, payload in enumerate(payloads):
      key = {'step': i} if boundary == 'step' else {'epoch': 0, 'index': i}
      led.record(boundary, fingerprint_bytes(payload), **key)
  led.close()
  return str(directory)


class TestAuditFold:

  def _history(self, tmp_path, values=(10.0, 10.1, 9.9, 10.05, 10.0)):
    _write_history(tmp_path / 'bench_history.jsonl', list(values))

  def test_matching_runs_pass_combined_gate(self, tmp_path, capsys):
    self._history(tmp_path)
    run = _write_ledger(tmp_path / 'run', 0,
                        [('collate', [b'a', b'b', b'c'])])
    ref = _write_ledger(tmp_path / 'ref', 0,
                        [('collate', [b'a', b'b', b'c'])])
    assert main(['--root', str(tmp_path), '--gate',
                 '--audit', run, ref]) == 0
    assert 'determinism audit ok' in capsys.readouterr().out

  def test_divergent_ledger_fails_gate_despite_healthy_perf(
      self, tmp_path, capsys):
    self._history(tmp_path)  # perf leg alone would pass
    run = _write_ledger(tmp_path / 'run', 0,
                        [('collate', [b'a', b'b', b'c'])])
    ref = _write_ledger(tmp_path / 'ref', 0,
                        [('collate', [b'a', b'X', b'c'])])
    assert main(['--root', str(tmp_path), '--gate',
                 '--audit', run, ref]) == 1
    assert 'index=1' in capsys.readouterr().out  # audit findings printed

  def test_audit_without_gate_reports_but_exits_zero(self, tmp_path):
    self._history(tmp_path)
    run = _write_ledger(tmp_path / 'run', 0, [('collate', [b'a'])])
    ref = _write_ledger(tmp_path / 'ref', 0, [('collate', [b'Z'])])
    assert main(['--root', str(tmp_path), '--audit', run, ref]) == 0

  def test_perf_regression_wins_over_audit_code(self, tmp_path):
    # Both legs fire; the exit code is perf's 1, not audit's 2.
    _write_history(tmp_path / 'bench_history.jsonl',
                   [10.0, 10.1, 9.9, 10.05, 3.0])
    assert main(['--root', str(tmp_path), '--gate',
                 '--audit', str(tmp_path / 'absent')]) == 1

  def test_single_path_self_checks_wire(self, tmp_path, capsys):
    from lddl_tpu.telemetry.ledger import Ledger, fingerprint_bytes
    self._history(tmp_path)
    led = Ledger(directory=str(tmp_path / 'run'), rank=0)
    for gi in range(3):
      led.record('serve.tx', fingerprint_bytes(b'%d' % gi), epoch=0, gi=gi)
      rx = b'%d' % gi if gi != 1 else b'damaged'
      led.record('serve.rx', fingerprint_bytes(rx), epoch=0, gi=gi)
    led.close()
    assert main(['--root', str(tmp_path), '--gate',
                 '--audit', str(tmp_path / 'run')]) == 1
    assert 'wire' in capsys.readouterr().out

  def test_three_audit_paths_usage_error(self, tmp_path, capsys):
    self._history(tmp_path)
    assert main(['--root', str(tmp_path), '--gate',
                 '--audit', 'a', 'b', 'c']) == 2
    assert '--audit takes' in capsys.readouterr().err

  def test_json_carries_audit_exit(self, tmp_path, capsys):
    self._history(tmp_path)
    run = _write_ledger(tmp_path / 'run', 0, [('step', [b'a', b'b'])])
    ref = _write_ledger(tmp_path / 'ref', 0, [('step', [b'a', b'b'])])
    assert main(['--root', str(tmp_path), '--json',
                 '--audit', run, ref]) == 0
    # The audit leg prints its findings first; the verdict JSON starts at
    # the indent=2 opening brace.
    out = capsys.readouterr().out
    payload = json.loads(out[out.index('{\n  "verdicts"'):])
    assert payload['audit_exit'] == 0
