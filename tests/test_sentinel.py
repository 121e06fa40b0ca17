"""Streaming sentinels + flight recorder (``LDDL_SENTINEL``).

Covers the subsystem's acceptance contract end to end:

- no-op discipline: gate unset resolves both the sentinel and the
  flight recorder to shared inert singletons — zero threads, zero
  files, the host stream passes through untouched;
- every detector's fire/no-fire thresholds on synthetic streams
  (non-finite loss, robust-z loss/grad spikes, data stall, HBM
  headroom, serve-backlog runaway, live ledger divergence), plus the
  cooldown and the ``sentinel.trigger`` force-fire drill;
- the flight ring: bounded capacity, ledger coordinates per entry,
  incident capture whose bundles verify byte-for-byte, the
  ``flight.dump`` raise/corrupt drills, and the ``lddl-incident`` CLI;
- the live train-loop acceptance criterion: an injected trigger during
  ``TrainLoop.run()`` produces — with no human action — an incident
  whose bundled batch replays through ``replay_step_coordinate`` to a
  bit-for-bit match of the recorded fingerprint, and
  ``lddl-perf --gate --incidents`` fails on that directory;
- the silent-NaN fix: a non-finite loss stops the loop behind an
  emergency checkpoint regardless of the sentinel gate
  (``LDDL_NONFINITE=ignore`` opts out);
- monitor surfacing (``/snapshot`` sentinel block, INCIDENT panel,
  ``--once --json``) and the enabled-path overhead bound.
"""

import json
import math
import os
import threading
import time

import pytest

from lddl_tpu.core import faults
import lddl_tpu.telemetry.sentinel as sentinel_mod
import lddl_tpu.training.flight as flight_mod
from lddl_tpu.replay import ReplayMismatch, read_bundle
from lddl_tpu.telemetry.sentinel import (DETECTORS, NOOP_SENTINEL, Sentinel,
                                         enable_sentinel, get_sentinel,
                                         sentinel_status)
from lddl_tpu.testing import SyntheticBatchLoader
from lddl_tpu.training.flight import (NOOP_FLIGHT, enable_flight,
                                      get_flight_recorder, replay_command,
                                      scan_incidents)
from lddl_tpu.training.flight import main as incident_main

from test_training import _loop, _with_ledger
from test_benchmarks import shards  # noqa: F401  (fixture reuse)


def _fresh_gate(monkeypatch, value=None):
  """Reset both module gates and pin the env spelling under test."""
  if value is None:
    monkeypatch.delenv('LDDL_SENTINEL', raising=False)
  else:
    monkeypatch.setenv('LDDL_SENTINEL', value)
  sentinel_mod._active = None
  flight_mod._active = None


def _synthetic_ring(recorder, n=5, **loader_kw):
  """Drive ``n`` synthetic batches through the recorder's tee."""
  kw = dict(batch_size=4, seq_len=16, steps=8, vocab_size=100)
  kw.update(loader_kw)
  loader = SyntheticBatchLoader(**kw)
  stream = recorder.wrap_host_stream(iter(loader), loader, ordinal0=0)
  for i, _ in enumerate(stream):
    recorder.record_step(i, loss=1.0, grad_norm=0.5, data_wait=0.001)
    if i + 1 >= n:
      break
  return loader


# ---------------------------------------------------------------------------
# no-op discipline (LDDL_SENTINEL unset)


class TestNoopDiscipline:

  def test_unset_gate_is_shared_noop(self, monkeypatch):
    _fresh_gate(monkeypatch)
    sent = get_sentinel()
    assert sent is NOOP_SENTINEL and sent is get_sentinel()
    assert not sent.enabled and sent.detectors == ()
    assert sent.observe_step(1, loss=float('nan')) is None
    assert sent.observe_backlog(10 ** 9) is None
    assert sent.status() is None and sentinel_status() is None
    rec = get_flight_recorder()
    assert rec is NOOP_FLIGHT and not rec.enabled
    it = iter([1, 2, 3])
    assert rec.wrap_host_stream(it) is it  # stream passes through
    assert rec.capture({'detector': 'x', 'step': 1}) is None

  def test_off_spellings_disable(self, monkeypatch):
    for off in ('0', 'false', 'off', 'no', ''):
      _fresh_gate(monkeypatch, off)
      assert get_sentinel() is NOOP_SENTINEL

  def test_on_and_subset_spellings(self, monkeypatch):
    _fresh_gate(monkeypatch, '1')
    assert get_sentinel().detectors == DETECTORS
    _fresh_gate(monkeypatch, 'loss_spike, nonfinite_loss')
    assert get_sentinel().detectors == ('loss_spike', 'nonfinite_loss')
    _fresh_gate(monkeypatch, 'bogus_detector')
    with pytest.raises(ValueError, match='unknown sentinel detector'):
      get_sentinel()

  def test_disabled_creates_no_threads_or_files(self, monkeypatch,
                                                tmp_path):
    _fresh_gate(monkeypatch)
    monkeypatch.setenv('LDDL_FLIGHT_DIR', str(tmp_path / 'inc'))
    before = set(threading.enumerate())
    sent, rec = get_sentinel(), get_flight_recorder()
    for i in range(1000):
      sent.observe_step(i, loss=1.0, grad_norm=1.0, data_wait=0.0)
      rec.record_step(i, loss=1.0)
    assert rec.capture({'detector': 'x', 'step': 3}) is None
    assert set(threading.enumerate()) == before
    assert not (tmp_path / 'inc').exists()

  def test_disabled_hot_path_is_cheap(self, monkeypatch):
    _fresh_gate(monkeypatch)
    sent = get_sentinel()
    t0 = time.perf_counter()
    for i in range(200_000):
      sent.observe_step(i, loss=1.0, grad_norm=1.0, data_wait=0.0)
    assert time.perf_counter() - t0 < 2.0  # generous CI bound


# ---------------------------------------------------------------------------
# detectors on synthetic streams


class TestDetectors:

  def test_nonfinite_loss(self):
    s = Sentinel(detectors=('nonfinite_loss',))
    assert s.observe_step(1, loss=2.5) is None
    trig = s.observe_step(2, loss=float('nan'))
    assert trig['detector'] == 'nonfinite_loss' and trig['step'] == 2
    assert s.triggers == 1 and s.last_trigger['detector'] == 'nonfinite_loss'

  def test_loss_spike_fire_and_no_fire(self):
    s = Sentinel(detectors=('loss_spike',), warmup=8, z_threshold=8.0,
                 min_rel=0.5, cooldown=4)
    # warmup: even an outlier cannot fire before the baseline exists
    assert s.observe_step(0, loss=100.0) is None
    for i in range(1, 12):
      assert s.observe_step(i, loss=1.0 + 0.01 * (i % 3)) is None
    # +20% is real movement but under min_rel: no fire
    assert s.observe_step(12, loss=1.2) is None
    trig = s.observe_step(13, loss=30.0)
    assert trig['detector'] == 'loss_spike'
    assert trig['stats']['robust_z'] > 8.0
    assert trig['stats']['rel_change'] > 0.5
    # cooldown mutes the immediate refire...
    assert s.observe_step(14, loss=30.0) is None
    # ...and a *drop* never fires (upward-only)
    assert s.observe_step(30, loss=0.01) is None

  def test_grad_spike_and_nonfinite_grad(self):
    s = Sentinel(detectors=('grad_spike',), warmup=6, cooldown=0)
    for i in range(6):
      assert s.observe_step(i, grad_norm=2.0) is None
    trig = s.observe_step(6, grad_norm=500.0)
    assert trig['detector'] == 'grad_spike'
    s2 = Sentinel(detectors=('grad_spike',))
    trig = s2.observe_step(1, grad_norm=float('inf'))
    assert trig['detector'] == 'grad_spike' and 'non-finite' in trig['reason']

  def test_data_stall(self):
    s = Sentinel(detectors=('data_stall',), stall_sec=5.0)
    assert s.observe_step(1, data_wait=0.5) is None
    trig = s.observe_step(2, data_wait=6.0)
    assert trig['detector'] == 'data_stall' and trig['value'] == 6.0

  def test_hbm_headroom(self, monkeypatch):
    import lddl_tpu.telemetry.roofline as roofline
    monkeypatch.setattr(roofline, 'sample_hbm',
                        lambda telemetry=None: {'headroom_frac': 0.01})
    s = Sentinel(detectors=('hbm_headroom',), hbm_every=1,
                 headroom_min=0.03)
    trig = s.observe_step(1)
    assert trig['detector'] == 'hbm_headroom' and trig['value'] == 0.01
    monkeypatch.setattr(roofline, 'sample_hbm',
                        lambda telemetry=None: {'headroom_frac': 0.5})
    assert Sentinel(detectors=('hbm_headroom',), hbm_every=1,
                    headroom_min=0.03).observe_step(1) is None

  def test_serve_backlog_one_trigger_per_excursion(self):
    s = Sentinel(detectors=('serve_backlog',), backlog_max=10)
    assert s.observe_backlog(5) is None
    trig = s.observe_backlog(10)
    assert trig['detector'] == 'serve_backlog' and trig['step'] is None
    assert s.observe_backlog(12) is None   # muted while still high
    assert s.observe_backlog(9) is None    # above half: still muted
    assert s.observe_backlog(4) is None    # recovery below half re-arms
    assert s.observe_backlog(11)['detector'] == 'serve_backlog'

  def test_ledger_divergence_fires_once_per_verdict(self, tmp_path):
    import lddl_tpu.telemetry.ledger as ledger_mod
    ledger_mod._active = None
    led = ledger_mod.enable_ledger(directory=str(tmp_path), rank=0)
    try:
      s = Sentinel(detectors=('ledger_divergence',))
      assert s.observe_step(1) is None  # no verdict yet
      led.set_fleet_verdict({'status': 'diverged',
                             'first': {'boundary': 'collate'}})
      trig = s.observe_step(2)
      assert trig['detector'] == 'ledger_divergence'
      assert s.observe_step(3) is None  # same verdict: no refire
      led.set_fleet_verdict({'status': 'diverged',
                             'first': {'boundary': 'step'}})
      assert s.observe_step(4)['detector'] == 'ledger_divergence'
      led.set_fleet_verdict({'status': 'ok'})
      assert s.observe_step(5) is None
    finally:
      ledger_mod.disable_ledger()

  def test_fault_injected_trigger_bypasses_cooldown(self, monkeypatch):
    monkeypatch.setenv('LDDL_FAULTS', 'raise:sentinel.trigger')
    faults.reset()
    try:
      s = Sentinel(detectors=('nonfinite_loss',), cooldown=10 ** 6)
      t1 = s.observe_step(1, loss=1.0)
      t2 = s.observe_step(2, loss=1.0)
      assert t1['detector'] == t2['detector'] == 'injected'
      assert s.triggers == 2
    finally:
      faults.reset()

  def test_enabled_hot_path_overhead(self):
    s = Sentinel(detectors=('nonfinite_loss', 'loss_spike', 'grad_spike',
                            'data_stall'), window=64)
    t0 = time.perf_counter()
    for i in range(20_000):
      s.observe_step(i, loss=1.0 + 0.001 * (i % 7),
                     grad_norm=2.0 + 0.001 * (i % 5), data_wait=0.001)
    elapsed = time.perf_counter() - t0
    assert s.triggers == 0
    # ~robust-stats over a 64-float window per signal: must stay far
    # below a training step. Generous CI bound: < 250 us/step average.
    assert elapsed < 5.0, f'{elapsed / 20_000 * 1e6:.0f} us/step'


# ---------------------------------------------------------------------------
# flight recorder


class TestFlightRecorder:

  def test_ring_is_bounded_with_coordinates(self, tmp_path):
    rec = enable_flight(out_dir=str(tmp_path), capacity=3)
    _synthetic_ring(rec, n=7)
    assert [e['ordinal'] for e in rec._ring] == [4, 5, 6]
    # ordinal -> (epoch, index) via the loader's public contract
    assert [(e['epoch'], e['index']) for e in rec._ring] == [
        (0, 4), (0, 5), (0, 6)]

  def test_capture_writes_verifiable_bundles(self, tmp_path):
    enable_sentinel(detectors=('loss_spike',))
    rec = enable_flight(out_dir=str(tmp_path / 'inc'), capacity=3)
    _synthetic_ring(rec, n=5)
    rec.note_checkpoint(str(tmp_path / 'ckpt'), 4)
    trigger = {'detector': 'loss_spike', 'step': 4, 'reason': 'test',
               'value': 9.0}
    out = rec.capture(trigger)
    assert out and os.path.isdir(out)
    man = json.load(open(os.path.join(out, 'incident.json')))
    assert man['trigger']['detector'] == 'loss_spike'
    assert man['step'] == 4 and man['replay_step'] == 5
    assert man['suspect']['coordinate'] == {'epoch': 0, 'index': 4}
    assert man['checkpoint']['step'] == 4
    assert len(man['ring']) == 3 and len(man['metrics']) == 5
    # every bundle re-verifies; the suspect's digest is the *batch*
    # fingerprint (the same bytes the ledger hashes)
    from lddl_tpu.telemetry.ledger import fingerprint_batch
    for entry in man['ring']:
      bman, batch = read_bundle(os.path.join(out, entry['bundle']))
      assert bman['digest'] == entry['digest']
      assert fingerprint_batch(batch) == entry['digest']
    # with a checkpoint ref the one-command repro is a full step replay
    cmd = replay_command(out, man)
    assert cmd.startswith('lddl-replay step --bundle')
    assert '--step 5' in cmd
    # the sentinel's status now carries the incident registration
    status = sentinel_status()
    assert status['incidents'][-1]['dir'] == out
    assert scan_incidents(str(tmp_path / 'inc'))[0]['dir'] == out

  def test_incident_cap(self, tmp_path):
    enable_sentinel(detectors=('loss_spike',))
    rec = enable_flight(out_dir=str(tmp_path), capacity=2,
                        max_incidents=2)
    _synthetic_ring(rec, n=3)
    trig = {'detector': 'loss_spike', 'step': 2, 'reason': 'r'}
    assert rec.capture(trig) and rec.capture(trig)
    assert rec.capture(trig) is None  # capped
    assert len(scan_incidents(str(tmp_path))) == 2

  def test_dump_raise_drill_never_crashes(self, monkeypatch, tmp_path):
    enable_sentinel(detectors=('loss_spike',))
    rec = enable_flight(out_dir=str(tmp_path / 'inc'))
    _synthetic_ring(rec, n=3)
    monkeypatch.setenv('LDDL_FAULTS', 'raise:flight.dump')
    faults.reset()
    try:
      out = rec.capture({'detector': 'loss_spike', 'step': 2,
                         'reason': 'r'})
    finally:
      faults.reset()
    assert out is None  # dump died at entry, run survives
    assert scan_incidents(str(tmp_path / 'inc')) == []

  def test_dump_corrupt_drill_is_rejected_at_replay(self, monkeypatch,
                                                    tmp_path):
    enable_sentinel(detectors=('loss_spike',))
    rec = enable_flight(out_dir=str(tmp_path / 'inc'), capacity=2)
    _synthetic_ring(rec, n=3)
    monkeypatch.setenv('LDDL_FAULTS', 'corrupt:flight.dump:at=7')
    faults.reset()
    try:
      out = rec.capture({'detector': 'loss_spike', 'step': 2,
                         'reason': 'r'})
    finally:
      faults.reset()
      monkeypatch.delenv('LDDL_FAULTS')
    assert out is not None
    man = json.load(open(os.path.join(out, 'incident.json')))
    # the dump "succeeded" but carries damaged payloads against the
    # pristine fingerprints — the replay reader must refuse them
    with pytest.raises(ReplayMismatch, match='bundle payload rejected'):
      read_bundle(os.path.join(out, man['suspect']['bundle']))
    assert incident_main(['replay', out]) == 1

  def test_cli_list_show_replay(self, tmp_path, capsys):
    enable_sentinel(detectors=('loss_spike',))
    rec = enable_flight(out_dir=str(tmp_path / 'inc'), capacity=2)
    _synthetic_ring(rec, n=3)
    out = rec.capture({'detector': 'loss_spike', 'step': 2,
                       'reason': 'test spike'})
    assert incident_main(['list', '--root', str(tmp_path / 'inc')]) == 0
    listing = capsys.readouterr().out
    assert 'detector=loss_spike' in listing and out in listing
    assert incident_main(['show', out]) == 0
    shown = capsys.readouterr().out
    assert 'loss_spike' in shown and '<- suspect' in shown
    assert incident_main(['replay', out]) == 0
    assert 'bundle ok' in capsys.readouterr().out
    # not-an-incident paths are usage errors, not tracebacks
    assert incident_main(['show', str(tmp_path)]) == 2
    assert incident_main(['replay', str(tmp_path)]) == 2
    assert incident_main(['bisect', out]) == 2  # no checkpoint ref
    assert incident_main(['list', '--root', str(tmp_path / 'nope')]) == 0


# ---------------------------------------------------------------------------
# lddl-perf --gate --incidents


class TestPerfIncidentGate:

  def _incident(self, tmp_path):
    enable_sentinel(detectors=('loss_spike',))
    rec = enable_flight(out_dir=str(tmp_path / 'inc'), capacity=2)
    _synthetic_ring(rec, n=3)
    return rec.capture({'detector': 'loss_spike', 'step': 2,
                        'reason': 'test spike'})

  def test_gate_fails_on_incident_and_prints_replay(self, tmp_path,
                                                    capsys):
    from lddl_tpu.telemetry.perf import main as perf_main
    out = self._incident(tmp_path)
    rc = perf_main(['--gate', '--incidents', str(tmp_path / 'inc'),
                    '--root', str(tmp_path)])
    assert rc == 1
    err = capsys.readouterr().err
    assert 'loss_spike at step 2' in err
    assert 'replay:' in err and out in err

  def test_gate_passes_on_clean_tree(self, tmp_path, capsys):
    from lddl_tpu.telemetry.perf import main as perf_main
    rc = perf_main(['--gate', '--incidents', str(tmp_path / 'empty'),
                    '--root', str(tmp_path)])
    assert rc == 0

  def test_without_gate_incidents_report_but_exit_zero(self, tmp_path):
    from lddl_tpu.telemetry.perf import main as perf_main
    self._incident(tmp_path)
    rc = perf_main(['--incidents', str(tmp_path / 'inc'),
                    '--root', str(tmp_path)])
    assert rc == 0

  def test_gate_with_bench_history_folds_incidents(self, tmp_path):
    from lddl_tpu.telemetry.perf import main as perf_main
    hist = tmp_path / 'bench_history.jsonl'
    with open(hist, 'w') as f:
      for v in (10.0, 10.1, 9.9, 10.0, 10.05):
        f.write(json.dumps({'mb_per_sec_per_chip': v}) + '\n')
    assert perf_main(['--gate', '--root', str(tmp_path), '--incidents',
                      str(tmp_path / 'empty')]) == 0
    self._incident(tmp_path)
    assert perf_main(['--gate', '--root', str(tmp_path), '--incidents',
                      str(tmp_path / 'inc')]) == 1

  def test_bench_stamp(self, monkeypatch):
    import importlib.util
    path = os.path.join(os.path.dirname(__file__), '..', 'bench.py')
    spec = importlib.util.spec_from_file_location('_bench_stamp', path)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    _fresh_gate(monkeypatch)
    assert bench._sentinel_stamp() == {'enabled': False, 'detectors': []}
    enable_sentinel(detectors=('nonfinite_loss',))
    assert bench._sentinel_stamp() == {'enabled': True,
                                       'detectors': ['nonfinite_loss']}


# ---------------------------------------------------------------------------
# monitor surfacing


class TestMonitorSurfacing:

  def test_live_status_sentinel_block(self, monkeypatch):
    from lddl_tpu.telemetry.live import SnapshotWindow, live_status
    _fresh_gate(monkeypatch)
    assert 'sentinel' not in live_status(SnapshotWindow())
    sent = enable_sentinel(detectors=('nonfinite_loss',))
    sent.observe_step(3, loss=float('nan'))
    status = live_status(SnapshotWindow())
    assert status['sentinel']['triggers'] == 1
    assert status['sentinel']['last']['detector'] == 'nonfinite_loss'

  def test_render_frame_incident_panel_and_grad_norm(self):
    from lddl_tpu.telemetry.monitor import render_frame
    snap = {'pid': 1, 'verdict': {}, 'rates': {}, 'hbm': None,
            'goodput': {'grad_norm': {'mean': 1.5, 'min': 1.0,
                                      'max': 2.0}}}
    fleet = {'ranks': {0: snap}, 'errors': {}, 'straggler': None,
             'verdicts': {}, 'determinism': None,
             'sentinel': {0: {'triggers': 2,
                              'last': {'detector': 'loss_spike',
                                       'step': 42,
                                       'reason': 'loss spiked'},
                              'incidents': [{'dir': '/tmp/i1'}]}}}
    text = render_frame(fleet, clear=False)
    assert '!! INCIDENT' in text
    assert 'last loss_spike at step 42' in text
    assert 'lddl-incident show /tmp/i1' in text
    assert 'grad-norm 1.5' in text
    quiet = dict(fleet, sentinel=None)
    assert '!! INCIDENT' not in render_frame(quiet, clear=False)

  def test_snapshot_and_once_json(self, monkeypatch, tmp_path, capsys):
    from lddl_tpu.telemetry import monitor as monitor_mod
    from lddl_tpu.telemetry.metrics import enable
    from lddl_tpu.telemetry.server import maybe_start_monitor, stop_monitor
    monkeypatch.setenv('LDDL_MONITOR', '1')
    monkeypatch.setenv('LDDL_MONITOR_DIR', str(tmp_path))
    stop_monitor()
    enable()
    sent = enable_sentinel(detectors=('nonfinite_loss',))
    sent.observe_step(7, loss=float('nan'))
    mon = maybe_start_monitor(rank=0)
    try:
      snap = monitor_mod.fetch_snapshot(mon.url)
      assert snap['sentinel']['triggers'] == 1
      fleet = monitor_mod.poll_fleet([mon.url])
      assert fleet['sentinel'][0]['last']['detector'] == 'nonfinite_loss'
      assert '!! INCIDENT' in monitor_mod.render_frame(fleet, clear=False)
      assert monitor_mod.main(['--url', mon.url, '--once', '--json']) == 0
      payload = json.loads(capsys.readouterr().out)
      assert payload['sentinel']['0']['triggers'] == 1
    finally:
      stop_monitor()


# ---------------------------------------------------------------------------
# train-loop integration (the acceptance criterion)


class TestTrainLoopIntegration:

  def _poison(self, loop, at_step):
    """Wrap the loop's step_fn so step ``at_step`` returns a NaN loss."""
    orig, seen = loop.step_fn, [0]

    def poisoned(params, opt_state, rng, batch):
      params, opt_state, metrics = orig(params, opt_state, rng, batch)
      if seen[0] == at_step:
        metrics = dict(metrics)
        metrics['loss'] = float('nan')
      seen[0] += 1
      return params, opt_state, metrics

    loop.step_fn = poisoned

  def test_nonfinite_loss_stops_behind_emergency_ckpt(
      self, shards, tiny_vocab, tmp_path, monkeypatch):
    monkeypatch.delenv('LDDL_NONFINITE', raising=False)
    _fresh_gate(monkeypatch)  # the fix is independent of the gate
    ckpt = str(tmp_path / 'ckpt')
    loop = _loop(shards, tiny_vocab)
    self._poison(loop, at_step=1)
    losses = loop.run(6, ckpt_dir=ckpt, log_every=0)
    assert loop.stop_reason == 'nonfinite_loss'
    # At most one step late: step 2 was on the device when step 1's loss
    # was read; it is drained and counted, nothing is launched after it.
    assert len(losses) == 3 and math.isnan(losses[1])
    # the trailing save IS the emergency checkpoint
    assert loop._last_saved == loop.step == 3

  def test_nonfinite_ignore_opts_out(self, shards, tiny_vocab, tmp_path,
                                     monkeypatch):
    monkeypatch.setenv('LDDL_NONFINITE', 'ignore')
    _fresh_gate(monkeypatch)
    loop = _loop(shards, tiny_vocab)
    self._poison(loop, at_step=1)
    losses = loop.run(3, log_every=0)
    assert loop.stop_reason is None and len(losses) == 3

  def test_injected_trigger_captures_replayable_incident(
      self, shards, tiny_vocab, tmp_path, monkeypatch, capsys):
    """The tentpole acceptance test: a fault-injected sentinel trigger
    during a live run produces, with no human action, an incident
    whose bundled suspect batch replays the recorded train step
    bit-for-bit — and the perf gate fails on the directory."""
    from lddl_tpu.replay.steps import replay_step_coordinate
    from lddl_tpu.telemetry.audit import load_run
    from lddl_tpu.replay.rematerialize import lookup_digest
    ckpt, led = str(tmp_path / 'ckpt'), str(tmp_path / 'led')
    inc = str(tmp_path / 'inc')
    # 3rd observe_step == step_no 2: the spike lands mid-run
    monkeypatch.setenv('LDDL_FAULTS', 'raise:sentinel.trigger:nth=3')
    faults.reset()
    enable_sentinel()
    enable_flight(out_dir=inc)
    parent = _loop(shards, tiny_vocab)
    try:
      _with_ledger(tmp_path / 'led', 0,
                   lambda: parent.run(3, ckpt_dir=ckpt, ckpt_every=1,
                                      log_every=0))
    finally:
      monkeypatch.delenv('LDDL_FAULTS')
      faults.reset()
    assert 'incident captured' in capsys.readouterr().out

    incidents = scan_incidents(inc)
    assert len(incidents) == 1
    man = incidents[0]['manifest']
    assert man['trigger']['detector'] == 'injected'
    assert man['step'] == 2 and man['replay_step'] == 3
    # the suspect is the batch step 3 consumed: collate key (0, 2),
    # and its bundled digest equals the ledger's recorded line
    assert man['suspect']['coordinate'] == {'epoch': 0, 'index': 2}
    recorded, _ = lookup_digest(load_run(led),
                                (('epoch', 0), ('index', 2)),
                                boundary='collate')
    assert man['suspect']['digest'] == recorded
    assert man['checkpoint'] == {'dir': os.path.abspath(ckpt), 'step': 2}
    assert man['ledger'] and 'collate' in man['ledger']

    # bit-for-bit: restore ckpt 2 on a loader-free loop, re-execute
    # step 3 from the incident's bundle, match the recorded fingerprint
    bundle = os.path.join(incidents[0]['dir'], man['suspect']['bundle'])
    _, batch = read_bundle(bundle)
    fresh = _loop(None, tiny_vocab)
    out = replay_step_coordinate(fresh, ckpt, 3, ledger_path=led,
                                 batches=[batch])
    assert out['restored_step'] == 2
    assert out['match'] is True, out
    assert out['digest'] == parent.state_digest()

    # ...and the CI gate refuses the tree
    from lddl_tpu.telemetry.perf import main as perf_main
    assert perf_main(['--gate', '--incidents', inc,
                      '--root', str(tmp_path)]) == 1

  def test_grad_norm_exported_to_goodput(self, shards, tiny_vocab,
                                         monkeypatch):
    from lddl_tpu.telemetry.live import SnapshotWindow, live_status
    from lddl_tpu.telemetry.metrics import enable
    _fresh_gate(monkeypatch)
    enable()
    loop = _loop(shards, tiny_vocab)
    loop.run(2, log_every=0)
    status = live_status(SnapshotWindow())
    gn = status['goodput']['grad_norm']
    assert gn is not None and gn['mean'] > 0.0
    assert math.isfinite(gn['mean'])
