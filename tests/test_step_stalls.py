"""Host stalls named from inside the train loop (``telemetry/stalls.py``).

The rule on made-up intervals (a median per step shape, eight intervals
before any is judged, twice the median and 10 ms over it); what the
loop's watch leaves out of the judgement and of the medians (a compile,
a capture, a drain and the step after it); ``TrainLoop.run`` on the CPU
with a delay injected at one step, which alone is counted and printed;
Python's collector, timed on whatever thread collects, a ``host.gc``
span only where a sink is on, and its hook gone when ``run`` returns;
and the sentinel's ``step_stall`` incident.
"""

import gc
import json
import os
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lddl_tpu.telemetry as telemetry
from lddl_tpu.core import faults
from lddl_tpu.telemetry import profiling, stalls, trace
from lddl_tpu.training.pretrain import TrainLoop

BATCH = 6
STEP_S = 0.040  # what each made-up step keeps the "device" busy for


# ----------------------------------------------------------------------------
# the rule


def test_each_shape_is_judged_against_its_own_median():
  rule = stalls.StallRule()
  for _ in range(8):
    assert rule.judge('short', 0.020) == (None, False)
    assert rule.judge('long', 0.100) == (None, False)
  # 150 ms is a stall for the short shape, not for the long one.
  assert rule.judge('long', 0.150) == (pytest.approx(0.100), False)
  assert rule.judge('short', 0.150) == (pytest.approx(0.020), True)


def test_nothing_is_judged_before_a_shape_has_eight_intervals():
  rule = stalls.StallRule()
  for _ in range(7):
    rule.judge('s', 0.050)
  assert rule.judge('s', 5.0) == (None, False)  # the eighth
  assert rule.judge('s', 5.0) == (pytest.approx(0.050), True)


@pytest.mark.parametrize('interval, stalled', [
    (0.099, False),  # under twice the median
    (0.101, True),
])
def test_twice_the_median(interval, stalled):
  rule = stalls.StallRule()
  for _ in range(8):
    rule.judge('s', 0.050)
  assert rule.judge('s', interval)[1] is stalled


@pytest.mark.parametrize('interval, stalled', [
    (0.013, False),  # over twice the median of 4 ms, 9 ms over it
    (0.0145, True),  # 10.5 ms over it
])
def test_and_ten_ms_over_it(interval, stalled):
  rule = stalls.StallRule()
  for _ in range(8):
    rule.judge('s', 0.004)
  assert rule.judge('s', interval)[1] is stalled


# ----------------------------------------------------------------------------
# what the watch leaves out


def test_compile_capture_and_drain_steps_are_left_out(monkeypatch, capsys):
  tele = telemetry.enable()
  stalls.step_record().clear()
  watch = stalls.StepWatch(tele, trace.get_tracer())
  profiler = profiling.get_step_profiler()
  key = (('x', (6, 4), 'float32'),)
  t = [100.0]
  step = [0]

  def find(seconds, misses=1, queued=True):
    t[0] += seconds
    q = (types.SimpleNamespace(t_pull=t[0] - seconds + 0.001,
                               t_wait=t[0] - seconds + 0.001,
                               t_step=t[0] - seconds + 0.002)
         if queued else None)
    facts = watch.found(step[0], key, misses, t[0] - 0.010, t[0], q)
    step[0] += 1
    return facts

  find(0.050)                      # step 0: the first find, no interval
  for _ in range(7):
    assert find(0.050) is None     # steps 1-7: seven intervals
  assert find(1.0, misses=2) is None   # 8: its interval holds a compile
  monkeypatch.setattr(profiler, '_active_steps', 3)
  assert find(1.0, misses=2) is None   # 9: inside a capture
  monkeypatch.setattr(profiler, '_active_steps', 0)
  assert find(1.0, misses=2, queued=False) is None  # 10: a drain
  assert find(1.0, misses=2) is None   # 11: the step after the drain
  # None of the four entered the shape's window: seven intervals, so the
  # next one is still the warm-up's eighth.
  assert find(1.0, misses=2) is None   # 12
  facts = find(1.0, misses=2)          # 13: judged against 50 ms
  assert facts is not None and facts['step'] == 13
  assert facts['median_s'] == pytest.approx(0.050)
  assert sum(facts['parts'].values()) == pytest.approx(facts['interval_s'])
  rows = list(stalls.step_record())
  assert [r[0] for r in rows] == list(range(1, 14))
  assert [r[0] for r in rows if r[2] is not None] == [13]  # judged
  assert [r[0] for r in rows if r[4]] == [13]
  assert tele.counter('train.stalled_steps').total == 1
  assert tele.histogram('train.stall_seconds').sum == pytest.approx(0.950)
  out = capsys.readouterr().out.splitlines()
  assert [line.split(' [')[0] for line in out] == ['stall: step 13']
  assert out[0].startswith('stall: step 13 [6, 4] 1000.0 ms (median 50.0)')


# ----------------------------------------------------------------------------
# the loop


class Loader:
  """``per_epoch`` batches of one shape; ``gc_at`` collects every
  generation while making that batch (on the device feed's thread)."""

  batch_size = BATCH

  def __init__(self, per_epoch, gc_at=None):
    self.per_epoch = per_epoch
    self.gc_at = gc_at

  def __iter__(self):
    for i in range(self.per_epoch):
      if i == self.gc_at:
        gc.collect(2)
      rng = np.random.default_rng(i)
      yield {'x': rng.standard_normal((BATCH, 4)).astype(np.float32)}


class SlowLoss:
  """A loss that takes ``STEP_S`` to read, as a device step would."""

  def __float__(self):
    time.sleep(STEP_S)
    return 1.0


def slow_step(params, opt_state, rng, batch):
  return params, opt_state, {'loss': SlowLoss()}


def make_loop(step_fn=slow_step, per_epoch=100, gc_at=None):
  return TrainLoop(
      model=None, tx=None, mesh=None, loader=Loader(per_epoch, gc_at),
      params={'w': jnp.zeros((4,), jnp.float32)},
      opt_state={'count': jnp.zeros((), jnp.int32)},
      rng=jax.random.key(3), step_fn=step_fn)


@pytest.mark.parametrize('delayed', [False, True])
def test_a_delayed_step_is_the_one_stall(monkeypatch, capsys, delayed):
  """16 steps of 40 ms; with the fault, 0.5 s of sleep before step 11 is
  launched, inside the interval that ends when step 10 is found finished
  (step 11 is launched before step 10's loss is read)."""
  faults.reset()
  if delayed:
    monkeypatch.setenv('LDDL_FAULTS', 'delay:train.step:sec=0.5,nth=12')
  tele = telemetry.enable()
  stalls.step_record().clear()
  make_loop().run(16, log_every=0)
  lines = [line for line in capsys.readouterr().out.splitlines()
           if line.startswith('stall:')]
  stalled = [r[0] for r in stalls.step_record() if r[4]]
  if delayed:
    assert stalled == [10]
    assert len(lines) == 1 and lines[0].startswith('stall: step 10 [6, 4] ')
    assert tele.counter('train.stalled_steps').total == 1
  else:
    assert stalled == [] and lines == []
    assert tele.counter('train.stalled_steps').total == 0
  judged = [r for r in stalls.step_record() if r[2] is not None]
  assert [r[0] for r in judged] == list(range(9, 15))  # 15 is the drain's


def test_a_collection_in_the_loader_is_timed_and_recorded():
  tele = telemetry.enable()
  tracer = trace.enable_trace()
  stalls.step_record().clear()
  make_loop(per_epoch=14, gc_at=8).run(14, log_every=0)
  pauses = tele.histogram('host.gc_seconds')
  assert pauses.count >= 1
  spans = [e for e in tracer.event_dicts() if e['name'] == 'host.gc']
  feed = [e for e in spans if e['args']['generation'] == 2 and
          e['tid'] != tracer.main_thread]
  assert feed, 'the forced collection, on the feed thread'
  rows = list(stalls.step_record())
  assert sum(r[3] for r in rows) >= feed[0]['dur'] * 0.99
  assert sum(r[3] for r in rows) <= pauses.sum + 1e-9


@pytest.mark.parametrize('fails', [False, True])
def test_no_hook_is_left_registered(fails):
  before = list(gc.callbacks)
  seen = []

  def step(params, opt_state, rng, batch):
    seen.append(sum(isinstance(cb, stalls.GcClock) for cb in gc.callbacks))
    if fails and len(seen) == 3:
      raise RuntimeError('a step that fails')
    return params, opt_state, {'loss': jnp.float32(1.0)}

  if fails:
    with pytest.raises(RuntimeError):
      make_loop(step).run(5, log_every=0)
  else:
    make_loop(step).run(5, log_every=0)
  assert set(seen) == {1}  # registered once while the loop ran
  assert gc.callbacks == before


@pytest.mark.parametrize('sink', ['none', 'ring', 'capture'])
def test_a_collection_is_a_span_only_where_a_sink_is_on(monkeypatch, sink):
  made = []

  class Annotation:

    def __init__(self, name, **kwargs):
      made.append([name, kwargs, 'made'])

    def __enter__(self):
      made[-1][2] = 'entered'
      return self

    def set_metadata(self, **kwargs):
      made[-1][1].update(kwargs)

    def __exit__(self, *exc):
      made[-1][2] = 'left'
      return False

  monkeypatch.setattr(jax.profiler, 'TraceAnnotation', Annotation)
  tracer = trace.enable_trace() if sink == 'ring' else trace.get_tracer()
  if sink == 'capture':
    monkeypatch.setattr(profiling.get_step_profiler(), '_active_steps', 1)
  clock = stalls.GcClock(tracer, telemetry.get_telemetry())
  gc.callbacks.append(clock)
  try:
    gc.collect(1)
  finally:
    gc.callbacks.remove(clock)
  assert clock.counts[1] >= 1 and clock.seconds > 0
  ring = [e for e in tracer.event_dicts() if e['name'] == 'host.gc']
  if sink == 'none':
    assert made == [] and ring == []
  elif sink == 'ring':
    assert made == []
    assert {'generation': 1} == {'generation': ring[-1]['args']['generation']}
    assert 'collected' in ring[-1]['args'] and ring[-1]['dur'] > 0
  else:
    assert ring == []
    name, kwargs, state = made[-1]
    assert (name, kwargs['generation'], state) == ('host.gc', 1, 'left')
    assert 'collected' in kwargs


def test_a_collection_never_flushes_the_ring(monkeypatch, tmp_path):
  # A ring due for its flush at the next check: the collector's span is
  # appended, and no file is written from inside the collection.
  monkeypatch.setenv('LDDL_TELEMETRY_DIR', str(tmp_path))
  tracer = trace.Tracer(flush_interval=0.0)
  tracer._since_check = trace._FLUSH_CHECK_EVERY - 1
  clock = stalls.GcClock(tracer, telemetry.get_telemetry())
  gc.callbacks.append(clock)
  try:
    gc.collect(0)
  finally:
    gc.callbacks.remove(clock)
  assert [e['name'] for e in tracer.event_dicts()] == ['host.gc']
  assert os.listdir(tmp_path) == []
  tracer.complete('x', 0.0, 1.0)  # any other event still flushes
  assert os.listdir(tmp_path) != []


# ----------------------------------------------------------------------------
# the sentinel


def test_step_stall_incident_keeps_the_facts_and_the_spans(monkeypatch,
                                                           tmp_path, capsys):
  from lddl_tpu.telemetry.sentinel import enable_sentinel
  from lddl_tpu.training.flight import MANIFEST, enable_flight
  faults.reset()
  monkeypatch.setenv('LDDL_FAULTS', 'delay:train.step:sec=0.5,nth=12')
  enable_sentinel(detectors=('data_stall', 'step_stall'))
  enable_flight(out_dir=str(tmp_path))
  trace.enable_trace()
  make_loop().run(16, log_every=0)
  out = capsys.readouterr().out
  assert 'sentinel: step_stall fired at step 10' in out
  assert 'data_stall' not in out  # a batch wait of 60 s, as before
  (incident,) = os.listdir(tmp_path)
  assert incident == 'incident-step10-step_stall'
  with open(tmp_path / incident / MANIFEST) as f:
    manifest = json.load(f)
  stats = manifest['trigger']['stats']
  assert stats['step'] == 10 and stats['interval_s'] > 0.5
  assert set(stats['parts']) == set(stalls.PARTS)
  names = {(e['name'], (e.get('args') or {}).get('step'))
           for e in manifest['extra']['spans']}
  # The launch of step 11 and the read of step 10 lie inside (the step's
  # own train.step span is still open when its observers run).
  assert {('train.data_wait', 11), ('train.dispatch', 11),
          ('train.loss_read', 10)} <= names
