"""Host stalls of the train loop, named from inside it.

The benchmark times the loop from outside and can only say that one
interval was long. ``TrainLoop.run`` knows more: it takes the time at
which it finds each step finished (where the loss read returns), and the
interval between two such times is the one the benchmark's step times
read. Three pieces name what held such an interval up:

  - :class:`StallRule` judges an interval against the running median of
    the last ``WINDOW`` intervals of the same step shape (the
    ``CompiledStepCache`` key): *stalled* when it is more than ``FACTOR``
    times that median and longer than it by ``MIN_EXCESS`` seconds at
    least. A shape is judged once it has ``WARMUP`` intervals.
  - :class:`GcClock` is a ``gc.callbacks`` hook, registered while
    ``TrainLoop.run`` runs. Each collection's pause, on whatever thread
    collected, goes into two cumulative numbers (seconds; collections per
    generation); with telemetry on into the histogram ``host.gc_seconds``;
    and while the ring tracer or a profiler capture is on it is a
    ``host.gc`` span with ``generation`` and ``collected``, through the
    same two sinks as ``tracer.phase`` (:mod:`.trace`): ``tracer.complete``
    on ``time.monotonic``, and a ``jax.profiler.TraceAnnotation`` entered
    at the collection's start and left at its stop on the collecting
    thread, so that the span lies on the device trace's clock. The name
    starts with ``host.`` so that :mod:`.capture` never takes it for a
    phase that splits a gap.
  - :class:`StepWatch` is the loop's side. Each time a step is found
    finished it takes the clock, the process's CPU time and context
    switches (``getrusage``) and the collector's clock, and judges the
    interval since the previous find. Left out of the judgement and of
    the medians: an interval that holds a compile (a step-cache miss),
    one ending inside a profiler capture, one ending in a drain (no step
    queued behind it) and the one after a drain. A stalled step prints
    one line: its parts under the loop's phases, the collector's pauses,
    CPU time and context switches inside the interval, which tell a
    collection, CPU busy without one (the feed thread's Python, the
    lock) and CPU idle (the runtime, a descheduled process) apart. With
    telemetry on it also counts ``train.stalled_steps``, observes the
    excess over the median into ``train.stall_seconds`` and appends every
    judged or left-out step to :func:`step_record`. The sentinel's
    ``step_stall`` detector reads the same verdict.

Off (no telemetry, no tracer, no capture) this costs a clock read, one
``getrusage``, a median of at most ``WINDOW`` floats and a comparison a
step, and two clock reads and two adds a collection.
"""

import collections
import gc
import resource
import statistics
import time

from . import profiling

WINDOW = 32          # intervals of one shape the running median is taken over
WARMUP = 8           # intervals a shape needs before any of its steps is judged
FACTOR = 2.0         # stalled: over FACTOR times the median,
MIN_EXCESS = 0.010   # and longer than it by this many seconds at least
RECORD_STEPS = 4096  # rows the per-step record keeps
GC_SPAN = 'host.gc'

PARTS = ('after_step', 'epoch_turn', 'data_wait', 'dispatch', 'loss_read')

_record = collections.deque(maxlen=RECORD_STEPS)


def step_record():
  """The process's per-step record, newest last: one ``(step, interval_s,
  median_s, gc_s, stalled)`` row for each step ``TrainLoop.run`` found
  finished after another one, with telemetry on. ``median_s`` is None
  for a step that was not judged (warm-up of its shape, a compile, a
  capture, a drain); ``gc_s`` is the collector's pause inside the
  interval, on every thread."""
  return _record


class StallRule:
  """The stall rule, per step shape (see the module docstring)."""

  def __init__(self):
    self._intervals = {}

  def judge(self, shape, interval):
    """``(median, stalled)`` of ``interval`` against ``shape``'s earlier
    intervals (``median`` None while the shape warms up); the interval
    then joins the shape's window."""
    window = self._intervals.get(shape)
    if window is None:
      window = self._intervals[shape] = collections.deque(maxlen=WINDOW)
    median = statistics.median(window) if len(window) >= WARMUP else None
    window.append(interval)
    stalled = (median is not None and interval > FACTOR * median and
               interval - median >= MIN_EXCESS)
    return median, stalled


class GcClock:
  """The ``gc.callbacks`` hook (see the module docstring). ``seconds`` and
  ``counts`` (collections per oldest generation collected) are
  cumulative; collections cannot overlap, so one pair of start values
  serves every thread."""

  def __init__(self, tracer=None, tele=None):
    from jax.profiler import TraceAnnotation
    self._annotation = TraceAnnotation
    self.seconds = 0.0
    self.counts = [0, 0, 0]
    self._tracer = tracer if tracer is not None and tracer.enabled else None
    self._hist = (tele.histogram('host.gc_seconds')
                  if tele is not None and tele.enabled else None)
    self._t0 = None   # None: no collection under way that this hook saw
    self._m0 = 0.0
    self._span = None

  def __call__(self, phase, info):
    if phase == 'start':
      profiler = profiling._profiler
      if profiler is not None and profiler._active_steps:
        self._span = self._annotation(GC_SPAN,
                                      generation=info['generation'])
        self._span.__enter__()
      if self._tracer is not None:
        self._m0 = time.monotonic()
      self._t0 = time.perf_counter()
      return
    if self._t0 is None:  # registered while another thread collected
      return
    pause = time.perf_counter() - self._t0
    self._t0 = None
    self.seconds += pause
    self.counts[info['generation']] += 1
    if self._span is not None:
      self._span.set_metadata(collected=info['collected'])
      self._span.__exit__(None, None, None)
      self._span = None
    if self._tracer is not None:
      # No flush from inside a collection: no file I/O in the pause.
      self._tracer.complete(
          GC_SPAN, self._m0, time.monotonic() - self._m0,
          args={'generation': info['generation'],
                'collected': info['collected']}, flush=False)
    if self._hist is not None:
      self._hist.observe(pause)


def shape_of(key):
  """The ``[batch, seq]`` a ``CompiledStepCache`` key stands for: its
  longest array shape."""
  return list(max((entry[1] for entry in key), key=len)) if key else []


def format_stall(facts):
  """The one line a stalled step prints."""
  ms = {p: 1e3 * facts['parts'][p] for p in PARTS}
  gens = ', '.join(f'gen{g} x{n}' for g, n in
                   enumerate(facts['gc_collections']) if n)
  turn = ' [epoch turn]' if facts['parts']['epoch_turn'] > 0 else ''
  return (f'stall: step {facts["step"]} {facts["shape"]} '
          f'{1e3 * facts["interval_s"]:.1f} ms (median '
          f'{1e3 * facts["median_s"]:.1f}){turn}: ' +
          ', '.join(f'{p} {ms[p]:.1f}' for p in PARTS) +
          f'; gc {facts["gc_s"]:.3f} s ({gens or "none"}); cpu '
          f'{facts["cpu_user_s"] + facts["cpu_sys_s"]:.3f} s (user '
          f'{facts["cpu_user_s"]:.3f}, sys {facts["cpu_sys_s"]:.3f}), nvcsw '
          f'{facts["nvcsw"]}, nivcsw {facts["nivcsw"]}')


class StepWatch:
  """The loop's stall watch for one ``TrainLoop.run`` call; the
  collector's hook is registered from :meth:`install` to
  :meth:`uninstall`."""

  def __init__(self, tele, tracer):
    self.rule = StallRule()
    self.clock = GcClock(tracer, tele)
    self._record = _record if tele.enabled else None
    self._stalled_c = tele.counter('train.stalled_steps')
    self._excess_h = tele.histogram('train.stall_seconds')
    self._last = None      # (t, rusage, gc seconds, gc counts) at the last find
    self._misses = None
    self._drained = False  # the last find was a drain's

  def install(self):
    gc.callbacks.append(self.clock)
    return self

  def uninstall(self):
    gc.callbacks.remove(self.clock)

  def found(self, step, key, misses, t_read, t_found, queued):
    """Step ``step`` (shape ``key``) was found finished at ``t_found``, its
    loss read having begun at ``t_read``; ``misses`` is the step cache's
    count now; ``queued`` is the step launched behind it (with its
    ``t_pull``, ``t_wait``, ``t_step``), None in a drain. Returns the
    stall's facts, or None."""
    clock = self.clock
    now = (t_found, resource.getrusage(resource.RUSAGE_SELF), clock.seconds,
           tuple(clock.counts))
    last, self._last = self._last, now
    compiled, self._misses = misses != self._misses, misses
    after_drain, self._drained = self._drained, queued is None
    if last is None:
      return None
    interval = t_found - last[0]
    profiler = profiling._profiler
    median, stalled = None, False
    if not (compiled or after_drain or queued is None or
            (profiler is not None and profiler._active_steps)):
      median, stalled = self.rule.judge(key, interval)
    gc_s = now[2] - last[2]
    if self._record is not None:
      self._record.append((step, interval, median, gc_s, stalled))
    if not stalled:
      return None
    ru0, ru1 = last[1], now[1]
    facts = {
        'step': step, 'shape': shape_of(key), 'interval_s': interval,
        'median_s': median, 't0': last[0], 't1': t_found,
        'parts': {'after_step': queued.t_pull - last[0],
                  'epoch_turn': queued.t_wait - queued.t_pull,
                  'data_wait': queued.t_step - queued.t_wait,
                  'dispatch': t_read - queued.t_step,
                  'loss_read': t_found - t_read},
        'gc_s': gc_s,
        'gc_collections': [b - a for a, b in zip(last[3], now[3])],
        'cpu_user_s': ru1.ru_utime - ru0.ru_utime,
        'cpu_sys_s': ru1.ru_stime - ru0.ru_stime,
        'nvcsw': ru1.ru_nvcsw - ru0.ru_nvcsw,
        'nivcsw': ru1.ru_nivcsw - ru0.ru_nivcsw,
    }
    print(format_stall(facts))
    if self._record is not None:
      self._stalled_c.add(1)
      self._excess_h.observe(interval - median)
    return facts


def spans_during(tracer, facts):
  """The ring tracer's events, from every thread, that overlap a stall's
  interval (its ``t0``/``t1`` are ``time.perf_counter`` seconds; the ring
  is on ``time.monotonic``): what a step-stall incident keeps."""
  if not tracer.enabled:
    return []
  shift = time.monotonic() - time.perf_counter()
  lo, hi = facts['t0'] + shift, facts['t1'] + shift
  return [e for e in tracer.event_dicts()
          if e['ts'] < hi and e['ts'] + e.get('dur', 0.0) >= lo]
