"""On-demand ``jax.profiler`` capture, for scripts and live runs.

Two entry points over one code path:

  - :func:`trace_capture` — a context manager around one profiled region
    of a script; no-op when the directory is falsy, so callers never
    branch.
  - :class:`StepProfiler` — the live-run half: ``GET /profile?steps=N``
    on the monitor endpoint (or ``lddl-monitor --profile N``) *arms* the
    profiler, and the train loop's per-step ``on_step()`` hook starts a
    trace at the next step boundary and stops it N steps later. The loop
    keeps one step in flight, so it asks :attr:`StepProfiler.at_edge`
    before each launch and drains first where a capture is about to start
    or stop: a capture holds N whole step programs, the first of them
    launched into an idle chip (:mod:`.capture` places the device's clock
    by it). Traces
    land under ``LDDL_TELEMETRY_DIR/profiles/`` (same layout the
    context manager uses), numbered per capture, so a long pretrain can
    be profiled without a restart and costs nothing while unarmed: the
    unarmed ``on_step`` path is two attribute reads. While a capture
    runs, ``tracer.phase`` (:mod:`.trace`) writes the loop's phases into
    the profiler's trace; when ``on_step()`` stops it, the capture is
    summarized (:mod:`.capture`: idle gaps by phase, device time by
    module class and pass) into ``last_summary`` and ``summary.json``.

The profiler singleton is plain state, not a thread or a socket — with
``LDDL_MONITOR`` unset nothing ever arms it, preserving the PR 7 no-op
guarantees (pinned by tests/test_monitor.py and tests/test_roofline.py).
"""

import contextlib
import logging
import os
import threading


@contextlib.contextmanager
def trace_capture(trace_dir):
  """Profile the enclosed region into ``trace_dir`` (TensorBoard /
  Perfetto layout). Falsy ``trace_dir`` → no-op, zero overhead."""
  if not trace_dir:
    yield None
    return
  import jax
  os.makedirs(trace_dir, exist_ok=True)
  jax.profiler.start_trace(trace_dir)
  try:
    yield trace_dir
  finally:
    jax.profiler.stop_trace()


def default_profile_dir():
  """Where live captures go: ``$LDDL_TELEMETRY_DIR/profiles`` (cwd-
  relative ``lddl_profiles/`` when the telemetry dir is unset)."""
  base = os.environ.get('LDDL_TELEMETRY_DIR')
  return os.path.join(base, 'profiles') if base else 'lddl_profiles'


class StepProfiler:
  """Arms ``jax.profiler`` for the next N train steps.

  ``arm()`` is called from the monitor's HTTP thread; ``on_step()`` from
  the train loop. The hot path (unarmed) reads two attributes and
  returns — no lock. The armed transitions take ``_lock`` so an arm
  racing a step boundary cannot double-start a trace; jax allows only
  one active trace per process.
  """

  def __init__(self):
    self._lock = threading.Lock()
    self._armed_steps = 0      # steps requested, not yet started
    self._active_steps = 0     # steps remaining in a running trace
    self._out_dir = None
    self._capture_index = 0
    self.last_trace_dir = None
    # :func:`lddl_tpu.telemetry.capture.summarize` of the newest finished
    # capture, or None (no capture yet, or its trace was not readable).
    self.last_summary = None

  def arm(self, steps, out_dir=None):
    """Request a capture of the next ``steps`` train steps; returns the
    directory the trace will land in. Re-arming while armed or active
    replaces the pending request (it does not extend a running trace)."""
    steps = max(1, int(steps))
    with self._lock:
      self._out_dir = out_dir or default_profile_dir()
      self._armed_steps = steps
      return self._out_dir

  @property
  def at_edge(self):
    """Whether the next :meth:`on_step` starts or stops a trace. The
    train loop reads this before it launches a step and, if so, first
    waits for the step in flight. Unarmed: two attribute reads."""
    return (self._active_steps == 1 or
            bool(self._armed_steps and not self._active_steps))

  def on_step(self, in_flight=False):
    """Call once per observed train step. Returns the trace directory
    when this call *finished* a capture, else None. ``in_flight`` says
    that a later step already runs on the device: a trace neither starts
    nor stops then (an ``arm()`` that raced the launch waits one step for
    the loop's drain), so that a capture holds whole step programs."""
    if not self._armed_steps and not self._active_steps:
      return None
    if in_flight and self.at_edge:
      return None
    finished = None
    with self._lock:
      if self._armed_steps and not self._active_steps:
        import jax
        n = self._capture_index
        self._capture_index += 1
        trace_dir = os.path.join(self._out_dir or default_profile_dir(),
                                 f'capture{n:04d}')
        os.makedirs(trace_dir, exist_ok=True)
        jax.profiler.start_trace(trace_dir)
        self.last_trace_dir = trace_dir
        self._active_steps = self._armed_steps
        self._armed_steps = 0
        return None
      if self._active_steps:
        self._active_steps -= 1
        if self._active_steps == 0:
          import jax
          jax.profiler.stop_trace()
          finished = self.last_trace_dir
    if finished is not None:
      # Outside the lock: reading the trace takes about a second.
      self.last_summary = _summarize(finished)
    return finished

  def close(self):
    """Stop any in-flight trace (train-loop teardown); idempotent."""
    with self._lock:
      self._armed_steps = 0
      if self._active_steps:
        self._active_steps = 0
        import jax
        try:
          jax.profiler.stop_trace()
        except RuntimeError:
          # jax raises when no trace is running — a crash between our
          # start and this stop already tore the session down; the goal
          # (no trace left open) holds either way.
          pass

  @property
  def armed(self):
    return bool(self._armed_steps or self._active_steps)


def _summarize(trace_dir):
  """The capture's summary, or None: a trace that is missing or cannot be
  read costs the operator the table, never the training run."""
  from .capture import summarize_capture
  try:
    return summarize_capture(trace_dir)
  except Exception:  # the train loop's boundary to a diagnostics reader
    logging.getLogger('lddl_tpu').warning(
        'profiler: no summary of the capture under %s', trace_dir,
        exc_info=True)
    return None


_profiler = None
_profiler_lock = threading.Lock()


def get_step_profiler():
  """The process-wide :class:`StepProfiler` (created on first use; plain
  state, no threads)."""
  global _profiler
  if _profiler is None:
    with _profiler_lock:
      if _profiler is None:
        _profiler = StepProfiler()
  return _profiler


def _reset_for_tests():
  global _profiler
  if _profiler is not None:
    _profiler.close()
  _profiler = None
