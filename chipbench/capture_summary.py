"""What the per-layer readers take from the program's capture summary.

In a traced run the loop's own profiler hook stops the trace and the
program summarizes it there and then (``lddl_tpu/telemetry/capture.py``:
per device, every gap between two step programs split over the loop's
phases, and the busy time inside step programs by module class and by
pass, all in nanoseconds). ``run.py`` deletes the trace directory before
it calls the readers, so they read the summary the program kept in
memory. A program without such a summary (the parent of the PR that
added it, a CPU rehearsal, an untraced run) gives None, and the metric
is left out.

The arithmetic from nanoseconds to a metric lives here, with the
benchmark.
"""

import statistics


def summary():
  """The newest capture's summary with at least one device in it, or
  None."""
  from lddl_tpu.telemetry.profiling import get_step_profiler
  found = getattr(get_step_profiler(), 'last_summary', None)
  if not found or not found.get('devices'):
    return None
  return found


def _gaps(found):
  return [g for d in found['devices'] for g in d['gaps']]


def gap_median_ms(phase):
  """Median over the gaps between step programs of the part that lies
  under ``phase``."""
  found = summary()
  gaps = _gaps(found) if found else []
  if not gaps:
    return None
  return statistics.median(g['phases'].get(phase, 0) for g in gaps) / 1e6


def gap_unattributed_pct():
  """Summed gap time under no phase over summed gap time."""
  found = summary()
  gaps = _gaps(found) if found else []
  total = sum(g['ns'] for g in gaps)
  if not total:
    return None
  return 100.0 * sum(g['phases'].get('unattributed', 0) for g in gaps) / total


def busy_share_pct(key, name):
  """Entry ``name`` of ``classes`` or ``passes`` over the busy time inside
  step programs, all devices together."""
  found = summary()
  busy = sum(d['busy_ns'] for d in found['devices']) if found else 0
  if not busy:
    return None
  return 100.0 * sum(d[key].get(name, 0) for d in found['devices']) / busy
