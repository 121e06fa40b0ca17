"""The rows of the program's per-step stall record that the three
``loop.*`` stall readers sum (``lddl_tpu.telemetry.stalls.step_record``:
one ``(step, interval_s, median_s, gc_s, stalled)`` row per step found
finished, with telemetry on, as in a ``--trace 1`` run).

The range is the window's steps, and nothing else. ``run.py`` ends the
loop through its preemption path once the step tap finds the window's
last step finished. The tap finds it at the call that launches the step
two behind it, or, where the host lags the device, at the call before:
the loop then finds two more steps finished, or one (the one in flight
and, in the first case, the one launched behind it). Of those two
ranges the one whose intervals lie closest to the window's,
``ctx['intervals_ms']`` element by element, is taken.

The two clocks differ only at the window's ends: the tap finds step k
finished after the record does (by the host's work up to the next step
call), but before the record finds step k + 1, and never before the
record found step k - 1. So the window's wall time, ``ctx['wall_s']``,
lies within the record's summed intervals less the record's interval of
each end step, and plus the record's interval of the step either side.
A range outside those bounds is not the window: None. None too where the
program keeps no record (a program before the record existed) or the
record lacks a row of the range or of the step either side of it."""


def window_rows(ctx):
  try:
    from lddl_tpu.telemetry.stalls import step_record
  except ImportError:
    return None
  rows = list(step_record())
  outside = ctx.get('intervals_ms') or ()
  if not rows or not outside:
    return None
  n, last = len(outside), rows[-1][0]
  interval = {row[0]: row[1] for row in rows}
  fits = []
  for after in (2, 1):  # rows after the window's last one
    first, end = last - after - n + 1, last - after
    if all(step in interval for step in range(first - 1, end + 2)):
      misfit = sum(abs(1e3 * interval[first + i] - ms)
                   for i, ms in enumerate(outside))
      fits.append((misfit, first, end))
  if not fits:
    return None
  _, first, end = min(fits)
  late = ctx['wall_s'] - sum(interval[s] for s in range(first, end + 1))
  if not (-(interval[first] + interval[end]) < late <
          interval[first - 1] + interval[end + 1]):
    return None
  return [row for row in rows if first <= row[0] <= end]
