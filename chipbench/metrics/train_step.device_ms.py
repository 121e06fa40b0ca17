"""Device busy time per step program (union of its operations' intervals),
mean over the traced steps. Layer: train step. Moves ``tokens_per_s``."""

import statistics


def read(ctx):
  trace = ctx['trace']
  if not trace:
    return None
  busy = [b for d in trace['devices'] for b in d['step_busy_ns']]
  return statistics.fmean(busy) / 1e6 if busy else None
