"""Median idle gap on the device between one step program's last
operation and the next one's first, from the trace: what the host loop
(loss read, bookkeeping, next dispatch) costs the chip per step. Layer:
train loop. Moves ``tokens_per_s``."""

import statistics


def read(ctx):
  trace = ctx['trace']
  if not trace:
    return None
  gaps = [g for d in trace['devices'] for g in d['step_gap_ns']]
  return statistics.median(gaps) / 1e6 if gaps else None
