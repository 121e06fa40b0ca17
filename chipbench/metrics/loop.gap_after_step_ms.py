"""Median over the idle gaps between two step programs of the part under
``train.after_step``: the second scalar read, sentinel, flight recorder,
profiler hook, telemetry, log, writer/guard/membership checks.
From the program's capture summary (phases on the profiler's clock).
Layer: train loop. Moves ``tokens_per_s``."""

from chipbench import capture_summary


def read(ctx):
  return capture_summary.gap_median_ms('train.after_step')
