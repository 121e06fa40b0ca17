"""Median over the idle gaps between two step programs of the part under
``train.dispatch``: the step cache's key and lookup and the executable
call, until the program starts on the device.
From the program's capture summary (phases on the profiler's clock).
Layer: train loop. Moves ``tokens_per_s``."""

from chipbench import capture_summary


def read(ctx):
  return capture_summary.gap_median_ms('train.dispatch')
