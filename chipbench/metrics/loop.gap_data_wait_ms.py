"""Median over the idle gaps between two step programs of the part under
``train.data_wait``: the queue get of the next device batch and the
donation delete of the previous one.
From the program's capture summary (phases on the profiler's clock).
Layer: loader. Moves ``tokens_per_s``."""

from chipbench import capture_summary


def read(ctx):
  return capture_summary.gap_median_ms('train.data_wait')
