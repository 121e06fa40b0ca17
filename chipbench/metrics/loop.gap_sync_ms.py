"""Median over the idle gaps between two step programs of the part under
``train.loss_read``: from the device's last operation of a step to the
host holding the loss scalar (the wake-up from the device sync).
From the program's capture summary (phases on the profiler's clock).
Layer: train loop. Moves ``tokens_per_s``."""

from chipbench import capture_summary


def read(ctx):
  return capture_summary.gap_median_ms('train.loss_read')
