"""Share of the device's busy time inside step programs spent in the
gated short-convolution operators with their in and out projections (the
capture summary's class ``conv``). From the program's capture summary.
Layer: train step. Moves ``tokens_per_s``."""

from chipbench import capture_summary


def read(ctx):
  return capture_summary.busy_share_pct('classes', 'conv')
