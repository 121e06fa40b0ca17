"""Share of the device's busy time inside step programs spent in the
sparse-expert layers: router, dispatch, the experts' grouped products and
the combine, forward, backward and recomputed (the capture summary's class
``moe``). From the program's capture summary. Layer: train step. Moves
``tokens_per_s``."""

from chipbench import capture_summary


def read(ctx):
  return capture_summary.busy_share_pct('classes', 'moe')
