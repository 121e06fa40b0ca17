"""Share of the device's busy time inside step programs spent in
the MLM gather, the MLM and NSP heads and both losses.
From the program's capture summary (device operations billed by their
``op_name``). Layer: train step. Moves ``tokens_per_s``."""

from chipbench import capture_summary


def read(ctx):
  return capture_summary.busy_share_pct('classes', 'head_loss')
