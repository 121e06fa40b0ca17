"""Share of the device's busy time inside step programs spent in
the feed-forward block (its two gemms and the GELU): the
required work.
From the program's capture summary (device operations billed by their
``op_name``). Layer: train step. Moves ``tokens_per_s``."""

from chipbench import capture_summary


def read(ctx):
  return capture_summary.busy_share_pct('classes', 'ffn')
