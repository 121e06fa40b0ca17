"""Share of the device's busy time inside step programs spent in
operations with no module class (no ``op_name``, or one outside
every class): bounds how far the other shares can be trusted.
From the program's capture summary (device operations billed by their
``op_name``). Layer: train step. Moves ``tokens_per_s``."""

from chipbench import capture_summary


def read(ctx):
  return capture_summary.busy_share_pct('classes', 'unscoped')
