"""Summed time of the gaps between step programs that lies under no phase
of ``TrainLoop.run``, over the summed gap time: the instrumentation's own
check. From the program's capture summary. Layer: train loop. Moves
``tokens_per_s``."""

from chipbench import capture_summary


def read(ctx):
  return capture_summary.gap_unattributed_pct()
