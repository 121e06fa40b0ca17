"""Python's collector per step of the window: the pauses of every
generation, on every thread, inside the range's intervals, summed, in
ms, over its steps, whether or not they stalled the chip (the program's
step record, ``lddl_tpu/telemetry/stalls.py``; the range: ``chipbench/
stall_record.py``). None when the program keeps no step record or its
steps do not match the window's. Layer: train loop. Moves
``tokens_per_s``."""


def read(ctx):
  from chipbench.stall_record import window_rows
  rows = window_rows(ctx)
  if rows is None:
    return None
  return 1e3 * sum(row[3] for row in rows) / len(rows)
