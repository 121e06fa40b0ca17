"""The share of the window's time that stalls cost: the summed excess of
the stalled intervals over their shapes' running medians, over the
summed intervals of the range, times 100 (the program's step record,
``lddl_tpu/telemetry/stalls.py``; the range: ``chipbench/
stall_record.py``). The share of ``tokens_per_s`` the stalls took, to
hold against a cell's spread. None when the program keeps no step
record or its steps do not match the window's. Layer: train loop.
Moves ``tokens_per_s``."""


def read(ctx):
  from chipbench.stall_record import window_rows
  rows = window_rows(ctx)
  if rows is None:
    return None
  total = sum(row[1] for row in rows)
  excess = sum(row[1] - row[2] for row in rows if row[4])
  return 100.0 * excess / total if total > 0 else None
