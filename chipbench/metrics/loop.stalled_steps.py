"""Steps of the window that the program's stall rule judged stalled: the
interval to finding the step finished over twice the running median of
its shape's last 32 and longer than it by 10 ms or more
(``lddl_tpu/telemetry/stalls.py``; the range: ``chipbench/
stall_record.py``). Each such step also printed a ``stall:`` line that
names its cause. None when the program keeps no step record or its
steps do not match the window's. Layer: train loop. Moves
``tokens_per_s``."""


def read(ctx):
  from chipbench.stall_record import window_rows
  rows = window_rows(ctx)
  if rows is None:
    return None
  return sum(1 for row in rows if row[4])
