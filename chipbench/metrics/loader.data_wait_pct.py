"""Share of the window's wall time the loop spent waiting for a batch:
the program's ``train.data_wait_seconds`` histogram (exact sum), over the
window. Layer: loader. Moves ``tokens_per_s``."""


def read(ctx):
  tele = ctx['telemetry']
  if not tele or not tele['train.data_wait_seconds']['count']:
    return None
  return 100.0 * tele['train.data_wait_seconds']['sum'] / ctx['wall_s']
