"""1 - union of device-operation intervals over the traced window (whole
step programs, first start to last end), mean over the chips. Layer:
device. Moves ``tokens_per_s``."""


def read(ctx):
  trace = ctx['trace']
  if not trace:
    return None
  return 100.0 * (1.0 - trace['busy_s'] / trace['window_s'])
