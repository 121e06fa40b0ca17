"""The Mosaic kernels' (flash kernels') device time over the device's busy
time in the traced window. Layer: attention kernel. Moves
``tokens_per_s``."""


def read(ctx):
  trace = ctx['trace']
  if not trace:
    return None
  device = trace['devices'][0]
  if not device['kernel_ns']:
    return None
  return 100.0 * device['kernel_ns'] / device['busy_ns']
