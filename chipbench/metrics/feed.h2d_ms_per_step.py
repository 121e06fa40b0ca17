"""Host-to-device placement time per batch on the prefetch thread: the
program's ``train.h2d_seconds`` histogram, sum over count, inside the
window. Layer: device feed. Moves ``tokens_per_s``."""


def read(ctx):
  tele = ctx['telemetry']
  if not tele or not tele['train.h2d_seconds']['count']:
    return None
  h2d = tele['train.h2d_seconds']
  return 1e3 * h2d['sum'] / h2d['count']
