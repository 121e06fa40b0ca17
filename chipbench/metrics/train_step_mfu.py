"""The whole step's share of the chip's peak, on the device's clock:
required FLOPs of the traced steps (the model family's ``required_flops``
on what each of those batches really held) over the summed time of their
step programs in the trace x chips x peak FLOP/s. Recomputation is not
counted, padding is not counted; the host's time between two programs is
not in it (that is ``loop.host_gap_ms`` and ``device.idle_pct``), so it
tells a slow step from a slow loop. Layer: train step. Moves
``tokens_per_s``."""


def read(ctx):
  trace = ctx['trace']
  if not trace or not ctx['peaks']:
    return None
  steps = ctx['traced_steps']
  if any(len(steps) != d['steps'] for d in trace['devices']):
    return None  # the trace does not hold exactly the steps it was armed for
  flops = sum(ctx['family'].required_flops(ctx['config'], ctx['train'], s)
              for s in steps)
  seconds = max(sum(d['step_ns']) for d in trace['devices']) / 1e9
  return 100.0 * flops / (seconds * ctx['chips'] * ctx['peaks']['flops_per_s'])
