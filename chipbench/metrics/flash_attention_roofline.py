"""The flash kernels' share of their roofline: the least time the chip
could take for the attention core's required work of the traced steps
(the model family's ``flash_required``; BERT's is
``required_work.flash_required``: forward 4 len^2 d_head, backward
8 len^2 d_head per head and document; q, k, v, o, do, dq, dk, dv moved
once) over the summed device time of the Mosaic kernels in the trace.
Nothing to read where the step holds no such kernel or the family counts
none. Layer: attention kernel. Moves ``tokens_per_s``."""

from chipbench import required_work


def read(ctx):
  trace = ctx['trace']
  if not trace or not ctx['peaks']:
    return None
  flash_required = getattr(ctx['family'], 'flash_required', None)
  device = trace['devices'][0]
  if not device['kernel_ns'] or flash_required is None:
    return None
  steps = ctx['traced_steps']
  if len(steps) != device['steps']:
    return None  # the trace does not hold exactly the steps it was armed for
  least = 0.0
  for s in steps:
    seconds, _ = required_work.roofline_seconds(
        flash_required(ctx['config'], ctx['train'], s), ctx['peaks'])
    least += seconds
  return 100.0 * least / (device['kernel_ns'] / 1e9)
