"""The held experts' grouped products against their roofline: the least
time the chip could take for the traced steps' expert products (the
family's ``experts_required``: 6 FLOPs a routed assignment, hidden and
expert width forward and twice that backward, from the program's routed
counts; the held experts' weights and each assignment's rows moved once)
over the device time the capture summary bills to the scope ``experts``
(the products and the gate between them, recomputation included). None
where the program's summary has no such scope, or the family or the
program counts nothing. Layer: experts. Moves ``tokens_per_s``."""

from chipbench import capture_summary, required_work


def read(ctx):
  found = capture_summary.summary()
  experts_required = getattr(ctx['family'], 'experts_required', None)
  if not found or not ctx['peaks'] or experts_required is None:
    return None
  ns = sum(d.get('scopes', {}).get('experts', 0) for d in found['devices'])
  if not ns or any(d['steps'] != len(ctx['traced_steps'])
                   for d in found['devices']):
    return None
  work = experts_required(ctx['config'], ctx['train'], ctx['traced_steps'])
  if work is None:
    return None
  seconds, _ = required_work.roofline_seconds(work, ctx['peaks'])
  return 100.0 * seconds / (ns / 1e9)
