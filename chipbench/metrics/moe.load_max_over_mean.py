"""The busiest held expert's routed assignments over the held experts'
mean, in the step's worst sparse layer, averaged over every step the loop
observed (the program's ``moe.load_max_over_mean`` histogram, kept with
telemetry on, as in a ``--trace 1`` run). 1 is an even load; a drop-free
layer's step lasts as long as its busiest expert. None where the program
keeps no such histogram. Layer: experts. Moves ``tokens_per_s``."""


def read(ctx):
  from lddl_tpu.telemetry import get_telemetry
  tele = get_telemetry()
  if not tele.enabled:
    return None
  histogram = tele.histogram('moe.load_max_over_mean')
  if not histogram.count:
    return None
  return histogram.sum / histogram.count
