"""Rise of ``CompiledStepCache.misses`` inside the window; anything but 0
also makes the run not correct. Layer: train loop. Moves
``tokens_per_s``."""


def read(ctx):
  return ctx['compiles_in_window']
