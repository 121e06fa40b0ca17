"""Peak memory of the fullest chip after the window over its
``bytes_limit``: ``peak_bytes_in_use`` (buffers) plus
``peak_bytes_reserved`` (the programs' scratch), as ``memory_stats()``
gives them. Layer: device. Moves ``tokens_per_s`` (memory left is batch
that could be added)."""


def read(ctx):
  memory = ctx['memory']
  if not memory['peak_bytes'] or not memory['bytes_limit']:
    return None
  return 100.0 * memory['peak_bytes'] / memory['bytes_limit']
