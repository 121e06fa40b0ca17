"""Where XLA's persistent compilation cache lives.

A BERT-base train step takes 10-20 s to compile per bin shape, and every
process that trains, replays or benchmarks pays it again unless the
compiled executables persist. Every entry point that compiles for the
chip calls :func:`use_compile_cache` once, before its first compile (and
after any ``jax.distributed`` bootstrap: it asks jax for its backend).

The directory is part of the cache key, so it must not move between
runs: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (jax
reads that variable itself — this module then sets no directory), otherwise
one fixed directory at the root of the checkout. The ``cpu`` backend
gets no default: it compiles in seconds, and XLA:CPU's cached code is
tied to the CPU features of the host that built it (its loader warns of
SIGILL on a mismatch), which a directory inside a copied tree invites.

The key covers the operations' metadata. By default jax strips it
(``op_name``, source lines) before hashing, so a program whose scopes
were renamed but whose arithmetic stayed comes back from the cache as
the executable that was compiled *first*, under the old names: PR 25's
first chip run read ``jit(step)/add`` where its program says
``jit(step)/optimizer/add``, because the parent commit had filled the
cache. The capture summary (:mod:`lddl_tpu.telemetry.capture`) bills
device time by those names, so here they are part of the key; the price
is a fresh compile when a traced file's lines move.
"""

import os

#: Fixed fallback location (git- and docker-ignored): ``<checkout>/.jax_cache``.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), '.jax_cache')


def use_compile_cache():
  """Point jax at the persistent compile cache; returns its directory
  (None on the cpu backend when the environment names none)."""
  import jax
  jax.config.update('jax_compilation_cache_include_metadata_in_key', True)
  placed = os.environ.get('JAX_COMPILATION_CACHE_DIR')
  if placed:
    return placed
  if jax.default_backend() == 'cpu':
    return None
  jax.config.update('jax_compilation_cache_dir', DEFAULT_CACHE_DIR)
  return DEFAULT_CACHE_DIR
