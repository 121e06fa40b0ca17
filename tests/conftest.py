"""Test configuration: force an 8-device virtual CPU platform so that
multi-chip sharding (mesh/pjit) is exercised without TPU hardware.

Must run before jax is first imported anywhere in the test process.
"""

import os

# Force CPU regardless of the ambient JAX_PLATFORMS (the machine may pin a
# real TPU platform, and pytest's plugin autoload can import jax before this
# file's env vars would be read): tests need the 8-device virtual mesh and
# tight float32 numerics, not one bf16 TPU chip.
os.environ['JAX_PLATFORMS'] = 'cpu'
_flags = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in _flags:
  os.environ['XLA_FLAGS'] = (
      _flags + ' --xla_force_host_platform_device_count=8').strip()

import jax  # noqa: E402

jax.config.update('jax_platforms', 'cpu')

import pytest  # noqa: E402


def _descends_from(pid, ancestor):
  """Whether the live process ``pid`` is ``ancestor`` or a child of it,
  at any depth (``/proc/<pid>/stat``'s fourth field is the parent)."""
  while pid > 1:
    if pid == ancestor:
      return True
    try:
      with open(f'/proc/{pid}/stat') as f:
        pid = int(f.read().rsplit(')', 1)[1].split()[1])
    except (OSError, ValueError, IndexError):
      return False
  return False


def own_shm_segments():
  """The ``lddl_<pid>_<nonce>`` segments whose owner is this process or a
  live child of it. The other xdist workers' loaders make and unlink
  theirs all the time, so the whole machine's segments say nothing about
  this test. (An owner that was killed has its segments unlinked by its
  own resource tracker; its name cannot be traced to a parent any more.)"""
  from lddl_tpu.loader.shm import SEGMENT_PREFIX, live_segments
  me = os.getpid()
  owners = ((n, n[len(SEGMENT_PREFIX):].split('_')[0])
            for n in live_segments())
  return [n for n, pid in owners
          if pid.isdigit() and _descends_from(int(pid), me)]


@pytest.fixture(autouse=True)
def _no_leaked_shm_segments():
  """Fail any test that leaves an ``lddl_`` shared-memory segment behind:
  the loader's shm batch transport must unlink its slot rings on clean
  shutdown, consumer abandonment, and worker SIGKILL alike."""
  before = set(own_shm_segments())
  yield
  leaked = sorted(set(own_shm_segments()) - before)
  assert not leaked, f'leaked shared-memory segments: {leaked}'


@pytest.fixture(autouse=True)
def _reset_telemetry_registries():
  """Restore the process-global telemetry and trace registries around
  every test: a test calling ``telemetry.enable()`` (or flipping
  ``LDDL_TELEMETRY``/``LDDL_TRACE`` and re-resolving) without disabling
  must not leak an enabled registry into later tests."""
  import lddl_tpu.telemetry.ledger as _tl
  import lddl_tpu.telemetry.metrics as _tm
  import lddl_tpu.telemetry.profiling as _tp
  import lddl_tpu.telemetry.roofline as _tr
  import lddl_tpu.telemetry.sentinel as _tsn
  import lddl_tpu.telemetry.server as _ts
  import lddl_tpu.telemetry.trace as _tt
  import lddl_tpu.training.flight as _tf
  old = (_tm._active, _tt._active, _tl._active)
  old_sentinel = (_tsn._active, _tf._active)
  yield
  # A test that enabled the determinism ledger must not leak its open
  # append fd (or its cached resolution) into later tests.
  if _tl._active is not None and _tl._active.enabled and \
      _tl._active is not old[2]:
    _tl._active.close()
  _tm._active, _tt._active, _tl._active = old
  # A test that started an LDDL_MONITOR server must not leak its thread
  # (or its cached resolution) into later tests.
  if _ts._active is not None and _ts._active.enabled:
    _ts._active.stop()
  _ts._active = None
  # A test that enabled the sentinel/flight recorder must not leak the
  # armed instances (or their cached gate resolution) into later tests.
  _tsn._active, _tf._active = old_sentinel
  # Device-side caches: tests flip LDDL_PEAK_* env overrides and arm the
  # step profiler; both must re-resolve per test.
  _tr._reset_for_tests()
  _tp._reset_for_tests()


WORDS = [
    'alpha', 'bravo', 'charlie', 'delta', 'echo', 'foxtrot', 'golf',
    'hotel', 'india', 'juliet', 'kilo', 'lima', 'mike', 'november',
]


def make_nsp_sample(r, bin_id, bin_size, with_mask=False, serializer=None):
  """One NSP-pair row whose num_tokens lands inside bin_id's range.

  ``serializer`` controls the masked_lm_positions wire format (defaults
  to this repo's serialize_np_array; interop tests inject the
  reference's np.save-based serializer, which is byte-compatible)."""
  import numpy as np
  lo = bin_id * bin_size + 1
  hi = (bin_id + 1) * bin_size
  nt = r.randrange(max(lo, 8), hi + 1)
  na = r.randrange(2, nt - 3 - 2)
  nb = nt - 3 - na
  a = [r.choice(WORDS) for _ in range(na)]
  b = [r.choice(WORDS) for _ in range(nb)]
  row = {
      'A': ' '.join(a),
      'B': ' '.join(b),
      'is_random_next': bool(r.getrandbits(1)),
      'num_tokens': nt,
  }
  if with_mask:
    # Mask 2 content positions of the assembled [CLS] A [SEP] B [SEP] seq.
    cand = list(range(1, 1 + na)) + list(range(2 + na, 2 + na + nb))
    picked = sorted(r.sample(cand, 2))
    seq = ['[CLS]'] + a + ['[SEP]'] + b + ['[SEP]']
    if serializer is None:
      from lddl_tpu.core.utils import serialize_np_array
      serializer = serialize_np_array
    row['masked_lm_positions'] = serializer(
        np.asarray(picked, dtype=np.uint16))
    row['masked_lm_labels'] = ' '.join(seq[p] for p in picked)
  return row


@pytest.fixture(scope='session')
def tiny_vocab(tmp_path_factory):
  """A minimal WordPiece vocab covering the tmp_corpus words."""
  path = tmp_path_factory.mktemp('vocab') / 'vocab.txt'
  tokens = ['[PAD]', '[UNK]', '[CLS]', '[SEP]', '[MASK]', '.', ',']
  tokens += WORDS
  tokens += ['##' + w[1:] for w in WORDS]
  path.write_text('\n'.join(tokens) + '\n')
  return str(path)


@pytest.fixture()
def tmp_corpus(tmp_path):
  """A tiny one-document-per-line corpus in the framework's source format:

  first whitespace-separated token of each line is the document id.
  """
  src = tmp_path / 'source'
  src.mkdir()
  docs = []
  rng_words = WORDS
  import random
  r = random.Random(1234)
  for d in range(24):
    sents = []
    for _ in range(r.randrange(3, 9)):
      n = r.randrange(4, 12)
      sents.append(
          (' '.join(r.choice(rng_words) for _ in range(n)) + '.').capitalize())
    docs.append(f'doc-{d} ' + ' '.join(sents))
  for shard in range(4):
    with open(src / f'{shard}.txt', 'w') as f:
      for line in docs[shard::4]:
        f.write(line + '\n')
  return str(src)
