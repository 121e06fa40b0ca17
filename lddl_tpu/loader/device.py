"""Host-batch -> device pipeline: global array formation + prefetch.

This replaces the reference's pinned-memory DataLoader worker handoff
(``lddl/torch/bert.py:382-386``, persistent workers + pin_memory) with the
TPU-idiomatic equivalents:

  - :func:`make_global_batch` turns each process's local numpy batch into a
    global ``jax.Array`` laid out over a ``Mesh``'s data axis via
    ``jax.make_array_from_process_local_data`` — on a multi-host pod every
    process contributes its dp shard and XLA addresses the union; on one
    host it degenerates to a sharded ``device_put``. Model-parallel
    (tensor/pipeline) axes receive *replicated* data by construction,
    which is exactly the reference torch_mp guarantee that all TP/PP ranks
    of a dp group see identical batches (``torch_mp/bert.py:217-223``).
  - :func:`prefetch_to_device` overlaps host collate/IO with device
    compute by running the loader iterator in a background thread and
    keeping ``size`` batches in flight.
"""

import collections
import os
import queue
import threading

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding

from ..telemetry import get_telemetry
from ..telemetry.ledger import fingerprint_batch, get_ledger
from ..telemetry.trace import get_tracer

_DEFAULT_MESH = None


def _default_batch_mesh():
  """A one-axis ``data`` mesh over this process's devices.

  ``mesh=None`` callers of :func:`prefetch_to_device` still get the
  canonical batch-dim ``NamedSharding`` placement (the classic
  ``Mesh(devices, ('batch',))`` + ``P('batch')`` pattern) instead of a
  whole-batch ``device_put`` onto device 0 — on a multi-device host the
  batch dim is spread over the chips, on one device it degenerates to
  the old placement.
  """
  global _DEFAULT_MESH
  if _DEFAULT_MESH is None:
    _DEFAULT_MESH = Mesh(np.asarray(jax.local_devices()), ('data',))
  return _DEFAULT_MESH


def make_global_batch(batch, mesh, data_axis=None, seq_axis=None):
  """Shard a dict of per-process numpy arrays with the canonical batch
  layout.

  Layout comes from :func:`lddl_tpu.parallel.mesh.canonical_batch_spec`
  (``P(('data','fsdp'), 'seq')`` restricted to the axes the mesh actually
  has and to divisible dims) — so an fsdp>1 or seq>1 mesh gets the layout
  ``make_train_step`` documents instead of silent replication over those
  axes, while a plain ``Mesh(devices, ('data',))`` still works unchanged.
  Pass ``data_axis`` (str or tuple) / ``seq_axis`` explicitly to override.
  """
  from ..parallel.mesh import canonical_batch_spec
  out = {}
  for k, v in batch.items():
    spec = canonical_batch_spec(mesh, v.shape, data_axis=data_axis,
                                seq_axis=seq_axis)
    out[k] = jax.make_array_from_process_local_data(
        NamedSharding(mesh, spec), v)
  return out


def prefetch_to_device(iterator, mesh=None, data_axis=None, seq_axis=None,
                       size=2, donate=True):
  """Yield device-resident batches, keeping up to ``size`` in flight.

  ``iterator`` yields numpy batch dicts (or micro-batch lists, which are
  transferred element-wise). ``data_axis``/``seq_axis`` forward to
  :func:`make_global_batch`. With ``mesh=None`` batch dicts are placed
  with the same canonical batch-dim ``NamedSharding`` over a default
  one-axis mesh of the local devices (:func:`_default_batch_mesh`), so
  every path produces mesh-addressable global arrays; non-dict items
  (and batch dims the local device count does not divide) fall back to a
  plain ``device_put``.

  Double buffering: the producer thread transfers batch ``k+1`` while
  the caller's step consumes batch ``k`` (the ``train.h2d`` trace spans
  it emits overlap the main thread's compute spans). This consumption
  pattern satisfies the loader's ``zero_copy=True`` contract
  (:mod:`.workers`): each batch is transferred to device *before* the
  next one is pulled from ``iterator``, so a shared-memory view is
  always consumed while its slot is still held.

  Donation (``donate=True``): pulling batch ``k+1`` deletes batch
  ``k``'s device buffers, so steady-state HBM holds exactly the
  in-flight transfer plus the batch being consumed — the same
  valid-until-the-next-pull lifetime the zero-copy slot views have on
  the host side. Keep a batch alive across pulls (or pass
  ``donate=False``) only if you re-read it after stepping. The train
  loop does *not* wait for step ``k`` before it pulls batch ``k+1``
  (``TrainLoop.run`` keeps one step in flight), so the deletion routinely
  lands while the step that reads the batch still runs, or is still
  queued behind the one before it: ``Array.delete()`` only drops the
  array's reference, and the runtime frees the memory once every
  execution already enqueued on it has finished (its usage holds). The
  loop rests on that; whatever an observer wants of a batch it takes
  before the next pull.
  """

  def _put(item):
    if isinstance(item, (list, tuple)):
      return [_put(x) for x in item]
    if mesh is not None:
      return make_global_batch(item, mesh, data_axis=data_axis,
                               seq_axis=seq_axis)
    if isinstance(item, dict):
      default = _default_batch_mesh()
      n = default.devices.size
      if all(getattr(v, 'ndim', 0) and v.shape[0] % n == 0
             for v in item.values()):
        return make_global_batch(item, default, data_axis=data_axis,
                                 seq_axis=seq_axis)
    return jax.device_put(item)

  q = queue.Queue(maxsize=size)
  _SENTINEL = object()
  err = []
  stop = threading.Event()

  def _blocking_put(item):
    # Bounded put that gives up when the consumer abandoned the generator,
    # so the producer thread (and the device batches it holds) never leak.
    while not stop.is_set():
      try:
        q.put(item, timeout=0.1)
        return True
      except queue.Full:
        continue
    return False

  # Ring-buffer spans under LDDL_TRACE, spans of the profiler's own trace
  # while a capture runs (telemetry/trace.py), else the shared no-op.
  phase = get_tracer().phase
  tele = get_telemetry()
  # Histogram twin of the train.h2d trace span: the live overlap meter
  # needs h2d totals in the metrics registry (1 - data_wait/h2d), and
  # spans only land in the trace ring. Handle fetched once per prefetch.
  h2d_hist = tele.histogram('train.h2d_seconds')
  # Live-array accounting: bytes/batches this prefetcher currently holds
  # on device — the measured form of the donation contract's
  # "steady-state HBM = in-flight transfer + batch being consumed"
  # claim. Producer thread adds at placement, consumer subtracts at
  # donation delete, so a watcher scraping the gauge sees the claim hold
  # (or not) in real time. Zero-cost when telemetry is off.
  live_bytes_g = tele.gauge('loader.device_live_bytes')
  live_batches_g = tele.gauge('loader.device_live_batches')
  live_sizes = {}  # id(placed batch) -> device bytes
  live_lock = threading.Lock()

  def _device_nbytes(item):
    if isinstance(item, (list, tuple)):
      return sum(_device_nbytes(x) for x in item)
    if isinstance(item, dict):
      return sum(_device_nbytes(v) for v in item.values())
    # Addressable shards = what actually sits in this process's HBM (a
    # multi-host global array's .nbytes would count remote shards too).
    shards = getattr(item, 'addressable_shards', None)
    if shards:
      return sum(int(s.data.nbytes) for s in shards)
    return int(getattr(item, 'nbytes', 0) or 0)

  def _track(placed, sign):
    with live_lock:
      if sign > 0:
        live_sizes[id(placed)] = _device_nbytes(placed)
      else:
        live_sizes.pop(id(placed), None)
      live_bytes_g.set(sum(live_sizes.values()))
      live_batches_g.set(len(live_sizes))

  ledger = get_ledger()
  feed_index = 0

  def _producer():
    nonlocal feed_index
    try:
      host_batches = iter(iterator)
      while True:
        # The pull of the host batch, on the producer's lane: what the
        # loader costs when the feed is not hidden behind the step.
        with phase('loader.next'):
          item = next(host_batches, _SENTINEL)
        if item is _SENTINEL:
          break
        if ledger.enabled:
          # The device boundary: the last stop where the batch is still
          # host bytes. Hashed on the producer thread, so the cost
          # overlaps the main thread's compute like the transfer does.
          ledger.record('device', fingerprint_batch(item), index=feed_index)
        feed_index += 1
        # The host-to-device transfer phase, on the producer thread's
        # own trace lane (overlaps the main thread's compute span).
        with phase('train.h2d'), h2d_hist.time():
          placed = _put(item)
        if tele.enabled:
          _track(placed, +1)
        if not _blocking_put(placed):
          return
    except BaseException as e:  # propagate into the consumer
      err.append(e)
    finally:
      _blocking_put(_SENTINEL)

  t = threading.Thread(target=_producer, daemon=True)
  t.start()
  try:
    while True:
      item = q.get()
      if item is _SENTINEL:
        if err:
          raise err[0]
        return
      yield item
      if donate:
        # The consumer just asked for the next batch: the previous one's
        # device buffers are dead by contract (see docstring). Deletion
        # defers to XLA usage holds, so the step that reads this batch,
        # which TrainLoop.run has launched and not waited for, finishes
        # before the memory is actually freed.
        _delete_device_batch(item)
        if tele.enabled:
          _track(item, -1)
  finally:
    stop.set()
    # Serialize with the producer: after close() returns, the source
    # iterator is guaranteed quiescent (it may be mid-pull right now, e.g.
    # finishing an epoch and mutating loader state). Bounded: on the
    # preemption path a wedged upstream (dead shm peer, hung mount) must
    # not eat the grace window the emergency checkpoint needs, so after
    # the timeout the daemon thread is abandoned with a loud warning —
    # only the epoch-rebuild path relies on quiescence, and it only runs
    # after a clean, prompt join.
    t.join(timeout=_close_join_timeout())
    if t.is_alive():
      import warnings
      warnings.warn(
          'prefetch producer still running '
          f'{_close_join_timeout():g}s after close(); abandoning the '
          'daemon thread (source iterator may not be quiescent)')
      tele.counter('loader.prefetch_join_timeouts').add(1)
    if tele.enabled and live_sizes:
      # The stream is closed and the producer joined: whatever we still
      # tracked is dead (yielded refs are dropped with the generator).
      live_sizes.clear()
      live_bytes_g.set(0)
      live_batches_g.set(0)


def _close_join_timeout():
  """Bound on waiting out the prefetch producer at close() (env
  ``LDDL_PREFETCH_JOIN_TIMEOUT`` seconds, default 10 — inside the ~30s
  spot-preemption grace window with room left for the checkpoint)."""
  try:
    return max(0.1,
               float(os.environ.get('LDDL_PREFETCH_JOIN_TIMEOUT', '10')))
  except ValueError:
    return 10.0


def _delete_device_batch(item):
  """Free a yielded batch's device buffers (donation); tolerates leaves a
  jitted step already donated."""
  if isinstance(item, (list, tuple)):
    for x in item:
      _delete_device_batch(x)
    return
  if isinstance(item, dict):
    for x in item.values():
      _delete_device_batch(x)
    return
  delete = getattr(item, 'delete', None)
  if delete is None:
    return
  is_deleted = getattr(item, 'is_deleted', None)
  if is_deleted is not None and is_deleted():
    return
  delete()


class SeqlenAwarePrefetcher:
  """Pull-style iterator with ``next_seqlen()`` lookahead for pipeline

  schedulers (reference ``torch_mp/dataloader.py:103-133``): buffers one
  decoded batch ahead so the upcoming static shape is known before the
  batch is consumed.
  """

  def __init__(self, loader_iter, seqlen_of_batch):
    self._it = iter(loader_iter)
    self._seqlen_of = seqlen_of_batch
    self._pending = collections.deque()

  def close(self):
    """Close the wrapped iterator and drop the lookahead buffer.

    Abandoning a :func:`prefetch_to_device` stream mid-epoch without this
    leaks its producer thread (and the device batches it holds): generator
    ``close()`` only runs when the *generator* is dropped, and this wrapper
    kept a reference to it.
    """
    self._pending.clear()
    close = getattr(self._it, 'close', None)
    if close is not None:
      close()

  def next_seqlen(self):
    if not self._pending:
      self._pending.append(next(self._it))
    return self._seqlen_of(self._pending[0])

  def __iter__(self):
    return self

  def __next__(self):
    if self._pending:
      return self._pending.popleft()
    return next(self._it)
