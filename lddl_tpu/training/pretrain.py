"""Pretraining loop: mesh-sharded steps + checkpoint/resume.

One loop for every model family: BERT (MLM + NSP on pair or packed
shards) and LFM2-MoE (a causal decoder on packed shards,
:mod:`lddl_tpu.models.lfm2`); ``TrainLoop.build`` builds the one its
model configuration names, through the step's
:class:`~lddl_tpu.parallel.train.Objective`.

The reference delegates training to external consumers and supports their
checkpoints only through ``start_epoch``/``samples_seen`` loader replay
(``lddl/torch_mp/bert.py:426-456``). Here the trainer is part of the
framework and the two halves are tied together: a checkpoint stores the
sharded model/optimizer state *and* the global ``samples_seen`` counter,
so a restart resumes both the parameter trajectory and the data stream
position. Resume determinism matches the reference's contract exactly:
every restart from the same checkpoint continues identically (bin draws
replay, dynamic-mask Philox keys are (seed, epoch, rank, step)-keyed, the
epoch's sample set is preserved); the shuffle buffer restarts fresh after
the skip (reference ``torch_mp/datasets.py:87-98``), so within-bin sample
*order* may differ from the never-interrupted trajectory.

Checkpointing uses orbax with sharding-aware restore: each host writes
its shards, restore places leaves directly onto the mesh.

CLI: ``python -m lddl_tpu.cli pretrain_bert --path <balanced> ...``.
"""

import argparse
import dataclasses
import json
import logging
import math
import os
import time


class CompiledStepCache:
  """Per-bin compiled train-step cache.

  ``jax.jit`` already memoizes traces by abstract signature, but its
  misses are silent and its hits still pay signature dispatch. This
  wrapper makes the (seq-bucket, batch shape) -> executable mapping
  explicit: the first batch of a given shape signature AOT-lowers and
  compiles the jitted step (timed and counted as a miss / retrace), and
  every later batch of that signature invokes the stored executable
  directly — so a binned loader cycling through its seq buckets hits a
  warm cache after one pass over the bins, and the telemetry counters
  (``train.step_cache_hits``/``misses``, ``train.retrace_seconds``)
  prove bin switches after warmup cause zero retraces.

  Compile time is also where XLA's exact cost model is free: each new
  executable's ``cost_analysis()`` FLOPs/bytes are captured once per
  (bin, shape) entry and re-billed per step as the
  ``train.xla_flops`` / ``train.xla_bytes`` counters — the measured
  numerators the roofline verdict and MFU gauge run on, at zero
  steady-state cost (two counter adds per step).
  """

  def __init__(self, step_fn):
    from ..telemetry import get_telemetry
    self.inner = step_fn
    self._compiled = {}
    self._costs = {}   # key -> (process flops, process bytes) per step
    self.hits = 0
    self.misses = 0
    self.last_key = None  # the shape of the step called last
    self.retrace_seconds = 0.0
    # Process-total costs of the most recently executed entry (the MFU
    # numerator); None until a compiled entry reported a cost model.
    self.last_costs = None
    tele = get_telemetry()
    self._tele = tele
    self._hits_c = tele.counter('train.step_cache_hits')
    self._misses_c = tele.counter('train.step_cache_misses')
    self._retrace_h = tele.histogram('train.retrace_seconds')
    self._flops_c = tele.counter('train.xla_flops')
    self._bytes_c = tele.counter('train.xla_bytes')

  @staticmethod
  def key_of(batch):
    return tuple(
        sorted((k, tuple(v.shape), str(v.dtype)) for k, v in batch.items()))

  def __call__(self, params, opt_state, rng, batch):
    key = self.last_key = self.key_of(batch)
    fn = self._compiled.get(key)
    if fn is None:
      t0 = time.perf_counter()
      lower = getattr(self.inner, 'lower', None)
      if lower is not None:
        fn = lower(params, opt_state, rng, batch).compile()
        # cost_analysis() reports the per-device partitioned module;
        # scale to the process total once here so the per-step billing
        # below is two plain adds.
        from ..telemetry.roofline import compiled_step_costs
        costs = compiled_step_costs(fn)
        if costs is not None:
          import jax
          n = jax.local_device_count()
          self._costs[key] = (costs[0] * n, costs[1] * n)
      else:
        fn = self.inner  # plain-callable step fns still work, uncached
      dt = time.perf_counter() - t0
      self._compiled[key] = fn
      self.misses += 1
      self.retrace_seconds += dt
      self._misses_c.add(1)
      self._retrace_h.observe(dt)
    else:
      self.hits += 1
      self._hits_c.add(1)
    costs = self._costs.get(key)
    if costs is not None:
      self.last_costs = costs
      if self._tele.enabled:
        self._flops_c.add(costs[0])
        self._bytes_c.add(costs[1])
    return fn(params, opt_state, rng, batch)


def state_fingerprint(snap):
  """Content fingerprint of a train-state pytree (params + opt_state +
  rng key data), the exact digest the ledger's ``step`` boundary
  records. ``snap`` should be a donation-safe snapshot
  (:func:`~lddl_tpu.parallel.train.snapshot_for_checkpoint`); multi-host
  sharded leaves are reduced to their local addressable bytes, identical
  across runs of the same topology. Module-level so
  :mod:`lddl_tpu.replay` can diff a re-executed step against the
  recorded line without a live ledger."""
  import jax
  import numpy as np

  from ..telemetry.ledger import fingerprint_batch

  def _host(x):
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
      return np.asarray(x.addressable_data(0))
    return x
  return fingerprint_batch(jax.tree_util.tree_map(_host, snap))


@dataclasses.dataclass
class TrainLoop:
  """Owns model/optimizer state, the loader, and the step function."""

  model: object
  tx: object
  mesh: object
  loader: object
  params: object
  opt_state: object
  rng: object
  step_fn: object
  samples_seen: int = 0
  step: int = 0
  # (per_rank_batch, seq_len) -> analytic FLOPs of one train step; set by
  # build() so run() can report MFU without re-deriving the model config.
  flops_fn: object = None
  # The model's attention is causal (the host mirror of the tile skip
  # counts the tiles above the diagonal as skipped).
  causal: bool = False
  dp_rank: int = 0
  dp_world: int = 1
  # Why the last run() stopped early (preemption / membership event), or
  # None when it ran to max_steps. The supervisor's relaunch signal.
  stop_reason: object = None
  _last_saved: int = dataclasses.field(default=-1, repr=False)
  # Most recent step loss, carried onto the ledger's checkpoint-boundary
  # fingerprint as context (never part of the alignment key).
  _last_loss: object = dataclasses.field(default=None, repr=False)

  @classmethod
  def build(cls, path, tokenizer, *, model_cfg, mesh, learning_rate=1e-4,
            warmup_steps=100, total_steps=10000, weight_decay=0.01,
            batch_size_per_rank=64, bin_size=None, max_seq_length=512,
            masking='dynamic', seed=127, samples_seen=0, loader_kwargs=None,
            max_predictions=None, data_format='pairs',
            block_diagonal=False, dp_rank=None, dp_world=None):
    import jax
    import optax

    from ..loader import (get_bert_pretrain_data_loader,
                          get_packed_pretrain_data_loader)
    from ..models import BertForPretraining, lfm2
    from ..parallel import make_train_step
    from ..parallel.train import bert_objective, init_params, state_shardings

    causal = isinstance(model_cfg, lfm2.Lfm2Config)
    schedule = optax.warmup_cosine_decay_schedule(
        0.0, learning_rate, warmup_steps, max(total_steps, warmup_steps + 1))
    if causal:
      if data_format != 'packed':
        raise ValueError("a causal decoder trains on data_format='packed'")
      model, objective = lfm2.build_objective(model_cfg, mesh)
      tx = optax.adamw(schedule, weight_decay=weight_decay,
                       mask=lfm2.decay_mask)
    else:
      model = BertForPretraining(model_cfg, mesh=mesh)
      objective = bert_objective(model, max_predictions, model_cfg)
      tx = optax.adamw(schedule, weight_decay=weight_decay)
    # Overridable for elastic resume: a fleet reformed at a different
    # world size passes its new coordinates explicitly (and the file-
    # backend multi-rank tests run several dp ranks inside independent
    # single-process jax worlds).
    dp_rank = jax.process_index() if dp_rank is None else dp_rank
    dp_world = jax.process_count() if dp_world is None else dp_world
    if block_diagonal and data_format != 'packed':
      raise ValueError("block_diagonal requires data_format='packed' "
                       '(pair shards carry no doc_offsets)')
    if path is None:
      # Loader-free loop: replay feeds batches from a hermetic bundle
      # (lddl-replay step --bundle), so no corpus is needed on disk.
      loader = None
    elif data_format == 'packed':
      # Long-context document-packed shards (preprocess_packed_pretrain):
      # always dynamic masking, no NSP pairs; a decoder's next-token
      # batches where the model is causal.
      if masking != 'dynamic' and not causal:
        raise ValueError("data_format='packed' supports masking='dynamic' "
                         'only (no stored masks in packed shards)')
      loader = get_packed_pretrain_data_loader(
          path,
          dp_rank=dp_rank,
          dp_world_size=dp_world,
          batch_size_per_rank=batch_size_per_rank,
          tokenizer=tokenizer,
          max_seq_length=max_seq_length,
          bin_size=bin_size,
          base_seed=seed,
          samples_seen=samples_seen,
          block_diagonal=block_diagonal,
          causal=causal,
          **(loader_kwargs or {}))
    else:
      loader = get_bert_pretrain_data_loader(
          path,
          dp_rank=dp_rank,
          dp_world_size=dp_world,
          batch_size_per_rank=batch_size_per_rank,
          tokenizer=tokenizer,
          masking=masking,
          max_seq_length=max_seq_length,
          bin_size=bin_size,
          base_seed=seed,
          samples_seen=samples_seen,
          **(loader_kwargs or {}))
    if causal:
      params = lfm2.init_params(model_cfg, mesh, jax.random.key(seed))
    else:
      params = init_params(model, mesh, jax.random.key(seed),
                           seq_len=min(128, max_seq_length))
    # Every optimizer-state leaf gets an explicit mesh placement (the
    # same one the step keeps it in): a layout jit happened to pick
    # would be reproduced faithfully by a checkpoint restore and then
    # conflict with the mesh-sharded params inside the jitted step.
    opt_state = jax.jit(
        tx.init,
        out_shardings=state_shardings(
            mesh, params, jax.eval_shape(tx.init, params),
            objective.param_spec_fn)[1])(params)
    if max_predictions is not None:
      from ..parallel.train import check_max_predictions
      check_max_predictions(
          max_predictions, max_seq_length, masking,
          mlm_probability=(loader_kwargs or {}).get('mlm_probability', 0.15))
    step_fn = make_train_step(objective, tx, mesh)
    global_batch = batch_size_per_rank * dp_world
    return cls(model=model, tx=tx, mesh=mesh, loader=loader, params=params,
               opt_state=opt_state, rng=jax.random.key(seed + 1),
               step_fn=step_fn, samples_seen=samples_seen,
               step=samples_seen // global_batch,
               flops_fn=objective.flops_fn, causal=objective.causal,
               dp_rank=dp_rank, dp_world=dp_world)

  # ---- checkpointing ----

  def _manager(self, ckpt_dir, keep=3):
    import orbax.checkpoint as ocp
    return ocp.CheckpointManager(
        os.path.abspath(ckpt_dir),
        options=ocp.CheckpointManagerOptions(max_to_keep=keep,
                                             create=True))

  def save(self, ckpt_dir, keep=3, writer=None):
    """Write (params, opt_state, rng, counters) at the current step.

    With ``writer`` (an :class:`~lddl_tpu.training.elastic.
    AsyncCheckpointWriter`) the orbax write runs on the background
    thread over a donation-safe snapshot taken here, synchronously —
    the jitted step donates params/opt_state, so the *next* step call
    invalidates the live buffers and the copy cannot wait for the
    writer. Submit blocks only at the writer's bounded depth; a failed
    background write surfaces on the next :meth:`save`/``raise_pending``
    /``flush`` (first-error-wins).
    """
    import jax
    state = {'params': self.params, 'opt_state': self.opt_state,
             'rng': jax.random.key_data(self.rng)}
    meta = {'samples_seen': self.samples_seen, 'step': self.step}
    from ..telemetry.ledger import get_ledger
    ledger = get_ledger()
    if writer is not None:
      from ..parallel.train import snapshot_for_checkpoint
      from ..telemetry import get_telemetry
      snap = snapshot_for_checkpoint(state)
      if ledger.enabled:
        self._record_step_fingerprint(ledger, snap)
      writer.submit(self._write_ckpt, ckpt_dir, keep, self.step, snap, meta)
      get_telemetry().gauge('train.ckpt_backlog').set(writer.backlog)
    else:
      if ledger.enabled:
        from ..parallel.train import snapshot_for_checkpoint
        self._record_step_fingerprint(ledger,
                                      snapshot_for_checkpoint(state))
      self._write_ckpt(ckpt_dir, keep, self.step, state, meta)
    self._last_saved = self.step
    return self.step

  def _record_step_fingerprint(self, ledger, snap):
    """The ``step`` ledger boundary: a content fingerprint of the full
    train state (params + opt_state + rng, the donation-safe host
    snapshot the checkpoint writer serializes) at every checkpoint
    boundary, keyed by global step. Train state is rank-identical after
    the gradient all-reduce, so this is the boundary the cross-rank
    divergence verdict compares by default — and the one that catches a
    resumed/resharded run whose arithmetic drifted from the parent.
    Digest arithmetic lives in the module-level
    :func:`state_fingerprint` (shared with :mod:`lddl_tpu.replay`)."""
    digest = state_fingerprint(snap)
    coords = {'step': self.step, 'samples': self.samples_seen}
    if self._last_loss is not None:
      coords['loss'] = self._last_loss
    ledger.record('step', digest, **coords)

  def _write_ckpt(self, ckpt_dir, keep, step, state, meta):
    """The actual orbax write — runs inline (sync save) or on the
    async writer's thread, where a raised fault/IO error is retained
    first-error-wins instead of crashing the step loop."""
    import orbax.checkpoint as ocp
    from ..core import faults
    faults.inject('train.ckpt', rank=self.dp_rank)
    mngr = self._manager(ckpt_dir, keep)
    mngr.save(
        step,
        args=ocp.args.Composite(
            state=ocp.args.StandardSave(state),
            meta=ocp.args.JsonSave(meta)))
    mngr.wait_until_finished()
    mngr.close()

  @staticmethod
  def latest_meta(ckpt_dir, max_step=None):
    """(step, samples_seen) of the newest *readable* checkpoint, or None.

    ``max_step`` bounds the search to steps <= it — replay/bisect
    restores the newest ancestor of a target step this way.

    Robust by design — this is the first call of every restarted rank:
    directory reads retry transient IO errors with the comm layer's
    bounded backoff, and a half-finished newest step (a preemption
    landing mid-write) is skipped in favor of the next-older complete
    one rather than failing the resume.
    """
    import orbax.checkpoint as ocp

    from ..comm.backend import _retry_io
    if not os.path.isdir(ckpt_dir):
      return None
    mngr = _retry_io(
        lambda: ocp.CheckpointManager(os.path.abspath(ckpt_dir)),
        'open checkpoint dir')
    try:
      steps = sorted(_retry_io(mngr.all_steps, 'list checkpoint steps'),
                     reverse=True)
      if max_step is not None:
        steps = [s for s in steps if s <= max_step]
      for step in steps:
        try:
          meta = mngr.restore(step, args=ocp.args.Composite(
              meta=ocp.args.JsonRestore()))['meta']
          return meta['step'], meta['samples_seen']
        except Exception as e:
          # A half-written step dir (preemption mid-write): fall back to
          # the next-older step instead of failing the whole resume.
          logging.getLogger('lddl_tpu').warning(
              'checkpoint step %s in %s unreadable (%s: %s); trying an '
              'older step', step, ckpt_dir, type(e).__name__, e)
          continue
      return None
    finally:
      mngr.close()

  def restore(self, ckpt_dir, step=None):
    """Restore sharded state from a checkpoint in ``ckpt_dir``.

    ``step=None`` restores the newest step; an explicit ``step``
    restores that exact checkpoint (the time-travel entry point —
    ``lddl-replay`` restores ``S - 1`` to re-execute step ``S``). The
    device state lands on the loop's existing shardings, which may
    belong to a *different* mesh than the one the checkpoint was written
    on — ``build()`` lays the template tree out canonically on whatever
    mesh the resumed run has, and every restored leaf is re-placed
    through :func:`~lddl_tpu.parallel.mesh.reshard_pytree`, so
    world-size-changing resume (2 ranks die, restart on 1; or scale
    1 -> 8) is the same code path as same-size resume. The loader (when
    the loop has one) is re-seeked to the restored ``samples_seen``
    through the public positioning contract, so restoring an *older*
    step also rewinds the data stream.
    """
    import jax
    import orbax.checkpoint as ocp

    from ..comm.backend import _retry_io
    from ..parallel import reshard_pytree
    mngr = self._manager(ckpt_dir)
    if step is None:
      step = _retry_io(mngr.latest_step, 'find latest checkpoint')
    elif step not in _retry_io(mngr.all_steps, 'list checkpoint steps'):
      mngr.close()
      raise FileNotFoundError(
          f'no checkpoint for step {step} under {ckpt_dir}')
    if step is None:
      raise FileNotFoundError(f'no checkpoint under {ckpt_dir}')
    target = {'params': self.params, 'opt_state': self.opt_state,
              'rng': jax.random.key_data(self.rng)}
    restored = _retry_io(
        lambda: mngr.restore(
            step,
            args=ocp.args.Composite(
                state=ocp.args.StandardRestore(target),
                meta=ocp.args.JsonRestore())), 'restore checkpoint')
    mngr.close()

    # Re-place every leaf onto the template's sharding: orbax restores
    # unsharded scalars (e.g. the optimizer step count) onto a single
    # device, which would then conflict with the mesh-sharded params
    # inside the jitted step — and on a resized fleet the template's
    # mesh is the *new* topology the leaves must land on.
    self.params = reshard_pytree(restored['state']['params'], self.mesh,
                                 like=self.params)
    self.opt_state = reshard_pytree(restored['state']['opt_state'],
                                    self.mesh, like=self.opt_state)
    # Replicate the restored key over the mesh: orbax hands back an array
    # committed to one device, and a committed single-device key conflicts
    # with mesh-sharded params inside the jitted step (a fresh
    # jax.random.key is uncommitted, so the bug only bites after restore
    # on multi-device meshes).
    from jax.sharding import NamedSharding, PartitionSpec
    self.rng = jax.device_put(
        jax.random.wrap_key_data(restored['state']['rng']),
        NamedSharding(self.mesh, PartitionSpec()))
    self.step = restored['meta']['step']
    self.samples_seen = restored['meta']['samples_seen']
    self._last_saved = self.step  # this step already exists on disk
    from .elastic import reseek_loader
    reseek_loader(self.loader, self.samples_seen, self.dp_world)
    return self

  def state_digest(self):
    """:func:`state_fingerprint` of the loop's live train state — equal
    to the ledger's ``step`` record when the loop sits at that step."""
    import jax

    from ..parallel.train import snapshot_for_checkpoint
    return state_fingerprint(snapshot_for_checkpoint(
        {'params': self.params, 'opt_state': self.opt_state,
         'rng': jax.random.key_data(self.rng)}))

  # ---- the loop ----

  def run(self, max_steps, ckpt_dir=None, ckpt_every=0, log_every=50,
          prefetch=2, membership=None, async_ckpt=None):
    """Train until ``max_steps`` (global); returns per-step loss list.

    One step is always in flight. An iteration pulls batch k, launches
    step k (the call returns at once; the step's inputs are the previous
    step's outputs, still on the device) and only then reads step k-1's
    loss and runs step k-1's observers (non-finite check, sentinel,
    flight recorder, profiler hook, telemetry, log line, writer, guard,
    membership), so the device works on step k while the host looks at
    step k-1. The loop waits for the step in flight (a *drain*: its loss
    read, its observers) before a checkpoint, before the profiler starts
    or stops a capture, and before it returns: every launched step has
    its loss in the returned list, and ``step``, ``samples_seen`` and the
    state a checkpoint holds always describe the same step.

    Preemption-tolerant: a SIGTERM (or ``LDDL_PREEMPTION_FILE`` notice)
    is looked for before each launch, so it launches nothing more; the
    step in flight is drained and the loop stops behind one final
    synchronous checkpoint. What an observer finds (a non-finite loss, a
    membership event of a :class:`~lddl_tpu.training.elastic.
    RankMembership` passed as ``membership``, polled at its heartbeat
    cadence) **stops the loop at most one step after the step that caused
    it**: the step already in flight is drained and counted, then the
    loop stops behind the same checkpoint path, with :attr:`stop_reason`
    telling the supervisor why. ``async_ckpt`` overrides
    ``LDDL_ASYNC_CKPT``: in-loop checkpoints ride the background writer,
    overlapping orbax IO with compute.
    """
    import jax

    from ..core import faults
    from ..loader.device import prefetch_to_device
    from ..telemetry import get_telemetry
    from ..telemetry.profiling import get_step_profiler
    from ..telemetry.sentinel import get_sentinel
    from ..telemetry.server import maybe_start_monitor
    from ..telemetry.stalls import StepWatch, spans_during
    from ..telemetry.trace import get_tracer
    from .elastic import (AsyncCheckpointWriter, PreemptionGuard,
                          async_ckpt_enabled)
    from .flight import get_flight_recorder

    # Live metrics endpoint (LDDL_MONITOR): no-op singleton when unset.
    maybe_start_monitor(rank=max(jax.process_index(), 0))
    # GET /profile?steps=N arms this; unarmed, at_edge and on_step() are
    # two attribute reads each, so the hook costs nothing on unwatched
    # runs.
    profiler = get_step_profiler()
    # Streaming anomaly sentinels + black-box recorder (LDDL_SENTINEL):
    # both resolve to shared no-op singletons when the gate is off.
    sentinel = get_sentinel()
    flight = get_flight_recorder()
    # A non-finite loss stops the run *regardless* of the sentinel gate
    # — training on garbage is never the right default. LDDL_NONFINITE=
    # ignore restores the old behavior (e.g. for loss-scaling probes).
    nonfinite_stop = (os.environ.get('LDDL_NONFINITE', '')
                      .strip().lower() != 'ignore')
    global_batch = self.loader.batch_size * max(self.dp_world, 1)
    tele = get_telemetry()
    tracer = get_tracer()
    # One instrumentation site, two sinks (telemetry/trace.py): the ring
    # buffer under LDDL_TRACE, and the profiler's own trace while a
    # capture runs, where telemetry/capture.py lays these phases over the
    # device's idle gaps. Off, each call returns the shared no-op span.
    phase = tracer.phase
    data_wait_h = tele.histogram('train.data_wait_seconds')
    compute_h = tele.histogram('train.compute_seconds')
    step_h = tele.histogram('train.step_seconds')
    loss_read_h = tele.histogram('train.loss_read_seconds')
    epoch_turn_h = tele.histogram('train.epoch_turn_seconds')
    steps_c = tele.counter('train.steps')
    samples_c = tele.counter('train.samples')
    grad_norm_g = tele.gauge('train.grad_norm')
    samples_per_sec_g = tele.gauge('train.samples_per_sec')
    tiles_total_c = tele.counter('train.attn_tiles_total')
    tiles_skipped_c = tele.counter('train.attn_tiles_skipped')
    peak_total = _peak_flops_total() if tele.enabled else None
    if not isinstance(self.step_fn, CompiledStepCache):
      # Persisted on the loop (not run()-local) so repeated run() calls —
      # and every epoch within one — keep the warm per-bin executables.
      self.step_fn = CompiledStepCache(self.step_fn)
    self.stop_reason = None
    use_async = async_ckpt_enabled() if async_ckpt is None else async_ckpt
    writer = AsyncCheckpointWriter() if (ckpt_dir and use_async) else None
    guard = PreemptionGuard().install()
    # Membership poll cadence + the steps_per_sec window it publishes.
    poll_at = time.monotonic()
    rate_anchor = (self.step, time.monotonic())
    losses = []
    t_log = time.perf_counter()
    # The number of the next step to launch: self.step counts observed
    # steps and trails it by one while a step is in flight.
    next_step = self.step
    in_flight = None
    t_pull = None  # the first pull since the last step was found finished

    def open_stream():
      # The flight recorder tees the *host* iterator (device arrays
      # can't be packed); ordinal0 = the global step the next batch
      # feeds, so ring entries carry their ledger collate coordinate.
      return prefetch_to_device(
          flight.wrap_host_stream(iter(self.loader), self.loader,
                                  ordinal0=next_step),
          mesh=self.mesh, size=prefetch)

    def read_loss(launched, queued=None):
      """Blocks until the device has finished ``launched``: the device
      sync, with ``queued`` (the next step, None in a drain) already on
      the device. Where it returns the step is found finished: the stall
      watch judges the interval since the last such time."""
      t_read = time.perf_counter()
      with phase('train.loss_read', launched.step):
        loss = float(launched.metrics['loss'])
      t_found = time.perf_counter()
      loss_read_h.observe(t_found - t_read)
      launched.stall = watch.found(launched.step, launched.key,
                                   self.step_fn.misses, t_read, t_found,
                                   queued)
      return loss

    def observe(launched, loss, drained=False):
      """``launched``'s observers, given its loss; ``drained`` says that
      no later step is on the device."""
      nonlocal t_log, poll_at, rate_anchor
      step_no = launched.step
      metrics = launched.metrics
      with phase('train.after_step', step_no):
        # The loss read above already paid the device sync; this one
        # is a host copy of an already-materialized scalar.
        gn = metrics.get('grad_norm')
        grad_norm = float(gn) if gn is not None else None
        losses.append(loss)
        self._last_loss = loss
        self.step += 1
        self.samples_seen += global_batch
        if not math.isfinite(loss) and nonfinite_stop:
          # Stop behind the trailing emergency checkpoint (the
          # preemption stop path) instead of training on garbage; the
          # step in flight, if any, is drained first.
          # LDDL_NONFINITE=ignore opts out.
          self.stop_reason = 'nonfinite_loss'
        trigger = sentinel.observe_step(step_no, loss=loss,
                                        grad_norm=grad_norm,
                                        data_wait=launched.data_wait,
                                        stall=launched.stall)
        flight.record_step(step_no, loss=loss, grad_norm=grad_norm,
                           data_wait=launched.data_wait)
        if trigger is not None:
          extra = None
          if trigger['detector'] == 'step_stall':
            extra = {'spans': spans_during(tracer, launched.stall)}
          incident = flight.capture(trigger, extra)
          if incident:
            print(f'sentinel: {trigger["detector"]} fired at step '
                  f'{step_no} — incident captured to {incident}')
          else:
            print(f'sentinel: {trigger["detector"]} fired at step '
                  f'{step_no} ({trigger["reason"]})')
        finished_trace = profiler.on_step(in_flight=not drained)
        if finished_trace:
          print(f'profiler: wrote trace for step {self.step} window to '
                f'{finished_trace}')
          if profiler.last_summary is not None:
            from ..telemetry.capture import format_table
            print(format_table(profiler.last_summary))
        if tracer.enabled or tele.enabled:
          # The step's own interval, pull to pull (in a drain with no
          # pull behind it: pull to the loss in hand).
          step_seconds = max(launched.t_end - launched.t_wait, 1e-9)
          samples_per_sec = self.loader.batch_size / step_seconds
          tracer.counter('train.samples_per_sec', samples_per_sec)
        if tele.enabled:
          compute_h.observe(launched.t_end - launched.t_step)
          step_h.observe(launched.t_end - launched.t_wait)
          steps_c.add(1)
          samples_c.add(self.loader.batch_size)
          if grad_norm is not None:
            grad_norm_g.set(grad_norm)
          samples_per_sec_g.set(samples_per_sec)
          if launched.flops:
            tele.gauge('train.mfu').set(
                launched.flops / (step_seconds * peak_total))
          if launched.tiles is not None:
            tiles_total_c.add(launched.tiles[0])
            tiles_skipped_c.add(launched.tiles[1])
          if 'expert_load' in metrics:
            # Materialized with the loss read above: a host copy, no sync.
            from ..ops.moe import observe_load
            observe_load(tele, step_no, metrics['expert_load'],
                         self.model.cfg)
        if log_every and self.step % log_every == 0:
          dt = time.perf_counter() - t_log
          t_log = time.perf_counter()
          print(f'step={self.step} loss={loss:.4f} '
                f'samples_seen={self.samples_seen} '
                f'({log_every * global_batch / max(dt, 1e-9):.1f} '
                'samples/s)')
        if writer is not None:
          # First-error-wins: a checkpoint that died in the background
          # fails the run at the next step, not at the final flush.
          writer.raise_pending()
        if guard.requested:
          self.stop_reason = 'preempted'
        elif membership is not None:
          now_m = time.monotonic()
          # lddl: noqa[LDA003] membership poll cadence: the clock only
          # rate-limits lease-store sweeps to one per heartbeat
          # interval; a late poll delays noticing an already-recorded
          # fleet event, it never changes any rank's verdict.
          if now_m >= poll_at:
            poll_at = now_m + membership.interval
            w_step, w_t = rate_anchor
            membership.publish_signals(
                {'steps_per_sec':
                 (self.step - w_step) / max(now_m - w_t, 1e-9)})
            rate_anchor = (self.step, now_m)
            # Conditional assign: a quiet poll (None) must not wipe a
            # stop reason an earlier check set (e.g. nonfinite_loss).
            reason = membership.poll()
            if reason is not None:
              self.stop_reason = reason

    def drain():
      """Wait for the step in flight and observe it: afterwards the
      device is idle and ``self.step`` describes ``self.params``, so this
      is where the in-loop checkpoint is written."""
      nonlocal in_flight, t_pull
      launched, in_flight = in_flight, None
      t_pull = None
      loss = read_loss(launched)
      if launched.t_end is None:
        launched.t_end = time.perf_counter()
      observe(launched, loss, drained=True)
      if (self.stop_reason is None and ckpt_dir and ckpt_every and
          self.step % ckpt_every == 0):
        self.save(ckpt_dir, writer=writer)
        flight.note_checkpoint(ckpt_dir, self.step)

    stream = None
    # Python's collector is timed (and traced) while the loop runs.
    watch = StepWatch(tele, tracer).install()
    try:
      if next_step < max_steps:
        stream = open_stream()
      steps_this_epoch = 0
      t_turn = None  # set while an epoch turn waits for its first batch
      while next_step < max_steps and self.stop_reason is None:
        if guard.requested:
          # A preemption launches nothing more.
          self.stop_reason = 'preempted'
          break
        if in_flight is not None and (
            profiler.at_edge or
            (ckpt_dir and ckpt_every and next_step % ckpt_every == 0)):
          # A checkpoint boundary and the two ends of a profiler capture
          # are syncs: the state to save is the drained step's, and a
          # capture holds whole step programs.
          drain()
          continue
        with phase('train.step', next_step):
          # Pull the batch explicitly so the stall waiting on the input
          # pipeline (data wait) is timed separately from the step itself:
          # the split is the report's loader-vs-compute bottleneck signal.
          # The pull also deletes the previous batch's device buffers,
          # while the step that reads them may still run (loader/device.py:
          # the deletion waits for it).
          t_wait = time.perf_counter()
          if t_pull is None:
            t_pull = t_wait
          if in_flight is not None and in_flight.t_end is None:
            in_flight.t_end = t_wait
          with phase('train.data_wait', next_step):
            batch = next(stream, None)
          if batch is None:
            # The epoch is exhausted: close its feed (joins the prefetch
            # thread) and open the next one's, with the epoch's last step
            # still in flight.
            t_turn = time.perf_counter()
            with phase('train.epoch_turn', next_step):
              stream.close()
              if steps_this_epoch == 0:
                raise ValueError(
                    'loader yielded zero batches for a full epoch (dataset '
                    'smaller than one global batch?); refusing to spin — '
                    'reduce --batch-size or provide more data')
              stream = open_stream()
            steps_this_epoch = 0
            t_log = time.perf_counter()
            continue
          t_step = time.perf_counter()
          data_wait = t_step - t_wait
          data_wait_h.observe(data_wait)
          if t_turn is not None:
            epoch_turn_h.observe(t_step - t_turn)
            t_turn = None
          # After the batch pull, before the step: a 'kill' here models a
          # rank dying mid-training, a 'term' models the preemption notice.
          faults.inject('train.step', rank=self.dp_rank)
          steps_this_epoch += 1
          # train.compute: the ring buffer's parent of this step's
          # dispatch + the previous step's loss read (the Perfetto merge's
          # compute lane).
          with tracer.span('train.compute',
                           {'step': next_step} if tracer.enabled else None):
            with phase('train.dispatch', next_step):
              self.params, self.opt_state, metrics = self.step_fn(
                  self.params, self.opt_state, self.rng, batch)
            launched = _Launched(next_step, metrics, t_wait, t_step,
                                 data_wait, t_pull, self.step_fn.last_key)
            t_pull = None
            if tele.enabled:
              # What the observers report of this step's batch is taken
              # now: the next pull deletes the batch.
              launched.note_batch(batch, self.step_fn, self.flops_fn,
                                  peak_total, self.causal)
            next_step += 1
            previous, in_flight = in_flight, launched
            if previous is not None:
              loss = read_loss(previous, launched)
          if previous is not None:
            observe(previous, loss)
      if in_flight is not None:
        drain()
      if stream is not None:
        stream.close()
      # A capture armed near the end of the run may still be tracing; jax
      # allows one trace per process, so close it before returning.
      profiler.close()
      if writer is not None:
        # Bounded by the already-submitted saves; raises the first
        # retained background failure.
        writer.flush()
      # Skip when the in-loop ckpt_every save (or the restore we started
      # from) already covers this step: orbax refuses duplicate steps.
      # After a preemption or membership stop this synchronous trailing
      # save IS the emergency checkpoint — complete before the return.
      if ckpt_dir and self._last_saved != self.step:
        self.save(ckpt_dir)
        flight.note_checkpoint(ckpt_dir, self.step)
    finally:
      watch.uninstall()
      guard.uninstall()
      if writer is not None:
        # Idempotent after flush(); raise_errors=False so cleanup
        # never masks an exception already propagating.
        writer.close(raise_errors=False)
    if self.stop_reason is not None:
      print(f'stopping early: {self.stop_reason} '
            f'(step={self.step} samples_seen={self.samples_seen})')
    return losses


@dataclasses.dataclass
class _Launched:
  """A step on the device, as its observers need it one step later."""

  step: int
  metrics: object    # the step's outputs: device scalars, not yet read
  t_wait: float      # perf_counter at its batch pull
  t_step: float      # perf_counter at its launch
  data_wait: float
  t_pull: float      # at the first pull since a step was found finished
  key: object        # its shape: the step cache's key
  stall: object = None   # the stall watch's facts, if its interval stalled
  t_end: object = None   # at the first pull after its launch: pull to pull
  flops: object = None   # the train.mfu numerator, or None
  tiles: object = None   # (total, skipped) attention tiles of a packed batch

  def note_batch(self, batch, step_fn, flops_fn, peak_total, causal=False):
    if peak_total:
      # Prefer XLA's own cost model (captured at compile time by the
      # step cache) over the analytic estimate: the measured numerator
      # reflects fusion, remat, and the real partitioned program, so MFU
      # stops drifting from what the chip ran.
      measured = getattr(step_fn, 'last_costs', None)
      if measured is not None:
        self.flops = measured[0]
      elif flops_fn is not None:
        self.flops = flops_fn(*batch['input_ids'].shape)
    if 'segment_ids' in batch:
      # Host-side mirror of the kernel's tile-skip rule: the goodput
      # signal for how much attention work block-diagonal packing
      # actually removed this step.
      import numpy as np

      from ..ops.flash_attention import count_skippable_tiles
      self.tiles = count_skippable_tiles(np.asarray(batch['segment_ids']),
                                         causal=causal)


def _peak_flops_total():
  """Per-process peak FLOP/s for the MFU denominator: per-device peak x
  local device count. ``LDDL_PEAK_TFLOPS`` (per device, in TFLOP/s)
  overrides the chip table. On the CPU backend without the override it
  returns None and MFU is omitted; an accelerator the table does not
  know raises (:func:`~lddl_tpu.models.flops.peak_flops_per_device`)."""
  import jax

  from ..models.flops import peak_flops_per_device
  env = os.environ.get('LDDL_PEAK_TFLOPS')
  per_device = float(env) * 1e12 if env else peak_flops_per_device()
  if not per_device:
    return None
  return per_device * jax.local_device_count()


def export_telemetry(comm):
  """Per-rank JSONL + rank-0 merged stall report, when telemetry is on.

  Every rank writes ``telemetry.rank<R>.jsonl`` under
  ``LDDL_TELEMETRY_DIR`` (skipped when unset), then the snapshots are
  merged over the run's own comm backend and rank 0 prints the
  cross-rank report. When ``LDDL_TRACE`` is on, the rank's event buffer
  is exported to ``trace.rank<R>.jsonl`` alongside (merge offline with
  ``telemetry-trace``). No-op (and free) when both are off.
  """
  from ..telemetry import get_telemetry, rank_file_name
  from ..telemetry.trace import get_tracer, trace_file_name
  tele = get_telemetry()
  tracer = get_tracer()
  out_dir = os.environ.get('LDDL_TELEMETRY_DIR')
  if tracer.enabled and out_dir:
    os.makedirs(out_dir, exist_ok=True)
    tracer.set_identity(rank=comm.rank)
    tracer.write_jsonl(trace_file_name(out_dir, comm.rank), rank=comm.rank)
  if not tele.enabled:
    return None
  if out_dir:
    os.makedirs(out_dir, exist_ok=True)
    tele.write_jsonl(rank_file_name(out_dir, comm.rank), rank=comm.rank)
  from ..telemetry.report import aggregate_over_comm, render_report
  merged = aggregate_over_comm(comm)
  if comm.rank == 0:
    print(render_report(merged))
  return merged


MODEL_SIZES = {
    'tiny': dict(hidden_size=128, num_layers=2, num_heads=2,
                 intermediate_size=512),
    'base': dict(hidden_size=768, num_layers=12, num_heads=12,
                 intermediate_size=3072),
    'large': dict(hidden_size=1024, num_layers=24, num_heads=16,
                  intermediate_size=4096),
}


def model_config(name, vocab_size, max_seq_length, attention, remat):
  """The model configuration ``--model name`` builds, over a vocabulary of
  ``vocab_size`` rows: a BERT size of :data:`MODEL_SIZES`, the LFM2 preset
  of :data:`lddl_tpu.models.lfm2.PRESETS`, or an LFM2-MoE configuration
  file in the source's keys (``*.json``,
  :func:`lddl_tpu.models.lfm2.config_from_hf`)."""
  from ..models import BertConfig, lfm2
  if name in lfm2.PRESETS:
    return lfm2.Lfm2Config(vocab_size=vocab_size, attention_impl=attention,
                           remat=remat, **lfm2.PRESETS[name])
  if name.endswith('.json'):
    with open(name) as f:
      return lfm2.config_from_hf(json.load(f), vocab_size=vocab_size,
                                 attention_impl=attention, remat=remat)
  if name not in MODEL_SIZES:
    raise ValueError(f'--model {name!r}: a BERT size of '
                     f'{sorted(MODEL_SIZES)}, an LFM2 preset of '
                     f'{sorted(lfm2.PRESETS)} or an LFM2 *.json file')
  return BertConfig(
      vocab_size=vocab_size,
      max_position_embeddings=max(max_seq_length, 512),
      attention_impl=attention,
      remat=remat,
      **MODEL_SIZES[name])


def attach_args(parser):
  from ..models.lfm2 import PRESETS
  from ..ops.attention import ATTENTION_IMPLS
  parser.add_argument('--path', required=True, help='balanced shard dir')
  parser.add_argument('--vocab-file', default=None)
  parser.add_argument('--tokenizer', default=None)
  parser.add_argument('--model', default='base',
                      help=f'a BERT size ({", ".join(sorted(MODEL_SIZES))}), '
                      f'or an LFM2-MoE decoder: a preset '
                      f'({", ".join(sorted(PRESETS))}) or a configuration '
                      'file in its source\'s keys (*.json); a decoder '
                      'trains on a causal next-token loss and needs '
                      '--data-format packed')
  parser.add_argument('--attention', choices=ATTENTION_IMPLS,
                      default='dense')
  parser.add_argument('--remat', action='store_true',
                      help='selective recomputation: the backward pass '
                      'remakes what is O(seq^2) or element-wise in a layer '
                      '(dense scores and softmax, GELU, norms, dropout '
                      'masks) and its attention-out projection, and is '
                      'handed the outputs of its other five projections and '
                      'its context in bfloat16: (5*hidden + intermediate)*2 '
                      'bytes a token and layer (18.4 KB for large, 13.8 KB '
                      'for base)')
  parser.add_argument('--prng', default='threefry',
                      choices=['threefry', 'rbg'],
                      help="jax PRNG impl; 'rbg' draws dropout bits with "
                      'the hardware generator (its step-time effect is '
                      'not measured on the current chip)')
  parser.add_argument('--dp', type=int, default=1)
  parser.add_argument('--fsdp', type=int, default=1)
  parser.add_argument('--tp', type=int, default=1)
  parser.add_argument('--sp', type=int, default=1)
  parser.add_argument('--batch-size', type=int, default=64,
                      help='per-process samples per step')
  parser.add_argument('--bin-size', type=int, default=None)
  parser.add_argument('--max-seq-length', type=int, default=512)
  parser.add_argument('--masking', choices=['dynamic', 'static'],
                      default='dynamic')
  parser.add_argument('--data-format', choices=['pairs', 'packed'],
                      default='pairs',
                      help="'pairs': NSP-pair shards (preprocess_bert_"
                      "pretrain); 'packed': long-context document-packed "
                      'id shards (preprocess_packed_pretrain, s=8k-32k)')
  parser.add_argument('--block-diagonal', action='store_true',
                      help="packed rows only: decode per-doc segment ids "
                      'from the stored doc_offsets, restrict attention to '
                      'within-document pairs (flash/ring skip cross-doc '
                      'tiles), and normalize the MLM loss per document '
                      '(arXiv:2107.02027)')
  parser.add_argument('--steps', type=int, default=1000)
  parser.add_argument('--learning-rate', type=float, default=1e-4)
  parser.add_argument('--warmup-steps', type=int, default=100)
  parser.add_argument('--weight-decay', type=float, default=0.01)
  parser.add_argument('--seed', type=int, default=127)
  parser.add_argument('--max-predictions', type=int, default=None,
                      help='masked-only MLM head: compute vocab logits '
                           'only at this many gathered MLM positions '
                           'per row (identical loss, ~6x less head '
                           'compute/HBM; size generously for dynamic '
                           'masking)')
  parser.add_argument('--checkpoint-dir', default=None)
  parser.add_argument('--checkpoint-every', type=int, default=500)
  parser.add_argument('--log-every', type=int, default=50)
  parser.add_argument('--resume', action='store_true',
                      help='resume from the newest checkpoint in '
                           '--checkpoint-dir (model state AND data '
                           'stream position)')
  parser.add_argument('--comm', choices=['null', 'file', 'jax'],
                      default='null')
  return parser


def main(args=None):
  if args is None or isinstance(args, list):
    args = attach_args(argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)).parse_args(
            args)
  import jax

  if getattr(args, 'prng', 'threefry') != 'threefry':
    jax.config.update('jax_default_prng_impl', args.prng)

  from ..comm import get_backend
  from ..core.compile_cache import use_compile_cache
  from ..parallel import make_mesh, mesh_summary
  from ..tokenization.wordpiece import load_bert_tokenizer

  comm = get_backend(args.comm)  # bootstraps jax.distributed under --comm jax
  use_compile_cache()
  from ..telemetry.trace import get_tracer
  tracer = get_tracer()
  if tracer.enabled:
    # Identity up front, so the periodic crash-tail flushes during the
    # run already land at this rank's canonical trace file.
    tracer.set_identity(rank=comm.rank)
  tokenizer = load_bert_tokenizer(
      vocab_file=args.vocab_file, hub_name=args.tokenizer, backend='hf')
  vocab = ((tokenizer.vocab_size + 63) // 64) * 64
  cfg = model_config(args.model, vocab, args.max_seq_length, args.attention,
                     args.remat)
  mesh = make_mesh(data=args.dp, fsdp=args.fsdp, tensor=args.tp,
                   seq=args.sp)
  print(f'backend={jax.default_backend()} '
        f'device_kind={jax.devices()[0].device_kind!r} '
        f'devices={jax.device_count()}; mesh: {mesh_summary(mesh)}; '
        f'model={args.model} attention={args.attention}')

  samples_seen = 0
  resume = False
  if args.resume and args.checkpoint_dir:
    meta = TrainLoop.latest_meta(args.checkpoint_dir)
    if meta is not None:
      _, samples_seen = meta
      resume = True
      print(f'resuming from samples_seen={samples_seen}')

  loop = TrainLoop.build(
      args.path, tokenizer, model_cfg=cfg, mesh=mesh,
      learning_rate=args.learning_rate, warmup_steps=args.warmup_steps,
      total_steps=args.steps, weight_decay=args.weight_decay,
      batch_size_per_rank=args.batch_size, bin_size=args.bin_size,
      max_seq_length=args.max_seq_length, masking=args.masking,
      seed=args.seed, samples_seen=samples_seen,
      max_predictions=args.max_predictions,
      data_format=args.data_format,
      block_diagonal=args.block_diagonal)
  if resume:
    loop.restore(args.checkpoint_dir)
  from .elastic import maybe_membership
  membership = maybe_membership(comm, step=loop.step)
  try:
    losses = loop.run(args.steps, ckpt_dir=args.checkpoint_dir,
                      ckpt_every=args.checkpoint_every,
                      log_every=args.log_every, membership=membership)
  finally:
    if membership is not None:
      membership.stop()
  export_telemetry(comm)
  if losses:
    print(json.dumps({'final_step': loop.step,
                      'final_loss': round(losses[-1], 4),
                      'samples_seen': loop.samples_seen}))
  return loop


if __name__ == '__main__':
  main()
