"""Re-execute recorded train steps and bisect loss spikes.

The ledger's ``step`` boundary fingerprints the full train state
(params + opt_state + rng) at every checkpoint boundary. Because the
jitted step folds its dropout key from the optimizer's own step counter
(:func:`~lddl_tpu.parallel.train.make_train_step`) and the loaders are
coordinate-addressable, *state at step S* is a pure function of
*(checkpoint at S0 < S, batches S0..S-1)* — so any recorded step can be
re-executed bit-for-bit on a fresh process: restore the newest
checkpoint at or below ``S - 1``, drive the jitted step through the
:class:`~lddl_tpu.training.pretrain.CompiledStepCache` over the
deterministic batch stream (or a hermetic bundle's batches, no corpus
needed), and diff :func:`~lddl_tpu.training.pretrain.state_fingerprint`
against the recorded line.

``bisect`` rides the same machinery: replay a step window, find the
largest per-step loss jump, and name the ``(epoch, index)`` batch
coordinate that fed it — optionally re-scoring that batch per sample
(:func:`~lddl_tpu.parallel.train.pretrain_loss` on singleton slices,
the packed-sequence per-doc loss normalization included) to attribute
the spike below batch granularity.
"""


def _wrap_step_cache(loop):
  from ..training.pretrain import CompiledStepCache
  if not isinstance(loop.step_fn, CompiledStepCache):
    loop.step_fn = CompiledStepCache(loop.step_fn)


def _global_batch_of(loop, batch):
  if loop.loader is not None:
    per_rank = loop.loader.batch_size
  else:
    arr = next(v for v in batch.values() if hasattr(v, 'shape'))
    per_rank = int(arr.shape[0])
  return per_rank * max(loop.dp_world, 1)


def replay_steps(loop, target_step, batches=None, prefetch=2):
  """Advance ``loop`` from its current (restored) step to ``target_step``.

  Mirrors the live loop's step execution exactly — same
  device-placement path (:func:`~lddl_tpu.loader.device.
  prefetch_to_device`), same step-cache wrapping, rng passed through
  unchanged (the step fn folds in the optimizer count itself) — so the
  resulting state is bit-identical to the recorded run's. ``batches``
  (host batches, e.g. from a bundle) overrides the loop's loader; they
  must cover ``target_step - loop.step`` steps. Returns
  ``[(step, loss), ...]`` keyed like the ledger (the loss of *reaching*
  step S).
  """
  from ..core import faults
  from ..loader.device import prefetch_to_device
  _wrap_step_cache(loop)
  if loop.step >= target_step:
    raise ValueError(
        f'loop is at step {loop.step}, at/past target {target_step}; '
        'restore an older checkpoint first')
  if batches is not None and len(batches) < target_step - loop.step:
    raise ValueError(
        f'{len(batches)} bundled batch(es) cannot cover steps '
        f'{loop.step + 1}..{target_step}')
  if batches is None and loop.loader is None:
    raise ValueError(
        'loop has no loader (built with path=None); step replay needs '
        'bundled batches')

  def _source():
    if batches is not None:
      for b in batches:
        yield b
    else:
      while True:  # epoch-iterable loader: chain epochs like run() does
        yield from iter(loop.loader)

  stream = prefetch_to_device(_source(), mesh=loop.mesh, size=prefetch)
  losses = []
  try:
    while loop.step < target_step:
      try:
        batch = next(stream)
      except StopIteration:
        raise ValueError(
            f'batch stream ended at step {loop.step} before target '
            f'{target_step}')
      faults.inject('replay.step', rank=loop.dp_rank, gi=loop.step)
      loop.params, loop.opt_state, metrics = loop.step_fn(
          loop.params, loop.opt_state, loop.rng, batch)
      loss = float(metrics['loss'])
      loop.step += 1
      loop.samples_seen += _global_batch_of(loop, batch)
      loop._last_loss = loss
      losses.append((loop.step, loss))
  finally:
    close = getattr(stream, 'close', None)
    if close is not None:
      close()
  return losses


def replay_step_coordinate(loop, ckpt_dir, target_step, ledger_path=None,
                           batches=None, prefetch=2, rank=None):
  """Rematerialize train state at ``step=target_step`` and (optionally)
  verify it against a ledger's recorded ``step`` fingerprint.

  Restores the newest checkpoint at or below ``target_step - 1`` from
  ``ckpt_dir``, replays forward, and fingerprints the resulting state.
  With ``ledger_path`` the result carries ``recorded``/``match`` — the
  acceptance check that a replayed step reproduces the recorded
  fingerprint bit-for-bit.
  """
  target_step = int(target_step)
  meta = type(loop).latest_meta(ckpt_dir, max_step=target_step - 1)
  if meta is None:
    raise FileNotFoundError(
        f'no checkpoint at or below step {target_step - 1} under '
        f'{ckpt_dir}')
  loop.restore(ckpt_dir, step=meta[0])
  losses = replay_steps(loop, target_step, batches=batches,
                        prefetch=prefetch)
  digest = loop.state_digest()
  from ..telemetry.ledger import ALGO
  out = {'step': target_step, 'restored_step': meta[0], 'digest': digest,
         'losses': losses, 'algo': ALGO}
  if ledger_path is not None:
    from ..telemetry.audit import load_run
    from .rematerialize import _check_algo, lookup_digest
    run = load_run(ledger_path, rank=rank)
    _check_algo(run)
    recorded, _ = lookup_digest(run, (('step', target_step),),
                                boundary='step')
    out['recorded'] = recorded
    out['match'] = digest == recorded
  return out


def bisect_window(loop, ckpt_dir, lo, hi, prefetch=2, per_sample=False):
  """Walk steps ``(lo, hi]`` and attribute the largest loss jump.

  Restores the newest checkpoint at or below ``lo``, replays through
  ``hi`` collecting per-step losses, and reports the step with the
  largest positive loss delta plus the ``(epoch, index)`` collate
  coordinate of the batch that fed it (step ``S`` consumes this rank's
  batch ordinal ``S - 1`` — one batch per rank per global step).
  ``per_sample=True`` additionally re-restores at the spike step's
  predecessor and scores the spike batch row by row with the
  pre-spike params, naming the sample index that contributed most.
  """
  lo, hi = int(lo), int(hi)
  if hi <= lo:
    raise ValueError(f'empty bisect window ({lo}, {hi}]')
  meta = type(loop).latest_meta(ckpt_dir, max_step=lo)
  if meta is None:
    raise FileNotFoundError(
        f'no checkpoint at or below step {lo} under {ckpt_dir}')
  loop.restore(ckpt_dir, step=meta[0])
  losses = replay_steps(loop, hi, prefetch=prefetch)
  by_step = dict(losses)
  deltas = [(by_step[s] - by_step[s - 1], s)
            for s in range(max(lo, meta[0] + 1) + 1, hi + 1)
            if s in by_step and s - 1 in by_step]
  if not deltas:
    raise ValueError(
        f'window ({lo}, {hi}] left no consecutive step pair to compare '
        f'(restored at {meta[0]})')
  delta, spike = max(deltas)
  out = {'window': [lo, hi], 'restored_step': meta[0],
         'losses': losses, 'spike_step': spike,
         'spike_loss': by_step[spike], 'delta': delta}
  if loop.loader is not None:
    epoch, index = loop.loader.coordinate_of_batch(spike - 1)
    out['batch_coordinate'] = {'epoch': epoch, 'index': index}
    if per_sample:
      out['per_sample'] = _per_sample_losses(loop, ckpt_dir, spike)
      out['spike_sample'] = max(
          range(len(out['per_sample'])), key=out['per_sample'].__getitem__)
  return out


def _per_sample_losses(loop, ckpt_dir, spike_step):
  """Loss of each row of the batch feeding ``spike_step``, scored with
  the params the spike step started from (leaves ``loop`` positioned at
  ``spike_step - 1``). Single-host only — the eager forward pass runs
  outside the jitted/partitioned step."""
  from ..parallel.train import pretrain_loss
  meta = type(loop).latest_meta(ckpt_dir, max_step=spike_step - 1)
  loop.restore(ckpt_dir, step=meta[0])
  if loop.step < spike_step - 1:
    replay_steps(loop, spike_step - 1)
  epoch, index = loop.loader.coordinate_of_batch(spike_step - 1)
  loop.loader.seek(epoch, index)
  batch = next(iter(loop.loader.iter_steps((0, 1))))[1]
  if not isinstance(batch, dict):
    raise ValueError('per-sample attribution supports dict batches only '
                     '(micro-batch loaders yield lists)')
  rows = batch['input_ids'].shape[0]
  out = []
  for i in range(rows):
    one = {k: (v[i:i + 1] if hasattr(v, 'shape') and v.shape
               and v.shape[0] == rows else v)
           for k, v in batch.items()}
    loss, _ = pretrain_loss(loop.model, loop.params, one)
    out.append(float(loss))
  return out
