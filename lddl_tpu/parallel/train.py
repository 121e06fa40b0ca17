"""Sharded train step, for any model family.

The reference stops at the DataLoader boundary; its consumers (NVIDIA BERT
training recipes) own the step. Here the step is part of the framework so
the binned loader's static-shape contract can be demonstrated end-to-end:
one jitted program per bin shape, params laid out by a family's
``param_spec_fn`` over the (data, fsdp, tensor, seq) mesh, gradients
reduced by GSPMD over ICI.

What a model family hands the step is an :class:`Objective`: its loss, the
placement of its parameters and its FLOPs per step (the seam every family
goes through; :func:`bert_objective` is BERT's). BERT's loss
(:func:`pretrain_loss`) is masked-LM cross entropy (ignore label -100, mean
over masked positions) + next-sentence-prediction cross entropy — the
standard BERT pretraining objective over exactly the dict the loader
yields.
"""

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..loader.bert import IGNORE_INDEX
from ..models import spec_for_param
from .mesh import canonical_batch_spec


@dataclasses.dataclass(frozen=True)
class Objective:
  """What a model family hands the step and the loop.

  ``loss_fn(params, batch, rng) -> (loss, metrics)``: ``rng`` is the
  step's dropout key, or None for a deterministic step.
  ``param_spec_fn(path, shape) -> PartitionSpec`` places each parameter.
  ``flops_fn(batch, seq)``: analytic FLOPs of one step (the ``train.mfu``
  gauge's fallback numerator). ``after_update(params, metrics) ->
  params``, where given, moves state that no gradient moves, after the
  optimizer. ``causal``: the family's attention sees no later key (the
  loop's host mirror of the tile skip needs to know)."""
  loss_fn: Callable
  param_spec_fn: Callable = spec_for_param
  flops_fn: Optional[Callable] = None
  after_update: Optional[Callable] = None
  causal: bool = False


def bert_objective(model, max_predictions=None, cfg=None):
  """BERT's :class:`Objective`: :func:`pretrain_loss` with a dropout key,
  :func:`lddl_tpu.models.spec_for_param`, and the analytic FLOPs of
  ``cfg`` (where given)."""
  flops_fn = None
  if cfg is not None:
    from ..models.flops import bert_pretrain_flops_per_step
    flops_fn = functools.partial(bert_pretrain_flops_per_step, cfg,
                                 max_predictions=max_predictions)

  def loss_fn(params, batch, rng):
    return pretrain_loss(model, params, batch, dropout_rng=rng,
                         max_predictions=max_predictions)

  return Objective(loss_fn=loss_fn, flops_fn=flops_fn)


def param_shardings(mesh, abs_params, spec_fn=spec_for_param):
  """NamedSharding tree for a (possibly abstract) param tree."""
  flat = jax.tree_util.tree_flatten_with_path(abs_params)[0]
  tree = jax.tree_util.tree_structure(abs_params)
  shardings = [
      NamedSharding(mesh,
                    spec_fn([getattr(k, 'key', k) for k in path],
                            leaf.shape)) for path, leaf in flat
  ]
  return jax.tree_util.tree_unflatten(tree, shardings)


def state_shardings(mesh, params, opt_state, spec_fn=spec_for_param):
  """NamedSharding trees for ``(params, opt_state)``, from tree paths and
  shapes alone (abstract and traced trees work). Params follow
  ``spec_fn`` (BERT's :func:`lddl_tpu.models.spec_for_param` by
  default); every optimizer-state subtree that mirrors the params tree
  (Adam's ``mu``/``nu``) inherits the params' layout; the rest (step
  counters) is replicated."""
  p_sh = param_shardings(mesh, params, spec_fn)
  p_def = jax.tree_util.tree_structure(params)

  def mirrors(node):
    return jax.tree_util.tree_structure(node) == p_def

  rep = NamedSharding(mesh, P())
  o_sh = jax.tree_util.tree_map(
      lambda node: p_sh if mirrors(node) else rep, opt_state,
      is_leaf=mirrors)
  return p_sh, o_sh


def init_params(model, mesh, rng, seq_len=128, batch=None):
  """Initialize params directly into their mesh placement: the init
  computation is jitted with ``out_shardings`` so no single device ever
  holds the full parameter set. The dummy init batch defaults to two
  rows per (data, fsdp) shard: the flash/ring ``shard_map`` refuses a
  batch the mesh does not divide, at init as at every step."""
  if batch is None:
    batch = 2 * mesh.shape.get('data', 1) * mesh.shape.get('fsdp', 1)
  dummy = {
      'input_ids': jnp.zeros((batch, seq_len), jnp.int32),
      'token_type_ids': jnp.zeros((batch, seq_len), jnp.int32),
      'attention_mask': jnp.ones((batch, seq_len), jnp.int32),
  }

  def init_fn():
    return model.init(rng, dummy['input_ids'], dummy['token_type_ids'],
                      dummy['attention_mask'])['params']

  return init_sharded(init_fn, mesh)


def init_sharded(init_fn, mesh, spec_fn=spec_for_param):
  """``init_fn()``'s parameters, made by one jitted call straight into the
  placement ``spec_fn`` gives them."""
  shardings = param_shardings(mesh, jax.eval_shape(init_fn), spec_fn)
  return jax.jit(init_fn, out_shardings=shardings)()


def snapshot_for_checkpoint(tree):
  """Donation-safe copy of a state pytree for background checkpointing.

  :func:`make_train_step` donates params/opt_state, so the *next* step
  call invalidates the buffers a background checkpoint writer would
  still be serializing. The snapshot must therefore happen
  synchronously at submit time: fully-addressable leaves come back as
  host numpy arrays (the single-host case — orbax then serializes host
  memory and never touches the donated originals); multi-host global
  arrays get an on-device copy that preserves their sharding in fresh
  buffers, so donating the originals is harmless. Non-array leaves
  pass through.
  """

  def _copy(x):
    if not isinstance(x, jax.Array):
      return x
    if x.is_fully_addressable:
      return jax.device_get(x)
    return jnp.copy(x)

  return jax.tree_util.tree_map(_copy, tree)


def per_doc_mlm_loss(mlm_ce, masked, seg, num_docs_cap):
  """Packing-aware MLM normalization (arXiv:2107.02027 §3.2).

  The naive packed loss is a masked-token mean over the whole batch,
  which weights a document by its masked-token count — long documents
  dominate, and the objective drifts from what the same documents would
  contribute trained unpacked (each sequence normalized by its own mask
  count). Here each document contributes its own masked-mean CE and the
  batch loss is the mean over documents with >= 1 MLM target, so packed
  and unpacked training optimize the same per-sequence objective.

  ``seg``: doc index per column (aligned with ``mlm_ce``/``masked``;
  callers using the masked-only head gather it at ``mlm_positions``).
  ``num_docs_cap``: static upper bound on docs per row (the sequence
  length serves — doc ids are strictly below it).
  """
  b = masked.shape[0]
  ids = (jnp.clip(seg, 0, num_docs_cap - 1) +
         num_docs_cap * jnp.arange(b, dtype=seg.dtype)[:, None])
  w = masked.astype(jnp.float32).reshape(-1)
  ce_sum = jax.ops.segment_sum(mlm_ce.reshape(-1) * w, ids.reshape(-1),
                               num_segments=b * num_docs_cap)
  cnt = jax.ops.segment_sum(w, ids.reshape(-1),
                            num_segments=b * num_docs_cap)
  has = cnt > 0
  per_doc = jnp.where(has, ce_sum / jnp.maximum(cnt, 1.0), 0.0)
  return per_doc.sum() / jnp.maximum(has.sum(), 1)


def pretrain_loss(model, params, batch, dropout_rng=None,
                  max_predictions=None):
  """Scalar loss + metrics dict for one batch.

  ``max_predictions=P`` selects the masked-only MLM head: the first P
  masked positions per row are gathered and only their ``[b, P, V]``
  logits are computed — numerically the same MLM cross entropy (CE is
  only ever evaluated at masked positions), at a fraction of the head
  FLOPs/HBM. Choose P at least the masking budget: static masking caps
  predictions at round(s·ratio)(+cap) so any P >= that bound is exact;
  dynamic masking is Bernoulli per position, so rows in the far binomial
  tail (> P masked) would silently drop their overflow targets — size P
  with headroom there.

  A ``segment_ids`` batch key (packed loader, ``block_diagonal=True``)
  switches on both block-diagonal attention in the model and the
  :func:`per_doc_mlm_loss` normalization.
  """
  deterministic = dropout_rng is None
  rngs = None if deterministic else {'dropout': dropout_rng}
  labels = batch['labels']
  mlm_positions = None
  if max_predictions is not None:
    # The first P masked column indices per row, padded with arbitrary
    # unmasked columns whose gathered labels are IGNORE_INDEX (stable
    # argsort of the ~masked bitmap = masked columns first, in order).
    p = min(max_predictions, labels.shape[1])
    # 'loss' names what is no flax module for the capture summary
    # (telemetry/capture.py); a scope is metadata, not arithmetic.
    with jax.named_scope('loss'):
      mlm_positions = jnp.argsort(
          labels == IGNORE_INDEX, axis=1, stable=True,
      )[:, :p].astype(jnp.int32)
      labels = jnp.take_along_axis(labels, mlm_positions, axis=1)
  segment_ids = batch.get('segment_ids')
  mlm_logits, nsp_logits = model.apply(
      {'params': params},
      batch['input_ids'],
      batch['token_type_ids'],
      batch['attention_mask'],
      deterministic=deterministic,
      mlm_positions=mlm_positions,
      segment_ids=segment_ids,
      rngs=rngs)
  with jax.named_scope('loss'):
    masked = labels != IGNORE_INDEX
    safe_labels = jnp.where(masked, labels, 0)
    mlm_ce = optax.softmax_cross_entropy_with_integer_labels(
        mlm_logits, safe_labels)
    denom = jnp.maximum(masked.sum(), 1)
    if segment_ids is not None:
      seg = segment_ids
      if mlm_positions is not None:
        seg = jnp.take_along_axis(segment_ids, mlm_positions, axis=1)
      mlm_loss = per_doc_mlm_loss(mlm_ce, masked, seg,
                                  num_docs_cap=batch['input_ids'].shape[1])
    else:
      mlm_loss = jnp.where(masked, mlm_ce, 0.0).sum() / denom
    nsp_loss = optax.softmax_cross_entropy_with_integer_labels(
        nsp_logits, batch['next_sentence_labels']).mean()
    mlm_acc = jnp.where(
        masked, jnp.argmax(mlm_logits, -1) == labels, False).sum() / denom
    return mlm_loss + nsp_loss, {
        'mlm_loss': mlm_loss,
        'nsp_loss': nsp_loss,
        'mlm_acc': mlm_acc,
    }


def check_max_predictions(max_predictions, seq_len, masking,
                          mlm_probability=0.15):
  """Warn when a masked-only head budget under-covers the masking mode.

  Static masking caps per-row predictions at ``round(s·ratio)(+1)``;
  dynamic masking is per-position Bernoulli, so its count has a binomial
  tail — require ~4 standard deviations of headroom before calling the
  budget safe. An under-sized P silently drops the overflow targets from
  loss and gradients, which is exactly the quiet failure this warning
  exists to surface.
  """
  budget = round(seq_len * mlm_probability) + 1
  if masking == 'dynamic':
    sd = (seq_len * mlm_probability * (1 - mlm_probability)) ** 0.5
    budget = int(seq_len * mlm_probability + 4 * sd) + 1
  if max_predictions < min(budget, seq_len):
    import warnings
    warnings.warn(
        f'max_predictions={max_predictions} is below the {masking}-masking '
        f'budget ~{budget} for seq_len {seq_len}: rows with more masked '
        'positions silently drop their overflow MLM targets from the loss')


def make_train_step(objective, tx, mesh, max_predictions=None):
  """Returns ``step(params, opt_state, rng, batch) ->
  (params, opt_state, metrics)``, jitted with donated state.

  ``objective`` is the family's :class:`Objective`; a BERT model in its
  place stands for :func:`bert_objective` of it (``max_predictions``
  selects the masked-only MLM head, see :func:`pretrain_loss`).
  Batches arrive sharded ``P(('data','fsdp'), 'seq')`` (the loader's
  device pipeline does this); params carry their own shardings from
  :func:`init_sharded`, so jit needs no in_shardings — placement is taken
  from the arguments and GSPMD inserts every collective.
  """
  if not isinstance(objective, Objective):
    objective = bert_objective(objective, max_predictions)

  @functools.partial(jax.jit, donate_argnums=(0, 1))
  def step(params, opt_state, rng, batch):
    with jax.named_scope('dropout_key'):
      rng = jax.random.fold_in(
          rng, opt_state[0].count if hasattr(opt_state[0], 'count') else 0)

    def loss_fn(p):
      return objective.loss_fn(p, batch, rng)

    (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
    # Global gradient norm of the *raw* grads (pre-optimizer): one fused
    # reduction inside the compiled step, read on the host for free once
    # the loss scalar has already forced the device sync. This is the
    # sentinel's grad_spike signal and the train.grad_norm gauge.
    with jax.named_scope('grad_norm'):
      metrics['grad_norm'] = optax.global_norm(grads)
    with jax.named_scope('optimizer'):
      updates, opt_state = tx.update(grads, opt_state, params)
      params = optax.apply_updates(params, updates)
      if objective.after_update is not None:
        params = objective.after_update(params, metrics)
    # The state leaves the step laid out as it came in. Left to itself the
    # partitioner hands replicated-by-rule leaves (biases, norms) back
    # split over fsdp, which the next call of an AOT-compiled step rejects
    # (and a plain jit call silently recompiles for).
    params, opt_state = jax.lax.with_sharding_constraint(
        (params, opt_state),
        state_shardings(mesh, params, opt_state, objective.param_spec_fn))
    metrics['loss'] = loss
    return params, opt_state, metrics

  return step


def shard_batch(batch, mesh):
  """Place a host batch dict onto the mesh with the canonical data layout."""
  return {
      k: jax.device_put(
          v, NamedSharding(mesh, canonical_batch_spec(mesh, v.shape)))
      for k, v in batch.items()
  }
