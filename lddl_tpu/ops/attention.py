"""The one place that knows which attention back-ends exist.

``attend`` is what :class:`lddl_tpu.models.bert.SelfAttention` calls on
its ``[batch, heads, seq, head_dim]`` projections; a term every back-end
must honour (the padding mask, the same-document restriction and
causality) is added here and nowhere else. The kernels themselves stay in
:mod:`lddl_tpu.ops.flash_attention` and :mod:`lddl_tpu.parallel.ring`,
imported only by the branch that runs them.
"""

import jax
import jax.numpy as jnp

ATTENTION_IMPLS = ('dense', 'flash', 'ring', 'ring_flash')
# The back-ends whose blocks run the Pallas kernels, with or without a mesh.
FLASH_IMPLS = ('flash', 'ring_flash')

# The ``checkpoint_name``s of the flash forward kernel's output and
# log-sum-exp (tagged in ``flash_attention._flash_fwd``). They live here so
# that a dense model names them without importing Pallas.
FLASH_RESIDUAL_NAMES = ('flash_out', 'flash_lse')

# Everything ``nn.remat``'s policy keeps of a layer (``models/bert.py``
# tags the rest): the outputs of the ``query``, ``key``, ``value``,
# ``intermediate`` and ``output`` projections, each as
# ``[batch, seq, width]``, and its context, which the flash kernels name
# themselves and the dense path below names as ``dense_context``. What is
# O(seq^2) or element-wise carries no name and is remade, and so is the
# ``out`` projection: its output feeds a layer norm, so keeping it costs a
# store, a load and a pass of the norm's own where remaking it costs one
# narrow gemm that carries the norm in its fusion (PERF.md, PR 35).
REMAT_KEPT_NAMES = FLASH_RESIDUAL_NAMES + (
    'query_out', 'key_out', 'value_out', 'dense_context',
    'intermediate_out', 'output_out')


def attend(q, k, v, attention_mask, segment_ids, *, impl, mesh, dtype,
           causal=False):
  """Context ``[batch, heads, seq, head_dim]`` of softmax attention.

  ``attention_mask`` bool ``[batch, seq]`` masks padding keys;
  ``segment_ids`` int32 ``[batch, seq]`` (doc index per token, -1 =
  padding) or None restricts attention to same-document pairs;
  ``causal`` (static) lets a query see no later key. ``k`` and ``v`` may
  hold fewer heads than ``q``, a divisor of its count: grouped query
  heads, each group reading one key/value head.

  ``impl`` is one of :data:`ATTENTION_IMPLS`. 'ring' and 'ring_flash'
  need a ``mesh`` (the sequence is sharded over its ``seq`` axis, each
  chip's block dense or flash); without one there is no ring to rotate
  and they run as 'dense' and 'flash'. 'flash' with a mesh runs the
  Pallas kernel under ``shard_map`` (batch over data/fsdp, heads over
  tensor). 'dense' leaves the partitioning to GSPMD and rounds its
  probabilities to ``dtype`` before the product with ``v``.
  """
  if impl not in ATTENTION_IMPLS:
    raise ValueError(f'unknown attention impl {impl!r}: expected one of '
                     f'{ATTENTION_IMPLS}')
  if impl in ('ring', 'ring_flash') and mesh is not None:
    if causal or k.shape[1] != q.shape[1]:
      raise NotImplementedError('the ring rotates whole key/value heads '
                                'in both directions only')
    from ..parallel.ring import make_ring_attention
    block_impl = 'flash' if impl in FLASH_IMPLS else 'dense'
    return make_ring_attention(mesh, block_impl=block_impl)(
        q, k, v, attention_mask, segment_ids)
  if impl in FLASH_IMPLS:
    from .flash_attention import flash_attention, make_flash_attention
    if mesh is not None:
      return make_flash_attention(mesh, causal=causal)(
          q, k, v, attention_mask, segment_ids)
    return flash_attention(q, k, v, attention_mask, segment_ids, segment_ids,
                           causal)
  group = q.shape[1] // k.shape[1]
  if group > 1:  # [b, kv_heads, group, s, d]: a group shares its k and v
    b, h, s, d = q.shape
    ctx = _dense(q.reshape(b, h // group, group, s, d), k, v, attention_mask,
                 segment_ids, dtype, causal,
                 ('bkgqd,bkld->bkgql', 'bkgql,bkld->bkgqd'))
    return ctx.reshape(b, h, s, d)
  return _dense(q, k, v, attention_mask, segment_ids, dtype, causal,
                ('bhqd,bhkd->bhqk', 'bhqk,bhkd->bhqd'))


def _dense(q, k, v, attention_mask, segment_ids, dtype, causal, subscripts):
  """XLA's path: q [batch, ..., seq, head_dim] against k and v [batch,
  heads, seq, head_dim] by the two einsums ``subscripts``."""
  scale = 1.0 / (q.shape[-1] ** 0.5)
  scores = jnp.einsum(
      subscripts[0], q, k, preferred_element_type=jnp.float32) * scale
  lead = (slice(None),) + (None,) * (q.ndim - 3)
  bias = jnp.where(attention_mask, 0.0, -1e9)[lead + (None, slice(None))]
  if segment_ids is not None:
    # Same block-diagonal semantics as the flash tile skip — this
    # additive form keeps flash-vs-dense parity testable on CPU.
    same_doc = (segment_ids[lead + (slice(None), None)] ==
                segment_ids[lead + (None, slice(None))])
    bias = bias + jnp.where(same_doc, 0.0, -1e9)
  if causal:
    s = q.shape[-2]
    bias = bias + jnp.where(jnp.tril(jnp.ones((s, s), bool)), 0.0, -1e9)
  probs = jax.nn.softmax(scores + bias.astype(jnp.float32), axis=-1)
  return jnp.einsum(subscripts[1], probs.astype(dtype), v)
