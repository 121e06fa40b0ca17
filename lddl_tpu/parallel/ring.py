"""Ring attention: exact attention over a sequence-sharded mesh axis.

Long-context support the reference lacks entirely (SURVEY.md §2.2: no
sequence/context parallelism anywhere in LDDL). Each device holds a
``[batch, heads, seq/N, head_dim]`` shard of Q, K, V; K/V blocks (and the
key-side mask) rotate around the ``seq`` ring via ``lax.ppermute`` over
ICI neighbors while a streaming log-sum-exp accumulator keeps the softmax
exact — full K/V is never materialized on any chip, so max sequence length
scales linearly with the ring size at constant per-chip memory.

Block-diagonal packed rows compose with the ring: per-token kv segment
ids (doc index, -1 = padding) rotate alongside K/V, and a rotated shard
whose doc-id interval is disjoint from the local q shard's is skipped
*before* the local block kernel runs — the ppermute still fires (the
ring rotation is collective) but the chip spends no attention FLOPs on
a shard it provably can't attend to. Shards that partially overlap fall
through to the local flash kernel, which skips at (q-block, kv-block)
tile granularity (:mod:`lddl_tpu.ops.flash_attention`).

Numerics: scores and accumulators run in float32 regardless of input
dtype (bfloat16 Q/K/V is fine); output is cast back to the input dtype.

Usage: call :func:`ring_attention` *inside* ``shard_map`` (it uses the
collective axis name), or use :func:`make_ring_attention` to wrap it for a
mesh and call it from jitted GSPMD code.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def _block_attn(q, k, v, bias, scale):
  """One block's contribution: returns (scores_max, exp_scores @ v, denom)."""
  s = jnp.einsum('bhqd,bhkd->bhqk', q, k, preferred_element_type=jnp.float32)
  s = s * scale
  if bias is not None:
    s = s + bias
  m = jnp.max(s, axis=-1, keepdims=True)
  p = jnp.exp(s - m)
  o = jnp.einsum('bhqk,bhkd->bhqd', p, v.astype(jnp.float32))
  return m, o, jnp.sum(p, axis=-1, keepdims=True)


def _shard_interval(seg):
  """Per-batch-row (lo, hi) doc-id interval of a ``[b, s_shard]`` segment
  shard. Padding (-1) is excluded from ``lo`` and drags ``hi`` to -1, so
  an all-padding shard reports an empty interval (lo > hi) and tests
  disjoint against everything."""
  real = seg >= 0
  lo = jnp.min(jnp.where(real, seg, jnp.int32(2**30)), axis=1)
  hi = jnp.max(jnp.where(real, seg, jnp.int32(-1)), axis=1)
  return lo, hi


def ring_attention(q, k, v, kv_mask=None, axis_name='seq',
                   block_impl='dense', q_segment_ids=None,
                   kv_segment_ids=None):
  """Exact softmax attention with K/V sharded along ``axis_name``.

  Shapes (per-device shards): q,k,v ``[b, h, s_block, d]``; ``kv_mask``
  ``[b, s_block]`` with 1 = attend, 0 = padding (it rotates with K/V).
  Must run inside ``shard_map`` with ``axis_name`` bound.

  ``block_impl``: the per-chip block-attention kernel — 'dense' (einsum;
  materializes the per-shard score matrix) or 'flash'
  (:func:`lddl_tpu.ops.flash_attention.flash_attention_with_lse`; the
  flash (out, lse) pair enters the streaming-softmax merge as
  ``(m=lse, o=out, l=1)``, keeping per-chip attention memory O(block^2)
  on top of ring's cross-chip O(s/N) sharding).

  ``q_segment_ids`` / ``kv_segment_ids``: optional ``[b, s_block]``
  int32 per-token doc ids (-1 = padding) restricting attention to
  same-document pairs. The kv ids rotate with K/V; a rotated shard whose
  id interval is disjoint from the local q shard's contributes an exact
  zero and is skipped without running the block kernel.
  """
  if (q_segment_ids is None) != (kv_segment_ids is None):
    raise ValueError('q_segment_ids and kv_segment_ids must be given '
                     'together')
  n = lax.axis_size(axis_name)
  scale = 1.0 / (q.shape[-1] ** 0.5)
  qf = q.astype(jnp.float32)
  neg = jnp.float32(-1e9)

  def bias_of(mask, kv_seg):
    bias = None
    if mask is not None:
      bias = jnp.where(mask, 0.0, neg)[:, None, None, :].astype(jnp.float32)
    if kv_seg is not None:
      same = q_segment_ids[:, None, :, None] == kv_seg[:, None, None, :]
      seg_bias = jnp.where(same, 0.0, neg)
      bias = seg_bias if bias is None else bias + seg_bias
    return bias

  if block_impl == 'flash':
    from ..ops.flash_attention import flash_attention_with_lse

    def block(k_blk, v_blk, mask_blk, kv_seg_blk):
      out, lse = flash_attention_with_lse(
          q, k_blk, v_blk, mask_blk,
          q_segment_ids if kv_seg_blk is not None else None, kv_seg_blk)
      # Flash output is already normalized by its own denominator:
      # (m=lse, o=out, l=1) merges exactly — exp(lse - M) * out carries
      # the true exp(m - M) * unnormalized sum.
      lse = lse[..., None]
      return lse, out.astype(jnp.float32), jnp.ones_like(lse)
  elif block_impl == 'dense':
    def block(k_blk, v_blk, mask_blk, kv_seg_blk):
      return _block_attn(qf, k_blk, v_blk, bias_of(mask_blk, kv_seg_blk),
                         scale)
  else:
    raise ValueError(f'unknown block_impl {block_impl!r}')

  b, h, s, d = q.shape

  if q_segment_ids is not None:
    q_lo, q_hi = _shard_interval(q_segment_ids)

    def guarded_block(k_blk, v_blk, mask_blk, kv_seg_blk):
      kv_lo, kv_hi = _shard_interval(kv_seg_blk)
      live = jnp.any((q_lo <= kv_hi) & (kv_lo <= q_hi))

      def skip(_):
        # Finite -1e9 max (not -inf): against the -inf initial
        # accumulator, exp(-inf - -inf) would be NaN in the merge.
        return (jnp.full((b, h, s, 1), neg),
                jnp.zeros((b, h, s, d), jnp.float32),
                jnp.zeros((b, h, s, 1), jnp.float32))

      return lax.cond(live,
                      lambda _: block(k_blk, v_blk, mask_blk, kv_seg_blk),
                      skip, operand=None)
  else:
    guarded_block = block

  perm = [(i, (i + 1) % n) for i in range(n)]

  def body(i, carry):
    del i
    k_blk, v_blk, mask_blk, kv_seg_blk, m_acc, o_acc, l_acc = carry
    m_blk, o_blk, l_blk = guarded_block(k_blk, v_blk, mask_blk, kv_seg_blk)
    m_new = jnp.maximum(m_acc, m_blk)
    alpha = jnp.exp(m_acc - m_new)
    beta = jnp.exp(m_blk - m_new)
    o_acc = o_acc * alpha + o_blk * beta
    l_acc = l_acc * alpha + l_blk * beta
    k_blk = lax.ppermute(k_blk, axis_name, perm)
    v_blk = lax.ppermute(v_blk, axis_name, perm)
    if mask_blk is not None:
      mask_blk = lax.ppermute(mask_blk, axis_name, perm)
    if kv_seg_blk is not None:
      kv_seg_blk = lax.ppermute(kv_seg_blk, axis_name, perm)
    return k_blk, v_blk, mask_blk, kv_seg_blk, m_new, o_acc, l_acc

  m0 = jnp.full((b, h, s, 1), -jnp.inf, dtype=jnp.float32)
  o0 = jnp.zeros((b, h, s, d), dtype=jnp.float32)
  l0 = jnp.zeros((b, h, s, 1), dtype=jnp.float32)
  carry = (k, v, kv_mask, kv_segment_ids, m0, o0, l0)
  if n == 1:
    carry = body(0, carry)
    _, _, _, _, _, o_acc, l_acc = carry
  else:
    _, _, _, _, _, o_acc, l_acc = lax.fori_loop(0, n, body, carry)
  return (o_acc / jnp.maximum(l_acc, 1e-20)).astype(q.dtype)


def make_ring_attention(mesh, q_spec=None, mask_spec=None, axis_name='seq',
                        block_impl='dense'):
  """Wrap :func:`ring_attention` in ``shard_map`` for use from jitted code.

  ``q_spec`` defaults to ``P(('data','fsdp'), 'tensor', 'seq', None)`` —
  batch over dp, heads over tensor parallelism, sequence over the ring.
  ``block_impl='flash'`` runs each chip's block attention as the Pallas
  flash kernel. The wrapper takes ``(q, k, v, kv_mask, segment_ids)``;
  ``segment_ids`` ``[batch, seq]`` (used for both q and kv —
  self-attention) is sharded like the mask, or is None for full
  attention.
  """
  q_spec = q_spec or P(('data', 'fsdp'), 'tensor', axis_name, None)
  mask_spec = mask_spec or P(('data', 'fsdp'), axis_name)

  # A None operand is an empty pytree: its spec binds to no array.
  @functools.partial(
      jax.shard_map,
      mesh=mesh,
      in_specs=(q_spec, q_spec, q_spec, mask_spec, mask_spec),
      out_specs=q_spec,
      check_vma=False)
  def _sharded(q, k, v, kv_mask, segment_ids):
    return ring_attention(q, k, v, kv_mask, axis_name=axis_name,
                          block_impl=block_impl,
                          q_segment_ids=segment_ids,
                          kv_segment_ids=segment_ids)

  return _sharded
