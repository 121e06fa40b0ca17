"""Flash attention as a Pallas TPU kernel (forward + backward).

The attention score matrix is the one O(s^2) memory object in BERT-style
training; XLA materializes it per layer (``models/bert.py`` dense path).
This kernel never does: softmax runs online over key blocks with a
running (max, sum, accumulator) in VMEM, so per-core attention memory is
O(block^2) regardless of sequence length, and the backward pass
recomputes probabilities blockwise from the saved log-sum-exp instead of
storing them.

Layout: inputs ``[batch, heads, seq, head_dim]`` are flattened to
``[batch*heads, seq, head_dim]``; the grid walks (batch*heads,
q-blocks, k-blocks) for forward/dq and (batch*heads, k-blocks,
q-blocks) for dk/dv — the contracted sequence axis is the *innermost*
(sequential) grid dimension, with the running state (max/sum/acc or
gradient accumulators) in VMEM scratch that persists across those
steps. VMEM residency per grid step is one 128-row q/output tile plus
one kv block of up to ``_BLOCK_KV_FWD``/``_BLOCK_KV_BWD`` (4096/2048)
keys — a few MB total, independent of sequence length (an earlier
revision held full per-head K/V in VMEM, capping single-chip sequences
at ~8k; the grid-blocked form runs 32k+). K/V lengths that don't divide
into whole blocks are padded up to the next block boundary with
-inf-biased columns (``_kv_blocking``), never dropped to slow 128-wide
blocks.

Masking: a key-side additive bias ``[batch, seq]`` (0 = attend, -1e9 =
padding) — the same semantics as the dense path and the ring
(:mod:`lddl_tpu.parallel.ring`) path. Ring composes with this kernel
(``ring_attention(block_impl='flash')`` /
``BertConfig(attention_impl='ring_flash')``): ring shards the sequence
across chips and rotates K/V, each chip's local block runs here via
:func:`flash_attention_with_lse`, and the (out, lse) pair enters ring's
streaming-softmax merge exactly.

Block-diagonal packed attention: optional per-token ``segment_ids``
(doc index per token, -1 = padding — the packed loader derives them
from the stored ``doc_offsets``) restrict attention to within-document
pairs. Because a packed row's doc ids are monotone, every q/kv block
covers a contiguous id interval, so a (q-block, kv-block) tile whose
intervals are disjoint provably contains only masked pairs — the
kernels *skip* such tiles entirely (``pl.when`` around the whole tile
body: no MXU issue, no accumulator update), and only boundary-straddling
tiles pay the elementwise ``q_seg == kv_seg`` additive -1e9 bias on top
of the key-side padding bias. A row packing k documents therefore runs
~1/k of its attention tiles instead of computing and masking all of
them — the "no cross-contamination" masking of arXiv:2107.02027 as a
speedup rather than a cost.

Differentiation is a ``jax.custom_vjp``: forward saves (out, lse); the
backward runs two Pallas kernels — dq over q-blocks, (dk, dv) over
k-blocks — each recomputing P = exp(s - lse) blockwise.

On the ``cpu`` backend the kernels run in Pallas interpret mode, so the
CPU test suite exercises the identical code path. Every other backend
compiles them (Mosaic) or raises — there is no silent interpreter
fallback for an accelerator that announces itself under another name.
"""

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e9
# Softmax-denominator floor: a q row whose every tile was skipped (only
# padding rows qualify — a real token always overlaps its own document)
# ends the kv sweep with l == 0; the floor turns its 0/0 output into an
# exact 0 (and its lse finite) so the sliced-away row cannot leak NaN
# into `delta` in the backward pass. Real rows always have l >= 1
# (softmax includes the row max), so the floor never perturbs them.
_L_FLOOR = 1e-30


def _interpret(backend=None):
  """Only the ``cpu`` backend interprets; any other name compiles the
  kernel or fails (``backend`` defaults to the running one)."""
  return (backend or jax.default_backend()) == 'cpu'


def _padded_len(s):
  """Kernel sequence length: rounded up so BlockSpec blocks tile the
  array exactly — a block extending past the array end has undefined
  out-of-bounds contents, which would corrupt the tail q/kv block. The
  wrapper pads inputs to this length — padded key columns carry a -inf
  bias, padded query rows are sliced away."""
  if s <= 128:
    return ((s + 7) // 8) * 8  # sublane-tile multiple
  return ((s + 127) // 128) * 128


# Tuned on v5e: the q block sets the output tile (128 = one MXU tile of
# rows); the kv block is the unit streamed through the innermost grid
# dimension — larger blocks amortize per-grid-step overhead (128-wide kv
# blocks measured 3-4x slower than 2048-wide at s>=2048) while VMEM use
# stays modest (2 x block_k x 64 x 2B double-buffered ~= 1 MB at 2048).
# Env overrides (LDDL_FLASH_BLOCK_{Q,KV_FWD,KV_BWD}) support per-shape
# retuning without code edits — short sequences want smaller kv blocks,
# and block-diagonal packed rows skip at tile granularity, so many small
# documents per row skip more with smaller kv blocks.
_BLOCK_Q = int(os.environ.get('LDDL_FLASH_BLOCK_Q', 128))
_BLOCK_KV_FWD = int(os.environ.get('LDDL_FLASH_BLOCK_KV_FWD', 4096))
_BLOCK_KV_BWD = int(os.environ.get('LDDL_FLASH_BLOCK_KV_BWD', 2048))
# Segmented (block-diagonal) runs cap kv blocks finer: a tile can only
# skip whole, so the skip granularity IS the kv block — a 4096-wide
# block over a row packing 16 x ~512-token docs straddles ~8 documents
# and never skips, while 512-wide blocks skip ~7/8 of the grid. The
# extra per-block overhead is repaid as soon as rows pack >~2 docs.
_BLOCK_KV_SEG = int(os.environ.get('LDDL_FLASH_BLOCK_KV_SEG', 512))


def _kv_blocking(s_kv_pad, cap):
  """(block, padded_kv): a kv block <= cap (multiple of 128, or the whole
  length when it fits in one block) and the kv length rounded up to a
  whole number of blocks. Rather than requiring the block to divide the
  incoming length (which collapses to slow 128-wide blocks whenever the
  length has no large divisor), the caller pads K/V/bias up to
  ``padded_kv`` — masked padding columns cost at most one extra
  fractional block of compute (<= ~6% at s >= 2k)."""
  if s_kv_pad <= cap:
    return s_kv_pad, s_kv_pad
  n_steps = -(-s_kv_pad // cap)
  block = -(-s_kv_pad // (n_steps * 128)) * 128
  return block, block * n_steps


def _pad_kv(k, v, bias, kv_seg, padded_kv):
  s_kv = k.shape[1]
  if padded_kv == s_kv:
    return k, v, bias, kv_seg
  grow = ((0, 0), (0, padded_kv - s_kv), (0, 0))
  seg_grow = ((0, 0), (0, 0), (0, padded_kv - s_kv))
  return (jnp.pad(k, grow), jnp.pad(v, grow),
          jnp.pad(bias, seg_grow, constant_values=NEG_INF),
          None if kv_seg is None else jnp.pad(kv_seg, seg_grow,
                                              constant_values=-1.0))


def _seg_interval(seg):
  """(lo, hi) of the real (non-padding) segment ids in a tile row.

  Padding entries carry -1: excluding them from ``lo`` (and letting
  them drag ``hi`` down) makes an all-padding block's interval empty
  (lo > hi), so it reports disjoint against everything — padding-only
  tiles skip for free."""
  real = seg >= 0
  lo = jnp.min(jnp.where(real, seg, jnp.float32(2**30)))
  hi = jnp.max(jnp.where(real, seg, jnp.float32(-1)))
  return lo, hi


def _tile_live(qseg_ref, kseg_ref):
  """Scalar: does this (q-block, kv-block) tile contain any same-doc
  pair? Doc ids are monotone within a packed row, so each block spans a
  contiguous id interval and interval overlap is exact."""
  qlo, qhi = _seg_interval(qseg_ref[0, 0, :])
  klo, khi = _seg_interval(kseg_ref[0, 0, :])
  return (qlo <= khi) & (klo <= qhi)


def _seg_bias(qseg_ref, kseg_ref):
  """Elementwise cross-document mask for boundary-straddling tiles."""
  qseg = qseg_ref[0, 0, :]
  kseg = kseg_ref[0, 0, :]
  return jnp.where(qseg[:, None] == kseg[None, :], 0.0, NEG_INF)


def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, qseg_ref, kseg_ref,
                o_ref, lse_ref, m_ref, l_ref, acc_ref, *, scale):
  """Grid (bh, q-blocks, kv-blocks); kv is the innermost (sequential)
  dimension. The running (max, sum, accumulator) lives in VMEM scratch,
  which persists across grid steps: reset on the first kv block,
  updated by every *live* tile (cross-doc tiles skip the whole body),
  finalized into (o, lse) on the last."""
  j = pl.program_id(2)

  @pl.when(j == 0)
  def _init():
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

  def _tile():
    q = q_ref[0].astype(jnp.float32)  # [bq, d]
    k_blk = k_ref[0].astype(jnp.float32)  # [bk, d]
    v_blk = v_ref[0].astype(jnp.float32)
    scores = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32) * scale
    scores = scores + bias_ref[0, 0, :].astype(jnp.float32)[None, :]
    if qseg_ref is not None:
      scores = scores + _seg_bias(qseg_ref, kseg_ref)
    m = m_ref[...]
    m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
    p = jnp.exp(scores - m_new)
    alpha = jnp.exp(m - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v_blk, preferred_element_type=jnp.float32)

  if qseg_ref is None:
    _tile()
  else:
    pl.when(_tile_live(qseg_ref, kseg_ref))(_tile)

  @pl.when(j == pl.num_programs(2) - 1)
  def _finalize():
    l = jnp.maximum(l_ref[...], _L_FLOOR)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
    lse_ref[0] = m_ref[...] + jnp.log(l)


def _dq_kernel(q_ref, k_ref, v_ref, bias_ref, qseg_ref, kseg_ref, do_ref,
               lse_ref, delta_ref, dq_ref, dq_acc_ref, *, scale):
  """Grid (bh, q-blocks, kv-blocks), kv innermost; dq accumulates in
  scratch across the kv sweep. Cross-doc tiles contribute exactly zero
  (P underflows against their -1e9 bias) so they are skipped whole."""
  j = pl.program_id(2)

  @pl.when(j == 0)
  def _init():
    dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

  def _tile():
    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0]      # [bq, 1]
    delta = delta_ref[0]  # [bq, 1]
    k_blk = k_ref[0].astype(jnp.float32)
    v_blk = v_ref[0].astype(jnp.float32)
    scores = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32) * scale
    scores = scores + bias_ref[0, 0, :].astype(jnp.float32)[None, :]
    if qseg_ref is not None:
      scores = scores + _seg_bias(qseg_ref, kseg_ref)
    p = jnp.exp(scores - lse)
    dp = jnp.dot(do, v_blk.T, preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    dq_acc_ref[...] = dq_acc_ref[...] + jnp.dot(
        ds, k_blk, preferred_element_type=jnp.float32)

  if qseg_ref is None:
    _tile()
  else:
    pl.when(_tile_live(qseg_ref, kseg_ref))(_tile)

  @pl.when(j == pl.num_programs(2) - 1)
  def _finalize():
    dq_ref[0] = (dq_acc_ref[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, bias_ref, qseg_ref, kseg_ref, do_ref,
                lse_ref, delta_ref, dk_ref, dv_ref, dk_acc_ref, dv_acc_ref,
                *, scale):
  """Grid (bh, kv-blocks, q-blocks), q innermost; dk/dv accumulate in
  scratch across the q sweep while the (k, v) block stays resident."""
  i = pl.program_id(2)

  @pl.when(i == 0)
  def _init():
    dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
    dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

  def _tile():
    k_blk = k_ref[0].astype(jnp.float32)  # [bk, d]
    v_blk = v_ref[0].astype(jnp.float32)
    bias = bias_ref[0, 0, :].astype(jnp.float32)[None, :]
    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0]
    delta = delta_ref[0]
    # Rows beyond the real sequence carry lse from padded-q garbage; their
    # dO is zero (cotangents of padding outputs are never produced by the
    # loss) so they contribute nothing — but guard exp() overflow anyway.
    scores = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32) * scale
    scores = scores + bias
    if qseg_ref is not None:
      scores = scores + _seg_bias(qseg_ref, kseg_ref)
    p = jnp.exp(jnp.minimum(scores - lse, 30.0))
    dv_acc_ref[...] = dv_acc_ref[...] + jnp.dot(
        p.T, do, preferred_element_type=jnp.float32)
    dp = jnp.dot(do, v_blk.T, preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    dk_acc_ref[...] = dk_acc_ref[...] + jnp.dot(
        ds.T, q, preferred_element_type=jnp.float32)

  if qseg_ref is None:
    _tile()
  else:
    pl.when(_tile_live(qseg_ref, kseg_ref))(_tile)

  @pl.when(i == pl.num_programs(2) - 1)
  def _finalize():
    dk_ref[0] = (dk_acc_ref[...] * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv_acc_ref[...].astype(dv_ref.dtype)


def _plain(kernel):
  """The segment-free variant of a kernel: same body, no seg refs in the
  pallas_call signature (and the static ``qseg_ref is None`` branch
  keeps the whole skip/bias machinery out of the trace)."""

  def wrapped(q_ref, k_ref, v_ref, bias_ref, *rest, **kw):
    return kernel(q_ref, k_ref, v_ref, bias_ref, None, None, *rest, **kw)

  return wrapped


# Layout note for the BlockSpecs below: TPU lowering requires each
# block's last two dims to be (multiple-of-8, multiple-of-128) or equal
# to the array dims, so scalar rows ride as trailing-singleton 3-D
# arrays — bias/segment ids ``[b, 1, s]``, lse/delta ``[bh, s_q, 1]``.


def _qkv_specs(block_q, block_k, d, heads):
  """Shared specs for the (bh, q-blocks, kv-blocks) grid used by both
  the forward and dq pallas_calls — one point of truth so their block
  shapes and index maps cannot desynchronize. Returns
  (q_spec, kv_spec, bias_spec, qseg_spec, row_spec)."""
  q_spec = pl.BlockSpec((1, block_q, d), lambda i, b, j: (i, b, 0))
  kv_spec = pl.BlockSpec((1, block_k, d), lambda i, b, j: (i, j, 0))
  bias_spec = pl.BlockSpec((1, 1, block_k), lambda i, b, j: (i // heads, 0, j))
  qseg_spec = pl.BlockSpec((1, 1, block_q), lambda i, b, j: (i // heads, 0, b))
  row_spec = pl.BlockSpec((1, block_q, 1), lambda i, b, j: (i, b, 0))
  return q_spec, kv_spec, bias_spec, qseg_spec, row_spec


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _flash_pair(q, k, v, bias, q_seg, kv_seg, heads):
  """(out, lse) with gradients defined for both outputs — lse cotangents
  arise when results of separate flash calls are merged downstream (the
  ring composition's streaming-softmax combine). ``q_seg``/``kv_seg``
  are either both None (full attention) or float32 ``[b, 1, s]`` doc
  ids (-1 = padding) enabling the block-diagonal tile skip."""
  return _flash_fwd_impl(q, k, v, bias, q_seg, kv_seg, heads)


def _flash_fwd_impl(q, k, v, bias, q_seg, kv_seg, heads):
  bh, s_q, d = q.shape
  block_q = min(_BLOCK_Q, s_q)
  cap = _BLOCK_KV_FWD if q_seg is None else min(_BLOCK_KV_FWD, _BLOCK_KV_SEG)
  block_k, padded_kv = _kv_blocking(k.shape[1], cap)
  k, v, bias, kv_seg = _pad_kv(k, v, bias, kv_seg, padded_kv)
  grid = (bh, pl.cdiv(s_q, block_q), pl.cdiv(padded_kv, block_k))
  q_spec, kv_spec, bias_spec, qseg_spec, _ = _qkv_specs(
      block_q, block_k, d, heads)
  if q_seg is None:
    kernel, in_specs = _plain(_fwd_kernel), [q_spec, kv_spec, kv_spec,
                                             bias_spec]
    inputs = (q, k, v, bias)
  else:
    kernel = _fwd_kernel
    in_specs = [q_spec, kv_spec, kv_spec, bias_spec, qseg_spec, bias_spec]
    inputs = (q, k, v, bias, q_seg, kv_seg)
  out, lse = pl.pallas_call(
      functools.partial(kernel, scale=1.0 / d**0.5),
      grid=grid,
      in_specs=in_specs,
      out_specs=[
          pl.BlockSpec((1, block_q, d), lambda i, b, j: (i, b, 0)),
          pl.BlockSpec((1, block_q, 1), lambda i, b, j: (i, b, 0)),
      ],
      out_shape=[
          jax.ShapeDtypeStruct((bh, s_q, d), q.dtype),
          jax.ShapeDtypeStruct((bh, s_q, 1), jnp.float32),
      ],
      scratch_shapes=[
          pltpu.VMEM((block_q, 1), jnp.float32),
          pltpu.VMEM((block_q, 1), jnp.float32),
          pltpu.VMEM((block_q, d), jnp.float32),
      ],
      interpret=_interpret(),
      name='flash_fwd',
  )(*inputs)
  return out, lse


def _flash_fwd(q, k, v, bias, q_seg, kv_seg, heads):
  out, lse = _flash_fwd_impl(q, k, v, bias, q_seg, kv_seg, heads)
  return (out, lse), (q, k, v, bias, q_seg, kv_seg, out, lse)


def _flash_bwd(heads, res, cotangents):
  q, k, v, bias, q_seg, kv_seg, out, lse = res
  g, g_lse = cotangents
  bh, s_q, d = q.shape
  s_kv = k.shape[1]
  block_q = min(_BLOCK_Q, s_q)
  cap = _BLOCK_KV_BWD if q_seg is None else min(_BLOCK_KV_BWD, _BLOCK_KV_SEG)
  block_k, padded_kv = _kv_blocking(s_kv, cap)
  k, v, bias_padded, kv_seg_padded = _pad_kv(k, v, bias, kv_seg, padded_kv)
  g = g.astype(q.dtype)
  # d(out)/dS = P(delta-terms); d(lse)/dS = P — so an lse cotangent folds
  # into the shared (dp - delta) factor as delta -= g_lse.
  delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                  axis=-1, keepdims=True)  # [bh, s, 1]
  delta = delta - g_lse.astype(jnp.float32)
  scale = 1.0 / d**0.5
  segmented = q_seg is not None

  # dq: grid (bh, q-blocks, kv-blocks), kv innermost.
  q_spec, kv_spec, bias_spec, qseg_spec, row_blocked = _qkv_specs(
      block_q, block_k, d, heads)
  if segmented:
    dq_kernel = _dq_kernel
    dq_specs = [q_spec, kv_spec, kv_spec, bias_spec, qseg_spec, bias_spec,
                q_spec, row_blocked, row_blocked]
    dq_inputs = (q, k, v, bias_padded, q_seg, kv_seg_padded, g, lse, delta)
  else:
    dq_kernel = _plain(_dq_kernel)
    dq_specs = [q_spec, kv_spec, kv_spec, bias_spec, q_spec,
                row_blocked, row_blocked]
    dq_inputs = (q, k, v, bias_padded, g, lse, delta)
  dq = pl.pallas_call(
      functools.partial(dq_kernel, scale=scale),
      grid=(bh, pl.cdiv(s_q, block_q), pl.cdiv(padded_kv, block_k)),
      in_specs=dq_specs,
      out_specs=pl.BlockSpec((1, block_q, d), lambda i, b, j: (i, b, 0)),
      out_shape=jax.ShapeDtypeStruct((bh, s_q, d), q.dtype),
      scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
      interpret=_interpret(),
      name='flash_dq',
  )(*dq_inputs)

  # dk/dv: grid (bh, kv-blocks, q-blocks), q innermost; the (k, v) block
  # stays resident across the q sweep.
  q_by_i = pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0))
  kv_by_j = pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0))
  bias_by_j = pl.BlockSpec((1, 1, block_k), lambda b, j, i: (b // heads, 0, j))
  qseg_by_i = pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b // heads, 0, i))
  row_by_i = pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0))
  if segmented:
    dkv_kernel = _dkv_kernel
    dkv_specs = [q_by_i, kv_by_j, kv_by_j, bias_by_j, qseg_by_i, bias_by_j,
                 q_by_i, row_by_i, row_by_i]
    dkv_inputs = (q, k, v, bias_padded, q_seg, kv_seg_padded, g, lse, delta)
  else:
    dkv_kernel = _plain(_dkv_kernel)
    dkv_specs = [q_by_i, kv_by_j, kv_by_j, bias_by_j, q_by_i,
                 row_by_i, row_by_i]
    dkv_inputs = (q, k, v, bias_padded, g, lse, delta)
  dk, dv = pl.pallas_call(
      functools.partial(dkv_kernel, scale=scale),
      grid=(bh, pl.cdiv(padded_kv, block_k), pl.cdiv(s_q, block_q)),
      in_specs=dkv_specs,
      out_specs=[kv_by_j, kv_by_j],
      out_shape=[
          jax.ShapeDtypeStruct((bh, padded_kv, d), q.dtype),
          jax.ShapeDtypeStruct((bh, padded_kv, d), q.dtype),
      ],
      scratch_shapes=[
          pltpu.VMEM((block_k, d), jnp.float32),
          pltpu.VMEM((block_k, d), jnp.float32),
      ],
      interpret=_interpret(),
      name='flash_dkv',
  )(*dkv_inputs)
  return (dq, dk[:, :s_kv, :], dv[:, :s_kv, :], jnp.zeros_like(bias),
          None if q_seg is None else jnp.zeros_like(q_seg),
          None if kv_seg is None else jnp.zeros_like(kv_seg))


_flash_pair.defvjp(_flash_fwd, _flash_bwd)


def _prep_segments(segment_ids, s, s_pad):
  """[b, s] int doc ids -> the kernel's padded float32 [b, 1, s_pad] row
  (float so the custom_vjp cotangent is an ordinary zeros array; doc
  ids are < 65536, exact in float32). Pads extend with -1."""
  seg = jnp.asarray(segment_ids).astype(jnp.float32)[:, None, :]
  if s_pad != s:
    seg = jnp.pad(seg, ((0, 0), (0, 0), (0, s_pad - s)),
                  constant_values=-1.0)
  return seg


def flash_attention_with_lse(q, k, v, attention_mask=None,
                             q_segment_ids=None, kv_segment_ids=None):
  """Like :func:`flash_attention` but also returns the per-query
  log-sum-exp ``[batch, heads, seq]`` (float32) — the quantity needed to
  exactly merge attention results computed over disjoint key sets (ring
  attention's streaming-softmax combine). Gradients flow through both
  outputs.
  """
  b, h, s_q, d = q.shape
  s_kv = k.shape[2]
  if (q_segment_ids is None) != (kv_segment_ids is None):
    raise ValueError('q_segment_ids and kv_segment_ids must be given '
                     'together (self-attention passes the same array)')
  if attention_mask is None:
    bias = jnp.zeros((b, s_kv), jnp.float32)
  else:
    bias = jnp.where(attention_mask != 0, 0.0, NEG_INF).astype(jnp.float32)
  bias = bias[:, None, :]  # [b, 1, s_kv]: TPU block-tiling-friendly layout
  sq_pad, skv_pad = _padded_len(s_q), _padded_len(s_kv)
  if sq_pad != s_q:
    q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_pad - s_q), (0, 0)))
  if skv_pad != s_kv:
    kv_pad = ((0, 0), (0, 0), (0, skv_pad - s_kv), (0, 0))
    k = jnp.pad(k, kv_pad)
    v = jnp.pad(v, kv_pad)
    bias = jnp.pad(bias, ((0, 0), (0, 0), (0, skv_pad - s_kv)),
                   constant_values=NEG_INF)
  q_seg = kv_seg = None
  if q_segment_ids is not None:
    q_seg = _prep_segments(q_segment_ids, s_q, sq_pad)
    kv_seg = _prep_segments(kv_segment_ids, s_kv, skv_pad)
  out, lse = _flash_pair(q.reshape(b * h, sq_pad, d),
                         k.reshape(b * h, skv_pad, d),
                         v.reshape(b * h, skv_pad, d), bias, q_seg, kv_seg,
                         h)
  out = out.reshape(b, h, sq_pad, d)[:, :, :s_q, :]
  lse = lse.reshape(b, h, sq_pad)[:, :, :s_q]
  return out, lse


def flash_attention(q, k, v, attention_mask=None, q_segment_ids=None,
                    kv_segment_ids=None):
  """Blockwise-softmax attention; drop-in for the dense einsum path.

  ``q, k, v``: ``[batch, heads, seq, head_dim]``; ``attention_mask``:
  ``[batch, seq]`` with 1 = attend, 0 = padding (key side). Optional
  ``q_segment_ids``/``kv_segment_ids`` ``[batch, seq]`` int32 (doc index
  per token, -1 = padding) restrict attention block-diagonally to
  same-document pairs, skipping provably cross-document tiles (see
  module docstring). Returns the context ``[batch, heads, seq,
  head_dim]`` in the input dtype.
  """
  return flash_attention_with_lse(q, k, v, attention_mask, q_segment_ids,
                                  kv_segment_ids)[0]


def segment_block_intervals(segment_ids, block):
  """Per-block (lo, hi) doc-id intervals of a ``[b, s]`` id array —
  numpy, the host-side mirror of the kernel's ``_seg_interval``. The
  array is padded with -1 up to a whole number of blocks."""
  import numpy as np
  seg = np.asarray(segment_ids)
  b, s = seg.shape
  s_pad = -(-s // block) * block
  if s_pad != s:
    seg = np.pad(seg, ((0, 0), (0, s_pad - s)), constant_values=-1)
  tiles = seg.reshape(b, s_pad // block, block)
  real = tiles >= 0
  lo = np.where(real, tiles, 2**30).min(axis=2)
  hi = np.where(real, tiles, -1).max(axis=2)
  return lo, hi


def count_skippable_tiles(segment_ids, block_q=None, block_k=None):
  """(total, skipped) forward-grid tile counts for a ``[b, s]``
  segment-id batch under the kernel's interval-disjointness rule — the
  exact host-side account of the tiles the Pallas grid will skip (per
  (batch, q-block, kv-block); multiply by heads for per-head counts;
  the fraction is heads-invariant). Feeds the ``train.attn_tiles_*``
  telemetry counters and the benchmark skip-fraction columns."""
  s = int(segment_ids.shape[1])
  s_pad = _padded_len(s)
  if block_q is None:
    block_q = min(_BLOCK_Q, s_pad)
  if block_k is None:
    block_k, s_pad = _kv_blocking(s_pad, min(_BLOCK_KV_FWD, _BLOCK_KV_SEG))
  import numpy as np
  seg = np.asarray(segment_ids)
  if s_pad != s:
    seg = np.pad(seg, ((0, 0), (0, s_pad - s)), constant_values=-1)
  qlo, qhi = segment_block_intervals(seg, block_q)
  klo, khi = segment_block_intervals(seg, block_k)
  live = ((qlo[:, :, None] <= khi[:, None, :]) &
          (klo[:, None, :] <= qhi[:, :, None]))
  total = int(live.size)
  return total, total - int(live.sum())


def make_flash_attention(mesh, q_spec=None, mask_spec=None,
                         with_segment_ids=False):
  """Wrap :func:`flash_attention` in ``shard_map`` for jitted use over a
  mesh: batch over (data, fsdp), heads over tensor — a ``pallas_call``
  has no GSPMD partitioning rule, so without this the compiler would
  replicate q/k/v onto every chip. The sequence axis must be unsharded
  (flash is per-chip block math; sequence sharding is ring attention's
  job — use ``attention_impl='ring_flash'`` for both).

  ``with_segment_ids=True`` returns a wrapper taking an extra
  ``segment_ids`` ``[batch, seq]`` operand (used for both q and kv —
  self-attention), sharded like the mask.
  """
  from jax.sharding import PartitionSpec as P

  if dict(zip(mesh.axis_names, mesh.devices.shape)).get('seq', 1) > 1:
    raise ValueError(
        "flash attention does not shard the sequence axis; use "
        "attention_impl='ring_flash' on meshes with seq > 1")
  names = set(mesh.axis_names)
  batch_axes = tuple(a for a in ('data', 'fsdp') if a in names) or None
  head_axis = 'tensor' if 'tensor' in names else None
  q_spec = q_spec or P(batch_axes, head_axis, None, None)
  mask_spec = mask_spec or P(batch_axes, None)

  if with_segment_ids:
    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(q_spec, q_spec, q_spec, mask_spec, mask_spec),
        out_specs=q_spec,
        check_vma=False)
    def _sharded_seg(q, k, v, mask, segment_ids):
      return flash_attention(q, k, v, mask, segment_ids, segment_ids)

    return _sharded_seg

  @functools.partial(
      jax.shard_map,
      mesh=mesh,
      in_specs=(q_spec, q_spec, q_spec, mask_spec),
      out_specs=q_spec,
      check_vma=False)
  def _sharded(q, k, v, mask):
    return flash_attention(q, k, v, mask)

  return _sharded
