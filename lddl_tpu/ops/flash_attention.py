"""Flash attention as a Pallas TPU kernel (forward + backward).

The attention score matrix is the one O(s^2) memory object in BERT-style
training; XLA materializes it per layer (``ops/attention.py`` dense path).
This kernel never does: softmax runs online over key blocks with a
running (max, sum, accumulator) in VMEM, so per-core attention memory is
O(block^2) regardless of sequence length, and the backward pass
recomputes probabilities blockwise from the saved log-sum-exp instead of
storing them.

Layout: inputs ``[batch, heads, seq, head_dim]`` are flattened to
``[batch*heads, seq, head_dim]``; the grid walks (batch*heads,
q-blocks, k-blocks) for the forward and (batch*heads, k-blocks,
q-blocks) for the backward — the innermost (sequential) grid dimension
is the one the forward's softmax and the backward's dk/dv are summed
over, with the running state (max/sum/acc or gradient accumulators) in
VMEM scratch that persists across those steps. One grid step works on a
``[block_q, block_k]`` tile of up to 1024 x 1024 (``_tile_blocks``; a
grid step costs ~0.45 us before it computes anything, so the tile must
be large enough to hide that): VMEM per step is the q/output tile, one
k and one v block (128 KB each in bfloat16, double-buffered) and the
float32 ``[block_q, block_k]`` intermediates (4 MB each, which Mosaic
works through in pieces), independent of sequence length in the forward
(an earlier revision held full per-head K/V in VMEM, capping single-chip
sequences at ~8k; the grid-blocked form runs 32k+). The backward also
holds one head's dq (below). Lengths that don't divide into whole blocks
are padded up to the next block boundary — keys with -inf-biased
columns, queries with zero rows that are sliced away (``_blocking``) —
never dropped to slow 128-wide blocks.

Arithmetic: the MXU takes q, k, v and dO tiles in the dtype they arrive
in and sums in float32; the four products with a computed operand
(p.v, p^T.dO, dS.k, dS^T.q) round that operand to the inputs' dtype just
before the product, where the dense path rounds its probabilities
(``ops/attention.py``: ``probs.astype(dtype)``). Scores, the running
max and sum, ``exp``, ``lse``, ``delta``, dS and the accumulators are
float32. A model built in float32 therefore gets float32 operands
throughout. No ``[block, block]`` tile is turned for a product: it
stands as it is or contracts its minor dimension, as k does in q.k^T;
what the backward turns are ``[block, head_dim]`` operands, so that its
gradients come out of the MXU as lane-dense ``[head_dim, block]``.

Masking: a key-side additive bias ``[batch, seq]`` (0 = attend, -1e9 =
padding) — the same semantics as the dense path and the ring
(:mod:`lddl_tpu.parallel.ring`) path. Ring composes with this kernel
(``ring_attention(block_impl='flash')``, which
:func:`lddl_tpu.ops.attention.attend` runs for ``'ring_flash'``): ring
shards the sequence across chips and rotates K/V, each chip's local
block runs here via :func:`flash_attention_with_lse`, and the (out, lse)
pair enters ring's streaming-softmax merge exactly.

Block-diagonal packed attention: optional per-token ``segment_ids``
(doc index per token, -1 = padding — the packed loader derives them
from the stored ``doc_offsets``) restrict attention to within-document
pairs. Because a packed row's doc ids are monotone, every q/kv block
covers a contiguous id interval, so a (q-block, kv-block) tile whose
intervals are disjoint provably contains only masked pairs — the
kernels *skip* such tiles entirely (``pl.when`` around the whole tile
body: no MXU issue, no accumulator update), and only boundary-straddling
tiles pay the elementwise ``q_seg == kv_seg`` additive -1e9 bias on top
of the key-side padding bias: a tile whose two intervals are the same
single document runs the body without it (a segment id of -1 must come
with a masked key, as the packed loader gives them). A row packing k
documents therefore runs ~1/k of its attention tiles instead of
computing and masking all of them — the "no cross-contamination"
masking of arXiv:2107.02027 as a speedup rather than a cost.

Causal attention (``causal=True``, a decoder's): a query sees no key
after it. The tile rule takes a third term beside the two intervals: a
tile wholly above the diagonal is skipped like a cross-document one, a
tile wholly below it needs no causal mask, and a tile on it takes the
elementwise ``q >= k`` mask; a tile is bias-free (*interior*) only where
both rules leave every pair of it unmasked, so a diagonal tile never is.
With segment ids the two compose: causal within each document.

Grouped query heads: k and v may hold fewer heads than q, a divisor of its
count. Query head ``h`` reads key/value head ``h // group`` through the
BlockSpec's index map, in place (nothing is copied); the backward sums
each query head's share of dk and dv over its group in float32.

Differentiation is a ``jax.custom_vjp``: forward saves (out, lse); the
backward is one Pallas kernel (``_bwd_kernel``) that recomputes
P = exp(s - lse), dP and dS of a tile once and takes dq, dk and dv from
them: five products a tile. dk and dv are summed over the innermost
grid axis in scratch; dq is summed over the *outer* one, so one head's
dq stays resident in VMEM as the kernel's float32 output block (2 MB at
8192 x 64, two buffers), and XLA turns, scales and casts it behind the
call. A query axis too long for that (``_DQ_RESIDENT_BYTES``: past 32k
tokens at 64 wide) is cut into spans, one launch of the same kernel a
span, dk and dv summed over the spans in float32 (``_flash_bwd``). The
two saved values carry ``checkpoint_name``s
(``ops.attention.FLASH_RESIDUAL_NAMES``) so that a caller's
``jax.checkpoint`` can keep them by policy: a rematted layer
(``models/bert.py``) then runs the forward kernel once a step, not a
second time in its backward pass (``_flash_fwd``).

On the ``cpu`` backend the kernels run in Pallas interpret mode, so the
CPU test suite exercises the identical code path. Every other backend
compiles them (Mosaic) or raises — there is no silent interpreter
fallback for an accelerator that announces itself under another name.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import FLASH_RESIDUAL_NAMES

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


# Tuned on v5e (PERF.md section 5, PR 30's tables: the kernels alone at
# [24, 8192, 64] bfloat16). A grid step costs ~0.45 us before it computes
# anything (its DMAs' issue and wait, the pipeline's bookkeeping), and a
# [128 x 512] tile's own work takes no longer than that: the tile, not the
# operands' dtype, sets the pace (Mosaic's default float32 product is one
# bfloat16 pass already). Forward + backward of one layer's call, one
# document a row: 69.4 ms at [128 x 512], 33.2 at [512 x 512], 26.1 at
# [512 x 1024], 23.8 at [1024 x 1024]; rows of 30 short documents, where
# a larger tile skips more coarsely, 39.8 / 15.9 / 13.1 / 12.4: the
# largest tile wins there too. The float32 [block_q, block_k]
# intermediates (scores, p, dp, ds: 4 MB each at [1024 x 1024]) compile
# inside Mosaic's default scoped VMEM limit in the forward, which sets no
# ``vmem_limit_bytes``; kv blocks of 2048 bought nothing more.
# The two values are *caps*: a sequence shorter than a cap takes one
# block of its own (padded) length. With segment ids a tile can only skip
# whole, so the tile is also the skip granularity.
_BLOCK_Q = 1024
_BLOCK_KV = 1024
# The backward kernel keeps one head's dq in VMEM, float32 [d, span] in
# two buffers (Pallas double-buffers an output block): up to this many
# bytes, 32,768 query rows at d = 64; a longer query axis takes one
# launch a span (``_flash_bwd``). Beside it the kernel's blocks and tile
# intermediates take 6.6 MiB at [1024 x 1024] and d = 64 (the TPU
# compiler's count of the scoped allocation: 10.57 MiB with the 4 MiB of
# an 8192-row dq), so 16 + 6.6 = 22.6 MiB have to fit the limit: 32 MiB
# of a v5e core's 128, where Mosaic's default is 16. The raised limit
# costs nothing at 8192 (PERF.md section 5, PR 36's table).
_DQ_RESIDENT_BYTES = 16 * 2**20
_VMEM_LIMIT_BYTES = 32 * 2**20


def _blocking(s_pad, cap):
  """(block, padded): a block <= cap (multiple of 128, or the whole
  length when it fits in one block) and the length rounded up to a
  whole number of blocks. Rather than requiring the block to divide the
  incoming length (which collapses to slow 128-wide blocks whenever the
  length has no large divisor), the caller pads up to ``padded`` —
  masked padding columns (and zero query rows) cost at most one extra
  fractional block of compute (<= ~6% at s >= 2k)."""
  if s_pad <= cap:
    return s_pad, s_pad
  n_steps = -(-s_pad // cap)
  unit = min(cap, 128)  # a cap under one lane tile: interpreter-sized tests
  block = -(-s_pad // (n_steps * unit)) * unit
  return block, block * n_steps


def _tile_blocks(s_q, s_kv):
  """((block_q, padded_q), (block_k, padded_kv)) of one kernel launch,
  from the two (already ``_padded_len``-ed) sequence lengths alone. The
  one place a tile shape is chosen: the forward and backward launches and
  the host's ``count_skippable_tiles`` all ask here, so the count of
  tiles the host reports cannot drift from the grid the chip runs."""
  return _blocking(s_q, _BLOCK_Q), _blocking(s_kv, _BLOCK_KV)


def _pad_to(x, axis, length, value=0.0):
  """``x`` grown along ``axis`` to ``length`` with ``value``; None and an
  array already that long pass through."""
  if x is None or x.shape[axis] == length:
    return x
  widths = [(0, 0)] * x.ndim
  widths[axis] = (0, length - x.shape[axis])
  return jnp.pad(x, widths, constant_values=value)


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


def _for_live_tile(tile, qseg_ref, kseg_ref, diagonal=None):
  """Run ``tile(masked)`` unless the (q-block, kv-block) tile holds no
  pair that may attend. Doc ids are monotone within a packed row, so each
  block spans a contiguous id interval and interval overlap is exact.
  ``diagonal`` is None where every key may be seen, or, for causal
  attention, ``(q0, k0)``: the tile's first query and key position (its
  block sizes come from the refs' shapes). A live tile is *interior* when
  no pair of it is masked: both intervals are the same single document
  (padding keys carry the key-side bias already; an id of -1 comes with a
  masked key) and, under causality, every key stands at or before every
  query. An interior tile runs without the elementwise masks, which would
  add zeros; a tile on the diagonal is never interior.
  The seg refs hold the two blocks' ids (row or column), or are None for
  attention across documents: with no diagonal either, one tile body and
  no skip machinery in the trace."""
  if qseg_ref is None and diagonal is None:
    tile(False)
    return
  live = interior = None
  if qseg_ref is not None:
    qlo, qhi = _seg_interval(qseg_ref[...])
    klo, khi = _seg_interval(kseg_ref[...])
    live = (qlo <= khi) & (klo <= qhi)
    interior = (qlo == qhi) & (klo == khi) & (qlo == klo)
  if diagonal is not None:
    (q0, block_q), (k0, block_k) = diagonal
    seen = k0 <= q0 + (block_q - 1)   # some key at or before some query
    below = k0 + (block_k - 1) <= q0  # every key at or before every query
    live = seen if live is None else live & seen
    interior = below if interior is None else interior & below
  pl.when(interior)(lambda: tile(False))
  pl.when(live & jnp.logical_not(interior))(lambda: tile(True))


def _causal_order(diagonal, key_major):
  """bool tile: the query stands at or after the key. ``diagonal`` as in
  :func:`_for_live_tile`; the tile is ``[block_q, block_k]``, or
  ``[block_k, block_q]`` where ``key_major``."""
  (q0, block_q), (k0, block_k) = diagonal
  shape = (block_k, block_q) if key_major else (block_q, block_k)
  rows = lax.broadcasted_iota(jnp.int32, shape, 0)
  cols = lax.broadcasted_iota(jnp.int32, shape, 1)
  if key_major:
    return k0 + rows <= q0 + cols
  return q0 + rows >= k0 + cols


def _mxu(a, b, contract):
  """One MXU product summed in float32. ``contract`` names the dimension
  of ``a`` and of ``b`` that is summed over, so a transposed operand is
  a pair of dimension numbers and never a materialised tile. Operands go
  in as they are: bfloat16 inputs give the MXU its native single pass."""
  return lax.dot_general(a, b, (((contract[0],), (contract[1],)), ((), ())),
                         preferred_element_type=jnp.float32)


def _scores(a, b, bias, scale, a_seg=None, b_seg=None, order=None):
  """float32 ``[rows of a, rows of b]`` scores of one tile: a.b^T scaled,
  plus the key-side padding bias (a row ``[1, n]`` where ``b`` holds the
  keys, a column ``[m, 1]`` where ``a`` does) and, given the two blocks'
  segment ids (a column ``[m, 1]`` and a row ``[1, n]``) on a tile that
  straddles a document boundary, the elementwise cross-document mask;
  given ``order`` (:func:`_causal_order`) on a tile that straddles the
  diagonal, the causal one."""
  s = _mxu(a, b, (1, 1)) * scale + bias
  if a_seg is not None:
    s = s + jnp.where(a_seg == b_seg, 0.0, NEG_INF)
  if order is not None:
    s = s + jnp.where(order, 0.0, NEG_INF)
  return s


def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, qseg_ref, kseg_ref,
                o_ref, lse_ref, m_ref, l_ref, acc_ref, *, scale, causal):
  """Grid (bh, q-blocks, kv-blocks); kv is the innermost (sequential)
  dimension. The running (max, sum, accumulator) lives in VMEM scratch,
  which persists across grid steps: reset on the first kv block,
  updated by every *live* tile (cross-doc tiles, and under ``causal``
  tiles above the diagonal, skip the whole body), finalized into
  (o, lse) on the last."""
  j = pl.program_id(2)
  diagonal = None
  if causal:
    diagonal = ((pl.program_id(1) * q_ref.shape[1], q_ref.shape[1]),
                (j * k_ref.shape[1], k_ref.shape[1]))

  @pl.when(j == 0)
  def _init():
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

  def _tile(masked):
    v_blk = v_ref[0]  # [bk, d], the input's dtype
    segs = ((qseg_ref[0, 0, :][:, None], kseg_ref[0])
            if masked and qseg_ref is not None else (None, None))
    order = _causal_order(diagonal, False) if masked and causal else None
    scores = _scores(q_ref[0], k_ref[0], bias_ref[0], scale, *segs, order)
    m = m_ref[...]
    m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
    p = jnp.exp(scores - m_new)
    alpha = jnp.exp(m - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    # p is rounded to the input's dtype where the dense path rounds its
    # probabilities (ops/attention.py); float32 inputs keep it float32.
    acc_ref[...] = acc_ref[...] * alpha + _mxu(p.astype(v_blk.dtype), v_blk,
                                               (1, 0))

  _for_live_tile(_tile, qseg_ref, kseg_ref, diagonal)

  @pl.when(j == pl.num_programs(2) - 1)
  def _finalize():
    l = jnp.maximum(l_ref[...], _L_FLOOR)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
    lse_ref[0] = m_ref[...] + jnp.log(l)


def _bwd_kernel(q_ref, k_ref, v_ref, bias_ref, qseg_ref, kseg_ref, do_ref,
                lse_ref, delta_ref, dq_ref, dk_ref, dv_ref, dk_acc_ref,
                dv_acc_ref, *, scale, causal, q_offset):
  """Grid (bh, kv-blocks, q-blocks), q innermost: a tile's scores, P, dP
  and dS are made once and all three gradients taken from them, five
  products a tile. Cross-doc tiles contribute exactly zero (P underflows
  against their -1e9 bias) so they are skipped whole.

  Every gradient is summed *transposed*, ``[d, block]``: a product whose
  result is ``[block, 64]`` leaves the MXU in half-empty tiles (PERF.md
  section 5, PR 36's table). dk^T and dv^T accumulate in scratch across
  the q sweep while the (k, v) block stays resident. dq is summed over the
  *outer* axis, so it stays in VMEM for the whole head: ``dq_ref`` is the
  head's float32 ``[q-blocks, d, block_q]`` output block, zeroed at the
  head's first step (skipped tiles never write) and written back when the
  head changes.

  The tile is key-major, ``[block_k, block_q]``: it enters dq^T = k^T.dS^T
  as it stands and dv^T = dO^T.P, dk^T = q^T.dS by its minor dimension, as
  k enters q.k^T. So the per-key rows (bias, kv segment ids) arrive as
  columns ``[block_k, 1]`` and the per-query ones (lse, delta, q segment
  ids) as rows ``[1, block_q]`` — which also keeps what the innermost
  axis fetches every step small (a ``[block_q, 1]`` float32 column moves
  a whole 128-lane tile a row). Under ``causal`` the query rows of this
  launch start at position ``q_offset``."""
  j, i = pl.program_id(1), pl.program_id(2)
  diagonal = None
  if causal:
    diagonal = ((q_offset + i * q_ref.shape[1], q_ref.shape[1]),
                (j * k_ref.shape[1], k_ref.shape[1]))

  @pl.when((j == 0) & (i == 0))
  def _zero_dq():
    dq_ref[...] = jnp.zeros_like(dq_ref)

  @pl.when(i == 0)
  def _init():
    dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
    dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

  def _tile(masked):
    q, k, do = q_ref[0], k_ref[0], do_ref[0]  # [bq, d], [bk, d], [bq, d]
    segs = ((kseg_ref[0], qseg_ref[0])
            if masked and qseg_ref is not None else (None, None))
    order = _causal_order(diagonal, True) if masked and causal else None
    scores_t = _scores(k, q, bias_ref[0], scale, *segs, order)  # k.q^T
    # Rows beyond the real sequence carry lse from padded-q garbage; their
    # dO is zero (cotangents of padding outputs are never produced by the
    # loss) so they contribute nothing — but guard exp() overflow anyway.
    p_t = jnp.exp(jnp.minimum(scores_t - lse_ref[0], 30.0))
    dv_acc_ref[...] = dv_acc_ref[...] + _mxu(do, p_t.astype(do.dtype), (0, 1))
    dp_t = _mxu(v_ref[0], do, (1, 1))  # v.do^T
    ds_t = (p_t * (dp_t - delta_ref[0])).astype(q.dtype)
    dk_acc_ref[...] = dk_acc_ref[...] + _mxu(q, ds_t, (0, 1))
    dq_ref[0, i] = dq_ref[0, i] + _mxu(k, ds_t, (0, 0))

  _for_live_tile(_tile, qseg_ref, kseg_ref, diagonal)

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


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _flash_pair(q, k, v, bias, q_seg, kv_seg, heads, group, causal):
  """(out, lse) with gradients defined for both outputs — lse cotangents
  arise when results of separate flash calls are merged downstream (the
  ring composition's streaming-softmax combine). ``q_seg``/``kv_seg``
  are either both None (full attention) or float32 ``[b, 1, s]`` doc
  ids (-1 = padding) enabling the block-diagonal tile skip. ``group``
  query heads share one key/value head (k and v hold ``bh // group``
  heads); ``causal`` lets a query see no later key."""
  return _flash_fwd_impl(q, k, v, bias, q_seg, kv_seg, heads, group, causal)


def _kv_index(group, index):
  """The key/value head of query head ``index`` (grouped heads are read in
  place by the BlockSpec, never copied); no arithmetic without groups."""
  return index if group == 1 else index // group


def _flash_fwd_impl(q, k, v, bias, q_seg, kv_seg, heads, group, causal):
  bh, s_q, d = q.shape
  (block_q, padded_q), (block_k, padded_kv) = _tile_blocks(s_q, k.shape[1])
  # Whole blocks on both axes: zero query rows (segment id -1) are
  # sliced away below, padded keys are masked by their bias.
  q = _pad_to(q, 1, padded_q)
  q_seg = _pad_to(q_seg, 2, padded_q, -1.0)
  k, v = _pad_to(k, 1, padded_kv), _pad_to(v, 1, padded_kv)
  bias = _pad_to(bias, 2, padded_kv, NEG_INF)
  kv_seg = _pad_to(kv_seg, 2, padded_kv, -1.0)
  grid = (bh, padded_q // block_q, padded_kv // block_k)
  q_spec = pl.BlockSpec((1, block_q, d), lambda i, b, j: (i, b, 0))
  kv_spec = pl.BlockSpec((1, block_k, d),
                         lambda i, b, j: (_kv_index(group, i), j, 0))
  bias_spec = pl.BlockSpec((1, 1, block_k), lambda i, b, j: (i // heads, 0, j))
  qseg_spec = pl.BlockSpec((1, 1, block_q), lambda i, b, j: (i // heads, 0, b))
  row_spec = pl.BlockSpec((1, block_q, 1), lambda i, b, j: (i, b, 0))
  if q_seg is None:
    kernel, in_specs = _plain(_fwd_kernel), [q_spec, kv_spec, kv_spec,
                                             bias_spec]
    inputs = (q, k, v, bias)
  else:
    kernel = _fwd_kernel
    in_specs = [q_spec, kv_spec, kv_spec, bias_spec, qseg_spec, bias_spec]
    inputs = (q, k, v, bias, q_seg, kv_seg)
  out, lse = pl.pallas_call(
      functools.partial(kernel, scale=1.0 / d**0.5, causal=causal),
      grid=grid,
      in_specs=in_specs,
      out_specs=[q_spec, row_spec],
      out_shape=[
          jax.ShapeDtypeStruct((bh, padded_q, d), q.dtype),
          jax.ShapeDtypeStruct((bh, padded_q, 1), jnp.float32),
      ],
      scratch_shapes=[
          pltpu.VMEM((block_q, 1), jnp.float32),
          pltpu.VMEM((block_q, 1), jnp.float32),
          pltpu.VMEM((block_q, d), jnp.float32),
      ],
      interpret=_interpret(),
      name='flash_fwd',
  )(*inputs)
  return out[:, :s_q, :], lse[:, :s_q, :]


def _flash_fwd(q, k, v, bias, q_seg, kv_seg, heads, group, causal):
  """The forward rule. The kernel's two results are also the residuals
  that only it can make, so they carry the names by which a remat policy
  keeps them (``FLASH_RESIDUAL_NAMES``; outside remat a name is the
  identity and lowers to nothing). A kept value is stored in the shape it
  is named in, and the chip pads the minor dimension to 128 lanes: a head
  of 64 would keep ``out`` at twice its bytes and the ``[bh, s, 1]``
  column of ``lse`` at 128 times. So each is named as a lane-dense view
  and shaped back behind the name."""
  out, lse = _flash_fwd_impl(q, k, v, bias, q_seg, kv_seg, heads, group,
                             causal)
  out_name, lse_name = FLASH_RESIDUAL_NAMES
  bh, s_q, d = out.shape
  dense = out.reshape(bh, -1, math.gcd(s_q * d, 128))
  out = checkpoint_name(dense, out_name).reshape(bh, s_q, d)
  lse = checkpoint_name(lse.reshape(bh, s_q), lse_name).reshape(bh, s_q, 1)
  return (out, lse), (q, k, v, bias, q_seg, kv_seg, out, lse)


def _flash_bwd(heads, group, causal, res, cotangents):
  q, k, v, bias, q_seg, kv_seg, out, lse = res
  g, g_lse = cotangents
  bh, s_q, d = q.shape
  s_kv = k.shape[1]
  (block_q, padded_q), (block_k, padded_kv) = _tile_blocks(s_q, s_kv)
  g = g.astype(q.dtype)
  # d(out)/dS = P(delta-terms); d(lse)/dS = P — so an lse cotangent folds
  # into the shared (dp - delta) factor as delta -= g_lse.
  delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                  axis=-1, keepdims=True)  # [bh, s, 1]
  delta = delta - g_lse.astype(jnp.float32)
  # Whole blocks on both axes. A padded query row has q = dO = delta = 0
  # and lse = 0: its P is finite and its dS and P^T.dO terms are zeros.
  # The tile is key-major (``_bwd_kernel``): per-key rows go in as columns,
  # per-query columns as rows (a singleton axis swapped: one small copy
  # outside the kernel).
  turned = lambda x: jnp.swapaxes(x, 1, 2)  # row <-> column
  q_pad, g = _pad_to(q, 1, padded_q), _pad_to(g, 1, padded_q)
  lse_row = turned(_pad_to(lse, 1, padded_q))
  delta_row = turned(_pad_to(delta, 1, padded_q))
  k, v = _pad_to(k, 1, padded_kv), _pad_to(v, 1, padded_kv)
  bias_col = turned(_pad_to(bias, 2, padded_kv, NEG_INF))
  scale = 1.0 / d**0.5
  q_by_i = pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0))
  kv_by_j = pl.BlockSpec((1, block_k, d),
                         lambda b, j, i: (_kv_index(group, b), j, 0))
  kcol_by_j = pl.BlockSpec((1, block_k, 1), lambda b, j, i: (b // heads, j, 0))
  qrow_by_i = pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, i))
  kernel, seg_specs = _plain(_bwd_kernel), []
  if q_seg is not None:
    kernel = _bwd_kernel
    seg_specs = [pl.BlockSpec((1, 1, block_q),
                              lambda b, j, i: (b // heads, 0, i)), kcol_by_j]
    qseg_row = _pad_to(q_seg, 2, padded_q, -1.0)
    kseg_col = turned(_pad_to(kv_seg, 2, padded_kv, -1.0))

  # One launch a span of the query axis, the longest whose dq stays
  # resident (``_DQ_RESIDENT_BYTES``: two buffers of [d, span] float32);
  # dk and dv are summed over the spans below, and over the query heads of
  # a group, in float32 where there is more than one share.
  span = max(1, _DQ_RESIDENT_BYTES // (2 * 4 * d * block_q)) * block_q
  starts = range(0, padded_q, span)
  share_dtype = q.dtype if len(starts) == 1 and group == 1 else jnp.float32

  def launch(lo):
    """(dq^T / scale in float32, dk^T, dv^T) of the query rows from
    ``lo`` on, one span, against every key: dq whole, of dk and dv that
    span's share, each a block at a time as ``[d, block]``."""
    n = min(span, padded_q - lo)
    rows = lambda x: x[:, lo:lo + n]
    cols = lambda x: x[:, :, lo:lo + n]
    segs = () if q_seg is None else (cols(qseg_row), kseg_col)
    kt_by_j = pl.BlockSpec((1, d, block_k), lambda b, j, i: (b, 0, j))
    return pl.pallas_call(
        functools.partial(kernel, scale=scale, causal=causal, q_offset=lo),
        grid=(bh, padded_kv // block_k, n // block_q),
        in_specs=[q_by_i, kv_by_j, kv_by_j, kcol_by_j, *seg_specs, q_by_i,
                  qrow_by_i, qrow_by_i],
        out_specs=[pl.BlockSpec((1, n // block_q, d, block_q),
                                lambda b, j, i: (b, 0, 0, 0)),
                   kt_by_j, kt_by_j],
        out_shape=[
            jax.ShapeDtypeStruct((bh, n // block_q, d, block_q), jnp.float32),
            jax.ShapeDtypeStruct((bh, d, padded_kv), share_dtype),
            jax.ShapeDtypeStruct((bh, d, padded_kv), share_dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((d, block_k), jnp.float32),
            pltpu.VMEM((d, block_k), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=_interpret(),
        name='flash_bwd',
    )(rows(q_pad), k, v, bias_col, *segs, rows(g), cols(lse_row),
      cols(delta_row))

  dq_t, dk_t, dv_t = zip(*map(launch, starts))
  # Turned back by XLA behind the call; dq is scaled and cast here, where
  # dk is in the kernel's last step: its sum ends only with the head.
  dq = jnp.swapaxes(jnp.concatenate(dq_t, axis=1), 2, 3)
  dq = (dq.reshape(bh, padded_q, d) * scale).astype(q.dtype)
  dk, dv = (turned(sum(x[1:], x[0])) for x in (dk_t, dv_t))
  if group > 1:  # each query head's share of its key/value head
    dk, dv = (x.reshape(bh // group, group, padded_kv, d).sum(axis=1)
              for x in (dk, dv))
  dk, dv = dk.astype(q.dtype), dv.astype(q.dtype)
  return (dq[:, :s_q, :], dk[:, :s_kv, :], dv[:, :s_kv, :],
          jnp.zeros_like(bias),
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
                             q_segment_ids=None, kv_segment_ids=None,
                             causal=False):
  """Like :func:`flash_attention` but also returns the per-query
  log-sum-exp ``[batch, heads, seq]`` (float32) — the quantity needed to
  exactly merge attention results computed over disjoint key sets (ring
  attention's streaming-softmax combine). Gradients flow through both
  outputs.
  """
  b, h, s_q, d = q.shape
  s_kv, kv_heads = k.shape[2], k.shape[1]
  if h % kv_heads:
    raise ValueError(f'{h} query heads do not share {kv_heads} key/value '
                     'heads evenly')
  if causal and s_q != s_kv:
    raise ValueError('causal attention needs queries and keys of one '
                     'sequence')
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
                         k.reshape(b * kv_heads, skv_pad, d),
                         v.reshape(b * kv_heads, skv_pad, d), bias, q_seg,
                         kv_seg, h, h // kv_heads, causal)
  out = out.reshape(b, h, sq_pad, d)[:, :, :s_q, :]
  lse = lse.reshape(b, h, sq_pad)[:, :, :s_q]
  return out, lse


def flash_attention(q, k, v, attention_mask=None, q_segment_ids=None,
                    kv_segment_ids=None, causal=False):
  """Blockwise-softmax attention; drop-in for the dense einsum path.

  ``q``: ``[batch, heads, seq, head_dim]``; ``k, v``: the same with
  ``heads`` or a divisor of it (grouped query heads: head ``h`` reads
  key/value head ``h // (heads // kv_heads)``); ``attention_mask``:
  ``[batch, seq]`` with 1 = attend, 0 = padding (key side). Optional
  ``q_segment_ids``/``kv_segment_ids`` ``[batch, seq]`` int32 (doc index
  per token, -1 = padding) restrict attention block-diagonally to
  same-document pairs, skipping provably cross-document tiles (see
  module docstring). ``causal`` lets a query see no key after it (the
  two compose: causal within a document), skipping the tiles above the
  diagonal. Returns the context ``[batch, heads, seq, head_dim]`` in the
  input dtype.
  """
  return flash_attention_with_lse(q, k, v, attention_mask, q_segment_ids,
                                  kv_segment_ids, causal)[0]


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


def count_skippable_tiles(segment_ids, block_q=None, block_k=None,
                          causal=False):
  """(total, skipped) forward-grid tile counts for a ``[b, s]``
  segment-id batch under the kernel's interval-disjointness rule (and,
  where ``causal``, its diagonal rule) — the exact host-side account of
  the tiles the Pallas grid will skip (per (batch, q-block, kv-block);
  multiply by heads for per-head counts; the fraction is
  heads-invariant). Feeds the ``train.attn_tiles_*`` telemetry counters
  and the benchmark skip-fraction columns."""
  if block_q is None or block_k is None:
    s_pad = _padded_len(int(segment_ids.shape[1]))
    (grid_q, _), (grid_k, _) = _tile_blocks(s_pad, s_pad)
    block_q, block_k = block_q or grid_q, block_k or grid_k
  import numpy as np
  seg = np.asarray(segment_ids)
  qlo, qhi = segment_block_intervals(seg, block_q)
  klo, khi = segment_block_intervals(seg, block_k)
  live = ((qlo[:, :, None] <= khi[:, None, :]) &
          (klo[:, None, :] <= qhi[:, :, None]))
  if causal:
    q_end = (np.arange(qlo.shape[1]) + 1) * block_q - 1
    k_start = np.arange(klo.shape[1]) * block_k
    live &= (k_start[None, :] <= q_end[:, None])[None]
  total = int(live.size)
  return total, total - int(live.sum())


def make_flash_attention(mesh, q_spec=None, mask_spec=None, causal=False):
  """Wrap :func:`flash_attention` in ``shard_map`` for jitted use over a
  mesh: batch over (data, fsdp), heads over tensor — a ``pallas_call``
  has no GSPMD partitioning rule, so without this the compiler would
  replicate q/k/v onto every chip. The sequence axis must be unsharded
  (flash is per-chip block math; sequence sharding is ring attention's
  job — use ``attention_impl='ring_flash'`` for both).

  The wrapper takes ``(q, k, v, mask, segment_ids)``; ``segment_ids``
  ``[batch, seq]`` (used for both q and kv — self-attention) is sharded
  like the mask, or is None for full attention.
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

  # A None operand is an empty pytree: its spec binds to no array.
  @functools.partial(
      jax.shard_map,
      mesh=mesh,
      in_specs=(q_spec, q_spec, q_spec, mask_spec, mask_spec),
      out_specs=q_spec,
      check_vma=False)
  def _sharded(q, k, v, mask, segment_ids):
    return flash_attention(q, k, v, mask, segment_ids, segment_ids, causal)

  return _sharded
