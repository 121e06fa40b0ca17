"""Pallas flash-attention kernel: parity vs the dense path (interpret
mode on CPU — the same kernel code the TPU runs compiled)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lddl_tpu.ops.flash_attention import flash_attention


def _dense_with_lse(q, k, v, mask, q_seg=None, kv_seg=None):
  """(out, lse) of the dense path in float32, with the key-side mask and
  the block-diagonal one of the two id arrays."""
  scale = 1.0 / (q.shape[-1] ** 0.5)
  s = jnp.einsum('bhqd,bhkd->bhqk', q.astype(jnp.float32),
                 k.astype(jnp.float32)) * scale
  s = s + jnp.where(mask, 0.0, -1e9)[:, None, None, :]
  if q_seg is not None:
    same = q_seg[:, None, :, None] == kv_seg[:, None, None, :]
    s = s + jnp.where(same, 0.0, -1e9)
  lse = jax.nn.logsumexp(s, axis=-1)
  p = jnp.exp(s - lse[..., None])
  return jnp.einsum('bhqk,bhkd->bhqd', p, v.astype(jnp.float32)), lse


def _dense_reference(q, k, v, mask):
  if mask is None:
    mask = jnp.ones((k.shape[0], k.shape[2]), jnp.int32)
  return _dense_with_lse(q, k, v, mask)[0]


def _inputs(b, h, s, d, seed=0, masked=True):
  rng = np.random.default_rng(seed)
  q = rng.standard_normal((b, h, s, d), dtype=np.float32)
  k = rng.standard_normal((b, h, s, d), dtype=np.float32)
  v = rng.standard_normal((b, h, s, d), dtype=np.float32)
  if masked:
    lens = rng.integers(max(1, s // 2), s + 1, size=(b,))
    mask = (np.arange(s)[None, :] < lens[:, None]).astype(np.int32)
  else:
    mask = None
  return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), (
      None if mask is None else jnp.asarray(mask))


@pytest.mark.parametrize('shape', [
    (2, 2, 64, 32),    # single block
    (1, 3, 128, 64),   # exact block boundary
    (2, 2, 200, 64),   # padded tail (200 -> 256)
    (1, 2, 320, 64),   # multi-block both axes
])
def test_forward_matches_dense(shape):
  q, k, v, mask = _inputs(*shape)
  out = flash_attention(q, k, v, mask)
  ref = _dense_reference(q, k, v, mask)
  np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                             rtol=2e-5, atol=2e-5)


def test_forward_no_mask():
  q, k, v, _ = _inputs(1, 2, 96, 32, masked=False)
  out = flash_attention(q, k, v, None)
  ref = _dense_reference(q, k, v, None)
  np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                             rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize('shape', [(2, 2, 64, 32), (1, 2, 200, 64)])
def test_gradients_match_dense(shape):
  q, k, v, mask = _inputs(*shape, seed=3)
  cot = jnp.asarray(
      np.random.default_rng(9).standard_normal(q.shape, dtype=np.float32))

  def loss_flash(q, k, v):
    return jnp.sum(flash_attention(q, k, v, mask) * cot)

  def loss_dense(q, k, v):
    return jnp.sum(_dense_reference(q, k, v, mask) * cot)

  gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
  gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
  for a, b, name in zip(gf, gd, 'qkv'):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                               atol=2e-4, err_msg=f'd{name}')


def test_bf16_inputs():
  q, k, v, mask = _inputs(1, 2, 128, 64, seed=5)
  qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
  out = flash_attention(qb, kb, vb, mask)
  assert out.dtype == jnp.bfloat16
  ref = _dense_reference(q, k, v, mask)
  np.testing.assert_allclose(
      np.asarray(out, dtype=np.float32), np.asarray(ref), rtol=3e-2,
      atol=3e-2)


def _dense_as_the_model_rounds(q, k, v, mask, seg=None):
  """The dense path of ``models/bert.py`` at ``cfg.dtype`` = the inputs'
  dtype: float32 scores and softmax, probabilities rounded to the
  inputs' dtype before the second product, context in that dtype."""
  scale = 1.0 / (q.shape[-1] ** 0.5)
  s = jnp.einsum('bhqd,bhkd->bhqk', q, k,
                 preferred_element_type=jnp.float32) * scale
  bias = jnp.where(mask, 0.0, -1e9)[:, None, None, :]
  if seg is not None:
    same = seg[:, None, :, None] == seg[:, None, None, :]
    bias = bias + jnp.where(same, 0.0, -1e9)
  p = jax.nn.softmax(s + bias.astype(jnp.float32), axis=-1)
  return jnp.einsum('bhqk,bhkd->bhqd', p.astype(q.dtype), v)


def _two_documents(b, s):
  """Ids of two documents a row, the boundary on no block edge, and the
  mask that goes with them (no padding)."""
  seg = np.where(np.arange(s)[None, :] < int(s * 0.574), 0, 1)
  return (jnp.asarray(np.repeat(seg, b, 0), jnp.int32),
          jnp.ones((b, s), jnp.int32))


@pytest.mark.parametrize('segmented', [False, True],
                         ids=['plain', 'two-documents'])
def test_bf16_gradients_match_dense(monkeypatch, segmented):
  """bfloat16 operands with float32 sums, the computed operands (p, ds)
  rounded where the dense path rounds its probabilities: the gradients
  stay as close to the float32 truth as the dense path's own bfloat16
  gradients do, over several blocks on both axes."""
  from lddl_tpu.ops import flash_attention as fa
  monkeypatch.setattr(fa, '_BLOCK_Q', 128)
  monkeypatch.setattr(fa, '_BLOCK_KV', 256)
  b, h, s, d = 1, 2, 512, 64
  q, k, v, mask = _inputs(b, h, s, d, seed=21)
  seg = None
  if segmented:
    seg, mask = _two_documents(b, s)
  cot = jnp.asarray(
      np.random.default_rng(22).standard_normal(q.shape, dtype=np.float32))
  cot = cot * jnp.asarray(mask, jnp.float32)[:, None, :, None]
  qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))

  def grads(attend, *operands):
    return jax.grad(lambda *a: jnp.sum(attend(*a).astype(jnp.float32) * cot),
                    argnums=(0, 1, 2))(*operands)

  flash = grads(lambda *a: fa.flash_attention(*a, mask, seg, seg), qb, kb, vb)
  rounded = grads(lambda *a: _dense_as_the_model_rounds(*a, mask, seg),
                  qb, kb, vb)
  # The truth: the same bfloat16 values, every step in float32.
  truth = grads(lambda *a: _dense_as_the_model_rounds(*a, mask, seg),
                *(x.astype(jnp.float32) for x in (qb, kb, vb)))
  for got, dense, want, name in zip(flash, rounded, truth, 'qkv'):
    assert got.dtype == jnp.bfloat16
    got, dense, want = (np.asarray(x, np.float32) for x in (got, dense, want))
    norm = np.linalg.norm(want)
    flash_err = np.linalg.norm(got - want) / norm
    dense_err = np.linalg.norm(dense - want) / norm
    assert flash_err < 5e-3, f'd{name}: {flash_err}'
    assert flash_err < 1.25 * dense_err + 2e-4, (
        f'd{name}: flash {flash_err} against dense {dense_err}')
    np.testing.assert_allclose(got, dense, rtol=2e-2, atol=1e-2,
                               err_msg=f'd{name}')


def _kernel_products(dtype, segmented):
  """{kernel name: [(operand dtypes, dimension numbers), ...]} and the
  set of all primitives inside the traced kernels, for a forward
  and backward pass at ``dtype``."""
  b, h, s, d = 1, 2, 256, 64
  x = jnp.ones((b, h, s, d), dtype)
  seg = jnp.zeros((b, s), jnp.int32) if segmented else None

  def loss(q, k, v):
    return jnp.sum(flash_attention(q, k, v, None, seg, seg)
                   .astype(jnp.float32))

  def nested(eqn):
    for value in eqn.params.values():
      for item in value if isinstance(value, (list, tuple)) else [value]:
        item = getattr(item, 'jaxpr', item)
        if hasattr(item, 'eqns'):
          yield item

  products, primitives = {}, set()

  def inside(jaxpr, name):
    for eqn in jaxpr.eqns:
      primitives.add(eqn.primitive.name)
      if eqn.primitive.name == 'dot_general':
        products.setdefault(name, []).append(
            (tuple(var.aval.dtype.name for var in eqn.invars),
             eqn.params['dimension_numbers']))
      for sub in nested(eqn):
        inside(sub, name)

  def outside(jaxpr):
    for eqn in jaxpr.eqns:
      if eqn.primitive.name == 'pallas_call':
        inside(eqn.params['jaxpr'], eqn.params['name'])
      else:
        for sub in nested(eqn):
          outside(sub)

  outside(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, x, x).jaxpr)
  return products, primitives


@pytest.mark.parametrize('segmented', [False, True],
                         ids=['plain', 'segmented'])
@pytest.mark.parametrize('dtype', ['bfloat16', 'float32'])
def test_kernels_feed_the_mxu_the_input_dtype(dtype, segmented):
  """The mechanism engages: with bfloat16 inputs no product inside
  flash_fwd / flash_bwd has a float32 operand; with float32 inputs every
  operand is float32 (today's arithmetic); either way every product sums
  in float32, no tile is transposed by an operation of its own, and the
  backward makes its five products a tile, each gradient's transposed."""
  products, primitives = _kernel_products(jnp.dtype(dtype), segmented)
  minor, major = (1,), (0,)
  per_tile = {
      # q.k^T (both minor dimensions), p.v (a plain product)
      'flash_fwd': [(minor, minor), (minor, major)],
      # k.q^T, dv^T = dO^T.P, v.dO^T, dk^T = q^T.dS, dq^T = k^T.dS^T: the
      # [block_k, block_q] tile contracts its minor dimension or stands
      # as it is; what is turned is a [block, d] operand.
      'flash_bwd': [(minor, minor), (major, minor), (minor, minor),
                    (major, minor), (major, major)],
  }
  assert set(products) == set(per_tile)
  bodies = 2 if segmented else 1  # interior and boundary tiles
  for name, found in products.items():
    assert [contract for _, (contract, _) in found] == per_tile[name] * bodies
    for operands, (_, batch) in found:
      assert operands == (dtype, dtype), (name, operands)
      assert batch == ((), ())
  assert 'transpose' not in primitives


def test_model_flash_impl_matches_dense():
  from lddl_tpu.models import BertConfig, BertForPretraining
  mk = lambda impl: BertForPretraining(
      BertConfig(vocab_size=128, hidden_size=64, num_layers=2, num_heads=2,
                 intermediate_size=128, dtype=jnp.float32,
                 attention_impl=impl))
  rng = np.random.default_rng(0)
  ids = jnp.asarray(rng.integers(0, 128, (2, 64)), jnp.int32)
  types = jnp.zeros((2, 64), jnp.int32)
  mask = jnp.asarray(
      (np.arange(64)[None, :] < np.array([50, 64])[:, None]), jnp.int32)
  dense = mk('dense')
  flash = mk('flash')
  params = dense.init(jax.random.key(0), ids, types, mask)['params']
  mlm_d, nsp_d = dense.apply({'params': params}, ids, types, mask)
  mlm_f, nsp_f = flash.apply({'params': params}, ids, types, mask)
  np.testing.assert_allclose(np.asarray(mlm_f), np.asarray(mlm_d),
                             rtol=1e-4, atol=1e-4)
  np.testing.assert_allclose(np.asarray(nsp_f), np.asarray(nsp_d),
                             rtol=1e-4, atol=1e-4)


def test_lse_cotangent_merge_matches_dense():
  """Gradients must flow correctly through lse when two flash calls over
  disjoint key halves are merged with the streaming-softmax combine (the
  exact structure of the ring composition)."""
  from lddl_tpu.ops.flash_attention import flash_attention_with_lse
  q, k, v, mask = _inputs(2, 2, 64, 32, seed=11)
  half = 32

  def merged(q, k, v):
    o1, l1 = flash_attention_with_lse(q, k[:, :, :half], v[:, :, :half],
                                      mask[:, :half])
    o2, l2 = flash_attention_with_lse(q, k[:, :, half:], v[:, :, half:],
                                      mask[:, half:])
    m = jnp.maximum(l1, l2)
    w1 = jnp.exp(l1 - m)[..., None]
    w2 = jnp.exp(l2 - m)[..., None]
    return (o1 * w1 + o2 * w2) / (w1 + w2)

  def dense(q, k, v):
    return _dense_reference(q, k, v, mask)

  out = merged(q, k, v)
  ref = dense(q, k, v)
  np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                             atol=2e-5)
  cot = jnp.asarray(
      np.random.default_rng(4).standard_normal(q.shape, dtype=np.float32))
  gm = jax.grad(lambda *a: jnp.sum(merged(*a) * cot), argnums=(0, 1, 2))(
      q, k, v)
  gd = jax.grad(lambda *a: jnp.sum(dense(*a) * cot), argnums=(0, 1, 2))(
      q, k, v)
  for a, b, name in zip(gm, gd, 'qkv'):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                               atol=2e-4, err_msg=f'd{name}')


def _ids(*runs):
  """[1, s] ids from (id, length) runs."""
  return np.concatenate([np.full(n, i, np.int32) for i, n in runs])[None, :]


# Each case: (s_q, s_kv, q ids, kv ids, whether lse has a cotangent, dtype,
# q blocks a span). Ids of -1 are padding and come with a masked key.
_BACKWARD_CASES = {
    # The ring's local block: the queries of one shard against the keys
    # of another, other lengths and other documents.
    'unequal-lengths': (72, 200, _ids((0, 40), (1, 32)),
                        _ids((0, 100), (1, 60), (2, 40)), False, 'float32',
                        None),
    # The last q block is all padding: every one of its tiles is skipped,
    # and its dq rows are never written.
    'skipped-rows': (384, 384, _ids((0, 200), (1, 56), (-1, 128)), None,
                     False, 'float32', None),
    'lse-cotangent': (200, 200, None, None, True, 'float32', None),
    'padding-keys': (200, 200, _ids((0, 90), (1, 70), (-1, 40)), None, True,
                     'float32', None),
    'bfloat16': (256, 256, _ids((0, 150), (1, 106)), None, False,
                 'bfloat16', None),
    # Spans of two q blocks and of one: dk and dv summed over two launches.
    'two-spans': (384, 384, _ids((0, 300), (1, 84)), None, True, 'float32',
                  2),
}


@pytest.mark.parametrize('case', list(_BACKWARD_CASES))
def test_backward_cases_match_dense(monkeypatch, case):
  """The one backward kernel against ``jax.grad`` of the dense path, in
  the corners its callers reach (blocks of 128, several on both axes)."""
  from lddl_tpu.ops import flash_attention as fa
  s_q, s_kv, q_ids, kv_ids, lse_cot, dtype, span_blocks = _BACKWARD_CASES[case]
  monkeypatch.setattr(fa, '_BLOCK_Q', 128)
  monkeypatch.setattr(fa, '_BLOCK_KV', 128)
  d = 64
  if span_blocks:
    monkeypatch.setattr(fa, '_DQ_RESIDENT_BYTES', span_blocks * 2 * 4 * d * 128)
  if q_ids is not None and kv_ids is None:
    kv_ids = q_ids
  rng = np.random.default_rng(41)
  q, k, v, cot = (jnp.asarray(rng.standard_normal((1, 1, n, d),
                                                  dtype=np.float32), dtype)
                  for n in (s_q, s_kv, s_kv, s_q))
  mask = jnp.ones((1, s_kv), jnp.int32)
  q_seg = kv_seg = None
  if q_ids is not None:
    q_seg, kv_seg = jnp.asarray(q_ids), jnp.asarray(kv_ids)
    mask = (kv_seg >= 0).astype(jnp.int32)
    # No cotangent reaches a padding query's output or lse.
    cot = cot * (q_seg >= 0)[:, None, :, None].astype(cot.dtype)
  lse_w = jnp.asarray(rng.standard_normal((1, 1, s_q), dtype=np.float32))
  if q_seg is not None:
    lse_w = lse_w * (q_seg >= 0)[:, None, :]

  def loss(attend):
    def fn(q, k, v):
      out, lse = attend(q, k, v, mask, q_seg, kv_seg)
      total = jnp.sum(out.astype(jnp.float32) * cot.astype(jnp.float32))
      return total + jnp.sum(lse * lse_w) if lse_cot else total
    return fn

  flash = jax.grad(loss(fa.flash_attention_with_lse), argnums=(0, 1, 2))(
      q, k, v)
  dense = jax.grad(loss(_dense_with_lse), argnums=(0, 1, 2))(q, k, v)
  tol = dict(rtol=2e-4, atol=2e-4) if dtype == 'float32' else dict(
      rtol=3e-2, atol=3e-2)
  for got, want, name in zip(flash, dense, 'qkv'):
    assert got.dtype == jnp.dtype(dtype)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               err_msg=f'd{name}', **tol)
  if case == 'skipped-rows':
    assert not np.asarray(flash[0])[:, :, 256:].any()
    assert not np.asarray(flash[1])[:, :, 256:].any()


def test_ring_flash_matches_dense():
  from lddl_tpu.parallel import make_mesh
  from lddl_tpu.parallel.ring import make_ring_attention
  from jax.sharding import PartitionSpec as P
  mesh = make_mesh(data=1, fsdp=1, tensor=1, seq=4,
                   devices=jax.devices()[:4])
  q, k, v, mask = _inputs(2, 2, 64, 32, seed=2)
  fn = make_ring_attention(mesh, q_spec=P(None, None, 'seq', None),
                           mask_spec=P(None, 'seq'), block_impl='flash')
  out = fn(q, k, v, mask, None)
  ref = _dense_reference(q, k, v, mask)
  np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                             atol=2e-4)
  # The ring's local block is the one caller of the backward kernel with
  # an lse cotangent and k/v that are not the queries' own.
  cot = jnp.asarray(
      np.random.default_rng(8).standard_normal(q.shape, dtype=np.float32))
  ring = jax.grad(lambda *a: jnp.sum(fn(*a, mask, None) * cot),
                  argnums=(0, 1, 2))(q, k, v)
  dense = jax.grad(lambda *a: jnp.sum(_dense_reference(*a, mask) * cot),
                   argnums=(0, 1, 2))(q, k, v)
  for a, b, name in zip(ring, dense, 'qkv'):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                               atol=2e-4, err_msg=f'd{name}')


def test_make_flash_attention_sharded():
  from lddl_tpu.parallel import make_mesh
  from lddl_tpu.ops.flash_attention import make_flash_attention
  mesh = make_mesh()  # data=8 over the virtual CPU devices
  q, k, v, mask = _inputs(8, 2, 64, 32, seed=6)
  out = jax.jit(make_flash_attention(mesh))(q, k, v, mask, None)
  ref = _dense_reference(q, k, v, mask)
  np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                             atol=2e-5)


def test_make_flash_attention_rejects_seq_mesh():
  from lddl_tpu.parallel import make_mesh
  from lddl_tpu.ops.flash_attention import make_flash_attention
  mesh = make_mesh(seq=2)
  with pytest.raises(ValueError, match='ring_flash'):
    make_flash_attention(mesh)


@pytest.mark.parametrize('s', [128, 600])
def test_one_tile_rule_for_every_launch(monkeypatch, s):
  """``_tile_blocks`` gives one answer a shape: the forward launch, the
  backward launch and ``count_skippable_tiles``' default grid all
  run on it, with or without segment ids (caps made small, so that 600
  is five q blocks by three kv blocks)."""
  from lddl_tpu.ops import flash_attention as fa
  monkeypatch.setattr(fa, '_BLOCK_Q', 128)
  monkeypatch.setattr(fa, '_BLOCK_KV', 256)
  s_pad = fa._padded_len(s)
  (block_q, padded_q), (block_k, padded_kv) = fa._tile_blocks(s_pad, s_pad)
  n_q, n_k = padded_q // block_q, padded_kv // block_k
  assert (n_q, n_k) == ((1, 1) if s == 128 else (5, 3))
  seg = np.zeros((1, s), np.int32)
  seg[:, s // 3:] = 1
  assert fa.count_skippable_tiles(seg)[0] == n_q * n_k
  grids = {}
  real = fa.pl.pallas_call

  def spy(kernel, *, grid, name, **kw):
    grids.setdefault(name, set()).add(grid)
    return real(kernel, grid=grid, name=name, **kw)

  monkeypatch.setattr(fa.pl, 'pallas_call', spy)
  q, k, v, mask = _inputs(1, 2, s, 64, seed=31)
  for ids in (None, jnp.asarray(seg)):
    jax.grad(lambda q: jnp.sum(
        fa.flash_attention(q, k, v, mask, ids, ids)))(q)
  assert grids == {'flash_fwd': {(2, n_q, n_k)}, 'flash_bwd': {(2, n_k, n_q)}}


@pytest.mark.parametrize('block_q', [128, 256, 512])
@pytest.mark.parametrize('cap', [128, 256])
def test_multiblock_kv_grid(monkeypatch, cap, block_q):
  """Force the innermost kv grid dimension to take multiple steps (the
  default caps of 1024 make every CPU-sized test a single step, so
  the cross-step scratch accumulation — init/rescale/finalize — would
  otherwise go untested). The 256 case also exercises the
  non-divisor overshoot: s=600 pads to 640, which blocks as 256 x 3 =
  768 with -inf-biased padding columns; the q block does the same on
  its axis (256: 3 x 256 = 768 with zero query rows sliced away; 512:
  2 x 384)."""
  from lddl_tpu.ops import flash_attention as fa
  monkeypatch.setattr(fa, '_BLOCK_Q', block_q)
  monkeypatch.setattr(fa, '_BLOCK_KV', cap)
  q, k, v, mask = _inputs(1, 2, 600, 64, seed=11)
  out = fa.flash_attention(q, k, v, mask)
  ref = _dense_reference(q, k, v, mask)
  np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                             rtol=2e-5, atol=2e-5)
  cot = jnp.asarray(
      np.random.default_rng(12).standard_normal(q.shape, dtype=np.float32))
  gf = jax.grad(lambda q, k, v: jnp.sum(fa.flash_attention(q, k, v, mask)
                                        * cot), argnums=(0, 1, 2))(q, k, v)
  gd = jax.grad(lambda q, k, v: jnp.sum(_dense_reference(q, k, v, mask)
                                        * cot), argnums=(0, 1, 2))(q, k, v)
  for a, b, name in zip(gf, gd, 'qkv'):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                               atol=2e-4, err_msg=f'd{name}')


def test_interpret_rule_is_cpu_only():
  """Only the cpu backend interprets the kernels; an accelerator under
  any name compiles them or raises — never a silent interpreter."""
  from lddl_tpu.ops.flash_attention import _interpret
  assert _interpret('cpu')
  for backend in ('tpu', 'gpu', 'some-plugin'):
    assert not _interpret(backend)
  assert _interpret() == (jax.default_backend() == 'cpu')
