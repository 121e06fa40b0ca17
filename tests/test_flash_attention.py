"""Pallas flash-attention kernel: parity vs the dense path (interpret
mode on CPU — the same kernel code the TPU runs compiled)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lddl_tpu.ops.flash_attention import flash_attention


def _dense_reference(q, k, v, mask):
  scale = 1.0 / (q.shape[-1] ** 0.5)
  s = jnp.einsum('bhqd,bhkd->bhqk', q.astype(jnp.float32),
                 k.astype(jnp.float32)) * scale
  if mask is not None:
    s = s + jnp.where(mask, 0.0, -1e9)[:, None, None, :]
  p = jax.nn.softmax(s, axis=-1)
  return jnp.einsum('bhqk,bhkd->bhqd', p, v.astype(jnp.float32))


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


def test_ring_flash_matches_dense():
  from lddl_tpu.parallel import make_mesh
  from lddl_tpu.parallel.ring import make_ring_attention
  from jax.sharding import PartitionSpec as P
  mesh = make_mesh(data=1, fsdp=1, tensor=1, seq=4,
                   devices=jax.devices()[:4])
  q, k, v, mask = _inputs(2, 2, 64, 32, seed=2)
  fn = make_ring_attention(mesh, q_spec=P(None, None, 'seq', None),
                           mask_spec=P(None, 'seq'), block_impl='flash')
  out = fn(q, k, v, mask)
  ref = _dense_reference(q, k, v, mask)
  np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                             atol=2e-4)


def test_make_flash_attention_sharded():
  from lddl_tpu.parallel import make_mesh
  from lddl_tpu.ops.flash_attention import make_flash_attention
  mesh = make_mesh()  # data=8 over the virtual CPU devices
  q, k, v, mask = _inputs(8, 2, 64, 32, seed=6)
  out = jax.jit(make_flash_attention(mesh))(q, k, v, mask)
  ref = _dense_reference(q, k, v, mask)
  np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                             atol=2e-5)


def test_make_flash_attention_rejects_seq_mesh():
  from lddl_tpu.parallel import make_mesh
  from lddl_tpu.ops.flash_attention import make_flash_attention
  mesh = make_mesh(seq=2)
  with pytest.raises(ValueError, match='ring_flash'):
    make_flash_attention(mesh)


def test_block_env_overrides():
  """LDDL_FLASH_BLOCK_* env vars must be honored at import (the
  per-shape retuning knob benchmarks rely on; results stay equal across
  blockings — test_multiblock_kv_grid)."""
  import os
  import subprocess
  import sys
  env = dict(os.environ, LDDL_FLASH_BLOCK_Q='256',
             LDDL_FLASH_BLOCK_KV_FWD='512', LDDL_FLASH_BLOCK_KV_BWD='512',
             JAX_PLATFORMS='cpu')
  out = subprocess.run(
      [sys.executable, '-c',
       'from lddl_tpu.ops import flash_attention as fa;'
       'print(fa._BLOCK_Q, fa._BLOCK_KV_FWD, fa._BLOCK_KV_BWD)'],
      env=env, capture_output=True, text=True, check=True,
      cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
  assert out.stdout.split() == ['256', '512', '512']


@pytest.mark.parametrize('caps', [(128, 128), (256, 256)])
def test_multiblock_kv_grid(monkeypatch, caps):
  """Force the innermost kv grid dimension to take multiple steps (the
  default caps of 4096/2048 make every CPU-sized test a single step, so
  the cross-step scratch accumulation — init/rescale/finalize — would
  otherwise go untested). The (256, 256) case also exercises the
  non-divisor overshoot: s=600 pads to 640, which blocks as 256 x 3 =
  768 with -inf-biased padding columns."""
  from lddl_tpu.ops import flash_attention as fa
  cap_fwd, cap_bwd = caps
  monkeypatch.setattr(fa, '_BLOCK_KV_FWD', cap_fwd)
  monkeypatch.setattr(fa, '_BLOCK_KV_BWD', cap_bwd)
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
