"""What ``nn.remat(Layer)`` keeps (``ops.attention.REMAT_KEPT_NAMES``): the
outputs of five of the layer's six projections (not of ``out``, which the
chip remakes for less than keeping it costs) and its context, which is the
flash forward kernel's ``(out, lse)`` or the dense path's p.v.

  - a rematted flash layer runs ``flash_fwd`` once in ``jax.grad``, not
    twice, and with the names dropped it runs twice again;
  - the recomputation holds two matrix products (q.k^T and ``out``) on
    the dense path and one (``out``) on the flash path; with the
    projections' names dropped it holds the layer's eight, or its six
    beside the kernel;
  - keeping the values changes no gradient, on any path, mesh or none;
  - without remat a name is nothing: the step lowers to the text it has
    with ``checkpoint_name`` patched to the identity;
  - a dense model never imports the Pallas module.
"""

import os
import re
import subprocess
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lddl_tpu.ops.attention import FLASH_RESIDUAL_NAMES, REMAT_KEPT_NAMES

KEEP = jax.checkpoint_policies.save_only_these_names(*FLASH_RESIDUAL_NAMES)
B, S = 2, 32


def _model(impl, remat, mesh=None, num_layers=3):
  from lddl_tpu.models import BertConfig, BertForPretraining
  cfg = BertConfig(vocab_size=64, hidden_size=32, num_layers=num_layers,
                   num_heads=2, intermediate_size=64,
                   max_position_embeddings=S, dtype=jnp.float32,
                   attention_impl=impl, remat=remat)
  return BertForPretraining(cfg, mesh=mesh)


def _batch(segmented):
  rng = np.random.default_rng(0)
  ids = jnp.asarray(rng.integers(0, 64, (B, S)), jnp.int32)
  # Two documents a row and a padded tail, as the packed loader gives them.
  seg = np.repeat([[0, 1, -1]], [14, 12, 6], axis=1).repeat(B, 0)
  mask = jnp.asarray(seg >= 0, jnp.int32)
  return ids, jnp.zeros_like(ids), mask, (
      jnp.asarray(seg, jnp.int32) if segmented else None)


def _loss_fn(model, segmented):
  """params -> scalar, dropout on and drawn from one fixed key."""
  ids, types, mask, seg = _batch(segmented)

  def loss(params):
    mlm, nsp = model.apply(
        {'params': params}, ids, types, mask, deterministic=False,
        segment_ids=seg, rngs={'dropout': jax.random.key(7)})
    return jnp.mean(mlm ** 2) + jnp.mean(nsp ** 2)

  return loss, lambda: model.init(jax.random.key(0), ids, types, mask)['params']


def _with_policy(monkeypatch, policy):
  """``nn.remat(Layer)`` under another policy: ``None`` is what it was
  before any, ``KEEP`` what PR 32 left (the projections' names dropped)."""
  remat = nn.remat
  monkeypatch.setattr(nn, 'remat',
                      lambda layer, **_: remat(layer, policy=policy))


def _count(pattern, fn, *args):
  return len(re.findall(pattern, str(jax.make_jaxpr(fn)(*args))))


def _count_flash_fwd(fn, *args):
  return _count(r'name=flash_fwd\b', fn, *args)


@pytest.mark.parametrize('segmented', [False, True],
                         ids=['full', 'block-diagonal'])
@pytest.mark.parametrize('policy,expected', [('names', 1), ('none', 2)])
def test_rematted_flash_layer_runs_the_forward_kernel_once(
    monkeypatch, policy, expected, segmented):
  # The scan holds one layer body whatever num_layers is: the forward
  # pass's kernel, and under policy=None the backward pass's second.
  if policy == 'none':
    _with_policy(monkeypatch, None)
  loss, init = _loss_fn(_model('flash', remat=True), segmented)
  assert _count_flash_fwd(jax.grad(loss), init()) == expected


def _count_products(impl, remat, segmented):
  loss, init = _loss_fn(_model(impl, remat=remat), segmented)
  return _count(r'\bdot_general\b', jax.grad(loss), init())


@pytest.mark.parametrize('segmented', [False, True],
                         ids=['full', 'block-diagonal'])
@pytest.mark.parametrize('impl,policy,expected', [
    ('dense', 'names', 2),  # q.k^T for the softmax that is remade; out
    ('dense', 'flash names alone', 8),
    ('flash', 'names', 1),  # out
    ('flash', 'flash names alone', 6),
])
def test_the_recomputation_of_a_rematted_layer_holds_these_products(
    monkeypatch, impl, policy, expected, segmented):
  # What jax.grad of the rematted model holds beyond the plain model's
  # forward and backward products is what the backward pass remakes (one
  # scanned layer body, whatever num_layers is; the kernels' own products
  # are in both counts).
  if policy == 'flash names alone':
    _with_policy(monkeypatch, KEEP)
  remade = (_count_products(impl, True, segmented) -
            _count_products(impl, False, segmented))
  assert remade == expected


def test_every_kept_name_is_tagged_by_the_layer_that_runs():
  # A misspelt name keeps nothing and says nothing: each name of the tuple
  # is in the traced layer of the back-end that owns it, and in no other
  # (the kernel's names are in its forward rule, so under jax.grad).
  def tagged(impl):
    loss, init = _loss_fn(_model(impl, remat=False, num_layers=1), True)
    return set(re.findall(r'name\[name=(\w+)\]',
                          str(jax.make_jaxpr(jax.grad(loss))(init()))))

  context = set(FLASH_RESIDUAL_NAMES) | {'dense_context'}
  shared = set(REMAT_KEPT_NAMES) - context
  assert len(shared) == 5
  assert tagged('dense') == shared | {'dense_context'}
  assert tagged('flash') == shared | set(FLASH_RESIDUAL_NAMES)


def _mesh(**axes):
  from lddl_tpu.parallel import make_mesh
  n = int(np.prod(list(axes.values())))
  return make_mesh(**axes, devices=jax.devices()[:n])


@pytest.mark.parametrize('impl,axes', [
    ('flash', None),
    ('flash', dict(data=2)),
    ('ring_flash', dict(data=1, fsdp=1, tensor=1, seq=2)),
    ('dense', None),
    ('dense', dict(data=1, fsdp=1, tensor=2)),
])
def test_keeping_the_residuals_changes_no_gradient(impl, axes):
  from lddl_tpu.parallel.train import init_params
  mesh = _mesh(**axes) if axes else None
  grads = {}
  for remat in (False, True):
    model = _model(impl, remat, mesh, num_layers=1)
    loss, init = _loss_fn(model, True)
    if axes and axes.get('tensor', 1) > 1:
      # The parameters placed as a tensor-parallel job places them: the
      # kept values are then sharded as their gemms' outputs are.
      init = lambda: init_params(model, mesh, jax.random.key(0), seq_len=S)
    grads[remat] = jax.jit(jax.grad(loss))(init())
  for (path, kept), plain in zip(
      jax.tree_util.tree_flatten_with_path(grads[True])[0],
      jax.tree.leaves(grads[False])):
    np.testing.assert_allclose(np.asarray(kept), np.asarray(plain),
                               rtol=2e-4, atol=2e-6,
                               err_msg=jax.tree_util.keystr(path))


def test_lse_cotangent_under_the_policy_keeps_both_residuals(capsys):
  """The ring's merge: two flash calls over disjoint key halves, combined
  through their ``lse``, inside ``jax.checkpoint`` with the policy."""
  from lddl_tpu.ops.flash_attention import flash_attention_with_lse
  rng = np.random.default_rng(11)
  q, k, v, cot = (jnp.asarray(rng.standard_normal((2, 2, 64, 32),
                                                  dtype=np.float32))
                  for _ in range(4))
  half = 32

  def merged(q, k, v):
    o1, l1 = flash_attention_with_lse(q, k[:, :, :half], v[:, :, :half])
    o2, l2 = flash_attention_with_lse(q, k[:, :, half:], v[:, :, half:])
    m = jnp.maximum(l1, l2)
    w1, w2 = jnp.exp(l1 - m)[..., None], jnp.exp(l2 - m)[..., None]
    return jnp.sum((o1 * w1 + o2 * w2) / (w1 + w2) * cot)

  kept, remade = jax.checkpoint(merged, policy=KEEP), jax.checkpoint(merged)
  # Each call's out, as the lane-dense view it is named in, and its lse.
  for fn, times in ((kept, 2), (remade, 0)):
    jax.ad_checkpoint.print_saved_residuals(fn, q, k, v)
    saved = capsys.readouterr().out
    assert saved.count('f32[4,16,128] ') == times, saved
    assert saved.count('f32[4,64] ') == times, saved
  grad = lambda f: jax.grad(f, argnums=(0, 1, 2))
  assert _count_flash_fwd(grad(kept), q, k, v) == 2
  assert _count_flash_fwd(grad(remade), q, k, v) == 4
  for a, b in zip(grad(kept)(q, k, v), grad(merged)(q, k, v)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=2e-6)


def _lowered_dense_step():
  import optax

  from lddl_tpu.parallel import make_mesh, make_train_step
  from lddl_tpu.parallel.train import init_params
  mesh = make_mesh(data=1, devices=jax.devices()[:1])
  model = _model('dense', remat=False, num_layers=2)
  tx = optax.adamw(1e-4)
  params = init_params(model, mesh, jax.random.key(0), seq_len=S)
  ids, types, mask, seg = _batch(True)
  batch = {
      'input_ids': ids, 'token_type_ids': types, 'attention_mask': mask,
      'segment_ids': seg,
      'labels': jnp.full((B, S), -100, jnp.int32).at[:, 1::7].set(5),
      'next_sentence_labels': jnp.zeros((B,), jnp.int32),
  }
  step = make_train_step(model, tx, mesh, max_predictions=8)
  text = step.lower(params, jax.jit(tx.init)(params), jax.random.key(1),
                    batch).as_text()
  # A private function's symbol ends in the number of the equation that
  # called it, and a name is an equation (that lowers to nothing).
  return re.sub(r'(@\w+?)_\d+\b', r'\1', text)


def test_step_without_remat_lowers_to_the_program_it_was(monkeypatch):
  # Outside remat a name is the identity: the step of a model that does
  # not remat (bert-base.pairs-s128's) is the text it is with no name.
  from lddl_tpu.models import bert
  with_names = _lowered_dense_step()
  assert 'dot_general' in with_names
  monkeypatch.setattr(bert, 'checkpoint_name', lambda x, name: x)
  assert with_names == _lowered_dense_step()


def test_dense_model_does_not_import_the_flash_module():
  code = (
      'import sys, jax, jax.numpy as jnp\n'
      'from lddl_tpu.models import BertConfig, BertForPretraining\n'
      'cfg = BertConfig(vocab_size=64, hidden_size=32, num_layers=2,\n'
      '                 num_heads=2, intermediate_size=64, remat=True)\n'
      'ids = jnp.ones((2, 16), jnp.int32)\n'
      'model = BertForPretraining(cfg)\n'
      '# Traced, not compiled: tracing is what would import a back-end.\n'
      'params = jax.eval_shape(model.init, jax.random.key(0), ids, ids, ids)\n'
      'jax.eval_shape(jax.grad(\n'
      '    lambda p: model.apply(p, ids, ids, ids)[0].sum()), params)\n'
      "assert 'lddl_tpu.ops.attention' in sys.modules\n"
      "assert 'lddl_tpu.ops.flash_attention' not in sys.modules\n"
      "assert not any(m.startswith('jax.experimental.pallas')\n"
      '               for m in sys.modules)\n')
  subprocess.run([sys.executable, '-c', code], check=True,
                 env={**os.environ, 'JAX_PLATFORMS': 'cpu'})
