"""What the FFN's tanh-GELU hands its backward pass (``models/bert.py:Layer``).

Without remat nothing else remakes the GELU, so it keeps its input alone
and remakes tanh and its derivative in the backward pass; its plain
linearisation, the parent's formulation, kept six values at the
intermediate width a layer. Under remat the policy decides, and the GELU
traces as it did:

  - without remat at most two values at the intermediate width are kept a
    layer (the ``intermediate`` gemm's output and GELU's, which the
    ``output`` gemm's weight gradient takes), where the parent kept six;
  - with remat the saved set is the parent's, value for value;
  - the gradients of a tiny pretraining step are the parent's: bitwise in
    float32, within one bfloat16 step in bfloat16.
"""

import contextlib
import io
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lddl_tpu.models import bert

B, S, LAYERS, FF = 2, 16, 2, 96


class _ParentJax:
  """``bert``'s ``jax`` as the parent formulation sees it: GELU under no
  checkpoint, linearised where it stands. Everything else is jax's."""
  checkpoint = staticmethod(lambda fn, **_: fn)

  def __getattr__(self, name):
    return getattr(jax, name)


def _model(remat, dtype, impl='dense'):
  cfg = bert.BertConfig(vocab_size=64, hidden_size=32, num_layers=LAYERS,
                        num_heads=2, intermediate_size=FF,
                        max_position_embeddings=S, dtype=dtype,
                        attention_impl=impl, remat=remat)
  return bert.BertForPretraining(cfg)


def _batch():
  rng = np.random.default_rng(3)
  ids = jnp.asarray(rng.integers(0, 64, (B, S)), jnp.int32)
  return {
      'input_ids': ids,
      'token_type_ids': jnp.zeros_like(ids).at[:, S // 2:].set(1),
      'attention_mask': jnp.ones_like(ids).at[1, -3:].set(0),
      'labels': jnp.full((B, S), -100, jnp.int32).at[:, 1::5].set(7),
      'next_sentence_labels': jnp.asarray([0, 1], jnp.int32),
  }


def _loss_and_params(model):
  """The pretraining loss of one batch, dropout on from one fixed key."""
  from lddl_tpu.parallel.train import pretrain_loss
  batch = _batch()
  params = model.init(jax.random.key(0), batch['input_ids'],
                      batch['token_type_ids'],
                      batch['attention_mask'])['params']

  def loss(p):
    return pretrain_loss(model, p, batch, dropout_rng=jax.random.key(5),
                         max_predictions=4)[0]

  return loss, params


def _saved(model):
  """What jax.grad of the loss keeps for the backward pass, one line a
  value (``print_saved_residuals``' own words)."""
  loss, params = _loss_and_params(model)
  out = io.StringIO()
  with contextlib.redirect_stdout(out):
    jax.ad_checkpoint.print_saved_residuals(loss, params)
  return out.getvalue().splitlines()


def _layer_stacks_at_ff(lines):
  # The scan stacks a layer's kept values over its layers: [L, b, s, FF].
  return [l for l in lines if re.match(rf'\w+\[{LAYERS},{B},{S},{FF}\] ', l)]


@pytest.mark.parametrize('dtype', [jnp.bfloat16, jnp.float32],
                         ids=['bf16', 'f32'])
@pytest.mark.parametrize('impl', ['dense', 'flash'])
@pytest.mark.parametrize('formulation,expected', [('change', 2),
                                                  ('parent', 6)])
def test_without_remat_gelu_keeps_only_its_input(monkeypatch, formulation,
                                                 expected, impl, dtype):
  if formulation == 'parent':
    monkeypatch.setattr(bert, 'jax', _ParentJax())
  assert len(_layer_stacks_at_ff(_saved(_model(False, dtype, impl)))) == \
      expected


@pytest.mark.parametrize('impl', ['dense', 'flash'])
def test_under_remat_the_saved_set_is_the_parents(monkeypatch, impl):
  change = _saved(_model(True, jnp.bfloat16, impl))
  monkeypatch.setattr(bert, 'jax', _ParentJax())
  parent = _saved(_model(True, jnp.bfloat16, impl))
  assert change == parent
  # The policy's one value at the intermediate width: intermediate_out.
  assert len(_layer_stacks_at_ff(change)) == 1


def _grads(model):
  loss, params = _loss_and_params(model)
  return jax.jit(jax.grad(loss))(params)


@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16],
                         ids=['f32', 'bf16'])
def test_gradients_are_the_parents(monkeypatch, dtype):
  change = _grads(_model(False, dtype))
  monkeypatch.setattr(bert, 'jax', _ParentJax())
  parent = _grads(_model(False, dtype))
  for (path, c), p in zip(jax.tree_util.tree_flatten_with_path(change)[0],
                          jax.tree.leaves(parent)):
    c, p = np.asarray(c), np.asarray(p)
    if dtype == jnp.float32:
      np.testing.assert_array_equal(c, p, err_msg=jax.tree_util.keystr(path))
    else:
      # One bfloat16 step (8 significant bits) of the parent's value.
      step = np.exp2(np.floor(np.log2(np.maximum(np.abs(p), 1e-30))) - 7)
      assert np.all(np.abs(c - p) <= step), jax.tree_util.keystr(path)
