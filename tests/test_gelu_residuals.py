"""What the FFN's tanh-GELU and the two LayerNorms hand their backward pass
(``models/bert.py:Layer``, ``_keeps_its_input``).

Without remat nothing else remakes them, so each keeps its input (and a
norm its two row sums) and remakes the rest in the backward pass; their
plain linearisation kept six values at the intermediate width a layer for
GELU and six float32 values at the hidden width for the norms. Under remat
the policy decides, and they trace as they did:

  - without remat at most two values at the intermediate width are kept a
    layer (the ``intermediate`` gemm's output and GELU's, which the
    ``output`` gemm's weight gradient takes), where GELU linearised kept six;
  - without remat no float32 value at the hidden width is kept a layer in a
    bfloat16 model: the norms keep their bfloat16 inputs, where linearised
    they kept six float32 values (a float32 model keeps two for six);
  - with remat the saved set is the parent's, value for value;
  - the gradients of a tiny pretraining step are the parent's: bitwise in
    float32, within one bfloat16 step in bfloat16;
  - the parameter tree is the parent's, so the benchmark's adapter still
    maps its seed weights onto it.
"""

import contextlib
import io
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lddl_tpu.models import bert

# At 32 wide the CPU compiler sums a norm's row in another order wherever
# the program's fusions differ, so float32 gradients part in the last bit;
# from 64 wide they are bit-identical.
B, S, LAYERS, HIDDEN, FF = 2, 16, 2, 64, 96


def _linearised(*kinds):
  """``bert._keeps_its_input`` with the ops of ``kinds`` ('gelu', 'norm')
  left linearised where they stand, as they were before the rule took
  them."""
  keeps = bert._keeps_its_input

  def rule(cfg, op):
    kind = 'norm' if isinstance(op, type) else 'gelu'
    return op if kind in kinds else keeps(cfg, op)

  return rule


def _model(remat, dtype, impl='dense'):
  cfg = bert.BertConfig(vocab_size=64, hidden_size=HIDDEN, num_layers=LAYERS,
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


def _layer_stacks(lines, *width):
  # The scan stacks a layer's kept values over its layers: [L, b, s, ...].
  dims = ','.join(map(str, (LAYERS, B, S) + width))
  return [l.split('[')[0] for l in lines if re.match(rf'\w+\[{dims}\] ', l)]


@pytest.mark.parametrize('dtype', [jnp.bfloat16, jnp.float32],
                         ids=['bf16', 'f32'])
@pytest.mark.parametrize('impl', ['dense', 'flash'])
@pytest.mark.parametrize('formulation,expected', [('change', 2),
                                                  ('parent', 6)])
def test_without_remat_gelu_keeps_only_its_input(monkeypatch, formulation,
                                                 expected, impl, dtype):
  if formulation == 'parent':
    monkeypatch.setattr(bert, '_keeps_its_input', _linearised('gelu'))
  assert len(_layer_stacks(_saved(_model(False, dtype, impl)), FF)) == \
      expected


@pytest.mark.parametrize('dtype', [jnp.bfloat16, jnp.float32],
                         ids=['bf16', 'f32'])
@pytest.mark.parametrize('impl', ['dense', 'flash'])
@pytest.mark.parametrize('formulation,expected', [
    ('change', {jnp.bfloat16: {'bf16': 5}, jnp.float32: {'f32': 5}}),
    ('parent', {jnp.bfloat16: {'bf16': 3, 'f32': 6},
                jnp.float32: {'f32': 9}})])
def test_without_remat_the_norms_keep_only_their_inputs(monkeypatch,
                                                        formulation,
                                                        expected, impl, dtype):
  if formulation == 'parent':
    monkeypatch.setattr(bert, '_keeps_its_input', _linearised('norm'))
  saved = _saved(_model(False, dtype, impl))
  at_hidden = _layer_stacks(saved, HIDDEN)
  # The two dropout masks are kept either way; the rest are activations.
  assert at_hidden.count('bool') == 2
  assert {t: at_hidden.count(t) for t in set(at_hidden) - {'bool'}} == \
      expected[dtype]
  # Four float32 values a row and layer either way: the linearised norms'
  # statistics, or the row sums (of x and x**2) the change keeps.
  assert _layer_stacks(saved) == ['f32'] * 4


@pytest.mark.parametrize('impl', ['dense', 'flash'])
def test_under_remat_the_saved_set_is_the_parents(monkeypatch, impl):
  change = _saved(_model(True, jnp.bfloat16, impl))
  monkeypatch.setattr(bert, '_keeps_its_input', _linearised('gelu', 'norm'))
  parent = _saved(_model(True, jnp.bfloat16, impl))
  assert change == parent
  # The policy's one value at the intermediate width: intermediate_out.
  assert len(_layer_stacks(change, FF)) == 1


def _grads(model, compiler_options=None):
  loss, params = _loss_and_params(model)
  return jax.jit(jax.grad(loss)).lower(params).compile(compiler_options)(
      params)


def _assert_same_gradients(change, parent, dtype):
  for (path, c), p in zip(jax.tree_util.tree_flatten_with_path(change)[0],
                          jax.tree.leaves(parent)):
    c, p = np.asarray(c), np.asarray(p)
    if dtype == jnp.float32:
      np.testing.assert_array_equal(c, p, err_msg=jax.tree_util.keystr(path))
    else:
      # One bfloat16 step (8 significant bits) of the parent's value.
      step = np.exp2(np.floor(np.log2(np.maximum(np.abs(p), 1e-30))) - 7)
      assert np.all(np.abs(c - p) <= step), jax.tree_util.keystr(path)


@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16],
                         ids=['f32', 'bf16'])
def test_gradients_are_the_parents(monkeypatch, dtype):
  change = _grads(_model(False, dtype))
  monkeypatch.setattr(bert, '_keeps_its_input', _linearised('gelu'))
  parent = _grads(_model(False, dtype))
  _assert_same_gradients(change, parent, dtype)


@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16],
                         ids=['f32', 'bf16'])
@pytest.mark.parametrize('impl', ['dense', 'flash'])
def test_norm_gradients_are_the_parents(monkeypatch, impl, dtype):
  # The norms' backward is fused otherwise than the parent's, and the CPU
  # compiler may leave a bfloat16 rounding out inside a fusion (its excess
  # precision), in one program and not in the other: both are held to
  # every rounding their arithmetic asks for.
  exact = {'xla_allow_excess_precision': False}
  change = _grads(_model(False, dtype, impl), exact)
  monkeypatch.setattr(bert, '_keeps_its_input', _linearised('norm'))
  parent = _grads(_model(False, dtype, impl), exact)
  _assert_same_gradients(change, parent, dtype)


@pytest.mark.parametrize('remat', [False, True], ids=['no_remat', 'remat'])
def test_the_parameter_tree_is_the_parents(monkeypatch, remat):
  from chipbench import adapter
  _, change = _loss_and_params(_model(remat, jnp.bfloat16))
  monkeypatch.setattr(bert, '_keeps_its_input', _linearised('norm'))
  _, parent = _loss_and_params(_model(remat, jnp.bfloat16))
  assert jax.tree.structure(change) == jax.tree.structure(parent)
  assert jax.tree.map(jnp.shape, change) == jax.tree.map(jnp.shape, parent)
  adapter.check_tree({'hidden_size': HIDDEN, 'intermediate_size': FF,
                      'num_hidden_layers': LAYERS, 'vocab_size': 64,
                      'max_position_embeddings': S, 'type_vocab_size': 2},
                     change)
