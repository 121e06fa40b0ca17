"""The train step as the TPU compiler builds it, for a described v5e chip
that is not attached (nothing runs; see the ``on-chip-measurement`` guide).

Without remat the FFN's GELU and the layer's two LayerNorms keep only their
inputs (``models/bert.py:_keeps_its_input``), and what that buys rests on the
compiler, not on JAX:

  - the forward scan stores two values at the intermediate width a layer
    (the ``intermediate`` gemm's output and GELU's), where it stored six;
  - the remade tanh sits in the backward ``output`` gemm's own fusion, so
    GELU's derivative is never written out (with a CSE barrier around the
    remade GELU the compiler puts it in a loop fusion of its own);
  - no float32 value at the hidden width is stacked over the layers, where
    the norms stored six a layer, and what the backward remakes of a norm
    on its own is per row: the centred input is remade inside the fusions
    that consume it, never written out.
"""

import re

import jax
import jax.numpy as jnp
import pytest

B, S, LAYERS, HIDDEN, FF = 8, 128, 2, 256, 1024


@pytest.fixture(scope='module')
def topo():
  from jax.experimental import topologies
  try:
    return topologies.get_topology_desc(platform='tpu',
                                        topology_name='v5e:2x2')
  except Exception as e:  # no TPU compiler to describe one with
    pytest.skip(f'no v5e:2x2 topology can be described here: {e}')


@pytest.fixture(scope='module')
def step_text(topo):
  """The compiled text of jax.grad of a small no-remat model's loss."""
  from jax.sharding import SingleDeviceSharding

  from lddl_tpu.models import BertConfig, BertForPretraining
  from lddl_tpu.parallel.train import pretrain_loss
  model = BertForPretraining(BertConfig(
      vocab_size=256, hidden_size=HIDDEN, num_layers=LAYERS, num_heads=4,
      intermediate_size=FF, max_position_embeddings=S))
  chip = SingleDeviceSharding(topo.devices[0])
  on_chip = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)
  ids = jax.ShapeDtypeStruct((B, S), jnp.int32)
  params = jax.eval_shape(
      lambda i: model.init(jax.random.key(0), i, i, i)['params'], ids)
  batch = {k: ids for k in ('input_ids', 'token_type_ids', 'attention_mask',
                            'labels')}
  batch['next_sentence_labels'] = jax.ShapeDtypeStruct((B,), jnp.int32)

  def grad(p, b):
    return jax.grad(lambda p: pretrain_loss(
        model, p, b, dropout_rng=jax.random.key(1), max_predictions=20)[0])(p)

  return jax.jit(grad).lower(jax.tree.map(on_chip, params),
                             jax.tree.map(on_chip, batch)).compile().as_text()


def test_the_forward_scan_stores_two_values_at_the_intermediate_width(
    step_text):
  stored = re.findall(
      rf'= bf16\[{LAYERS},{B},{S},{FF}\]\S* dynamic-update-slice\(',
      step_text)
  assert len(stored) == 2


def test_the_remade_tanh_is_fused_into_the_output_gemms_backward(step_text):
  bodies = re.findall(r'^%?([\w.\-]+) \([^\n]*\) -> [^\n]*\{\n(.*?)\n\}',
                      step_text, re.M | re.S)
  with_tanh = {name for name, body in bodies if ' tanh(' in body}
  fusions = re.findall(
      r'fusion\(.*?kind=(\w+), calls=%([\w.\-]+).*?op_name="([^"]*)"',
      step_text)
  assert [kind for kind, called, op in fusions
          if called in with_tanh and op.startswith('jit(grad)/transpose(')
          and 'layers/output/dot_general' in op] == ['kOutput']


def test_no_float32_value_at_the_hidden_width_is_stacked(step_text):
  assert not re.findall(rf'f32\[{LAYERS},{B},{S},{HIDDEN}\]', step_text)


def test_the_remade_norms_write_nothing_at_the_hidden_width(step_text):
  # What the backward remakes under the norms' checkpoint and fuses apart
  # from its consumers: the per-row scale, f32[B, S], and nothing wider.
  fusions = re.findall(
      r'= (\(?[^=]*?\)?) fusion\(.*?op_name="([^"]*)"', step_text)
  remade = [shapes for shapes, op in fusions
            if op.startswith('jit(grad)/transpose(')
            and 'rematted_computation' in op
            and re.search(r'/(attention|output)_norm/', op)]
  assert remade
  for shapes in remade:
    assert set(re.findall(r'\w+\[[\d,]*\]', shapes)) == {f'f32[{B},{S}]'}
