"""The train step's scopes: every part of the step has a name the capture
summary (``lddl_tpu/telemetry/capture.py``) can bill device time to, and
naming changed no arithmetic.

  - the compiled step's ``op_name`` s hold every module class and every
    pass, and nothing of the optimizer or the loss is left without a
    scope;
  - ``TrainLoop.run`` on the ``tiny`` preset, dropout on, gives the loss
    sequence the tree before PR 25 (commit 16d149e, no scopes, no phases)
    gave on this installation, to the last bit, and the same parameter
    paths: a ``jax.named_scope`` and a ``pallas_call`` name are metadata,
    and flax folds the *module* path into each dropout key, which no scope
    touches.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from lddl_tpu.telemetry.capture import (CLASSES, DECODER_CLASSES, PASSES,
                                        classify)

from test_loader import BIN_SIZE, binned_shards  # noqa: F401


def _tiny_config(**kwargs):
  from lddl_tpu.models import BertConfig
  from lddl_tpu.training.pretrain import MODEL_SIZES
  return BertConfig(vocab_size=64, max_position_embeddings=128, remat=True,
                    **MODEL_SIZES['tiny'], **kwargs)


def _compiled_op_names(**kwargs):
  """The ``op_name`` of every instruction of the compiled tiny step (remat
  and dropout on, masked-only head: the cells' program in small)."""
  import optax

  from lddl_tpu.models import BertForPretraining
  from lddl_tpu.parallel import make_mesh, make_train_step
  from lddl_tpu.parallel.train import init_params
  mesh = make_mesh(data=1, devices=jax.devices()[:1])
  model = BertForPretraining(_tiny_config(**kwargs))
  tx = optax.adamw(1e-4)
  params = init_params(model, mesh, jax.random.key(0), seq_len=128)
  opt_state = jax.jit(tx.init)(params)
  b, s = 8, 128
  batch = {
      'input_ids': jnp.ones((b, s), jnp.int32),
      'token_type_ids': jnp.zeros((b, s), jnp.int32),
      'attention_mask': jnp.ones((b, s), jnp.int32),
      'labels': jnp.full((b, s), -100, jnp.int32).at[:, 1::7].set(5),
      'next_sentence_labels': jnp.zeros((b,), jnp.int32),
  }
  step = make_train_step(model, tx, mesh, max_predictions=20)
  text = step.lower(params, opt_state, jax.random.key(1),
                    batch).compile().as_text()
  return re.findall(r'op_name="([^"]*)"', text)


@pytest.fixture(scope='module')
def op_names():
  return _compiled_op_names()


@pytest.fixture(scope='module')
def flash_op_names():
  return _compiled_op_names(attention_impl='flash')


# scan_carry: the layer scan's own traffic, which no module owns. BERT has
# no experts and no convolution (tests/test_lfm2_family.py has them).
@pytest.mark.parametrize('module_class', [
    c for c in CLASSES if c != 'unscoped' and c not in DECODER_CLASSES])
def test_every_module_class_occurs_in_the_compiled_step(op_names,
                                                        module_class):
  assert any(classify(n)[0] == module_class for n in op_names)


@pytest.mark.parametrize('pass_', PASSES)
def test_every_pass_occurs_in_the_compiled_step(op_names, pass_):
  assert any(classify(n)[1] == pass_ for n in op_names)


def test_nothing_of_the_optimizer_or_the_loss_is_unscoped(op_names):
  # Whatever the step does outside the model (the loss, the gradient
  # norm, the update, the dropout key) runs under jit(step) and not under
  # BertForPretraining: all of it has a class.
  outside = [n for n in op_names
             if n.startswith('jit(step)/') and 'BertForPretraining' not in n]
  assert len(outside) > 500  # the update alone is hundreds of instructions
  assert [n for n in outside if classify(n)[0] == 'unscoped'] == []
  for scope in ('loss', 'optimizer', 'grad_norm'):
    assert any(re.search(rf'[/(]{scope}[/)]', n) for n in outside), scope


LAYER = 'encoder/while/body/closed_call/layers.body/'
REMADE = (f'jit(step)/transpose(jvp(BertForPretraining))/{LAYER}layers.body/'
          'checkpoint/rematted_computation/layers/')


def _products(names, pass_):
  return sorted({n.split('layers/')[-1] for n in names
                 if n.endswith('/dot_general') and LAYER in n
                 and classify(n)[1] == pass_})


GEMMS = ['attention/key/dot_general', 'attention/out/dot_general',
         'attention/query/dot_general', 'attention/value/dot_general',
         'intermediate/dot_general', 'output/dot_general']
SCORES, CONTEXT, OUT = ('attention/bhqd,bhkd->bhqk/dot_general',
                        'attention/bhqk,bhkd->bhqd/dot_general',
                        'attention/out/dot_general')


def test_the_recompute_pass_of_a_dense_layer_holds_scores_and_out(op_names):
  # Five projections' outputs and the context are kept by name
  # (models/bert.py): they run in the forward pass, their two gradients in
  # the backward pass.
  assert _products(op_names, 'forward') == sorted(GEMMS + [SCORES, CONTEXT])
  assert _products(op_names, 'backward') == sorted(GEMMS + [SCORES, CONTEXT])
  assert _products(op_names, 'recompute') == [SCORES, OUT]
  assert classify(REMADE + SCORES) == ('attention', 'recompute')
  assert classify(REMADE + OUT) == ('attention', 'recompute')


def test_the_recompute_pass_of_a_flash_layer_holds_out_alone(flash_op_names):
  in_kernels = [n for n in flash_op_names if re.search(r'/flash_\w+/', n)]
  assert {classify(n)[0] for n in in_kernels} == {'attention'}
  assert 'recompute' not in {classify(n)[1] for n in in_kernels}
  outside = [n for n in flash_op_names if n not in in_kernels]
  assert _products(outside, 'forward') == GEMMS
  assert _products(outside, 'backward') == GEMMS
  assert _products(outside, 'recompute') == [OUT]


@pytest.mark.parametrize('names', ['op_names', 'flash_op_names'])
def test_what_no_module_owns_in_the_encoder(names, request):
  # The kept values' stores and loads are the scan's own traffic
  # (scan_carry); beside them stand only jax's own two: the remat call's
  # container and the reduce_precision it puts on a kept value's producer,
  # which the TPU compiler folds into that producer (a bfloat16 value
  # reduced to bfloat16).
  names = [n for n in request.getfixturevalue(names) if '/encoder/' in n]
  assert {n.rsplit('/', 1)[-1] for n in names
          if classify(n)[0] == 'unscoped'} == {'reduce_precision', 'remat2'}
  carried = {(n.rsplit('/', 1)[-1], classify(n)[1]) for n in names
             if classify(n)[0] == 'scan_carry'}
  assert {('dynamic_update_slice', 'forward'),
          ('dynamic_slice', 'backward')} <= carried


@pytest.mark.parametrize('op_name,expected', [
    ('jit(step)/jvp(BertForPretraining)/encoder/while/body/closed_call/'
     'layers.body/layers/attention/query/dot_general',
     ('attention', 'forward')),
    ('jit(step)/transpose(jvp(BertForPretraining))/encoder/while/body/'
     'closed_call/layers.body/layers.body/checkpoint/rematted_computation/'
     'layers/attention/Dropout_0/jit(_bernoulli)/jit(_uniform)/threefry2x32',
     ('dropout', 'recompute')),
    ('jit(step)/transpose(jvp(BertForPretraining))/encoder/while/body/'
     'closed_call/layers.body/layers.body/checkpoint/layers/output/'
     'dot_general', ('ffn', 'backward')),
    ('jit(step)/jvp(BertForPretraining)/encoder/while/body/closed_call/'
     'layers.body/layers/gelu/tanh', ('ffn', 'forward')),
    ('jit(step)/jvp(BertForPretraining)/encoder/while/body/closed_call/'
     'layers.body/layers/residual/add', ('norms', 'forward')),
    ('jit(step)/jvp(BertForPretraining)/encoder/while/body/closed_call/'
     'layers.body/layers/attention_norm/rsqrt', ('norms', 'forward')),
    ('jit(step)/jvp(BertForPretraining)/embed/embed_norm/rsqrt',
     ('embed', 'forward')),
    ('jit(step)/jvp(BertForPretraining)/embed_dropout/jit(_bernoulli)/lt',
     ('dropout', 'forward')),
    ('jit(step)/jvp(BertForPretraining)/mlm_head/word_embeddings.attend/'
     'dot_general', ('head_loss', 'forward')),
    ('jit(step)/jvp(BertForPretraining)/mlm_head/mlm_norm/rsqrt',
     ('head_loss', 'forward')),
    ('jit(step)/transpose(jvp(loss))/jit(take_along_axis)/scatter-add',
     ('head_loss', 'backward')),
    ('jit(step)/jvp(BertForPretraining)/encoder/while/body/'
     'dynamic_update_slice', ('scan_carry', 'forward')),
    ('jit(step)/optimizer/add', ('optimizer', 'update')),
    ('jit(step)/grad_norm/sqrt', ('optimizer', 'update')),
    ('jit(step)/dropout_key/jit(_threefry_fold_in)/threefry2x32',
     ('dropout', 'update')),
    ('jit(step)/jvp(BertForPretraining)/encoder/while/body/closed_call/'
     'layers.body/layers/attention/jvp(flash_fwd)/pallas_call',
     ('attention', 'forward')),
    # Under the remat policy (tests/test_remat_policy.py) the backward
    # pass's kernels stand outside the rematted computation, and the kept
    # (out, lse) travel through the scan's stacked buffers: the `name`
    # itself lowers to nothing.
    ('jit(step)/transpose(jvp(BertForPretraining))/encoder/while/body/'
     'closed_call/layers.body/layers.body/checkpoint/layers/attention/'
     'flash_bwd/pallas_call', ('attention', 'backward')),
    ('jit(step)/transpose(jvp(BertForPretraining))/encoder/while/body/'
     'closed_call/layers.body/layers.body/checkpoint/rematted_computation/'
     'layers/attention/reshape', ('attention', 'recompute')),
    # Projections' outputs are kept too: such a projection's gradient stands
    # beside the rematted computation, what is element-wise inside it.
    ('jit(step)/transpose(jvp(BertForPretraining))/encoder/while/body/'
     'closed_call/layers.body/layers.body/checkpoint/layers/intermediate/'
     'dot_general', ('ffn', 'backward')),
    ('jit(step)/transpose(jvp(BertForPretraining))/encoder/while/body/'
     'closed_call/layers.body/layers.body/checkpoint/rematted_computation/'
     'layers/gelu/tanh', ('ffn', 'recompute')),
    ('jit(step)/transpose(jvp(BertForPretraining))/encoder/while/body/'
     'closed_call/layers.body/layers.body/checkpoint/rematted_computation/'
     'layers/attention_norm/rsqrt', ('norms', 'recompute')),
    ('jit(step)/transpose(jvp(BertForPretraining))/encoder/while/body/'
     'dynamic_slice', ('scan_carry', 'backward')),
    ('jit(step)/jvp(BertForPretraining)/encoder/while/body/closed_call/'
     'layers.body/layers/reduce_precision', ('unscoped', 'forward')),
    ('jit(step)/jvp(BertForPretraining)/encoder/while/body/'
     'broadcast_in_dim', ('scan_carry', 'forward')),
    ('jit(step)/transpose(jvp(BertForPretraining))/encoder/while/body/'
     'squeeze', ('scan_carry', 'backward')),
    ('jit(step)/add', ('unscoped', 'update')),
    ('', ('unscoped', 'update')),
])
def test_classify(op_name, expected):
  assert classify(op_name) == expected


# What commit 16d149e (the tree before PR 25) gives for the run below on
# this installation (jax 0.9.0 on the CPU backend), as float.hex(). A PR
# that changes the step's arithmetic on purpose reads them anew.
PARENT_LOSSES = [
    '0x1.32a0fc0000000p+2', '0x1.33f4da0000000p+2', '0x1.2be0f40000000p+2',
    '0x1.26de980000000p+2', '0x1.19b59e0000000p+2', '0x1.1aee2c0000000p+2',
    '0x1.0bda480000000p+2', '0x1.0536900000000p+2', '0x1.ffc70e0000000p+1',
    '0x1.f0f6a60000000p+1',
]
PARENT_PARAM_PATHS = [
    'embed_norm/bias', 'embed_norm/scale',
    'encoder/layers/attention/key/bias',
    'encoder/layers/attention/key/kernel',
    'encoder/layers/attention/out/bias',
    'encoder/layers/attention/out/kernel',
    'encoder/layers/attention/query/bias',
    'encoder/layers/attention/query/kernel',
    'encoder/layers/attention/value/bias',
    'encoder/layers/attention/value/kernel',
    'encoder/layers/attention_norm/bias',
    'encoder/layers/attention_norm/scale',
    'encoder/layers/intermediate/bias', 'encoder/layers/intermediate/kernel',
    'encoder/layers/output/bias', 'encoder/layers/output/kernel',
    'encoder/layers/output_norm/bias', 'encoder/layers/output_norm/scale',
    'mlm_bias', 'mlm_norm/bias', 'mlm_norm/scale', 'mlm_transform/bias',
    'mlm_transform/kernel', 'nsp_classifier/bias', 'nsp_classifier/kernel',
    'pooler/bias', 'pooler/kernel', 'position_embeddings/embedding',
    'token_type_embeddings/embedding', 'word_embeddings/embedding',
]


def test_losses_and_parameter_paths_are_the_parents(binned_shards,  # noqa: F811
                                                    tiny_vocab):
  from lddl_tpu.parallel import make_mesh
  from lddl_tpu.tokenization.wordpiece import load_bert_tokenizer
  from lddl_tpu.training.pretrain import TrainLoop
  tok = load_bert_tokenizer(vocab_file=tiny_vocab, backend='hf')
  loop = TrainLoop.build(
      binned_shards, tok, model_cfg=_tiny_config(), mesh=make_mesh(),
      learning_rate=1e-3, warmup_steps=2, total_steps=16,
      batch_size_per_rank=8, bin_size=BIN_SIZE, max_seq_length=128, seed=5,
      max_predictions=20, loader_kwargs={'shuffle_buffer_size': 16})
  # Ten steps of eight an epoch: the loop turns an epoch on the way.
  losses = loop.run(10, log_every=0)
  assert [x.hex() for x in losses] == PARENT_LOSSES
  paths = sorted(
      '/'.join(str(getattr(k, 'key', k)) for k in path) for path, _ in
      jax.tree_util.tree_flatten_with_path(loop.params)[0])
  assert paths == PARENT_PARAM_PATHS
