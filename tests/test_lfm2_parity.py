"""LFM2-MoE against its plain float32 reference (``chipbench/
lfm2_reference.py``), on the CPU at tiny widths: hidden 64, 4 query heads
of 16 over 2 key/value heads, 8 experts of width 32 with top-4 routing, 2
of them held.

The program is built in float32 here (``dtype=float32``), so that the
two sides differ by the order of float32 sums alone: the tolerances below
are float32 round-off over a few hundred terms, far under what bfloat16
(the cell's dtype, 3 significant digits) would give.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from chipbench import lfm2_reference as reference
from chipbench.families import lfm2_moe as family
from lddl_tpu.models import lfm2
from lddl_tpu.parallel import make_mesh, make_train_step
from lddl_tpu.parallel.train import state_shardings

CONFIG = {
    'hidden_size': 64, 'num_attention_heads': 4, 'num_key_value_heads': 2,
    'intermediate_size': 128, 'moe_intermediate_size': 32,
    'published_num_experts': 8, 'num_experts': 2, 'num_experts_per_tok': 4,
    'num_dense_layers': 1, 'conv_L_cache': 3,
    'layer_types': ['conv', 'full_attention', 'conv', 'conv', 'conv'],
    'vocab_size': 128, 'norm_eps': 1e-5, 'rope_theta': 1000000,
    'routed_scaling_factor': 1, 'weights_seed': 5,
}
TRAIN = {'learning_rate': 1e-3, 'warmup_steps': 2, 'total_steps': 100,
         'weight_decay': 0.01, 'expert_bias_rate': 1e-3}
# float32 on both sides: a few hundred terms of float32 round-off.
TOL = dict(rtol=2e-4, atol=2e-6)


def program_config(**kw):
  return lfm2.Lfm2Config(**{**lfm2.PRESETS['lfm2-tiny'],
                            'vocab_size': CONFIG['vocab_size'],
                            'dtype': jnp.float32, 'attention_impl': 'dense',
                            'loss_chunk': 48, 'moe_chunk': 64, **kw})


def packed_batch(rng, docs_per_row, seq):
  """Rows of documents of the given lengths, padded to ``seq``, in the
  causal collate's keys."""
  rows = len(docs_per_row)
  ids = np.zeros((rows, seq), np.int32)
  seg = np.full((rows, seq), -1, np.int32)
  pos = np.zeros((rows, seq), np.int32)
  labels = np.full((rows, seq), -100, np.int32)
  for r, docs in enumerate(docs_per_row):
    at = 0
    for d, n in enumerate(docs):
      toks = rng.integers(5, CONFIG['vocab_size'], n)
      ids[r, at:at + n] = toks
      seg[r, at:at + n] = d
      pos[r, at:at + n] = np.arange(n)
      labels[r, at:at + n - 1] = toks[1:]
      at += n
  return {'input_ids': ids, 'segment_ids': seg, 'positions': pos,
          'labels': labels}


def seeded(cfg, mesh):
  """The program's parameters holding the reference's weights."""
  return family.seeded_params(CONFIG, None,
                              lfm2.init_params(cfg, mesh, jax.random.key(0)))


def program_steps(cfg, batches):
  """The program's losses, first-gradient and change norms, as the
  harness reads them, from the reference's weights; and the first
  gradient itself (Adam's first moment after one step over ``1 - b1``)."""
  mesh = make_mesh(devices=jax.devices()[:1])
  params = seeded(cfg, mesh)
  _, objective = lfm2.build_objective(cfg, mesh)
  schedule = optax.warmup_cosine_decay_schedule(
      0.0, TRAIN['learning_rate'], TRAIN['warmup_steps'],
      TRAIN['total_steps'])
  tx = optax.adamw(schedule, weight_decay=TRAIN['weight_decay'],
                   mask=lfm2.decay_mask)
  # Placed as the step leaves it (TrainLoop.build's rule), so that the
  # step compiles once.
  opt_state = jax.jit(tx.init, out_shardings=state_shardings(
      mesh, params, jax.eval_shape(tx.init, params),
      objective.param_spec_fn)[1])(params)
  step = make_train_step(objective, tx, mesh)
  losses = []
  for i, batch in enumerate(batches):
    params, opt_state, metrics = step(params, opt_state, jax.random.key(1),
                                      batch)
    losses.append(float(metrics['loss']))
    if i == 0:
      grads = family.first_gradient_norms(opt_state)
      first = jax.tree.map(lambda m: np.asarray(m) / (1 - family.ADAM_B1),
                           opt_state[0].mu)
  return {'losses': losses, 'grad_norms': grads, 'first_gradient': first,
          'change_norms': family.change_norms(CONFIG, None, params)}


@pytest.fixture(scope='module')
def batches():
  rng = np.random.default_rng(0)
  return [packed_batch(rng, [[20, 30, 14], [64]], 64) for _ in range(3)]


@pytest.fixture(scope='module')
def program(batches):
  return program_steps(program_config(), batches)


def test_three_steps_match_the_reference(batches, program):
  ref = reference.follow(CONFIG, TRAIN, batches)
  got = program
  np.testing.assert_allclose(got['losses'], ref['losses'], **TOL)
  assert sorted(got['grad_norms']) == sorted(ref['grad_norms'])
  for name in ref['grad_norms']:
    np.testing.assert_allclose(got['grad_norms'][name],
                               ref['grad_norms'][name], err_msg=name, **TOL)
  # After three AdamW steps and three bias steps, every leaf (the expert
  # biases, which no gradient moves, among them) has moved as the
  # reference's did.
  # Adam divides each element's step by the root of its second moment:
  # where a gradient element is near nought the step is near ±lr whatever
  # its size, so float32 round-off in the gradient reaches the change ten
  # times magnified (rtol 2e-3 for the gradients' 2e-4).
  assert sorted(got['change_norms']) == sorted(ref['change_norms'])
  for name in ref['change_norms']:
    np.testing.assert_allclose(got['change_norms'][name],
                               ref['change_norms'][name], err_msg=name,
                               rtol=2e-3, atol=1e-7)
  bias = [ref['change_norms'][n] for n in reference.bias_names(CONFIG)]
  assert all(b > 0 for b in bias)


def test_gradients_leaf_by_leaf(batches, program):
  """Each leaf of the step's first gradient as an array, not its norm
  alone."""
  grads = program['first_gradient']
  flat = reference.init_params(CONFIG, CONFIG['weights_seed'])
  biases = set(reference.bias_names(CONFIG))
  learned = {k: v for k, v in flat.items() if k not in biases}
  with jax.default_matmul_precision('highest'):
    want = jax.jit(jax.grad(lambda p: reference.forward(
        CONFIG, {**p, **{k: flat[k] for k in biases}}, batches[0])[0]))(
            learned)
  names, leaves, _ = family._paths(grads)
  layout = family._layout(names)
  for name, leaf in zip(names, leaves):
    refs = family._reference_names(name, leaf.shape, layout)
    arrays = leaf if family._scanned(name) else [leaf]
    for ref_name, got in zip(refs, arrays):
      if ref_name in biases:
        assert not np.any(np.asarray(got)), ref_name
        continue
      # Element by element, not a norm: a small element is the difference
      # of larger terms summed in another order, so its relative error is
      # ten times a norm's (rtol 2e-3), and elements near nought are held
      # to float32 round-off of the leaf's larger ones (atol 2e-6).
      np.testing.assert_allclose(np.asarray(got), np.asarray(want[ref_name]),
                                 err_msg=ref_name, rtol=2e-3, atol=2e-6)
