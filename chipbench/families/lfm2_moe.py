"""The family ``lfm2_moe``: LiquidAI's LFM2-8B-A1B as the program's
``lddl_tpu/models/lfm2.py`` builds it, a causal decoder on packed rows.

Its reference is ``chipbench/lfm2_reference.py``; here are the adapter to
the program's parameter tree, the count of required work, and what ties
them to the family interface (``chipbench/README.md``). Keys of a traffic
file's ``train`` block that are this family's own: ``attention``,
``remat``, ``expert_bias_rate`` and the recipe (``learning_rate``,
``warmup_steps``, ``total_steps``, ``weight_decay``).

The weights are the configuration's, from its ``weights_seed``, never
from a run's ``--seed``: the weights set the router, and a router drawn
anew each run would route each run's tokens to other experts, so that
seeds did unlike work. ``--seed`` still sets the data order.

Routed counts: the program keeps, with telemetry on, the assignments to
the held experts of every step (``lddl_tpu.ops.moe.routed_record``), and
:func:`batch_facts` numbers the batches in the order the loop consumes
them, one a step; :func:`required_flops` and :func:`experts_required`
bill the experts by those counts.
"""

import itertools
import os
import re

import jax
import jax.numpy as jnp

from chipbench import lfm2_reference as reference
from chipbench.families import Refused

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if not os.path.exists(os.path.join(REPO, 'lddl_tpu', 'models', 'lfm2.py')):
  raise Refused('the program of this checkout builds no LFM2 decoder '
                '(lddl_tpu/models/lfm2.py)')
VOCAB_FILE = os.path.join(REPO, 'benchmarks', 'assets',
                          'bench_vocab_16384.txt')
CONTROL_PRECISION = 'fp8'
ADAM_B1 = 0.9
BYTES = 2  # bfloat16 activations and weights in the products


# ----------------------------------------------------------------------------
# building the program as pretrain.main does


def program_config(cell, train):
  """The program's ``Lfm2Config`` of the configuration file, as
  ``pretrain.main --model <file>`` makes it
  (``lddl_tpu.models.lfm2.config_from_hf``)."""
  from lddl_tpu.models import lfm2
  try:
    return lfm2.config_from_hf(cell['config_data'],
                               attention_impl=train['attention'],
                               remat=train['remat'],
                               bias_rate=train['expert_bias_rate'])
  except ValueError as e:
    raise Refused(f'configuration {cell["config"]!r}: {e}') from e


def build_loop(cell, shards, seed, mesh):
  """``TrainLoop.build`` as ``pretrain.main`` calls it. The vocabulary
  rule: the program pads the tokenizer's count to a multiple of 64, and
  that is the ``vocab_size`` the configuration file has to state."""
  from lddl_tpu.tokenization.wordpiece import load_bert_tokenizer
  from lddl_tpu.training.pretrain import TrainLoop
  train = cell['traffic_data']['train']
  tokenizer = load_bert_tokenizer(vocab_file=VOCAB_FILE, backend='hf')
  vocab = ((tokenizer.vocab_size + 63) // 64) * 64
  if vocab != cell['config_data']['vocab_size']:
    raise Refused(
        f'the tokenizer gives a padded vocabulary of {vocab}, the '
        f'configuration file says {cell["config_data"]["vocab_size"]}')
  return TrainLoop.build(
      shards, tokenizer, model_cfg=program_config(cell, train), mesh=mesh,
      learning_rate=train['learning_rate'],
      warmup_steps=train['warmup_steps'], total_steps=train['total_steps'],
      weight_decay=train['weight_decay'],
      batch_size_per_rank=train['batch_size'], bin_size=train['bin_size'],
      max_seq_length=train['max_seq_length'], seed=seed,
      data_format=train['data_format'])


def abstract_step(cell, mesh):
  import optax

  from lddl_tpu.models import lfm2
  from lddl_tpu.parallel import make_train_step
  train = cell['traffic_data']['train']
  cfg = program_config(cell, train)
  _, objective = lfm2.build_objective(cfg, mesh)
  tx = optax.adamw(train['learning_rate'], weight_decay=train['weight_decay'],
                   mask=lfm2.decay_mask)
  model = lfm2.Lfm2ForCausalLM(cfg)
  batch = lfm2.dummy_batch(2, 16)
  params = jax.eval_shape(lambda: model.init(
      jax.random.key(0), batch['input_ids'], batch['positions'],
      batch['segment_ids'], batch['labels'])['params'])
  return (make_train_step(objective, tx, mesh), params,
          jax.eval_shape(tx.init, params))


def fake_batch(train, seq):
  import numpy as np
  batch = train['batch_size']
  labels = np.full((batch, seq), -100, np.int32)
  labels[:, :-1] = 5
  return {
      'input_ids': np.ones((batch, seq), np.int32),
      'segment_ids': np.zeros((batch, seq), np.int32),
      'positions': np.tile(np.arange(seq, dtype=np.int32), (batch, 1)),
      'labels': labels,
  }


def bin_lengths(shards, train):
  return [train['max_seq_length']]


_BATCHES = itertools.count()


def batch_facts(batch):
  """``rows``: real tokens of each row; ``units``: the documents' lengths
  (attention's units); ``step``: the batch's number in the order the
  loop consumes them (the program's step number)."""
  import numpy as np
  seg = np.asarray(batch['segment_ids'])
  units = np.concatenate([np.bincount(r[r >= 0]) for r in seg])
  return {'rows': [int(n) for n in (seg >= 0).sum(axis=1)],
          'units': [int(n) for n in units[units > 0]],
          'step': next(_BATCHES)}


# ----------------------------------------------------------------------------
# the adapter: the program's parameter tree against the reference's names

_LEAF = {
    'operator_norm/scale': 'op_g', 'ffn_norm/scale': 'ffn_g',
    'conv/in_proj/kernel': 'conv_in', 'conv/conv_weight': 'conv_w',
    'conv/out_proj/kernel': 'conv_out',
    'attention/q_proj/kernel': 'q', 'attention/k_proj/kernel': 'k',
    'attention/v_proj/kernel': 'v', 'attention/out_proj/kernel': 'o',
    'attention/q_norm/scale': 'qn_g', 'attention/k_norm/scale': 'kn_g',
    'ffn/w1/kernel': 'w1', 'ffn/w3/kernel': 'w3', 'ffn/w2/kernel': 'w2',
    'moe/router': 'router', 'moe/expert_bias': 'bias', 'moe/w1': 'e1',
    'moe/w3': 'e3', 'moe/w2': 'e2',
}
_TOP = {'token_embeddings/embedding': 'emb', 'lm_head': 'head',
        'final_norm/scale': 'final_g'}
_DENSE = re.compile(r'^decoder/dense_(\d+)/(.+)$')
_PERIOD = re.compile(r'^decoder/periods/block_(\d+)/(.+)$')


def _paths(tree):
  flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
  names = ['/'.join(str(getattr(k, 'key', k)) for k in path)
           for path, _ in flat]
  return names, [leaf for _, leaf in flat], treedef


def _layout(names):
  """``(dense layers, blocks a period)`` of a program tree, by its names."""
  dense = {m[1] for m in map(_DENSE.match, names) if m}
  blocks = {m[1] for m in map(_PERIOD.match, names) if m}
  return len(dense), len(blocks)


def _reference_names(name, shape, layout):
  """The reference's names of one program leaf: one per period for a
  scanned leaf (along its leading axis), else one."""
  if name in _TOP:
    return [_TOP[name]]
  dense, period = layout
  match = _DENSE.match(name)
  if match and match[2] in _LEAF:
    return [f'L{int(match[1])}.{_LEAF[match[2]]}']
  match = _PERIOD.match(name)
  if not match or match[2] not in _LEAF:
    raise ValueError(f'the reference has no counterpart of {name}')
  j, leaf = int(match[1]), _LEAF[match[2]]
  return [f'L{dense + p * period + j}.{leaf}' for p in range(shape[0])]


def _scanned(name):
  return name.startswith('decoder/periods/')


def check_tree(config, program_params):
  """Every leaf of the program has its reference leaves, of the same
  shape, and the other way round."""
  names, leaves, _ = _paths(program_params)
  layout = _layout(names)
  shapes = reference.param_shapes(config)
  seen = []
  for name, leaf in zip(names, leaves):
    refs = _reference_names(name, leaf.shape, layout)
    seen += refs
    shape = tuple(leaf.shape[1:] if _scanned(name) else leaf.shape)
    if shape != tuple(shapes[refs[0]]):
      raise ValueError(f'{name}: the program holds {tuple(leaf.shape)}, '
                       f'the configuration file gives {shapes[refs[0]]}')
  if sorted(seen) != sorted(shapes):
    raise ValueError('the program\'s parameter tree and the reference '
                     f'differ: {sorted(set(shapes) ^ set(seen))}')


def _from_reference(names, leaves, made):
  layout = _layout(names)
  out = []
  for n, leaf in zip(names, leaves):
    refs = _reference_names(n, leaf.shape, layout)
    out.append(jnp.stack([made[r] for r in refs]) if _scanned(n) else
               made[refs[0]])
  return out


def seeded_params(config, seed, like):
  """The configuration's weights (``weights_seed``; ``seed`` orders the
  data only) in the program's tree, placed as ``like`` is."""
  del seed
  check_tree(config, like)
  names, leaves, treedef = _paths(like)
  shardings = jax.tree_util.tree_unflatten(
      treedef, [leaf.sharding for leaf in leaves])

  def make(seed):
    made = reference.init_params(config, seed)
    return jax.tree_util.tree_unflatten(
        treedef, _from_reference(names, leaves, made))

  return jax.jit(make, out_shardings=shardings)(
      jnp.uint32(config['weights_seed']))


def _leaf_norms(tree, scale=1.0, skip=()):
  """``{reference name: norm * scale}`` of a tree shaped like the
  program's parameters, leaving out the names that end in ``skip``."""
  names, leaves, _ = _paths(tree)
  layout = _layout(names)
  norms = jax.jit(lambda ls: [
      jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)),
                       axis=tuple(range(1 if _scanned(n) else 0, x.ndim))))
      for n, x in zip(names, ls)])(leaves)
  out = {}
  for n, leaf, v in zip(names, leaves, norms):
    for i, ref in enumerate(_reference_names(n, leaf.shape, layout)):
      if not ref.endswith(skip):
        out[ref] = float(v[i] if _scanned(n) else v) * scale
  return out


def first_gradient_norms(opt_state):
  """After one step Adam's first moment is ``(1 - b1)`` times the first
  gradient; the expert biases have none."""
  return _leaf_norms(opt_state[0].mu, 1.0 / (1.0 - ADAM_B1), skip=('.bias',))


def change_norms(config, seed, params):
  """Per-leaf norm of ``params`` minus the configuration's weights. Each
  reference leaf is made again inside the one jitted reduction, where it
  fuses into its own subtraction: the chip never holds a second tree."""
  del seed
  names, leaves, _ = _paths(params)
  layout = _layout(names)
  shapes = reference.param_shapes(config)
  refs = [_reference_names(n, leaf.shape, layout)
          for n, leaf in zip(names, leaves)]

  @jax.jit
  def norms(leaves, seed):
    out = []
    for n, x, rs in zip(names, leaves, refs):
      parts = [x[i] for i in range(len(rs))] if _scanned(n) else [x]
      out += [jnp.sqrt(jnp.sum(jnp.square(
          part - reference.init_leaf(r, shapes[r], seed))))
              for part, r in zip(parts, rs)]
    return out

  values = norms(leaves, jnp.uint32(config['weights_seed']))
  return {r: float(v) for r, v in
          zip((r for rs in refs for r in rs), values)}


def follow(config, train, seed, batches, precision='float32', keep=None):
  del seed
  return reference.follow(config, train, batches, precision=precision,
                          keep=keep)


# ----------------------------------------------------------------------------
# required work


def _widths(config):
  d, h = config['hidden_size'], config['num_attention_heads']
  return dict(d=d, h=h, kvh=config['num_key_value_heads'], hd=d // h,
              fm=config['moe_intermediate_size'],
              held=config['num_experts'], e=config['published_num_experts'],
              k=config['num_experts_per_tok'], v=config['vocab_size'])


def held_assignments(config, facts):
  """Assignments of the step whose batch gave ``facts`` to the held
  experts, all sparse layers together: the program's routed count where
  it kept one, else None."""
  from lddl_tpu.ops.moe import routed_record
  for step, counts in routed_record():
    if step == facts.get('step'):
      return sum(counts)
  return None


def _per_token_flops(config):
  """Forward FLOPs a token of every product but the experts and the
  attention core: the mixers' projections, the dense SwiGLU, the routers
  and the head."""
  w = _widths(config)
  d = w['d']
  total = 2 * d * w['v']
  for kind, dense in reference.layer_kinds(config):
    if kind == 'conv':
      total += 2 * (3 * d * d + d * d) + 6 * d
    else:
      total += 2 * (d * (w['h'] + 2 * w['kvh']) * w['hd'] + d * d)
    total += (6 * d * config['intermediate_size'] if dense else
              2 * d * w['e'])
  return total


def _causal_pairs(n):
  return n * (n + 1) // 2


def _attention_layers(config):
  return sum(kind != 'conv' for kind in config['layer_types'])


def _sparse_layers(config):
  return sum(not dense for _, dense in reference.layer_kinds(config))


def _step_flops(config, tokens, units, assignments):
  w = _widths(config)
  core = (_attention_layers(config) * w['h'] * 4 * w['hd'] *
          sum(_causal_pairs(n) for n in units))
  experts = 6 * assignments * w['d'] * w['fm']
  return 3 * (_per_token_flops(config) * tokens + core + experts)


def required_flops(config, train, facts):
  """Forward and backward (three times the forward) of what the batch
  held: every product of its real tokens, attention over each document's
  causal pairs, the held experts by their routed assignments (their
  expected share where the program kept no count)."""
  tokens = sum(facts['rows'])
  assignments = held_assignments(config, facts)
  if assignments is None:
    w = _widths(config)
    assignments = (_sparse_layers(config) * tokens * w['k'] * w['held'] /
                   w['e'])
  return _step_flops(config, tokens, facts['units'], assignments)


def padded_flops(config, train, seq):
  """The ceiling: every token real, one document a row, every assignment
  to a held expert."""
  batch = train['batch_size']
  w = _widths(config)
  tokens = batch * seq
  return _step_flops(config, tokens, [seq] * batch,
                     _sparse_layers(config) * tokens * w['k'])


def flash_required(config, train, facts):
  """``{'flops', 'bytes'}`` of the attention kernels of one step: forward
  ``4 * pairs * d_head`` and backward twice that per query head, over each
  document's causal pairs; q, o, dO, dq per query head and k, v, dk, dv
  per key/value head moved once."""
  w = _widths(config)
  layers = _attention_layers(config)
  pairs = sum(_causal_pairs(n) for n in facts['units'])
  tokens = sum(facts['units'])
  return {'flops': layers * w['h'] * 12 * pairs * w['hd'],
          'bytes': layers * 4 * (w['h'] + w['kvh']) * tokens * w['hd'] *
                   BYTES}


def experts_required(config, train, steps):
  """``{'flops', 'bytes'}`` of the held experts' grouped products over
  the steps whose batches gave ``steps``, forward and backward: 6 FLOPs a
  routed assignment, hidden and expert width (three products) forward,
  twice that backward; the held experts' weights and each assignment's
  rows (in, gate, up, product, out) moved once. None where the program
  kept no routed count of one of the steps."""
  w = _widths(config)
  assignments = [held_assignments(config, s) for s in steps]
  if not steps or None in assignments:
    return None
  a = sum(assignments)
  weights = len(steps) * _sparse_layers(config) * 3 * w['held'] * w['d'] * (
      w['fm'])
  return {'flops': 3 * 6 * a * w['d'] * w['fm'],
          'bytes': (weights + a * (2 * w['d'] + 3 * w['fm'])) * BYTES}
