"""Between the yardstick and the system under test: which leaf of the
program's parameter tree is which leaf of the reference.

The only file of the benchmark that knows the program's names. It hands
the reference's weights to the program in the program's own layout, and
reads the program's optimizer state and parameters back as per-leaf
norms under the reference's names. A leaf of the program that has no
counterpart (or the other way round, or a shape that differs) is an
error: the comparison would otherwise pass over it in silence.
"""

import jax
import jax.numpy as jnp

from chipbench import reference

_LAYER = 'encoder/layers/'
PROGRAM_PATH = {
    'word_emb': 'word_embeddings/embedding',
    'pos_emb': 'position_embeddings/embedding',
    'type_emb': 'token_type_embeddings/embedding',
    'emb_ln_g': 'embed_norm/scale', 'emb_ln_b': 'embed_norm/bias',
    'q_w': _LAYER + 'attention/query/kernel',
    'q_b': _LAYER + 'attention/query/bias',
    'k_w': _LAYER + 'attention/key/kernel',
    'k_b': _LAYER + 'attention/key/bias',
    'v_w': _LAYER + 'attention/value/kernel',
    'v_b': _LAYER + 'attention/value/bias',
    'o_w': _LAYER + 'attention/out/kernel',
    'o_b': _LAYER + 'attention/out/bias',
    'ln1_g': _LAYER + 'attention_norm/scale',
    'ln1_b': _LAYER + 'attention_norm/bias',
    'i_w': _LAYER + 'intermediate/kernel',
    'i_b': _LAYER + 'intermediate/bias',
    'f_w': _LAYER + 'output/kernel', 'f_b': _LAYER + 'output/bias',
    'ln2_g': _LAYER + 'output_norm/scale',
    'ln2_b': _LAYER + 'output_norm/bias',
    'pool_w': 'pooler/kernel', 'pool_b': 'pooler/bias',
    'nsp_w': 'nsp_classifier/kernel', 'nsp_b': 'nsp_classifier/bias',
    'mlm_w': 'mlm_transform/kernel', 'mlm_b': 'mlm_transform/bias',
    'mlm_ln_g': 'mlm_norm/scale', 'mlm_ln_b': 'mlm_norm/bias',
    'mlm_bias': 'mlm_bias',
}
_REFERENCE_NAME = {v: k for k, v in PROGRAM_PATH.items()}

# How the program keys its hidden dropout, for ``reference.dropout_masks``
# (which draws the masks itself): ``TrainLoop.build`` keeps
# ``jax.random.key(seed + 1)``, the train step folds the optimizer's count
# into it, ``nn.scan`` splits that key over the layers, and flax folds into
# a module's key the names on the way to it and the number of the draw.
# ``embed_dropout`` is the model's own attribute and draws once; the other
# two are the unnamed ``nn.Dropout`` of ``SelfAttention`` and of ``Layer``,
# whose draw in the scan proper is their second: ``nn.scan`` traces its body
# once beforehand for the shapes, and that trace took the first.
DROPOUT_STREAM = {
    'key_offset': 1,
    'embed': ('embed_dropout', 1),
    'attention_output': ('encoder', 'layers', 'attention', 'Dropout_0', 2),
    'ffn_output': ('encoder', 'layers', 'Dropout_0', 2),
}


def _paths(tree):
  flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
  names = ['/'.join(str(getattr(k, 'key', k)) for k in path)
           for path, _ in flat]
  return names, [leaf for _, leaf in flat], treedef


def check_tree(config, program_params):
  """Every leaf of the program has its reference leaf, of the same
  shape, and the other way round."""
  names, leaves, _ = _paths(program_params)
  shapes = reference.param_shapes(config)
  unknown = sorted(set(names) - set(_REFERENCE_NAME))
  missing = sorted(set(_REFERENCE_NAME) - set(names))
  if unknown or missing:
    raise ValueError(
        f'the program\'s parameter tree and the reference differ: the '
        f'reference has no {unknown}, the program has no {missing}')
  for name, leaf in zip(names, leaves):
    want = shapes[_REFERENCE_NAME[name]]
    if tuple(leaf.shape) != tuple(want):
      raise ValueError(f'{name}: the program holds {tuple(leaf.shape)}, '
                       f'the configuration file gives {tuple(want)}')


def seeded_program_params(config, seed, program_params):
  """The reference's weights for ``seed`` in the program's tree, made on
  the device in one jitted call and placed as ``program_params`` is."""
  check_tree(config, program_params)
  names, leaves, treedef = _paths(program_params)
  shardings = jax.tree_util.tree_unflatten(
      treedef, [leaf.sharding for leaf in leaves])

  def make(seed):
    made = reference.init_params(config, seed)
    return jax.tree_util.tree_unflatten(
        treedef, [made[_REFERENCE_NAME[n]] for n in names])

  # The seed is an argument, not a constant of the program: every seed
  # runs the one compiled program, which the persistent cache then holds.
  return jax.jit(make, out_shardings=shardings)(jnp.uint32(seed))


@jax.jit
def _norms(tree):
  return jax.tree.map(
      lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


def leaf_norms(program_tree, scale=1.0):
  """``{reference name: norm * scale}`` of a tree shaped like the
  program's parameters."""
  names, leaves, _ = _paths(_norms(program_tree))
  return {_REFERENCE_NAME[n]: float(v) * scale
          for n, v in zip(names, leaves)}


def change_norms(config, seed, program_params):
  """Per-leaf norm of ``program_params`` minus the seed's weights. The
  seed's leaves are made again inside the one jitted reduction (each
  fuses into its own subtraction), never kept."""
  names, leaves, _ = _paths(program_params)
  shapes = reference.param_shapes(config)

  @jax.jit
  def norms(leaves, seed):
    return [jnp.sqrt(jnp.sum(jnp.square(
        x - reference.init_leaf(_REFERENCE_NAME[n], shapes[_REFERENCE_NAME[n]],
                                seed)))) for n, x in zip(names, leaves)]

  values = norms(leaves, jnp.uint32(seed))
  return {_REFERENCE_NAME[n]: float(v) for n, v in zip(names, values)}
