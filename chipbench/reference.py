"""Plain reference of the BERT pretraining step, in float32 ``jax.numpy``.

Nothing here imports the program, and nothing here takes anything the
program made. The weights come from :func:`init_params` (a seed in, a
flat dict of float32 arrays out); the harness puts the same weights into
the program through ``adapter.py`` and, once the window has closed, this
file follows the program's first steps on the batches the program
consumed:

  forward (post-LN BERT, MLM + NSP heads, tied decoder), the loss of
  ``Devlin et al. 2018`` as the configuration file states it, gradients
  by ``jax.grad``, and AdamW under a warm-up + cosine schedule.

Departures from the paper, each stated by the configuration file and
followed here because the comparison is with the configuration *as run*:
``hidden_act`` is the tanh approximation of GELU, ``layer_norm_eps`` is
1e-6, there is no dropout on attention probabilities, the MLM head scores
only the first ``max_predictions`` masked positions of a row, and under
block-diagonal packing attention stays inside a document and the MLM
loss is a mean over documents of per-document means (arXiv:2107.02027).

Hidden dropout (``hidden_dropout_prob``, after the embedding norm and on
the output of each layer's attention and feed-forward block, before the
residual sum) is a random draw, and a comparison to rounding needs both
sides to draw the same units. The masks are made here, from the seed, by
``jax.random`` alone (:func:`dropout_masks`); *which* key belongs to
which site is the program's convention and comes in as data (the
``stream`` that ``adapter.py`` states), the way the parameter names do.

Every matrix product goes through :func:`_dot`. ``precision='float32'``
runs it at ``jax.lax.Precision.HIGHEST`` (on a TPU a float32 product
otherwise runs in bfloat16 passes). ``precision='fp8'`` is the
*control*: both operands of every product, forward and backward, are
rounded to 8-bit floats (e4m3 forward, e5m2 for cotangents, per-tensor
scaling) — the nearest precision below the bfloat16 the configuration
states, the step that would tempt a later PR. The control has to come
out as not correct.

Attention is computed in blocks of (row, head) pairs so that s=8192
fits, and inside a block a tile of queries at a time; each layer, each
block and each tile is rematerialised in the backward pass. (Rows short
enough for all their pairs to fit one block, s=512 and under, take the
whole-block path, which PR 29 left as it was.)
"""

import functools
import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np

IGNORE = -100
_SCORE_BLOCK_BYTES = 512 * 1024 * 1024
# Queries a tile in the blocked path. On a v5e the softmax over a whole
# [2, 8192, 8192] float32 block ran at 20 GB/s (55 ms a forward, 92 % of a
# reference step of 25.6 s); in tiles of 256 queries the block's forward
# and backward take 8.4 ms where they took 119.5 (my chip runs, PR 29).
_QUERY_TILE = 256

# ----------------------------------------------------------------------------
# weights


def param_shapes(config):
  """``{name: shape}`` of the reference's flat parameter dict. Layer
  leaves carry a leading ``num_hidden_layers`` axis."""
  d, ff = config['hidden_size'], config['intermediate_size']
  n, v = config['num_hidden_layers'], config['vocab_size']
  shapes = {
      'word_emb': (v, d),
      'pos_emb': (config['max_position_embeddings'], d),
      'type_emb': (config['type_vocab_size'], d),
      'emb_ln_g': (d,), 'emb_ln_b': (d,),
      'i_w': (n, d, ff), 'i_b': (n, ff), 'f_w': (n, ff, d), 'f_b': (n, d),
      'ln1_g': (n, d), 'ln1_b': (n, d), 'ln2_g': (n, d), 'ln2_b': (n, d),
      'pool_w': (d, d), 'pool_b': (d,), 'nsp_w': (d, 2), 'nsp_b': (2,),
      'mlm_w': (d, d), 'mlm_b': (d,), 'mlm_ln_g': (d,), 'mlm_ln_b': (d,),
      'mlm_bias': (v,),
  }
  for p in 'qkvo':
    shapes[f'{p}_w'] = (n, d, d)
    shapes[f'{p}_b'] = (n, d)
  return dict(sorted(shapes.items()))


def init_leaf(name, shape, seed):
  """One leaf from the seed: N(0, 0.02) for matrices and embeddings
  (Devlin et al.: ``initializer_range`` 0.02), ones for LayerNorm gains,
  zeros for biases. A leaf depends on its name and the seed alone, so it
  can be made again by itself."""
  if name.endswith('_g'):
    return jnp.ones(shape, jnp.float32)
  if name.endswith('_w') or name.endswith('_emb'):
    index = list(param_shapes_index()).index(name)
    key = jax.random.fold_in(jax.random.key(seed), index)
    return 0.02 * jax.random.normal(key, shape, jnp.float32)
  return jnp.zeros(shape, jnp.float32)


@functools.lru_cache(maxsize=None)
def param_shapes_index():
  """Leaf names in their fixed order (the fold-in index of a leaf)."""
  tiny = dict(hidden_size=2, intermediate_size=2, num_hidden_layers=1,
              vocab_size=2, max_position_embeddings=2, type_vocab_size=2)
  return tuple(param_shapes(tiny))


def init_params(config, seed):
  """The whole flat dict, made inside one traced function."""
  return {name: init_leaf(name, shape, seed)
          for name, shape in param_shapes(config).items()}


# ----------------------------------------------------------------------------
# products: float32 at HIGHEST, or the fp8 control

_E4M3_MAX = 448.0
_E5M2_MAX = 57344.0


def _round_to(x, dtype, top):
  scale = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
  return (x * scale).astype(dtype).astype(jnp.float32) / scale


def _matmul(a, b):
  return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


@jax.custom_vjp
def _fp8_dot(a, b):
  return _matmul(_round_to(a, jnp.float8_e4m3fn, _E4M3_MAX),
                 _round_to(b, jnp.float8_e4m3fn, _E4M3_MAX))


def _fp8_dot_fwd(a, b):
  return _fp8_dot(a, b), (a, b)


def _fp8_dot_bwd(res, g):
  a, b = res
  a8 = _round_to(a, jnp.float8_e4m3fn, _E4M3_MAX)
  b8 = _round_to(b, jnp.float8_e4m3fn, _E4M3_MAX)
  g8 = _round_to(g, jnp.float8_e5m2, _E5M2_MAX)
  return (_matmul(g8, jnp.swapaxes(b8, -1, -2)),
          _matmul(jnp.swapaxes(a8, -1, -2), g8))


_fp8_dot.defvjp(_fp8_dot_fwd, _fp8_dot_bwd)


def _dot(a, b, precision):
  """``a @ b`` for operands of equal rank (2-D, or batched alike)."""
  if precision == 'fp8':
    return _fp8_dot(a, b)
  return _matmul(a, b)


def _dense(x, w, b, precision):
  lead = x.shape[:-1]
  y = _dot(x.reshape(-1, x.shape[-1]), w, precision) + b
  return y.reshape(*lead, w.shape[-1])


# ----------------------------------------------------------------------------
# forward


def _layer_norm(x, g, b, eps):
  mean = jnp.mean(x, axis=-1, keepdims=True)
  var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
  return (x - mean) * jax.lax.rsqrt(var + eps) * g + b


def _gelu(x, act):
  if act == 'gelu_pytorch_tanh':
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))
  if act == 'gelu':
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))
  raise ValueError(f'hidden_act {act!r} is not one the reference knows')


def _attend_block(q, k, v, key_real, seg, precision, seg_q=None):
  """Softmax attention for a block of (row, head) pairs.

  q: [n, queries, d_head]; k, v: [n, s, d_head]; key_real: [n, s] bool;
  seg: [n, s] int or None, the keys' documents; seg_q: the queries'
  documents where the queries are a tile of the row. A query attends to
  the real keys of its own document."""
  scores = _dot(q, jnp.swapaxes(k, -1, -2), precision) / math.sqrt(
      q.shape[-1])
  keep = key_real[:, None, :]
  if seg is not None:
    seg_q = seg if seg_q is None else seg_q
    keep = keep & (seg_q[:, :, None] == seg[:, None, :])
  scores = jnp.where(keep, scores, -1e30)
  probs = jax.nn.softmax(scores, axis=-1)
  # A query with no key at all (a padding row) attends to nothing.
  probs = jnp.where(jnp.any(keep, axis=-1, keepdims=True), probs, 0.0)
  return _dot(probs, v, precision)


def _attend_tiled(q, k, v, key_real, seg, precision):
  """:func:`_attend_block`, a tile of ``_QUERY_TILE`` queries at a time."""
  n, s, hd = q.shape
  tiles = s // _QUERY_TILE
  if s % _QUERY_TILE or tiles < 2:
    return _attend_block(q, k, v, key_real, seg, precision)

  def tiled(t):  # [n, s, ...] -> [tiles, n, _QUERY_TILE, ...]
    return jnp.moveaxis(t.reshape(n, tiles, _QUERY_TILE, *t.shape[2:]), 1, 0)

  one = jax.checkpoint(lambda qt, sq: _attend_block(
      qt, k, v, key_real, seg, precision, seg_q=sq))
  if seg is None:
    ctx = jax.lax.map(lambda qt: one(qt, None), tiled(q))
  else:
    ctx = jax.lax.map(lambda a: one(*a), (tiled(q), tiled(seg)))
  return jnp.moveaxis(ctx, 0, 1).reshape(n, s, hd)


def _attention(x, lp, key_real, seg, heads, precision):
  b, s, d = x.shape
  hd = d // heads

  def split(t):  # [b, s, d] -> [b * heads, s, hd]
    return t.reshape(b, s, heads, hd).transpose(0, 2, 1, 3).reshape(
        b * heads, s, hd)

  q = split(_dense(x, lp['q_w'], lp['q_b'], precision))
  k = split(_dense(x, lp['k_w'], lp['k_b'], precision))
  v = split(_dense(x, lp['v_w'], lp['v_b'], precision))
  real = jnp.repeat(key_real, heads, axis=0)
  segs = None if seg is None else jnp.repeat(seg, heads, axis=0)
  n = b * heads
  block = max(1, min(n, _SCORE_BLOCK_BYTES // (4 * s * s)))
  while n % block:
    block -= 1
  if block == n:
    ctx = jax.checkpoint(functools.partial(
        _attend_block, precision=precision))(q, k, v, real, segs)
  else:
    fn = jax.checkpoint(
        functools.partial(_attend_tiled, precision=precision))
    def chunk(t):
      return t.reshape(n // block, block, *t.shape[1:])
    args = (chunk(q), chunk(k), chunk(v), chunk(real))
    if segs is None:
      ctx = jax.lax.map(lambda a: fn(a[0], a[1], a[2], a[3], None), args)
    else:
      ctx = jax.lax.map(lambda a: fn(*a), args + (chunk(segs),))
    ctx = ctx.reshape(n, s, hd)
  ctx = ctx.reshape(b, heads, s, hd).transpose(0, 2, 1, 3).reshape(b, s, d)
  return _dense(ctx, lp['o_w'], lp['o_b'], precision)


_LAYER_LEAVES = ('q_w', 'q_b', 'k_w', 'k_b', 'v_w', 'v_b', 'o_w', 'o_b',
                 'ln1_g', 'ln1_b', 'i_w', 'i_b', 'f_w', 'f_b', 'ln2_g',
                 'ln2_b')


def _drop(x, mask, rate):
  """Inverted dropout with a given mask of kept units."""
  return jnp.where(mask, x / (1.0 - rate), 0.0)


def forward(config, params, batch, max_predictions, precision='float32',
            masks=None):
  """``(loss, parts)`` of one batch (numpy or jax int arrays, the keys
  the loader yields). ``masks`` (from :func:`dropout_masks`) are the kept
  units of hidden dropout; without them nothing is dropped."""
  eps, act = config['layer_norm_eps'], config['hidden_act']
  rate = config['hidden_dropout_prob']
  heads = config['num_attention_heads']
  ids = jnp.asarray(batch['input_ids'])
  b, s = ids.shape
  key_real = jnp.asarray(batch['attention_mask']) != 0
  seg = batch.get('segment_ids')
  seg = None if seg is None else jnp.asarray(seg)
  labels = jnp.asarray(batch['labels'])

  x = (params['word_emb'][ids] + params['pos_emb'][:s][None] +
       params['type_emb'][jnp.asarray(batch['token_type_ids'])])
  x = _layer_norm(x, params['emb_ln_g'], params['emb_ln_b'], eps)
  if masks is not None:
    x = _drop(x, masks['embed'], rate)

  @jax.checkpoint
  def layer(x, scanned):
    lp, kept = scanned
    a = _attention(x, lp, key_real, seg, heads, precision)
    if kept is not None:
      a = _drop(a, kept[0], rate)
    x = _layer_norm(x + a, lp['ln1_g'], lp['ln1_b'], eps)
    h = _gelu(_dense(x, lp['i_w'], lp['i_b'], precision), act)
    h = _dense(h, lp['f_w'], lp['f_b'], precision)
    if kept is not None:
      h = _drop(h, kept[1], rate)
    return _layer_norm(x + h, lp['ln2_g'], lp['ln2_b'], eps)

  x, _ = jax.lax.scan(
      lambda c, scanned: (layer(c, scanned), None), x,
      ({k: params[k] for k in _LAYER_LEAVES},
       None if masks is None else masks['layers']))

  # MLM head over the first `max_predictions` targets of each row.
  masked = labels != IGNORE
  if max_predictions is None:
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
  else:
    p = min(int(max_predictions), s)
    pos = jnp.argsort(~masked, axis=1, stable=True)[:, :p]
  lab = jnp.take_along_axis(labels, pos, axis=1)
  hit = lab != IGNORE
  h = jnp.take_along_axis(x, pos[:, :, None], axis=1)
  h = _gelu(_dense(h, params['mlm_w'], params['mlm_b'], precision), act)
  h = _layer_norm(h, params['mlm_ln_g'], params['mlm_ln_b'], eps)
  logits = _dense(h, params['word_emb'].T, params['mlm_bias'], precision)
  logp = jax.nn.log_softmax(logits, axis=-1)
  ce = -jnp.take_along_axis(logp, jnp.where(hit, lab, 0)[:, :, None],
                            axis=2)[:, :, 0]
  ce = jnp.where(hit, ce, 0.0)
  if seg is None:
    mlm = ce.sum() / jnp.maximum(hit.sum(), 1)
  else:
    # Mean over documents (with a target) of each document's own mean.
    doc = jnp.clip(jnp.take_along_axis(seg, pos, axis=1), 0, s - 1)
    doc = doc + s * jnp.arange(b)[:, None]
    tot = jax.ops.segment_sum(ce.reshape(-1), doc.reshape(-1), b * s)
    cnt = jax.ops.segment_sum(hit.reshape(-1).astype(jnp.float32),
                              doc.reshape(-1), b * s)
    has = cnt > 0
    mlm = jnp.where(has, tot / jnp.maximum(cnt, 1.0), 0.0).sum() / (
        jnp.maximum(has.sum(), 1))

  pooled = jnp.tanh(_dense(x[:, 0], params['pool_w'], params['pool_b'],
                           precision))
  nsp_logits = _dense(pooled, params['nsp_w'], params['nsp_b'], precision)
  nsp_logp = jax.nn.log_softmax(nsp_logits, axis=-1)
  nsp = -jnp.mean(jnp.take_along_axis(
      nsp_logp, jnp.asarray(batch['next_sentence_labels'])[:, None],
      axis=1))
  return mlm + nsp, {'mlm': mlm, 'nsp': nsp}


# ----------------------------------------------------------------------------
# hidden dropout: the kept units of one step, from the seed


def _fold_in_path(key, path):
  """``key`` with a site's path folded in: the first four bytes of the
  SHA-1 of its parts (strings as UTF-8, whole numbers as their shortest
  big-endian bytes), as one unsigned 32-bit number."""
  digest = hashlib.sha1()
  for part in path:
    if isinstance(part, str):
      digest.update(part.encode('utf-8'))
    else:
      digest.update(part.to_bytes((part.bit_length() + 7) // 8, 'big'))
  return jax.random.fold_in(
      key, jnp.uint32(int.from_bytes(digest.digest()[:4], 'big')))


@functools.partial(jax.jit, static_argnames=('config', 'stream', 'shape'))
def _dropout_masks(seed, count, *, config, stream, shape):
  rate, layers = dict(config)['hidden_dropout_prob'], dict(config)['layers']
  stream = dict(stream)
  shape = (*shape, dict(config)['hidden_size'])
  step_key = jax.random.fold_in(
      jax.random.key(seed + jnp.uint32(stream['key_offset'])), count)
  layer_keys = jax.random.split(step_key, layers)

  def kept(key, site):
    return jax.random.bernoulli(_fold_in_path(key, stream[site]),
                                1.0 - rate, shape)

  return {
      'embed': kept(step_key, 'embed'),
      'layers': jax.vmap(lambda k: jnp.stack(
          [kept(k, 'attention_output'), kept(k, 'ffn_output')]))(layer_keys),
  }


def dropout_masks(config, stream, seed, count, shape):
  """The kept units of step ``count`` (updates already made) for a batch
  of ``shape`` = (rows, columns): ``{'embed': [rows, columns, hidden],
  'layers': [layers, 2, rows, columns, hidden]}``, booleans.

  ``stream`` states the program's convention, nothing more: the run's key
  is ``key(seed + key_offset)``; a step's key folds ``count`` into it; a
  layer's key is its row of ``split(step key, layers)``; and a site's key
  folds the site's path in (:func:`_fold_in_path`). A mask is Bernoulli
  (1 - rate) drawn *at the batch's own shape*: the bits of a draw depend
  on the shape, so a batch that is padded afterwards keeps its mask."""
  slim = (('hidden_dropout_prob', config['hidden_dropout_prob']),
          ('layers', config['num_hidden_layers']),
          ('hidden_size', config['hidden_size']))
  frozen = tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v)
                        for k, v in stream.items()))
  return _dropout_masks(jnp.uint32(seed), jnp.uint32(count), config=slim,
                        stream=frozen, shape=tuple(shape))


# ----------------------------------------------------------------------------
# AdamW under warm-up + cosine decay (the recipe the traffic file states)


def learning_rate(count, train):
  """Linear warm-up from 0 over ``warmup_steps`` then cosine decay to 0 at
  ``total_steps``; ``count`` is the number of updates already made."""
  peak, warm = train['learning_rate'], train['warmup_steps']
  total = max(train['total_steps'], warm + 1)
  if count < warm:
    return peak * count / warm
  frac = min(count - warm, total - warm) / (total - warm)
  return peak * 0.5 * (1.0 + math.cos(math.pi * frac))


def adamw_update(params, grads, mu, nu, count, train, b1=0.9, b2=0.999,
                 eps=1e-8):
  """One AdamW update of every leaf (weight decay on all of them, as the
  recipe states); returns ``(params, mu, nu)``."""
  lr = learning_rate(count, train)
  t = count + 1
  wd = train['weight_decay']
  new_p, new_mu, new_nu = {}, {}, {}
  for k, p in params.items():
    g = grads[k]
    m = b1 * mu[k] + (1 - b1) * g
    v = b2 * nu[k] + (1 - b2) * g * g
    step = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
    new_p[k] = p - lr * (step + wd * p)
    new_mu[k], new_nu[k] = m, v
  return new_p, new_mu, new_nu


def leaf_norms(tree):
  return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
          for k, v in tree.items()}


def follow(config, train, seed, batches, precision='float32', keep=None,
           stream=None):
  """Follow the first ``len(batches)`` steps from the seed's weights.

  Returns ``{'losses', 'grad_norms', 'change_norms'}`` as Python floats:
  each step's loss, the per-leaf norm of the first gradient, and the
  per-leaf norm of the parameters' change after the last step. ``keep``
  (a slice of rows) plants the half-batch fault: only those rows of each
  batch are seen, the mean taken over them. ``stream`` (see
  :func:`dropout_masks`) is needed where ``hidden_dropout_prob`` is not 0.
  """
  max_pred = train.get('max_predictions')
  longest = train['max_seq_length']
  dropping = config['hidden_dropout_prob'] > 0
  if dropping and stream is None:
    raise ValueError('hidden_dropout_prob is not 0: the reference needs the '
                     'stream of the program\'s dropout keys')

  def pad(batch):
    """Every compared step in the one shape [rows, max_seq_length], so the
    reference compiles once per cell: padding is masked out of attention
    and carries no label, so it changes no number."""
    out = {}
    for k, v in batch.items():
      v = np.asarray(v)
      if v.ndim == 2 and v.shape[1] < longest:
        fill = {'labels': IGNORE, 'segment_ids': -1}.get(k, 0)
        v = np.pad(v, ((0, 0), (0, longest - v.shape[1])),
                   constant_values=fill)
      out[k] = v
    return out

  def masks_for(count, batch):
    """Drawn at the batch's own shape, then cut and padded as the batch
    is (a padded unit is dropped or kept to no effect)."""
    if not dropping:
      return None
    rows, columns = np.shape(batch['input_ids'])
    masks = dropout_masks(config, stream, seed, count, (rows, columns))
    if keep is not None:
      masks = {'embed': masks['embed'][keep],
               'layers': masks['layers'][:, :, keep]}
    extra = longest - columns
    if extra:
      masks = {k: jnp.pad(v, [(0, 0)] * (v.ndim - 2) + [(0, extra), (0, 0)])
               for k, v in masks.items()}
    return masks

  def loss_fn(params, batch, masks):
    return forward(config, params, batch, max_pred, precision, masks)[0]

  step = jax.jit(jax.value_and_grad(loss_fn))

  # The seed is an argument of the compiled program, not a constant in it.
  p0 = jax.jit(lambda s: init_params(config, s))(jnp.uint32(seed))
  params = p0
  mu = jax.tree.map(jnp.zeros_like, p0)
  nu = jax.tree.map(jnp.zeros_like, p0)
  update = jax.jit(functools.partial(adamw_update, train=train),
                   static_argnames=('count',), donate_argnums=(2, 3))
  out = {'losses': []}
  for count, batch in enumerate(batches):
    masks = masks_for(count, batch)
    if keep is not None:
      batch = {k: v[keep] for k, v in batch.items()}
    batch = {k: jnp.asarray(v) for k, v in pad(batch).items()}
    loss, grads = step(params, batch, masks)
    del masks
    out['losses'].append(float(loss))
    if count == 0:
      out['grad_norms'] = {k: float(v)
                           for k, v in jax.jit(leaf_norms)(grads).items()}
    new_params, mu, nu = update(params, grads, mu, nu, count=count)
    if count:  # p0 is still needed for the change
      jax.tree.map(lambda a: a.delete(), params)
    params = new_params
    del grads
  change = jax.jit(lambda a, b: leaf_norms(
      {k: a[k] - b[k] for k in a}))(params, p0)
  out['change_norms'] = {k: float(v) for k, v in change.items()}
  return out
