"""Plain reference of the LFM2-MoE training step, in float32 ``jax.numpy``.

Nothing here imports the program, and nothing here takes anything the
program made. The weights come from :func:`init_params` (the
configuration's own ``weights_seed`` in, a flat dict of float32 arrays
out): N(0, 0.02) matrices and embeddings, ones for norm gains, zero expert
biases. The family puts the same weights into the program through its
adapter (``families/lfm2_moe.py``) and, once the window has closed, this
file follows the program's first steps on the batches it consumed:

  forward (LiquidAI's LFM2-8B-A1B configuration as the file states it:
  RMSNorm pre-norm blocks of gated short convolutions and of grouped-query
  attention with per-head q/k norms and RoPE, a SwiGLU feed-forward in the
  leading dense layers and sigmoid-routed experts in the others, a final
  norm and an untied head), the mean next-token cross entropy over the
  real labels, gradients by ``jax.grad``, AdamW under a warm-up + cosine
  schedule on every leaf but the expert biases, and the biases' step
  ``rate * sign(mean load - load)`` (DeepSeek-V3, arXiv:2412.19437
  §2.1.2) from the step's routed counts over every expert.

Every operator keeps to a token's document (``segment_ids``): attention
is causal within it, the convolution's taps read zero before its first
token, RoPE counts from it (``positions``). The chip's share is the
configuration's: its router scores every expert, the top ``k`` of the
scores plus the bias are chosen, and only the held experts' part of the
sum is added (each held expert computed on every token, weighted by its
routing weight where chosen and by nought elsewhere).

Every matrix product goes through BERT's reference's ``_dot``:
``precision='float32'`` runs it at ``Precision.HIGHEST``, ``'fp8'``
(the control) rounds both operands to 8-bit floats, forward and
backward. Attention goes a row and a tile of ``QUERY_TILE`` queries at a
time, the experts one at a time and the head ``HEAD_CHUNK`` rows at a
time, each rematerialised in the backward pass, so that [4, 8192] fits.
"""

import functools
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import _dot, learning_rate

IGNORE = -100
QUERY_TILE = 256
HEAD_CHUNK = 4096
NORM_EPS = 1e-6  # added to the chosen scores' sum (norm_topk_prob)

# ----------------------------------------------------------------------------
# weights


def layer_kinds(config):
  """``[(type, dense)]`` of every layer kept."""
  kinds = config['layer_types']
  return [(k, i < config['num_dense_layers']) for i, k in enumerate(kinds)]


def param_shapes(config):
  d, v = config['hidden_size'], config['vocab_size']
  h, kvh = config['num_attention_heads'], config['num_key_value_heads']
  hd = d // h
  e, held = config['published_num_experts'], config['num_experts']
  fm, ff = config['moe_intermediate_size'], config['intermediate_size']
  shapes = {'emb': (v, d), 'head': (d, v), 'final_g': (d,)}
  for i, (kind, dense) in enumerate(layer_kinds(config)):
    leaves = {'op_g': (d,), 'ffn_g': (d,)}
    if kind == 'conv':
      leaves.update(conv_in=(d, 3 * d), conv_w=(config['conv_L_cache'], d),
                    conv_out=(d, d))
    else:
      leaves.update(q=(d, h * hd), k=(d, kvh * hd), v=(d, kvh * hd),
                    o=(h * hd, d), qn_g=(hd,), kn_g=(hd,))
    if dense:
      leaves.update(w1=(d, ff), w3=(d, ff), w2=(ff, d))
    else:
      leaves.update(router=(d, e), bias=(e,), e1=(held, d, fm),
                    e3=(held, d, fm), e2=(held, fm, d))
    shapes.update({f'L{i}.{k}': s for k, s in leaves.items()})
  return dict(sorted(shapes.items()))


def init_leaf(name, shape, seed):
  """One leaf from the seed and its name alone."""
  if name.endswith('_g'):
    return jnp.ones(shape, jnp.float32)
  if name.endswith('.bias'):
    return jnp.zeros(shape, jnp.float32)
  key = jax.random.fold_in(jax.random.key(seed),
                           zlib.crc32(name.encode()) & 0x7fffffff)
  return 0.02 * jax.random.normal(key, shape, jnp.float32)


def init_params(config, seed):
  return {name: init_leaf(name, shape, seed)
          for name, shape in param_shapes(config).items()}


# ----------------------------------------------------------------------------
# forward


def _rms(x, g, eps):
  return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _proj(x, w, precision):
  lead = x.shape[:-1]
  return _dot(x.reshape(-1, x.shape[-1]), w, precision).reshape(
      *lead, w.shape[-1])


def _rope(x, positions, theta):
  d = x.shape[-1]
  inv = theta ** (-np.arange(0, d, 2, dtype=np.float32) / d)
  angle = positions.astype(jnp.float32)[..., None, None] * inv
  x1, x2 = jnp.split(x, 2, axis=-1)
  cos, sin = jnp.cos(angle), jnp.sin(angle)
  return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend_row(q, k, v, seg, precision):
  """One row: q [s, h, hd], k and v [s, kvh, hd], seg [s]; causal within
  a document, a tile of queries at a time."""
  s, h, hd = q.shape
  group = h // k.shape[1]
  k = jnp.repeat(k, group, axis=1).transpose(1, 2, 0)  # [h, hd, s]
  v = jnp.repeat(v, group, axis=1).transpose(1, 0, 2)  # [h, s, hd]
  cols = jnp.arange(s)

  @jax.checkpoint
  def tile(args):
    qt, rows, seg_q = args  # [t, h, hd], [t], [t]
    scores = _dot(qt.transpose(1, 0, 2), k, precision) / math.sqrt(hd)
    keep = ((seg_q[:, None] == seg[None, :]) & (seg[None, :] >= 0) &
            (cols[None, :] <= rows[:, None]))
    scores = jnp.where(keep[None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = jnp.where(jnp.any(keep, axis=-1)[None, :, None], probs, 0.0)
    return _dot(probs, v, precision).transpose(1, 0, 2)

  t = min(QUERY_TILE, s)
  n = s // t
  ctx = jax.lax.map(tile, (q.reshape(n, t, h, hd), cols.reshape(n, t),
                           seg.reshape(n, t)))
  return ctx.reshape(s, h, hd)


def _attention(x, p, positions, seg, config, precision):
  b, s, d = x.shape
  h, kvh = config['num_attention_heads'], config['num_key_value_heads']
  hd = d // h
  eps, theta = config['norm_eps'], config['rope_theta']
  q = _proj(x, p['q'], precision).reshape(b, s, h, hd)
  k = _proj(x, p['k'], precision).reshape(b, s, kvh, hd)
  v = _proj(x, p['v'], precision).reshape(b, s, kvh, hd)
  q = _rope(_rms(q, p['qn_g'], eps), positions, theta)
  k = _rope(_rms(k, p['kn_g'], eps), positions, theta)
  ctx = jax.lax.map(lambda a: _attend_row(*a, precision), (q, k, v, seg))
  return _proj(ctx.reshape(b, s, h * hd), p['o'], precision)


def _conv(x, p, seg, precision):
  bcx = _proj(x, p['conv_in'], precision)
  gate_in, gate_out, value = jnp.split(bcx, 3, axis=-1)
  z = gate_in * value
  w = p['conv_w']
  out = z * w[0]
  s = z.shape[1]
  for j in range(1, w.shape[0]):
    earlier = jnp.pad(z, ((0, 0), (j, 0), (0, 0)))[:, :s]
    same = jnp.pad(seg, ((0, 0), (j, 0)), constant_values=-2)[:, :s] == seg
    out = out + jnp.where(same[..., None], earlier, 0.0) * w[j]
  return _proj(gate_out * out, p['conv_out'], precision)


def _swiglu(x, w1, w3, w2, precision):
  return _proj(jax.nn.silu(_proj(x, w1, precision)) * _proj(x, w3, precision),
               w2, precision)


def route(u, router, bias, config):
  """``(weights [t, e] float32, nought where not chosen; chosen [t, e])``
  of tokens ``u`` [t, d]."""
  scores = jax.nn.sigmoid(jnp.matmul(u, router,
                                     precision=jax.lax.Precision.HIGHEST))
  k = config['num_experts_per_tok']
  _, top = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), k)
  chosen = jnp.sum(jax.nn.one_hot(top, scores.shape[-1]), axis=1) > 0
  picked = jnp.where(chosen, scores, 0.0)
  weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + NORM_EPS)
  return weights * config['routed_scaling_factor'], chosen


def _experts(x, p, real, config, precision, first_held=0):
  """``(held experts' part of the sum, load of every expert)``."""
  b, s, d = x.shape
  u = x.reshape(b * s, d)
  weights, chosen = route(u, p['router'], p['bias'], config)
  real = real.reshape(b * s)
  load = jnp.sum(chosen & real[:, None], axis=0)
  held = config['num_experts']
  w = jnp.where(real[:, None], weights, 0.0)[:, first_held:first_held + held]

  @jax.checkpoint
  def one(acc, args):
    e1, e3, e2, we = args
    return acc + we[:, None] * _swiglu(u, e1, e3, e2, precision), None

  out, _ = jax.lax.scan(one, jnp.zeros_like(u),
                        (p['e1'], p['e3'], p['e2'], w.T))
  return out.reshape(b, s, d), load


def _layer_params(params, i):
  prefix = f'L{i}.'
  return {k[len(prefix):]: v for k, v in params.items()
          if k.startswith(prefix)}


def _cross_entropy(x, head, labels, precision):
  """Sum of the cross entropy over the real labels, and their count."""
  t, d = x.shape
  n = max(1, t // HEAD_CHUNK)

  @jax.checkpoint
  def one(total, args):
    xc, lc = args
    logits = _dot(xc, head, precision)
    real = lc != IGNORE
    picked = jnp.take_along_axis(logits, jnp.where(real, lc, 0)[:, None],
                                 axis=-1)[:, 0]
    ce = jax.nn.logsumexp(logits, axis=-1) - picked
    return total + jnp.sum(jnp.where(real, ce, 0.0)), None

  total, _ = jax.lax.scan(one, jnp.float32(0.0),
                          (x.reshape(n, t // n, d), labels.reshape(n, -1)))
  return total, jnp.sum(labels != IGNORE)


def forward(config, params, batch, precision='float32'):
  """``(loss, loads [sparse layers, experts])`` of one batch."""
  seg, positions = batch['segment_ids'], batch['positions']
  real = seg >= 0
  eps = config['norm_eps']
  x = params['emb'][batch['input_ids']]
  loads = []
  for i, (kind, dense) in enumerate(layer_kinds(config)):
    p = _layer_params(params, i)

    @jax.checkpoint
    def layer(x, p, kind=kind, dense=dense):
      h = _rms(x, p['op_g'], eps)
      if kind == 'conv':
        h = _conv(h, p, seg, precision)
      else:
        h = _attention(h, p, positions, seg, config, precision)
      x = x + h
      h = _rms(x, p['ffn_g'], eps)
      if dense:
        return x + _swiglu(h, p['w1'], p['w3'], p['w2'], precision), None
      h, load = _experts(h, p, real, config, precision)
      return x + h, load

    x, load = layer(x, p)
    if load is not None:
      loads.append(load)
  x = _rms(x, params['final_g'], eps)
  total, count = _cross_entropy(x.reshape(-1, x.shape[-1]), params['head'],
                                batch['labels'].reshape(-1), precision)
  return total / jnp.maximum(count, 1), jnp.stack(loads)


# ----------------------------------------------------------------------------
# the steps


def bias_names(config):
  """The expert biases' names, in the order of the sparse layers."""
  return [f'L{i}.bias' for i, (_, dense) in enumerate(layer_kinds(config))
          if not dense]


def update(params, grads, mu, nu, loads, count, train, config, b1=0.9,
           b2=0.999, eps=1e-8):
  """One AdamW update of every leaf but the expert biases (weight decay
  on all of those), and the biases' balancing step."""
  lr = learning_rate(count, train)
  t = count + 1
  wd = train['weight_decay']
  new_p, new_mu, new_nu = dict(params), dict(mu), dict(nu)
  for k, g in grads.items():
    m = b1 * mu[k] + (1 - b1) * g
    v = b2 * nu[k] + (1 - b2) * g * g
    step = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
    new_p[k] = params[k] - lr * (step + wd * params[k])
    new_mu[k], new_nu[k] = m, v
  rate = train['expert_bias_rate']
  for name, load in zip(bias_names(config), loads):
    load = load.astype(jnp.float32)
    new_p[name] = params[name] + rate * jnp.sign(jnp.mean(load) - load)
  return new_p, new_mu, new_nu


def _norms(tree):
  return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in tree.items()}


def follow(config, train, batches, precision='float32', keep=None):
  """Follow the first ``len(batches)`` steps from the configuration's
  weights. Returns ``{'losses', 'grad_norms', 'change_norms'}``: the
  gradient's norms per learned leaf (the expert biases have none), the
  change's per leaf, biases included. ``keep`` (a slice of rows) plants
  the half-batch fault."""
  seed = config['weights_seed']
  biases = set(bias_names(config))

  def loss_fn(learned, fixed, batch):
    return forward(config, {**learned, **fixed}, batch, precision)

  step = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))
  update_fn = jax.jit(functools.partial(update, train=train, config=config),
                      static_argnames=('count',), donate_argnums=(0, 2, 3))
  params = jax.jit(lambda: init_params(config, seed))()
  mu = {k: jnp.zeros_like(v) for k, v in params.items() if k not in biases}
  nu = {k: jnp.zeros_like(v) for k, v in mu.items()}
  out = {'losses': []}
  with jax.default_matmul_precision('highest'):
    for count, batch in enumerate(batches):
      batch = {k: jnp.asarray(np.asarray(v)[keep] if keep is not None else
                              np.asarray(v)) for k, v in batch.items()}
      learned = {k: v for k, v in params.items() if k not in biases}
      fixed = {k: v for k, v in params.items() if k in biases}
      (loss, loads), grads = step(learned, fixed, batch)
      out['losses'].append(float(loss))
      if count == 0:
        out['grad_norms'] = {k: float(v)
                             for k, v in jax.jit(_norms)(grads).items()}
      params, mu, nu = update_fn(params, grads, mu, nu, loads, count=count)
      del grads
    del mu, nu
    change = jax.jit(lambda p: _norms(
        {k: v - init_leaf(k, v.shape, seed) for k, v in p.items()}))(params)
  out['change_norms'] = {k: float(v) for k, v in change.items()}
  return out
