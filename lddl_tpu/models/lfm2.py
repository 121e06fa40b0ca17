"""LFM2-MoE: a causal decoder of gated short convolutions, grouped-query
attention and sparse experts (LiquidAI's LFM2-8B-A1B,
https://huggingface.co/LiquidAI/LFM2-8B-A1B), trained on packed rows.

Consumes what :class:`lddl_tpu.loader.packed.CausalPackedCollate`
yields: ``input_ids``, ``segment_ids`` (document per token, -1 =
padding), ``positions`` (restarting at each document) and ``labels`` (the
next id of the same document, -100 elsewhere). Every operator keeps to
its document: attention is causal within it, the convolution restarts at
its first token, and RoPE counts from it.

The block (``x`` the residual stream, every norm an RMSNorm):
``h = x + op(norm(x))``, ``out = h + ffn(norm(h))``, where ``op`` is

  - a short convolution: ``[B, C, x~] = in_proj(u)``, ``z = B * x~``,
    ``z'_t = sum_j w_j * z_{t-j}`` (depthwise, causal, ``conv_kernel``
    taps, no bias), ``out_proj(C * z')``;
  - or attention: q, k, v projections, RMSNorm per head on q and k, RoPE,
    causal softmax with ``num_heads // num_kv_heads`` query heads to a
    key/value head (:func:`lddl_tpu.ops.attention.attend`), ``out_proj``;

and ``ffn`` is a SwiGLU ``w2(silu(w1 u) * w3 u)`` in the leading dense
layers and sparse experts (:mod:`lddl_tpu.ops.moe`) in the others. A
final norm comes before an untied head, whose loss is taken over chunks
of rows (``loss_chunk``) so that full logits never exist.

Depth: ``num_dense_layers`` leading dense layers, then ``num_periods``
periods of ``period`` (a layer type each), scanned. A chip holds experts
``[first_held_expert, first_held_expert + held_experts)`` of
``num_experts`` and routes over all of them.

bfloat16 activations, float32 parameters, router, norms' sums and
softmax. Under ``remat`` each block is remade in the backward pass but
for what :data:`REMAT_KEPT_NAMES` names; without it the norms and the
gate keep only their inputs (``models/bert.py:_keeps_its_input``).
"""

import dataclasses
import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..ops import moe
from ..ops.attention import FLASH_IMPLS, FLASH_RESIDUAL_NAMES, attend
from .bert import _keeps_its_input

IGNORE_INDEX = -100
ATTENTION, CONV = 'full_attention', 'conv'

# What a rematted block keeps, by PR 35's rule (keep what feeds a gemm or
# a kernel; remake what feeds a norm): the flash kernels' (out, lse) and
# the q, k, v projections that feed them. The convolution's in_proj is
# remade: kept, its [tokens, 3 * hidden] output is 1.2 GB of the step's
# temp at [4, 8192] and put a 16 GB v5e's peak at 96.8 %. The experts are
# remade whole: their sorted rows are ``tokens * top_k`` wide.
REMAT_KEPT_NAMES = FLASH_RESIDUAL_NAMES + (
    'q_proj_out', 'k_proj_out', 'v_proj_out', 'dense_context')


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
  vocab_size: int = 16384
  hidden_size: int = 2048
  num_heads: int = 32
  num_kv_heads: int = 8
  conv_kernel: int = 3             # the source's conv_L_cache
  intermediate_size: int = 7168    # the dense layers' SwiGLU
  moe_intermediate_size: int = 1792
  num_experts: int = 32            # routed over, all of them
  held_experts: int = 8            # computed on this chip
  first_held_expert: int = 0
  top_k: int = 4
  routed_scaling_factor: float = 1.0
  bias_rate: float = 1e-3          # expert bias step (DeepSeek-V3 §2.1.2)
  dense_layer_types: tuple = (CONV,)
  period: tuple = (ATTENTION, CONV, CONV, CONV)
  num_periods: int = 1
  rope_theta: float = 1e6
  norm_eps: float = 1e-5
  loss_chunk: int = 4096           # rows of the head computed at once
  moe_chunk: int = 8192            # tokens the experts take at once
  dtype: Any = jnp.bfloat16
  attention_impl: str = 'flash'
  remat: bool = False

  @property
  def head_dim(self):
    return self.hidden_size // self.num_heads

  @property
  def num_layers(self):
    return len(self.dense_layer_types) + self.num_periods * len(self.period)


# The preset ``pretrain.main --model`` builds for the CPU; a published
# configuration comes in as its file (:func:`config_from_hf`).
PRESETS = {
    'lfm2-tiny': dict(
        hidden_size=64, num_heads=4, num_kv_heads=2, conv_kernel=3,
        intermediate_size=128, moe_intermediate_size=32, num_experts=8,
        held_experts=2, top_k=4, num_periods=1, loss_chunk=128),
}


def config_from_hf(c, **overrides):
  """The ``Lfm2Config`` of a configuration in the source's keys (LiquidAI's
  ``config.json``). Where ``published_num_experts`` is given, the router
  goes over that many and ``num_experts`` is the count this chip holds.
  The layers after the ``num_dense_layers`` leading ones are scanned as
  repeats of their shortest period."""
  for key, built in (('conv_bias', False), ('use_expert_bias', True),
                     ('norm_topk_prob', True)):
    if c.get(key, built) != built:
      raise ValueError(f'{key}={c[key]!r}: the program builds {built}')
  dense = c['num_dense_layers']
  layers = tuple(c['layer_types'])
  rest = layers[dense:]
  width = next(n for n in range(1, len(rest) + 1)
               if rest == rest[:n] * (len(rest) // n))
  fields = dict(
      vocab_size=c['vocab_size'], hidden_size=c['hidden_size'],
      num_heads=c['num_attention_heads'],
      num_kv_heads=c['num_key_value_heads'], conv_kernel=c['conv_L_cache'],
      intermediate_size=c['intermediate_size'],
      moe_intermediate_size=c['moe_intermediate_size'],
      num_experts=c.get('published_num_experts', c['num_experts']),
      held_experts=c['num_experts'], top_k=c['num_experts_per_tok'],
      routed_scaling_factor=float(c['routed_scaling_factor']),
      dense_layer_types=layers[:dense], period=rest[:width],
      num_periods=len(rest) // width, rope_theta=float(c['rope_theta']),
      norm_eps=c['norm_eps'])
  return Lfm2Config(**{**fields, **overrides})


def _dense(features, cfg, name):
  return nn.Dense(features, use_bias=False, dtype=cfg.dtype,
                  param_dtype=jnp.float32,
                  kernel_init=nn.initializers.normal(0.02), name=name)


class RMSNorm(nn.Module):
  """``x / rms(x) * scale``, the sums in float32."""
  eps: float
  dtype: Any

  @nn.compact
  def __call__(self, x):
    scale = self.param('scale', nn.initializers.ones, (x.shape[-1],),
                       jnp.float32)
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) +
                        self.eps)
    return (x32 * inv * scale).astype(self.dtype)


def _norm(cfg, name):
  return _keeps_its_input(cfg, RMSNorm)(cfg.norm_eps, cfg.dtype, name=name)


def rope(x, positions, theta):
  """Rotary positions on ``x`` [b, s, heads, d], halves rotated (the
  source's ``rotate_half``), angles in float32."""
  d = x.shape[-1]
  inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
  angle = positions.astype(jnp.float32)[:, :, None, None] * inv
  cos, sin = jnp.cos(angle), jnp.sin(angle)
  x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
  return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                         axis=-1).astype(x.dtype)


class Attention(nn.Module):
  cfg: Lfm2Config
  mesh: Any = None

  @nn.compact
  def __call__(self, x, positions, segment_ids):
    cfg = self.cfg
    b, s, _ = x.shape
    hd = cfg.head_dim

    def project(name, heads):
      y = checkpoint_name(_dense(heads * hd, cfg, name)(x), name + '_out')
      return y.reshape(b, s, heads, hd)

    q = project('q_proj', cfg.num_heads)
    k = project('k_proj', cfg.num_kv_heads)
    v = project('v_proj', cfg.num_kv_heads)
    q = rope(_norm(cfg, 'q_norm')(q), positions, cfg.rope_theta)
    k = rope(_norm(cfg, 'k_norm')(k), positions, cfg.rope_theta)
    turn = lambda t: t.transpose(0, 2, 1, 3)
    ctx = attend(turn(q), turn(k), turn(v), segment_ids >= 0, segment_ids,
                 impl=cfg.attention_impl, mesh=self.mesh, dtype=cfg.dtype,
                 causal=True)
    ctx = turn(ctx).reshape(b, s, cfg.num_heads * hd)
    if cfg.attention_impl not in FLASH_IMPLS:
      ctx = checkpoint_name(ctx, 'dense_context')
    return _dense(cfg.hidden_size, cfg, 'out_proj')(ctx)


def short_conv(z, weight, segment_ids):
  """``z'_t = sum_j weight[j] * z_{t-j}`` over one row's documents:
  a tap that reaches into the document before (or before the row) reads
  zero. ``z`` [b, s, d], ``weight`` [taps, d]; float32 out."""
  out = z.astype(jnp.float32) * weight[0]
  for j in range(1, weight.shape[0]):
    earlier = jnp.pad(z, ((0, 0), (j, 0), (0, 0)))[:, :z.shape[1]]
    same = jnp.pad(segment_ids, ((0, 0), (j, 0)),
                   constant_values=-2)[:, :z.shape[1]] == segment_ids
    out = out + jnp.where(same[..., None], earlier.astype(jnp.float32),
                          0.0) * weight[j]
  return out


class ShortConv(nn.Module):
  cfg: Lfm2Config

  @nn.compact
  def __call__(self, x, segment_ids):
    cfg = self.cfg
    d = cfg.hidden_size
    bcx = checkpoint_name(_dense(3 * d, cfg, 'in_proj')(x), 'in_proj_out')
    gate_in, gate_out, value = jnp.split(bcx, 3, axis=-1)
    weight = self.param('conv_weight', nn.initializers.normal(0.02),
                        (cfg.conv_kernel, d), jnp.float32)
    z = short_conv(gate_in * value, weight, segment_ids)
    y = (gate_out.astype(jnp.float32) * z).astype(cfg.dtype)
    return _dense(d, cfg, 'out_proj')(y)


def _swiglu(gate, up):
  return (jax.nn.silu(gate.astype(jnp.float32)) *
          up.astype(jnp.float32)).astype(gate.dtype)


class FeedForward(nn.Module):
  cfg: Lfm2Config

  @nn.compact
  def __call__(self, x):
    cfg = self.cfg
    gate = _dense(cfg.intermediate_size, cfg, 'w1')(x)
    up = _dense(cfg.intermediate_size, cfg, 'w3')(x)
    with jax.named_scope('gate'):
      h = _keeps_its_input(cfg, _swiglu)(gate, up)
    return _dense(cfg.hidden_size, cfg, 'w2')(h)


class Experts(nn.Module):
  """Router over every expert, this chip's experts computed
  (:mod:`lddl_tpu.ops.moe`). Returns ``(out, load over every expert)``."""
  cfg: Lfm2Config

  @nn.compact
  def __call__(self, x, real):
    cfg = self.cfg
    b, s, d = x.shape
    f, e, held = cfg.moe_intermediate_size, cfg.num_experts, cfg.held_experts
    init = nn.initializers.normal(0.02)
    router = self.param('router', init, (d, e), jnp.float32)
    bias = self.param('expert_bias', nn.initializers.zeros, (e,),
                      jnp.float32)
    w1 = self.param('w1', init, (held, d, f), jnp.float32)
    w3 = self.param('w3', init, (held, d, f), jnp.float32)
    w2 = self.param('w2', init, (held, f, d), jnp.float32)
    u = x.reshape(b * s, d)
    real = real.reshape(b * s)
    with jax.named_scope('router'):
      logits = jnp.dot(u.astype(jnp.float32), router,
                       precision=jax.lax.Precision.HIGHEST)
      experts, weights, _ = moe.route(logits, bias, cfg.top_k,
                                      cfg.routed_scaling_factor)
      load = moe.expert_load(experts, real, e)
    w1, w3, w2 = (w.astype(cfg.dtype) for w in (w1, w3, w2))

    # The tokens go through the experts ``moe_chunk`` at a time, each
    # chunk remade in the backward pass from its inputs: a chunk's sorted
    # rows (``chunk * top_k``, the drop-free worst case) and their
    # intermediates are all that is ever live of them.
    @jax.checkpoint
    def mix(args):
      return moe.held_experts_mix(*args, w1, w3, w2,
                                  cfg.first_held_expert)[0]

    chunk = cfg.moe_chunk if (b * s) % cfg.moe_chunk == 0 else b * s
    split = lambda t: t.reshape(b * s // chunk, chunk, *t.shape[1:])
    out = jax.lax.map(mix, (split(u), split(experts), split(weights),
                            split(real)))
    return out.reshape(b, s, d), load


class Block(nn.Module):
  """One layer: ``kind`` (attention or convolution), then the dense
  feed-forward (``dense``) or the experts. Returns ``(x, load)``, load
  None for a dense layer."""
  cfg: Lfm2Config
  kind: str
  dense: bool
  mesh: Any = None

  @nn.compact
  def __call__(self, x, positions, segment_ids):
    cfg = self.cfg
    h = _norm(cfg, 'operator_norm')(x)
    if self.kind == ATTENTION:
      h = Attention(cfg, self.mesh, name='attention')(h, positions,
                                                      segment_ids)
    elif self.kind == CONV:
      h = ShortConv(cfg, name='conv')(h, segment_ids)
    else:
      raise ValueError(f'unknown layer type {self.kind!r}')
    with jax.named_scope('residual'):
      x = x + h
    h = _norm(cfg, 'ffn_norm')(x)
    load = None
    if self.dense:
      h = FeedForward(cfg, name='ffn')(h)
    else:
      h, load = Experts(cfg, name='moe')(h, segment_ids >= 0)
    with jax.named_scope('residual'):
      x = x + h
    return x, load


def _block(cfg):
  if not cfg.remat:
    return Block
  return nn.remat(Block, static_argnums=(), policy=jax.checkpoint_policies.
                  save_only_these_names(*REMAT_KEPT_NAMES))


class Period(nn.Module):
  """One period of ``cfg.period``: a block of each type in turn. Returns
  ``(x, loads [len(period), num_experts])``."""
  cfg: Lfm2Config
  mesh: Any = None

  @nn.compact
  def __call__(self, x, positions, segment_ids):
    loads = []
    for j, kind in enumerate(self.cfg.period):
      x, load = _block(self.cfg)(self.cfg, kind, False, self.mesh,
                                 name=f'block_{j}')(x, positions, segment_ids)
      loads.append(load)
    return x, jnp.stack(loads)


class Decoder(nn.Module):
  cfg: Lfm2Config
  mesh: Any = None

  @nn.compact
  def __call__(self, x, positions, segment_ids):
    cfg = self.cfg
    for i, kind in enumerate(cfg.dense_layer_types):
      x, _ = _block(cfg)(cfg, kind, True, self.mesh, name=f'dense_{i}')(
          x, positions, segment_ids)

    def body(period, carry, _):
      return period(carry, positions, segment_ids)

    x, loads = nn.scan(
        body, variable_axes={'params': 0}, split_rngs={'params': True},
        length=cfg.num_periods,
        metadata_params={nn.PARTITION_NAME: None},
    )(Period(cfg, self.mesh, name='periods'), x, None)
    return x, loads


def chunked_cross_entropy(x, kernel, labels, chunk, dtype):
  """``(sum of the cross entropy over real labels, their count)`` of the
  head ``x @ kernel`` [tokens, vocab], a chunk of rows at a time: each
  chunk's float32 logits are made, used and remade in the backward pass,
  never kept."""
  t, d = x.shape
  chunk = min(chunk, t)
  n = -(-t // chunk)
  if n * chunk != t:
    x = jnp.pad(x, ((0, n * chunk - t), (0, 0)))
    labels = jnp.pad(labels, (0, n * chunk - t),
                     constant_values=IGNORE_INDEX)
  w = kernel.astype(dtype)

  @jax.checkpoint
  def one(total, rows):
    xc, lc = rows
    logits = jnp.dot(xc, w, preferred_element_type=jnp.float32)
    real = lc != IGNORE_INDEX
    picked = jnp.take_along_axis(logits, jnp.where(real, lc, 0)[:, None],
                                 axis=-1)[:, 0]
    ce = jax.nn.logsumexp(logits, axis=-1) - picked
    return total + jnp.sum(jnp.where(real, ce, 0.0)), None

  total, _ = jax.lax.scan(one, jnp.float32(0.0),
                          (x.reshape(n, chunk, d), labels.reshape(n, chunk)))
  return total, jnp.sum(labels != IGNORE_INDEX)


class Lfm2ForCausalLM(nn.Module):
  cfg: Lfm2Config
  mesh: Any = None

  @nn.compact
  def __call__(self, input_ids, positions, segment_ids, labels):
    """``(loss, per-expert loads [num_periods, len(period), num_experts])``:
    the mean cross entropy over the real labels."""
    cfg = self.cfg
    x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                 param_dtype=jnp.float32,
                 embedding_init=nn.initializers.normal(0.02),
                 name='token_embeddings')(input_ids)
    x, loads = Decoder(cfg, self.mesh, name='decoder')(x, positions,
                                                       segment_ids)
    x = _norm(cfg, 'final_norm')(x)
    kernel = self.param('lm_head', nn.initializers.normal(0.02),
                        (cfg.hidden_size, cfg.vocab_size), jnp.float32)
    with jax.named_scope('loss'):
      total, count = chunked_cross_entropy(
          x.reshape(-1, cfg.hidden_size), kernel, labels.reshape(-1),
          cfg.loss_chunk, cfg.dtype)
      return total / jnp.maximum(count, 1), loads


def dummy_batch(batch, seq):
  """A batch of nothing in the causal collate's keys."""
  zeros = jnp.zeros((batch, seq), jnp.int32)
  return {'input_ids': zeros, 'positions': zeros, 'segment_ids': zeros,
          'labels': jnp.full((batch, seq), IGNORE_INDEX, jnp.int32)}


def causal_loss(model, params, batch):
  """``(loss, metrics)``: ``expert_load`` is the step's routed count of
  every expert in every sparse layer, int32 ``[periods, len(period),
  experts]``."""
  loss, loads = model.apply(
      {'params': params}, batch['input_ids'], batch['positions'],
      batch['segment_ids'], batch['labels'])
  return loss, {'expert_load': loads}


def balance_biases(cfg, params, metrics):
  """The step's expert-bias update (:func:`lddl_tpu.ops.moe.balance_bias`)
  of every sparse layer, from its loads in ``metrics``."""
  loads = metrics['expert_load']
  periods = dict(params['decoder']['periods'])
  for j in range(len(cfg.period)):
    block = dict(periods[f'block_{j}'])
    experts = dict(block['moe'])
    experts['expert_bias'] = moe.balance_bias(
        experts['expert_bias'], loads[:, j], cfg.bias_rate)
    block['moe'] = experts
    periods[f'block_{j}'] = block
  decoder = dict(params['decoder'], periods=periods)
  return dict(params, decoder=decoder)


def decay_mask(params):
  """AdamW's weight decay on every leaf but the expert biases, which no
  gradient moves."""
  return jax.tree_util.tree_map_with_path(
      lambda path, _: getattr(path[-1], 'key', None) != 'expert_bias',
      params)


_RULES = (
    ('token_embeddings/embedding', ('tensor', None)),
    ('lm_head', (None, 'tensor')),
)


def spec_for_param(path, shape):
  """PartitionSpec of one parameter: the vocabulary over ``tensor``, the
  rest whole on every chip (attention, convolution and the dense layer
  are data-parallel; the experts a chip holds are its own)."""
  name = '/'.join(str(p) for p in path)
  for suffix, spec in _RULES:
    if name.endswith(suffix):
      return P(*((None,) * (len(shape) - len(spec)) + tuple(spec)))
  return P(*((None,) * len(shape)))


def flops_per_step(cfg, batch, seq):
  """Analytic FLOPs of one train step of ``[batch, seq]`` (forward and
  backward, 6 per parameter and token, causal attention at half the
  square), with the held experts at their expected share of the
  routing. The ``train.mfu`` gauge's fallback numerator."""
  d, hd = cfg.hidden_size, cfg.head_dim
  tokens = batch * seq
  conv = 3 * d * d + d * d
  attn = d * (cfg.num_heads + 2 * cfg.num_kv_heads) * hd + d * d
  mixers = sum(attn if k == ATTENTION else conv
               for k in cfg.dense_layer_types + cfg.period * cfg.num_periods)
  dense = len(cfg.dense_layer_types) * 3 * d * cfg.intermediate_size
  sparse = cfg.num_periods * len(cfg.period)
  experts = (sparse * cfg.top_k * cfg.held_experts / cfg.num_experts * 3 * d *
             cfg.moe_intermediate_size)
  router = sparse * d * cfg.num_experts
  per_token = mixers + dense + experts + router + d * cfg.vocab_size
  n_attn = sum(k == ATTENTION for k in cfg.period) * cfg.num_periods
  attention = n_attn * 6 * batch * seq * seq * cfg.num_heads * hd
  return 6 * per_token * tokens + attention


def build_objective(cfg, mesh):
  """``(model, Objective)`` of the training step for ``cfg``."""
  from ..parallel.train import Objective
  model = Lfm2ForCausalLM(cfg, mesh=mesh)
  return model, Objective(
      loss_fn=lambda params, batch, rng: causal_loss(model, params, batch),
      param_spec_fn=spec_for_param,
      flops_fn=functools.partial(flops_per_step, cfg),
      after_update=functools.partial(balance_biases, cfg),
      causal=True)


def init_params(cfg, mesh, rng):
  """Parameters placed by :func:`spec_for_param`, made on a dense-attention
  copy of the model (the shapes do not depend on the back-end)."""
  from ..parallel.train import init_sharded
  model = Lfm2ForCausalLM(dataclasses.replace(cfg, attention_impl='dense'))
  batch = dummy_batch(2, 16)
  return init_sharded(
      lambda: model.init(rng, batch['input_ids'], batch['positions'],
                         batch['segment_ids'], batch['labels'])['params'],
      mesh, spec_for_param)
