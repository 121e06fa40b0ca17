"""BERT for pretraining (MLM + NSP), TPU-first.

Consumes exactly what :func:`lddl_tpu.loader.get_bert_pretrain_data_loader`
yields (input_ids / token_type_ids / attention_mask / labels /
next_sentence_labels). Design choices for the MXU/XLA:

  - bfloat16 activations, float32 params and softmax/LSE accumulation;
  - ``nn.scan`` over layers: one traced layer body regardless of depth
    (compile time O(1) in num_layers), with optional ``jax.checkpoint``
    rematerialization to trade FLOPs for HBM, selectively (Korthikanti et
    al. 2022, arXiv:2205.05198): beside a layer's input the backward pass
    is handed what is O(tokens * width) and a wide gemm to remake, i.e. the
    outputs of the ``query``, ``key``, ``value``, ``intermediate`` and
    ``output`` projections and the context (the flash kernels'
    ``(out, lse)``, or the dense path's p.v), in bfloat16
    ``(5 * hidden_size + intermediate_size) * 2`` bytes a token and layer;
    it remakes what is O(seq^2) or element-wise (the dense path's scores
    and softmax, GELU, the layer norms, the dropout masks) and the ``out``
    projection, which is cheaper remade than kept; without remat the FFN's
    GELU and the layer's two norms alone are remade, from their inputs
    (``_keeps_its_input``);
  - static shapes everywhere — the loader's per-bin padding means one
    compiled program per bin;
  - attention is pluggable through ``BertConfig.attention_impl``;
    :func:`lddl_tpu.ops.attention.attend` is the one place that knows
    the back-ends: 'dense' (XLA fuses the softmax chain; GSPMD inserts
    collectives if heads/seq are sharded), 'flash' (Pallas
    blockwise-softmax kernel, :mod:`lddl_tpu.ops.flash_attention` — no
    O(s^2) score materialization), or 'ring' / 'ring_flash'
    (:mod:`lddl_tpu.parallel.ring`) for sequence-parallel long context;
  - tied MLM decoder (logits against the word-embedding table), vocab
    sharded over the ``tensor`` axis.

Tensor-parallel sharding follows the Megatron pattern: QKV and MLP-in
kernels split column-wise, attention-out and MLP-out row-wise, so each
block needs a single all-reduce (inserted by GSPMD from the param specs in
:func:`spec_for_param`).
"""

import dataclasses
import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..ops.attention import FLASH_IMPLS, REMAT_KEPT_NAMES, attend


@dataclasses.dataclass(frozen=True)
class BertConfig:
  vocab_size: int = 30528  # 30522 padded up to a multiple of 64 for the MXU
  hidden_size: int = 768
  num_layers: int = 12
  num_heads: int = 12
  intermediate_size: int = 3072
  max_position_embeddings: int = 512
  type_vocab_size: int = 2
  dropout_rate: float = 0.1
  dtype: Any = jnp.bfloat16
  attention_impl: str = 'dense'  # one of ops.attention.ATTENTION_IMPLS
  # Remake each layer in the backward pass but for five of its gemms'
  # outputs and its context, which are kept (see above: 18.4 KB a token and
  # layer for BERT-large, 13.8 KB for base).
  remat: bool = False

  @property
  def head_dim(self):
    return self.hidden_size // self.num_heads


def _row_sums(prim, *_, **__):
  """What a kept op's backward takes from its forward beside the input: its
  sums over a row (a LayerNorm's of x and x**2), one float32 a row each,
  which the backward would otherwise remake by reading every row again."""
  return prim is jax.lax.reduce_sum_p


def _keeps_its_input(cfg, op):
  """``op`` (a function, or a flax module class) as the layer applies it.

  Without remat nothing else remakes ``op``: its backward keeps its input
  (and its row sums, ``_row_sums``) and remakes the rest, where its
  linearisation would keep what it made on the way as stacks over the
  layers: GELU's tanh and its derivatives at the intermediate width, a
  LayerNorm's float32 centred input and scales at the hidden width. Under
  remat the policy decides and ``op`` is left as it is. The scan keeps the
  two passes apart, so no CSE barrier is needed; with one, the TPU compiler
  does not fuse the remade GELU into the `output` gemm's backward and writes
  its derivative out (tests/test_deviceless_compile.py).
  """
  if cfg.remat:
    return op
  checkpoint = nn.remat if isinstance(op, type) else jax.checkpoint
  return checkpoint(op, prevent_cse=False, policy=_row_sums)


def _dense(features, cfg, name=None):
  return nn.Dense(
      features,
      dtype=cfg.dtype,
      param_dtype=jnp.float32,
      kernel_init=nn.initializers.normal(0.02),
      name=name)


class SelfAttention(nn.Module):
  cfg: BertConfig
  mesh: Any = None
  deterministic: bool = True

  @nn.compact
  def __call__(self, x, attention_mask, segment_ids=None):
    cfg, deterministic = self.cfg, self.deterministic
    b, s, _ = x.shape
    heads, hd = cfg.num_heads, cfg.head_dim
    # A name is what ``Encoder``'s remat policy keeps by (elsewhere it is
    # the identity and lowers to nothing). A kept value is stored in the
    # shape it is named in, so each is named as ``[b, s, width]``: a minor
    # dimension of a head's 64 would be padded to the chip's 128 lanes.
    q = checkpoint_name(_dense(cfg.hidden_size, cfg, 'query')(x), 'query_out')
    k = checkpoint_name(_dense(cfg.hidden_size, cfg, 'key')(x), 'key_out')
    v = checkpoint_name(_dense(cfg.hidden_size, cfg, 'value')(x), 'value_out')
    q = q.reshape(b, s, heads, hd).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, heads, hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, heads, hd).transpose(0, 2, 1, 3)
    ctx = attend(q, k, v, attention_mask, segment_ids,
                 impl=cfg.attention_impl, mesh=self.mesh, dtype=cfg.dtype)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, cfg.hidden_size)
    if cfg.attention_impl not in FLASH_IMPLS:
      # The flash kernels name their own output: a value is kept once.
      ctx = checkpoint_name(ctx, 'dense_context')
    out = _dense(cfg.hidden_size, cfg, 'out')(ctx)
    return nn.Dropout(cfg.dropout_rate)(out, deterministic=deterministic)


class Layer(nn.Module):
  """Post-LN transformer block (original BERT residual layout)."""
  cfg: BertConfig
  mesh: Any = None
  deterministic: bool = True

  @nn.compact
  def __call__(self, x, attention_mask, segment_ids=None):
    cfg, deterministic = self.cfg, self.deterministic
    attn = SelfAttention(cfg, self.mesh, deterministic, name='attention')(
        x, attention_mask, segment_ids)
    norm = _keeps_its_input(cfg, nn.LayerNorm)
    # The scopes name what is no flax module, for the capture summary
    # (telemetry/capture.py); they are metadata and change no arithmetic.
    with jax.named_scope('residual'):
      x = x + attn
    x = norm(dtype=cfg.dtype, name='attention_norm')(x)
    h = checkpoint_name(_dense(cfg.intermediate_size, cfg, 'intermediate')(x),
                        'intermediate_out')
    gelu = _keeps_its_input(cfg, functools.partial(nn.gelu, approximate=True))
    with jax.named_scope('gelu'):
      h = gelu(h)
    h = checkpoint_name(_dense(cfg.hidden_size, cfg, 'output')(h),
                        'output_out')
    h = nn.Dropout(cfg.dropout_rate)(h, deterministic=deterministic)
    with jax.named_scope('residual'):
      x = x + h
    return norm(dtype=cfg.dtype, name='output_norm')(x)


class Encoder(nn.Module):
  cfg: BertConfig
  mesh: Any = None

  @nn.compact
  def __call__(self, x, attention_mask, deterministic, segment_ids=None):
    cfg = self.cfg
    block = Layer
    if cfg.remat:
      # What remat keeps is read from the traced layer itself: a name that
      # the layer's back-end did not tag keeps nothing.
      block = nn.remat(Layer, policy=jax.checkpoint_policies.
                       save_only_these_names(*REMAT_KEPT_NAMES))

    def body(layer, carry, _):
      return layer(carry, attention_mask, segment_ids), None

    x, _ = nn.scan(
        body,
        variable_axes={'params': 0},
        split_rngs={'params': True, 'dropout': True},
        length=cfg.num_layers,
        metadata_params={nn.PARTITION_NAME: None},
    )(block(cfg, self.mesh, deterministic, name='layers'), x, None)
    return x


class BertForPretraining(nn.Module):
  cfg: BertConfig
  mesh: Any = None

  def setup(self):
    cfg = self.cfg
    self.word_embeddings = nn.Embed(
        cfg.vocab_size, cfg.hidden_size,
        dtype=cfg.dtype, param_dtype=jnp.float32,
        embedding_init=nn.initializers.normal(0.02),
        name='word_embeddings')
    self.position_embeddings = nn.Embed(
        cfg.max_position_embeddings, cfg.hidden_size,
        dtype=cfg.dtype, param_dtype=jnp.float32, name='position_embeddings')
    self.token_type_embeddings = nn.Embed(
        cfg.type_vocab_size, cfg.hidden_size,
        dtype=cfg.dtype, param_dtype=jnp.float32, name='token_type_embeddings')
    self.embed_norm = nn.LayerNorm(dtype=cfg.dtype, name='embed_norm')
    self.embed_dropout = nn.Dropout(cfg.dropout_rate)
    self.encoder = Encoder(cfg, self.mesh, name='encoder')
    self.pooler = _dense(cfg.hidden_size, cfg, 'pooler')
    self.nsp_classifier = _dense(2, cfg, 'nsp_classifier')
    self.mlm_transform = _dense(cfg.hidden_size, cfg, 'mlm_transform')
    self.mlm_norm = nn.LayerNorm(dtype=cfg.dtype, name='mlm_norm')
    self.mlm_bias = self.param('mlm_bias', nn.initializers.zeros,
                               (cfg.vocab_size,), jnp.float32)

  def __call__(self, input_ids, token_type_ids, attention_mask,
               deterministic=True, mlm_positions=None, segment_ids=None):
    """Returns (mlm_logits float32, nsp_logits [b,2] float32).

    ``segment_ids`` int32 ``[b, s]`` (doc index per token, -1 = padding,
    from the packed loader's ``block_diagonal`` mode) restricts
    attention block-diagonally to same-document pairs in every layer —
    dense via an additive bias, flash/ring via kernel tile skipping.

    ``mlm_positions=None``: logits over every position, ``[b, s, V]``.
    ``mlm_positions`` int32 ``[b, P]``: the masked-only head — hidden
    states are gathered at those positions *before* the transform and
    tied vocab projection, so logits are ``[b, P, V]``. With P = the
    static masking budget (~0.15·s) this removes the dominant
    ``b·s·V`` logits chain from compute and HBM (only ~15% of positions
    carry MLM targets); the classic BERT-pretraining optimization,
    expressed with the static shapes XLA wants.
    """
    cfg = self.cfg
    s = input_ids.shape[1]
    pos = jnp.arange(s, dtype=jnp.int32)[None, :]
    # embed / mlm_head / nsp_head name what happens between the modules
    # (the sum, the gather, the tied decoder, tanh) for the capture
    # summary; a named_scope is metadata and no flax scope, so parameter
    # paths and dropout keys stay as they are.
    with jax.named_scope('embed'):
      x = (self.word_embeddings(input_ids) + self.position_embeddings(pos) +
           self.token_type_embeddings(token_type_ids))
      x = self.embed_norm(x)
    x = self.embed_dropout(x, deterministic=deterministic)
    mask = attention_mask.astype(bool)
    x = self.encoder(x, mask, deterministic, segment_ids)

    with jax.named_scope('mlm_head'):
      x_mlm = x
      if mlm_positions is not None:
        x_mlm = jnp.take_along_axis(x, mlm_positions[:, :, None], axis=1)
      h = self.mlm_norm(nn.gelu(self.mlm_transform(x_mlm), approximate=True))
      mlm_logits = (self.word_embeddings.attend(h).astype(jnp.float32) +
                    self.mlm_bias)
    with jax.named_scope('nsp_head'):
      pooled = jnp.tanh(self.pooler(x[:, 0]))
      nsp_logits = self.nsp_classifier(pooled).astype(jnp.float32)
    return mlm_logits, nsp_logits


# --- Tensor/FSDP-parallel parameter placement (Megatron pattern) ---

_RULES = (
    ('word_embeddings/embedding', ('tensor', 'fsdp')),
    ('position_embeddings/embedding', (None, None)),
    ('token_type_embeddings/embedding', (None, None)),
    ('query/kernel', ('fsdp', 'tensor')),
    ('key/kernel', ('fsdp', 'tensor')),
    ('value/kernel', ('fsdp', 'tensor')),
    ('query/bias', ('tensor',)),
    ('key/bias', ('tensor',)),
    ('value/bias', ('tensor',)),
    ('attention/out/kernel', ('tensor', 'fsdp')),
    ('intermediate/kernel', ('fsdp', 'tensor')),
    ('intermediate/bias', ('tensor',)),
    ('output/kernel', ('tensor', 'fsdp')),
    ('mlm_bias', ('tensor',)),
)


def spec_for_param(path, shape):
  """PartitionSpec for one parameter, by its flax path tuple.

  Scanned-layer params carry a leading ``num_layers`` axis; any rule spec
  shorter than the param rank is left-padded with None to cover it.
  """
  name = '/'.join(str(p) for p in path)
  for suffix, spec in _RULES:
    if name.endswith(suffix) or f'/{suffix}' in name:
      pad = (None,) * (len(shape) - len(spec))
      return P(*(pad + tuple(spec)))
  return P(*((None,) * len(shape)))
