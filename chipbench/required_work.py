"""Required work of a BERT pretraining step, and the chip's peaks.

The numerators of ``train_step_mfu`` and ``flash_attention_roofline``.
"Required" is what the mathematics of the step needs for the tokens that
are really there, whatever implements it:

  - gemm terms over the *real* tokens of every row (padding bills
    nothing);
  - attention ``4 * len**2 * d`` per layer forward over each unit of
    attention: a row's real length, or under block-diagonal packing each
    *document's* length (cross-document scores are not required);
  - the MLM head over ``min(max_predictions, real masked positions)`` of
    each row, the pooler over each row;
  - backward = twice forward; recomputation (remat, the flash kernel's
    score recompute) is not counted.

A multiply-add is two operations. For a full, unpadded, dense batch this
equals the program's ``bert_pretrain_flops_per_step`` of the padded
shape; in every other case it is smaller, so a share of the peak built
on it cannot pass what the hardware executed.

The peaks live in ``peaks.json`` beside this file, keyed by
``device_kind``; a device that is not in the table is an error.
"""

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load_peaks(device_kind, path=None):
  """``{'flops_per_s', 'hbm_bytes_per_s', 'source'}`` of one chip."""
  with open(path or os.path.join(HERE, 'peaks.json')) as f:
    table = json.load(f)['chips']
  if device_kind not in table:
    raise KeyError(
        f'no peaks for device_kind {device_kind!r} in peaks.json (known: '
        f'{sorted(table)}); add the chip with its published source')
  return table[device_kind]


def widths(config):
  """The sizes the arithmetic needs, from a configuration file's keys."""
  d = config['hidden_size']
  return {
      'd': d,
      'd_ff': config['intermediate_size'],
      'layers': config['num_hidden_layers'],
      'heads': config['num_attention_heads'],
      'd_head': d // config['num_attention_heads'],
      'vocab': config['vocab_size'],
  }


def step_required_flops(config, row_lengths, unit_lengths, masked_counts,
                        max_predictions):
  """Required FLOPs (forward + backward) of one train step.

  ``row_lengths``: real tokens of each row. ``unit_lengths``: the lengths
  over which attention is required (one per row when dense, one per
  document under block-diagonal). ``masked_counts``: real MLM targets of
  each row. ``max_predictions``: the masked-only head's budget, or None
  for the full head (every position of every *real* token is then
  required).
  """
  w = widths(config)
  d, d_ff, vocab = w['d'], w['d_ff'], w['vocab']
  tokens = sum(int(n) for n in row_lengths)
  gemm = (8 * d * d + 4 * d * d_ff) * tokens
  attention = sum(4 * int(n) * int(n) * d for n in unit_lengths)
  fwd = w['layers'] * (gemm + attention)
  if max_predictions is None:
    head_positions = tokens
  else:
    head_positions = sum(min(int(max_predictions), int(m))
                         for m in masked_counts)
  fwd += 2 * head_positions * d * d        # MLM transform
  fwd += 2 * head_positions * d * vocab    # tied decoder
  fwd += 2 * len(row_lengths) * d * d      # pooler (the NSP head is d x 2)
  return 3 * fwd


def padded_step_flops(config, batch, seq_len, max_predictions):
  """What the program's ``bert_pretrain_flops_per_step`` bills for the
  padded shape (copied arithmetic; the ceiling ``step_required_flops``
  may reach and never pass)."""
  head = seq_len if max_predictions is None else min(max_predictions,
                                                     seq_len)
  return step_required_flops(
      config, [seq_len] * batch, [seq_len] * batch, [head] * batch,
      max_predictions)


def flash_required(config, unit_lengths, bytes_per_element=2):
  """Required work of the attention core (what the three flash kernels
  compute) for one train step, all layers and heads: forward
  ``4 * len**2 * d_head`` and backward ``8 * len**2 * d_head`` per head
  and unit; bytes = q, k, v, o, do, dq, dk, dv once each.

  Returns ``{'flops', 'bytes'}``.
  """
  w = widths(config)
  per_layer_flops = sum(12 * int(n) * int(n) * w['d'] for n in unit_lengths)
  per_layer_bytes = sum(8 * int(n) * w['d'] * bytes_per_element
                        for n in unit_lengths)
  return {'flops': w['layers'] * per_layer_flops,
          'bytes': w['layers'] * per_layer_bytes}


def roofline_seconds(work, peaks):
  """Least time the chip could take for ``work`` and which side bounds
  it: ``(seconds, 'compute' | 'memory')``."""
  t_flops = work['flops'] / peaks['flops_per_s']
  t_bytes = work['bytes'] / peaks['hbm_bytes_per_s']
  return (t_flops, 'compute') if t_flops >= t_bytes else (t_bytes, 'memory')
