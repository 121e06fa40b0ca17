"""Analytic FLOP accounting and peak-throughput lookup for MFU reporting.

The reference's mock training harness reports samples/s and latency only
(``/root/reference/benchmarks/torch_train.py:188-199``); on TPU the number
that actually tells you whether the input pipeline keeps the MXU busy is
**model FLOPs utilization** = model FLOPs per second / peak chip FLOPs.
This module provides the ingredients:

  - :func:`bert_pretrain_flops_per_step` — analytic matmul FLOPs of one
    BERT MLM+NSP train step over a padded ``[batch, seq]`` batch (standard
    transformer accounting: 24·B·S·d² + 4·B·S²·d per layer forward, MLM
    head 2·B·S·d·(d+V), backward = 2× forward);
  - :func:`peak_flops_per_device` — published bf16 peak for the running
    chip generation (None on the CPU backend; an accelerator the table
    does not know raises);
  - :func:`peak_hbm_bytes_per_device` / :func:`machine_balance` — the
    memory axis of the roofline: published HBM bandwidth per chip, and
    the FLOPs/byte ridge point that separates compute-bound from
    memory-bound (arXiv:2104.08335 shows this workload crosses it as
    sequence length and batch shape vary).
"""

import jax

# Published bf16 peak TFLOP/s and HBM bandwidth (GB/s) per chip, keyed by
# a lowercase substring of jax's device_kind. Order matters: first match
# wins.
_PEAK_TFLOPS_BF16 = (
    ('v6e', 918.0),
    ('trillium', 918.0),
    ('v5p', 459.0),
    ('v5 lite', 197.0),
    ('v5e', 197.0),
    # jax reports v5p as plain 'TPU v5' — this entry must stay after the
    # lite/v5e keys so they win for the lite chips.
    ('v5', 459.0),
    ('v4', 275.0),
    ('v3', 123.0),
    ('v2', 45.0),
)

_PEAK_HBM_GBPS = (
    ('v6e', 1640.0),
    ('trillium', 1640.0),
    ('v5p', 2765.0),
    ('v5 lite', 819.0),
    ('v5e', 819.0),
    # Same ordering constraint as the FLOPs table: the lite/v5e keys must
    # win before the plain-'v5' (= v5p) fallback.
    ('v5', 2765.0),
    ('v4', 1228.0),
    ('v3', 900.0),
    ('v2', 700.0),
)


def _lookup_peak(table, device, scale, what, flag):
  device = device or jax.devices()[0]
  kind = device.device_kind.lower()
  for key, peak in table:
    if key in kind:
      return peak * scale
  if getattr(device, 'platform', None) == 'cpu':
    return None
  raise ValueError(
      f'no peak-{what} entry for device_kind {device.device_kind!r}: add '
      f'the chip to the table in {__name__} or set {flag}')


def peak_flops_per_device(device=None):
  """Peak bf16 FLOP/s of ``device`` (default: jax.devices()[0]). None on
  the CPU backend (no published peak; MFU is omitted there); an
  accelerator whose ``device_kind`` is not in the table raises."""
  return _lookup_peak(_PEAK_TFLOPS_BF16, device, 1e12, 'FLOPs',
                      'LDDL_PEAK_TFLOPS')


def peak_hbm_bytes_per_device(device=None):
  """Peak HBM bandwidth (bytes/s) of ``device``; None on the CPU backend,
  raises for an accelerator not in the table (``LDDL_PEAK_HBM_GBPS``, in
  GB/s per device, overrides the table where the callers read it)."""
  return _lookup_peak(_PEAK_HBM_GBPS, device, 1e9, 'HBM-bandwidth',
                      'LDDL_PEAK_HBM_GBPS')


def machine_balance(device=None):
  """The roofline ridge point of ``device`` in FLOPs/byte (peak FLOP/s ÷
  peak HBM bytes/s): kernels whose arithmetic intensity exceeds this are
  compute-bound, below it memory-bound. None on the CPU backend."""
  flops = peak_flops_per_device(device)
  bw = peak_hbm_bytes_per_device(device)
  if not flops or not bw:
    return None
  return flops / bw


def bert_encoder_flops(cfg, batch, seq_len):
  """Forward matmul FLOPs of the encoder stack on a padded batch.

  Per layer: QKV+output projections 8·B·S·d², attention scores + context
  (QKᵀ and PV) 4·B·S²·d, MLP in+out 4·B·S·d·d_ff. A multiply-add counts
  as 2 FLOPs. Padded positions are counted — the MXU computes them.
  """
  b, s, d = batch, seq_len, cfg.hidden_size
  per_layer = (8 * b * s * d * d + 4 * b * s * s * d +
               4 * b * s * d * cfg.intermediate_size)
  return cfg.num_layers * per_layer


def bert_pretrain_flops_per_step(cfg, batch, seq_len, max_predictions=None):
  """Total matmul FLOPs of one pretraining train step (fwd + bwd).

  Head terms: MLM transform d², tied decoder d·V — over every position
  for the full head, or over ``max_predictions`` gathered positions for
  the masked-only head (the accounting must match what the model
  actually computes, so the masked-only mode reports its honestly
  smaller numerator). Pooler+NSP ≈ 2·B·d². Backward pass costs 2×
  forward; optimizer update FLOPs are vector ops, negligible next to the
  matmuls.
  """
  b, s, d = batch, seq_len, cfg.hidden_size
  # Clamp to s: the loss slices its position gather to at most s, so
  # billing more would inflate the numerator.
  head_positions = s if max_predictions is None else min(max_predictions, s)
  fwd = bert_encoder_flops(cfg, batch, seq_len)
  fwd += 2 * b * head_positions * d * d               # MLM transform
  fwd += 2 * b * head_positions * d * cfg.vocab_size  # tied decoder
  fwd += 2 * b * d * d                        # pooler (NSP head is d x 2)
  return 3 * fwd
