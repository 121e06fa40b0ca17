"""Two documents packed in one row read as the two run apart: the short
convolution restarts at a document's first token, RoPE counts from it,
and the flash kernels attend causally within it (causal ∧ same-document).
LFM2-MoE at tiny widths on the CPU (the kernels interpreted), float32."""

import jax
import numpy as np

from lddl_tpu.models import lfm2
from lddl_tpu.parallel import make_mesh
from test_lfm2_parity import TOL, packed_batch, program_config, seeded


def _logits(cfg, params, batch):
  """Per-token logits of the program: the final norm's output, captured,
  against the head."""
  model = lfm2.Lfm2ForCausalLM(cfg)

  @jax.jit
  def logits(params, batch):
    _, state = model.apply({'params': params}, batch['input_ids'],
                           batch['positions'], batch['segment_ids'],
                           batch['labels'], capture_intermediates=True)
    return state['intermediates']['final_norm']['__call__'][0] @ (
        params['lm_head'])

  return np.asarray(logits(params, batch))


def test_two_documents_packed_read_as_two_apart():
  cfg = program_config(attention_impl='flash')
  params = seeded(cfg, make_mesh(devices=jax.devices()[:1]))
  packed = packed_batch(np.random.default_rng(3), [[24, 40]], 64)
  apart = {}
  for k, v in packed.items():
    fill = {'segment_ids': -1, 'labels': -100}.get(k, 0)
    apart[k] = np.full((2, 64), fill, np.int32)
    apart[k][0, :24] = v[0, :24]
    apart[k][1, :40] = v[0, 24:]
  apart['segment_ids'][:, :] = np.where(apart['segment_ids'] >= 0, 0, -1)
  whole = _logits(cfg, params, packed)[0]
  alone = _logits(cfg, params, apart)
  np.testing.assert_allclose(whole[:24], alone[0, :24], **TOL)
  np.testing.assert_allclose(whole[24:], alone[1, :40], **TOL)
