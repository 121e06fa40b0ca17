"""The arithmetic of required work, on numbers worked by hand."""

import random

import pytest

from chipbench import required_work as rw

TOY = dict(hidden_size=4, intermediate_size=8, num_hidden_layers=2,
           num_attention_heads=2, vocab_size=10)


def test_pairs_rows_by_hand():
  # Rows of 3 and 5 real tokens (padded shape [2, 8]), 1 and 2 masked,
  # a head budget of 2. Per token and layer: 8*4*4 + 4*4*8 = 256.
  gemm = 256 * (3 + 5)
  attention = 4 * 3 * 3 * 4 + 4 * 5 * 5 * 4            # 144 + 400
  layers = 2 * (gemm + attention)                       # 5184
  head = 2 * 3 * 16 + 2 * 3 * 4 * 10                    # 3 positions: 336
  pooler = 2 * 2 * 16                                   # 64
  assert layers + head + pooler == 5584
  assert rw.step_required_flops(TOY, [3, 5], [3, 5], [1, 2], 2) == 3 * 5584


def test_packed_row_of_three_documents_by_hand():
  # One row of 8 real tokens in a shape of [1, 10]: documents of 3, 2, 3.
  gemm = 256 * 8
  attention = 4 * (9 + 4 + 9) * 4                       # 352, not 4*64*4
  layers = 2 * (gemm + attention)                       # 4800
  head = 2 * 2 * 16 + 2 * 2 * 4 * 10                    # 2 masked: 224
  pooler = 2 * 1 * 16
  assert rw.step_required_flops(TOY, [8], [3, 2, 3], [2], 3) == 3 * (
      layers + head + pooler)
  # Block-diagonal asks less than the same row attended whole.
  assert (rw.step_required_flops(TOY, [8], [3, 2, 3], [2], 3) <
          rw.step_required_flops(TOY, [8], [8], [2], 3))


def test_full_dense_batch_equals_the_padded_formula():
  assert rw.step_required_flops(TOY, [8, 8], [8, 8], [5, 4], 3) == (
      rw.padded_step_flops(TOY, 2, 8, 3))
  assert rw.step_required_flops(TOY, [8, 8], [8, 8], [0, 0], None) == (
      rw.padded_step_flops(TOY, 2, 8, None))


def test_padded_formula_is_the_programs():
  flops = pytest.importorskip('lddl_tpu.models.flops')
  bert = pytest.importorskip('lddl_tpu.models.bert')
  cfg = bert.BertConfig(vocab_size=30528, hidden_size=768, num_layers=12,
                        num_heads=12, intermediate_size=3072)
  config = dict(hidden_size=768, intermediate_size=3072,
                num_hidden_layers=12, num_attention_heads=12,
                vocab_size=30528)
  for b, s, p in ((64, 128, 20), (16, 512, 112), (2, 8192, 1359),
                  (4, 256, None)):
    assert rw.padded_step_flops(config, b, s, p) == (
        flops.bert_pretrain_flops_per_step(cfg, b, s, max_predictions=p))


def test_required_never_passes_the_padded_shape():
  rng = random.Random(7)
  for _ in range(200):
    b, s = rng.randint(1, 6), rng.randint(2, 40)
    p = rng.choice([None, rng.randint(1, s)])
    rows = [rng.randint(1, s) for _ in range(b)]
    units = []
    for n in rows:  # cut each row into documents, or leave it whole
      while n > 0:
        cut = rng.randint(1, n)
        units.append(cut)
        n -= cut
    masked = [rng.randint(0, n) for n in rows]
    need = rw.step_required_flops(TOY, rows, units, masked, p)
    assert need <= rw.padded_step_flops(TOY, b, s, p)
    flash = rw.flash_required(TOY, units)
    whole = rw.flash_required(TOY, [s] * b)
    assert flash['flops'] <= whole['flops']
    assert flash['bytes'] <= whole['bytes']


def test_flash_required_by_hand():
  # Two documents of 3 and 5 tokens, d=4, two layers, bfloat16.
  work = rw.flash_required(TOY, [3, 5])
  assert work['flops'] == 2 * 12 * (9 + 25) * 4          # fwd 4 + bwd 8
  assert work['bytes'] == 2 * 8 * (3 + 5) * 4 * 2        # 8 tensors once
  peaks = {'flops_per_s': 1000.0, 'hbm_bytes_per_s': 10.0}
  seconds, side = rw.roofline_seconds(work, peaks)
  assert side == 'memory' and seconds == work['bytes'] / 10.0
  peaks = {'flops_per_s': 10.0, 'hbm_bytes_per_s': 1000.0}
  seconds, side = rw.roofline_seconds(work, peaks)
  assert side == 'compute' and seconds == work['flops'] / 10.0


def test_peaks_table():
  v5e = rw.load_peaks('TPU v5 lite')
  assert v5e['flops_per_s'] == 197e12 and v5e['hbm_bytes_per_s'] == 819e9
  with pytest.raises(KeyError, match='no peaks'):
    rw.load_peaks('cpu')
