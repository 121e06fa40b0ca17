"""LFM2-MoE through the normal path and the benchmark's family seam:
``pretrain.main --model lfm2-tiny`` on packed shards, the family module
``chipbench/families/lfm2_moe.py`` against the interface, its adapter at
the published widths, its count of required work, and the capture
summary's classes in the compiled step."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from chipbench import families
from chipbench.families import lfm2_moe as family
from lddl_tpu.models import lfm2
from lddl_tpu.telemetry.capture import CLASSES, classify

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = 'lfm2-8b-a1b-ep4.packed-s8k-causal'


def _load(*parts):
  with open(os.path.join(REPO, *parts)) as f:
    return json.load(f)


@pytest.fixture(scope='module')
def cell():
  config = _load('chipbench', 'configs', 'lfm2-8b-a1b-ep4.json')
  return {'config': 'lfm2-8b-a1b-ep4', 'config_data': config,
          'traffic_data': _load('chipbench', 'traffic',
                                'packed-s8k-causal.json')}


def test_the_family_has_every_part_and_its_files(cell):
  assert families.load(cell['config_data']) is family
  for part in families.PARTS + ('flash_required', 'experts_required'):
    assert hasattr(family, part), part
  assert os.path.exists(family.VOCAB_FILE)
  with open(family.VOCAB_FILE) as f:
    assert sum(1 for _ in f) == cell['config_data']['vocab_size']
  bench = _load('BENCHMARK.json')
  assert CELL in {w['name'] for w in bench['workloads']}
  assert os.path.exists(os.path.join(REPO, 'chipbench', 'limits',
                                     CELL + '.json'))


def test_the_configuration_file_is_the_preset_it_names(cell):
  """The program's configuration is built from the file's own keys, as
  ``pretrain.main --model <file>`` builds it."""
  from lddl_tpu.training.pretrain import model_config
  train = cell['traffic_data']['train']
  cfg = family.program_config(cell, train)
  assert (cfg.hidden_size, cfg.num_experts, cfg.held_experts, cfg.top_k) == (
      2048, 32, 8, 4)
  assert (cfg.dense_layer_types, cfg.period, cfg.num_periods) == (
      ('conv',), ('full_attention', 'conv', 'conv', 'conv'), 1)
  path = os.path.join(REPO, 'chipbench', 'configs', 'lfm2-8b-a1b-ep4.json')
  assert model_config(path, cell['config_data']['vocab_size'],
                      train['max_seq_length'], train['attention'],
                      train['remat']) == cfg
  wrong = dict(cell, config_data=dict(cell['config_data'], conv_bias=True))
  with pytest.raises(families.Refused):
    family.program_config(wrong, train)


@pytest.mark.parametrize('types,dense,period,periods', [
    (['conv', 'conv', 'full_attention', 'conv'], 2, ('full_attention',
                                                     'conv'), 1),
    (['conv'] + ['full_attention', 'conv'] * 3, 1, ('full_attention',
                                                    'conv'), 3),
])
def test_the_layer_pattern_is_scanned_by_its_shortest_period(
    cell, types, dense, period, periods):
  c = dict(cell['config_data'], layer_types=types, num_dense_layers=dense,
           num_hidden_layers=len(types))
  cfg = lfm2.config_from_hf(c)
  assert (cfg.period, cfg.num_periods, cfg.num_layers) == (
      period, periods, len(types))


def test_the_adapter_covers_every_leaf_at_published_widths(cell):
  cfg = family.program_config(cell, cell['traffic_data']['train'])
  batch = lfm2.dummy_batch(1, 8)
  params = jax.eval_shape(lambda: lfm2.Lfm2ForCausalLM(cfg).init(
      jax.random.key(0), batch['input_ids'], batch['positions'],
      batch['segment_ids'], batch['labels'])['params'])
  family.check_tree(cell['config_data'], params)
  count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
  assert 535e6 < count < 545e6  # 541M by the layer count of PERF.md section 4


def test_required_work_of_a_batch(cell):
  config, train = cell['config_data'], cell['traffic_data']['train']
  batch = family.fake_batch(dict(train, batch_size=2), 64)
  batch['segment_ids'][0, 40:] = 1
  batch['segment_ids'][1, 50:] = -1
  facts = family.batch_facts(batch)
  assert facts['rows'] == [64, 50] and facts['units'] == [40, 24, 50]
  flops = family.required_flops(config, train, facts)
  assert 0 < flops < family.padded_flops(config, dict(train, batch_size=2),
                                         64)
  # Causal attention over each document's pairs: 12 FLOPs a pair, head
  # width and head, one attention layer in five.
  pairs = sum(n * (n + 1) // 2 for n in (40, 24, 50))
  assert family.flash_required(config, train, facts)['flops'] == (
      12 * pairs * 64 * 32)


def test_every_class_of_the_decoder_occurs_in_its_compiled_step():
  from lddl_tpu.parallel import make_mesh, make_train_step
  cfg = lfm2.Lfm2Config(**{**lfm2.PRESETS['lfm2-tiny'], 'vocab_size': 64,
                           'remat': True, 'attention_impl': 'dense'})
  mesh = make_mesh(devices=jax.devices()[:1])
  params = lfm2.init_params(cfg, mesh, jax.random.key(0))
  _, objective = lfm2.build_objective(cfg, mesh)
  tx = optax.adamw(1e-4, mask=lfm2.decay_mask)
  batch = {k: jnp.asarray(v) for k, v in family.fake_batch(
      {'batch_size': 2}, 64).items()}
  text = make_train_step(objective, tx, mesh).lower(
      params, jax.jit(tx.init)(params), jax.random.key(1),
      batch).compile().as_text()
  names = re.findall(r'op_name="([^"]*)"', text)
  found = {classify(n)[0] for n in names}
  assert set(CLASSES) - found <= {'dropout', 'unscoped'}
  assert any('/experts/' in n for n in names)


def test_pretrain_main_trains_lfm2_on_packed_rows(tmp_path, capsys):
  from test_packed import _build
  from lddl_tpu.training.pretrain import main
  _, _, bal, vocab, _ = _build(str(tmp_path), target=128, bin_size=128)
  loop = main(['--path', bal, '--vocab-file', vocab, '--model', 'lfm2-tiny',
               '--data-format', 'packed', '--bin-size', '128',
               '--max-seq-length', '128', '--batch-size', '8', '--steps', '2',
               '--warmup-steps', '1', '--log-every', '1'])
  assert loop.step == 2 and loop.causal
  assert isinstance(loop.model, lfm2.Lfm2ForCausalLM)
  assert 'final_loss' in capsys.readouterr().out
