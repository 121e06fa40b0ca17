"""The family ``bert``: post-LN encoder, MLM + NSP, pairs and packed rows.

A thin file. The reference (forward with hidden dropout, loss, AdamW) is
``chipbench/reference.py``, the program's parameter names and dropout
stream ``chipbench/adapter.py``, the count of required work
``chipbench/required_work.py``; here is what ties them to the family
interface (``chipbench/README.md``) and what the harness used
to know of BERT itself. Keys of a traffic file's ``train`` block that are
this family's own: ``masking``, ``max_predictions``, ``attention``,
``remat``, ``block_diagonal`` and the recipe (``learning_rate``,
``warmup_steps``, ``total_steps``, ``weight_decay``).
"""

import os

from chipbench import adapter, reference, required_work
from chipbench.families import Refused

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
VOCAB_FILE = os.path.join(REPO, 'benchmarks', 'assets',
                          'bench_vocab_30522.txt')
CONTROL_PRECISION = 'fp8'
ADAM_B1 = 0.9


def program_config(cell, train):
  """``BertConfig`` as ``pretrain.main`` makes it, from the configuration
  file; where the file names a preset of the program, its sizes have to
  be that preset's."""
  from lddl_tpu.models import BertConfig
  from lddl_tpu.training.pretrain import MODEL_SIZES
  c = cell['config_data']
  sizes = dict(hidden_size=c['hidden_size'],
               num_layers=c['num_hidden_layers'],
               num_heads=c['num_attention_heads'],
               intermediate_size=c['intermediate_size'])
  preset = c.get('program_preset')
  if preset and MODEL_SIZES[preset] != sizes:
    raise Refused(
        f'configuration {cell["config"]!r} says preset {preset!r} but its '
        f'sizes {sizes} are not MODEL_SIZES[{preset!r}]')
  if c['max_position_embeddings'] != max(train['max_seq_length'], 512):
    raise Refused('max_position_embeddings of the configuration file is not '
                  'max(max_seq_length, 512), which is what pretrain.main '
                  'builds')
  if c['attention_probs_dropout_prob'] != 0:
    raise Refused('the program has no dropout on attention probabilities; '
                  'the configuration file has to say 0 and list the key as '
                  'changed')
  return BertConfig(
      vocab_size=c['vocab_size'],
      max_position_embeddings=c['max_position_embeddings'],
      type_vocab_size=c['type_vocab_size'],
      dropout_rate=c['hidden_dropout_prob'],
      attention_impl=train['attention'], remat=train['remat'], **sizes)


def build_loop(cell, shards, seed, mesh):
  """``TrainLoop.build`` as ``pretrain.main`` calls it. The vocabulary
  rule: the program pads the tokenizer's count to a multiple of 64, and
  that is the ``vocab_size`` the configuration file has to state."""
  from lddl_tpu.tokenization.wordpiece import load_bert_tokenizer
  from lddl_tpu.training.pretrain import TrainLoop
  train = cell['traffic_data']['train']
  tokenizer = load_bert_tokenizer(vocab_file=VOCAB_FILE, backend='hf')
  vocab = ((tokenizer.vocab_size + 63) // 64) * 64
  if vocab != cell['config_data']['vocab_size']:
    raise Refused(
        f'the tokenizer gives a padded vocabulary of {vocab}, the '
        f'configuration file says {cell["config_data"]["vocab_size"]}')
  return TrainLoop.build(
      shards, tokenizer, model_cfg=program_config(cell, train), mesh=mesh,
      learning_rate=train['learning_rate'],
      warmup_steps=train['warmup_steps'], total_steps=train['total_steps'],
      weight_decay=train['weight_decay'],
      batch_size_per_rank=train['batch_size'], bin_size=train['bin_size'],
      max_seq_length=train['max_seq_length'], masking=train['masking'],
      seed=seed, max_predictions=train['max_predictions'],
      data_format=train['data_format'],
      block_diagonal=train['block_diagonal'])


def abstract_step(cell, mesh):
  """The jitted train step with the shapes of its parameters and of the
  optimizer's state; nothing is placed."""
  import jax
  import jax.numpy as jnp
  import optax

  from lddl_tpu.models import BertForPretraining
  from lddl_tpu.parallel import make_train_step
  train = cell['traffic_data']['train']
  model = BertForPretraining(program_config(cell, train), mesh=mesh)
  tx = optax.adamw(train['learning_rate'],
                   weight_decay=train['weight_decay'])
  dummy = jnp.zeros((2, 128), jnp.int32)
  params = jax.eval_shape(
      lambda: model.init(jax.random.key(0), dummy, dummy,
                         jnp.ones_like(dummy))['params'])
  step = make_train_step(model, tx, mesh,
                         max_predictions=train['max_predictions'])
  return step, params, jax.eval_shape(tx.init, params)


def fake_batch(train, seq):
  import numpy as np
  batch = train['batch_size']
  out = {
      'input_ids': np.ones((batch, seq), np.int32),
      'token_type_ids': np.zeros((batch, seq), np.int32),
      'attention_mask': np.ones((batch, seq), np.int32),
      'labels': np.full((batch, seq), -100, np.int32),
      'next_sentence_labels': np.zeros((batch,), np.int32),
  }
  out['labels'][:, 1::7] = 5
  if train['block_diagonal']:
    out['segment_ids'] = np.zeros((batch, seq), np.int32)
  return out


def bin_lengths(shards, train):
  """The sequence length of every batch shape the loader can yield."""
  if not train.get('bin_size'):
    return [train['max_seq_length']]
  ids = sorted({int(name.rsplit('_', 1)[1]) for name in os.listdir(shards)
                if '.parquet_' in name})
  align = 8 if train['data_format'] == 'pairs' else 128
  return sorted({
      min(-(-train['bin_size'] * (i + 1) // align) * align,
          train['max_seq_length']) for i in ids})


def batch_facts(batch):
  """``rows``: real tokens of each row; ``units``: the lengths attention
  is required over (a row, or under packing each document); ``masked``:
  MLM targets of each row."""
  import numpy as np
  mask = np.asarray(batch['attention_mask'])
  rows = mask.sum(axis=1)
  seg = batch.get('segment_ids')
  if seg is None:
    units = rows
  else:
    seg = np.asarray(seg)
    units = np.concatenate([np.bincount(r[r >= 0]) for r in seg])
    units = units[units > 0]
  return {
      'rows': [int(n) for n in rows],
      'units': [int(n) for n in units],
      'masked': [int(n) for n in
                 (np.asarray(batch['labels']) != -100).sum(axis=1)],
  }


seeded_params = adapter.seeded_program_params
change_norms = adapter.change_norms


def first_gradient_norms(opt_state):
  """After one step Adam's first moment is ``(1 - b1)`` times the first
  gradient, as the optimizer got it."""
  return adapter.leaf_norms(opt_state[0].mu, scale=1.0 / (1.0 - ADAM_B1))


def follow(config, train, seed, batches, precision='float32', keep=None):
  return reference.follow(config, train, seed, batches, precision=precision,
                          keep=keep, stream=adapter.DROPOUT_STREAM)


def required_flops(config, train, facts):
  return required_work.step_required_flops(
      config, facts['rows'], facts['units'], facts['masked'],
      train['max_predictions'])


def padded_flops(config, train, seq):
  return required_work.padded_step_flops(
      config, train['batch_size'], seq, train['max_predictions'])


def flash_required(config, train, facts):
  return required_work.flash_required(config, facts['units'])
