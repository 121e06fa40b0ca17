"""A family for ``tests/test_family_seam.py``, laid over a copy of the
benchmark as a later PR's new files would be: BERT under another module
name, whose configuration files state the feed-forward width under a key
of their own, ``ffn_width``."""

from chipbench.families import bert

VOCAB_FILE = bert.VOCAB_FILE
CONTROL_PRECISION = bert.CONTROL_PRECISION
fake_batch = bert.fake_batch
bin_lengths = bert.bin_lengths
batch_facts = bert.batch_facts
first_gradient_norms = bert.first_gradient_norms


def _bert(config):
  return dict(config, intermediate_size=config['ffn_width'])


def _cell(cell):
  return dict(cell, config_data=_bert(cell['config_data']))


def build_loop(cell, shards, seed, mesh):
  return bert.build_loop(_cell(cell), shards, seed, mesh)


def abstract_step(cell, mesh):
  return bert.abstract_step(_cell(cell), mesh)


def seeded_params(config, seed, like):
  return bert.seeded_params(_bert(config), seed, like)


def change_norms(config, seed, params):
  return bert.change_norms(_bert(config), seed, params)


def follow(config, train, seed, batches, **kwargs):
  return bert.follow(_bert(config), train, seed, batches, **kwargs)


def required_flops(config, train, facts):
  return bert.required_flops(_bert(config), train, facts)


def padded_flops(config, train, seq):
  return bert.padded_flops(_bert(config), train, seq)
