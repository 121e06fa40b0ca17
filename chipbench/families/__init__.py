"""The seam between the harness and what knows a model.

A configuration file names its family with the key ``family`` (a file
without the key is ``bert``); ``chipbench/families/<family>.py`` holds
everything ``run.py``, ``controls.py``, ``deviceless_compile.py`` and the
readers that bill work need to know of that model, so that a model of
another family arrives as new files only. ``PARTS`` names what every
family module has to have; ``chipbench/README.md`` ("The interface of a
family") says what each part is, one line a part. Optional beside them:
``flash_required``, without which ``flash_attention_roofline`` finds
nothing to read.
"""

import importlib

DEFAULT = 'bert'
PARTS = ('VOCAB_FILE', 'CONTROL_PRECISION', 'build_loop', 'abstract_step',
         'fake_batch', 'bin_lengths', 'batch_facts', 'seeded_params',
         'first_gradient_norms', 'change_norms', 'follow', 'required_flops',
         'padded_flops')


class Refused(Exception):
  """A family that cannot be used, or files that state what the family's
  program does not build: bad files, exit 2."""


def load(config):
  """The family module of a configuration file, whole or not at all."""
  name = config.get('family', DEFAULT)
  if not (isinstance(name, str) and name.isidentifier()):
    raise Refused(f'family {name!r} is not the name of a module')
  try:
    module = importlib.import_module(f'{__name__}.{name}')
  except ModuleNotFoundError as e:
    if e.name != f'{__name__}.{name}':
      raise
    raise Refused(f'no family {name!r}: chipbench/families/{name}.py is '
                  'not there') from None
  missing = [part for part in PARTS if not hasattr(module, part)]
  if missing:
    raise Refused(f'family {name!r} lacks {missing} of the interface '
                  '(chipbench/README.md)')
  return module
