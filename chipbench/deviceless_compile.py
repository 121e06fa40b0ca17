"""Compile a cell's largest step for a described (not attached) v5e chip.

    JAX_PLATFORMS=cpu python3 chipbench/deviceless_compile.py --workload <name>

Run by hand before a cell's first chip run: what the TPU compiler refuses
here costs no chip time, and ``memory_analysis()`` says what the step
program needs. Nothing runs; no number printed here is a measurement.
"""

import argparse
import os
import sys
import time

os.environ.setdefault('TPU_LOG_DIR', 'disabled')
HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
  sys.path.insert(0, REPO)


def main(argv=None):
  parser = argparse.ArgumentParser(description=__doc__)
  parser.add_argument('--workload', required=True)
  parser.add_argument('--seq', type=int, default=None,
                      help='bin length to compile (default: the longest)')
  args = parser.parse_args(argv)
  import jax
  import numpy as np
  from jax.experimental import topologies
  from jax.sharding import NamedSharding

  from chipbench import run
  from lddl_tpu.ops import flash_attention
  from lddl_tpu.parallel import make_mesh
  from lddl_tpu.parallel.mesh import canonical_batch_spec
  from lddl_tpu.parallel.train import state_shardings

  # The kernels must lower for the chip, not for the interpreter the CPU
  # backend of this process would otherwise get.
  flash_attention._interpret = lambda backend=None: False

  cell = run.find_cell(args.workload)
  train = cell['traffic_data']['train']
  topo = topologies.get_topology_desc(platform='tpu', topology_name='v5e:2x2')
  mesh = make_mesh(**train['mesh'],
                   devices=np.asarray(topo.devices[:cell['chips']]))
  step, params, opt_state = cell['family'].abstract_step(cell, mesh)
  b, s = train['batch_size'], args.seq or train['max_seq_length']
  p_sh, o_sh = state_shardings(mesh, params, opt_state)

  def with_sharding(tree, shardings):
    return jax.tree.map(
        lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
        tree, shardings)

  batch = {k: jax.ShapeDtypeStruct(
      v.shape, v.dtype,
      sharding=NamedSharding(mesh, canonical_batch_spec(mesh, v.shape)))
           for k, v in cell['family'].fake_batch(train, s).items()}
  key = jax.eval_shape(lambda: jax.random.key(0))
  t0 = time.perf_counter()
  compiled = step.lower(with_sharding(params, p_sh),
                        with_sharding(opt_state, o_sh), key, batch).compile()
  m = compiled.memory_analysis()
  live = (m.argument_size_in_bytes + m.output_size_in_bytes +
          m.temp_size_in_bytes - m.alias_size_in_bytes)
  print(f'{args.workload} [{b}, {s}]: compiled for {topo.devices[0]} in '
        f'{time.perf_counter() - t0:.1f}s; arguments '
        f'{m.argument_size_in_bytes} output {m.output_size_in_bytes} temp '
        f'{m.temp_size_in_bytes} alias {m.alias_size_in_bytes} -> live '
        f'{live} bytes ({live / 2**30:.2f} GiB); tpu_custom_call in the '
        f'program: {"tpu_custom_call" in compiled.as_text()}')


if __name__ == '__main__':
  main()
