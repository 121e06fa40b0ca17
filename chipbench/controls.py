"""Readings for the limits of a cell: the program's, the control's, the faults'.

    python3 chipbench/controls.py --workload <name> --seeds 1,2,3 \
        [--control-seeds 3] [--out chiprun_out/controls]

Not part of a benchmark run. In one process, for every seed: build the
loop as ``run.py`` does, let it take its first steps through
``TrainLoop.run`` (the compared steps and the one after them: no measured
window is needed for a training cell), and
follow the same steps with the plain reference. That gives the *lower*
reading of every number compared (the program against the reference). On
the first ``--control-seeds`` seeds it also puts in the program's place

  - the control: the reference computed in the family's
    ``CONTROL_PRECISION`` (BERT's: fp8; the side is named after it),
  - the fault "half of the batch left out, the mean taken over the rest"
    (the reference on the first half of the rows),

and reads the same numbers: the *upper* readings. A step that returns its
state unchanged reads 1 by the measure of ``compare.py`` (a change of
nought against the reference's) and needs no run.

Every reading goes through ``compare.judge`` with the cell's own limits
file, as a run's would: the row says whether that side came out correct
and which numbers were over their limit, and the last lines count the
verdicts per side (the program has to be correct on every seed, the
control and each fault on none). Prints one line of JSON per seed and
side, and writes them all to ``<out>/<workload>.json``.

    python3 chipbench/controls.py --workload <name> --rejudge <rows.json>

judges rows read earlier again, under the limits file as it is now.
"""

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
  sys.path.insert(0, REPO)


def program_side(run, cell, shards, seed):
  """The program's first steps: ``(readings, first batches)``."""
  window = run.Window(cell, seed, seconds=0.0, trace_dir=None)
  loop, tap = run.build_loop(cell, shards, seed, window)
  window.recording = True
  # The parameters' change is read at the call after the compared steps.
  losses = loop.run(run.COMPARED_STEPS + 1, log_every=0)
  window.recording = False
  batches = loop.loader.first
  out = {'losses': losses[:run.COMPARED_STEPS],
         'grad_norms': window.grad_norms,
         'change_norms': window.change_norms}
  del loop, tap
  gc.collect()
  return out, batches


def verdict(compare, row, limits):
  """``compare.judge`` on one row of readings, as on a run's."""
  values = {k: v for k, v in row.items()
            if k.endswith(('_gap', '_median', '_global')) or
            k.startswith('loss_gap_')}
  values['loss_gap'] = compare.worst_loss_gap(values)  # rows read before it
  values['compiles_in_window'] = 0
  correct, compared, _ = compare.judge(values, limits)
  return {'correct': correct,
          'over': sorted(k for k, pair in compared.items()
                         if not pair['value'] <= pair['limit'])}


def summary(rows):
  sides = {}
  for row in rows:
    sides.setdefault(row['side'], []).append(row['verdict']['correct'])
  for side, oks in sides.items():
    print(f'[controls] {side}: correct on {sum(oks)} of {len(oks)} seeds',
          file=sys.stderr, flush=True)
  return sides


def main(argv=None):
  parser = argparse.ArgumentParser(description=__doc__)
  parser.add_argument('--workload', required=True)
  parser.add_argument('--seeds')
  parser.add_argument('--control-seeds', type=int, default=3)
  parser.add_argument('--out', default=os.path.join(REPO, 'chiprun_out',
                                                    'controls'))
  parser.add_argument('--rejudge')
  args = parser.parse_args(argv)
  from chipbench import compare, run
  cell = run.find_cell(args.workload)
  family = cell['family']
  if args.rejudge:
    rows = run.load_json(args.rejudge)
    for row in rows:
      row['verdict'] = verdict(compare, row, cell['limits'])
      print(json.dumps({k: row[k] for k in ('seed', 'side', 'verdict')}))
    return summary(rows)
  run.require_device(cell)
  shards = run.prepare_data(cell['traffic_data'], family.VOCAB_FILE)
  train = cell['traffic_data']['train']
  config = cell['config_data']
  rows = []

  def emit(seed, side, values, seconds):
    row = {'workload': args.workload, 'seed': seed, 'side': side,
           'seconds': round(seconds, 1), **values}
    for name in ('grad_gap', 'change_gap'):  # the five worst leaves only
      leaves = row.pop(f'_{name}_leaves')
      row[f'_{name}_worst5'] = {
          k: round(leaves[k], 5)
          for k in sorted(leaves, key=leaves.get, reverse=True)[:5]}
    row['verdict'] = verdict(compare, row, cell['limits'])
    rows.append(row)
    print(json.dumps(row), flush=True)

  for n, seed in enumerate(int(s) for s in args.seeds.split(',')):
    seed %= 2147483629
    t0 = time.perf_counter()
    program, batches = program_side(run, cell, shards, seed)
    t1 = time.perf_counter()
    ref = family.follow(config, train, seed, batches)
    emit(seed, 'program', compare.numbers(program, ref), t1 - t0)
    run.say(f'seed {seed}: reference in {time.perf_counter() - t1:.1f}s')
    if n < args.control_seeds:
      t2 = time.perf_counter()
      control = family.follow(config, train, seed, batches,
                              precision=family.CONTROL_PRECISION)
      emit(seed, f'control_{family.CONTROL_PRECISION}',
           compare.numbers(control, ref), time.perf_counter() - t2)
      t3 = time.perf_counter()
      half = family.follow(config, train, seed, batches,
                           keep=slice(0, train['batch_size'] // 2))
      emit(seed, 'fault_half_batch', compare.numbers(half, ref),
           time.perf_counter() - t3)
      unchanged = dict(ref, change_norms={k: 0.0
                                          for k in ref['change_norms']})
      emit(seed, 'fault_state_unchanged', compare.numbers(unchanged, ref),
           0.0)
    del ref
    gc.collect()
  os.makedirs(args.out, exist_ok=True)
  with open(os.path.join(args.out, args.workload + '.json'), 'w') as f:
    json.dump(rows, f, indent=1)
  return summary(rows)


if __name__ == '__main__':
  main()
