"""The comparison that decides ``correct``.

Two sides follow the same first steps on the same batches from the same
seeded weights: the program (its timed step object, through the loop's
own call and feed) and the plain reference. Each side gives

  ``losses``        the loss of each step,
  ``grad_norms``    per leaf, the norm of the first gradient as the
                    optimizer got it,
  ``change_norms``  per leaf, the norm of the parameters' change after
                    the last step.

Compared are gaps between norms, never norms of differences:

  ``loss_gap_<i>``  |program - reference| / |reference| of step i's loss;
  ``loss_gap``      the worst of those over the compared steps: a coarser
                    precision shows in one step's loss or another's,
                    a different one from seed to seed, so each step
                    alone has readings that overlap the program's where
                    the worst of the three has not;
  ``grad_gap``      the worst leaf's |program - reference| over the
                    larger of the reference's norm of that leaf and of
                    its median leaf (some gradients are all but zero);
  ``change_gap``    the same for the change, over the leaves whose
                    reference gradient is at least a thousandth of the
                    median leaf's: a leaf with a gradient of nought to
                    rounding (a key's bias under softmax) moves under
                    Adam by round-off alone.

``grad_gap_median`` and ``change_gap_median`` are the same gaps at the
median leaf instead of the worst: steady from seed to seed where the
worst leaf is one small, noisy one (the NSP head at a batch of 16).
``grad_gap_global`` and ``change_gap_global`` are the gaps of the norms
over all leaves together. Rounding errors are random, so over millions of
elements their first-order effect on a norm averages out and what is left
is their energy, (error / gradient)**2 / 2: the number that tells a
coarser precision from a finer one, whatever the seed.

Each number has its limit in the cell's ``limits/<workload>.json``; a
number over its limit, or not finite, makes the run not correct. A number
the file lists under ``not_compared`` (with the reason: it has no upper
reading in that cell) is printed and decides nothing.
"""

import math
import statistics

GRADIENT_FLOOR = 1e-3  # of the median leaf's reference gradient norm


def leaf_gaps(program, reference, leaves):
  """``{leaf: gap}``: |program - reference| over the larger of the
  reference's norm of that leaf and of its median leaf."""
  median = statistics.median(reference[k] for k in reference)
  gaps = {}
  for k in leaves:
    gap = abs(program[k] - reference[k]) / max(reference[k], median)
    gaps[k] = gap if math.isfinite(gap) else float('inf')
  return gaps


def worst_loss_gap(values):
  return max(v for k, v in values.items() if k.startswith('loss_gap_'))


def numbers(program, reference):
  """``{name: value}`` of every number compared, plus ``_at`` notes
  (which leaf was worst) under names that start with an underscore."""
  out = {}
  for i, (a, b) in enumerate(zip(program['losses'], reference['losses']),
                             start=1):
    out[f'loss_gap_{i}'] = abs(a - b) / abs(b)
  out['loss_gap'] = worst_loss_gap(out)
  ref_g = reference['grad_norms']
  floor = GRADIENT_FLOOR * statistics.median(ref_g.values())
  moved = sorted(k for k in ref_g if ref_g[k] >= floor)
  for name, gaps in (
      ('grad_gap', leaf_gaps(program['grad_norms'], ref_g, sorted(ref_g))),
      ('change_gap', leaf_gaps(program['change_norms'],
                               reference['change_norms'], moved))):
    out[name] = max(gaps.values())
    out[name + '_median'] = statistics.median(gaps.values())
    which = name.split('_')[0] + '_norms'
    whole_p, whole_r = (math.sqrt(sum(side[which][k] ** 2 for k in gaps))
                        for side in (program, reference))
    out[name + '_global'] = abs(whole_p - whole_r) / whole_r
    out[f'_{name}_at'] = max(gaps, key=gaps.get)
    out[f'_{name}_leaves'] = gaps
  out['_left_out_of_change'] = sorted(set(ref_g) - set(moved))
  return out


def judge(values, limits):
  """``(correct, compared, observed)``: ``compared`` holds each number
  beside its limit, ``observed`` the numbers the cell's file lists as not
  compared. A number the file does not know at all is an error, not a
  pass."""
  compared, observed, correct = {}, {}, True
  for name, value in values.items():
    if name.startswith('_'):
      continue
    if name in limits.get('not_compared', {}):
      observed[name] = value
      continue
    if name not in limits:
      raise KeyError(f'no limit for {name!r} in the cell\'s limits file')
    compared[name] = {'value': value, 'limit': limits[name]}
    if not (math.isfinite(value) and value <= limits[name]):
      correct = False
  return correct, compared, observed
