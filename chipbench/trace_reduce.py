"""From a profiler trace to the numbers the per-layer metrics read.

Two stages, so that the second can be checked on a small recorded file:

  :func:`extract` reads an ``.xplane.pb`` with nothing but JAX
  (``jax.profiler.ProfileData``) and keeps, per device plane, the events
  of the op line and of the module line, and from the host planes the
  benchmark's own annotations (``chipbench.*``). All times in
  nanoseconds on the trace's one clock.

  :func:`reduce` turns that into: the traced window, the union of the
  intervals in which an operation ran (busy), each step program's
  length and busy time, the gaps between one step program's last
  operation and the next one's first, the time of the Mosaic kernels (the
  Pallas flash kernels are the only ones in a train step), the
  operations that took most time, and the longest idle gaps labelled by
  what the host was doing.

How a TPU trace names things (seen by hand in PR 24's first traces, see
PERF.md): device planes are ``/device:TPU:<n>``; the line ``XLA Ops``
holds one event per executed HLO operation, named by its whole HLO text
(``%attention.29 = (bf16[...], ...) custom-call(...)``), loops included:
a ``while`` is an event that spans the events of its body, so containers
are left out of every sum. ``XLA Modules`` holds one event per executed
program (``jit_step(<fingerprint>)``); ``Async XLA Ops`` holds DMA spans
that overlap compute and are not counted as busy. The three Pallas flash
kernels are the step's only ``custom-call`` operations whose
``custom_call_target`` is ``tpu_custom_call`` (the others are
``AllocateBuffer``, of no duration); they carry the name of the function
that calls them (``attention.<n>``). Host annotations sit
on the thread lines of ``/host:CPU``, on the same clock.
"""

import collections
import re

DEVICE_PLANE_PREFIX = '/device:TPU:'
OPS_LINE = 'XLA Ops'
MODULES_LINE = 'XLA Modules'
STEP_MODULE_PREFIX = 'jit_step'
ANNOTATION_PREFIX = 'chipbench.'
CONTAINERS = ('while', 'conditional', 'call')
KERNEL = 'custom-call:tpu_custom_call'  # a Mosaic (Pallas) kernel
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
TOP = 10


def split_hlo(text):
  """``(short name, opcode)`` of an op event's HLO text:
  ``%attention.29 = (bf16[..], bf16[..]) custom-call(...),
  custom_call_target="tpu_custom_call"`` gives ``('attention.29',
  'custom-call:tpu_custom_call')``; any other operation gives its bare
  opcode."""
  head, _, rest = text.partition(' = ')
  rest = rest.strip()
  if rest.startswith('('):  # a tuple shape: skip to its closing bracket
    depth = 0
    for i, ch in enumerate(rest):
      depth += (ch == '(') - (ch == ')')
      if depth == 0:
        break
    rest = rest[i + 1:]
  else:  # an array shape holds no space
    rest = rest.partition(' ')[2]
  opcode = rest.strip().partition('(')[0].strip()
  if opcode == 'custom-call':
    target = _TARGET.search(rest)
    opcode += ':' + (target.group(1) if target else '')
  return head.strip().lstrip('%'), opcode


def extract(path):
  """The events :func:`reduce` needs, as plain lists (JSON-serialisable)."""
  import jax
  data = jax.profiler.ProfileData.from_file(path)
  out = {'devices': [], 'host': []}
  for plane in data.planes:
    if plane.name.startswith(DEVICE_PLANE_PREFIX):
      device = {'plane': plane.name, 'ops': [], 'modules': []}
      for line in plane.lines:
        if line.name == OPS_LINE:
          device['ops'] = [[*split_hlo(e.name), int(e.start_ns),
                            int(e.duration_ns)] for e in line.events]
        elif line.name == MODULES_LINE:
          device['modules'] = [
              [e.name, int(e.start_ns), int(e.duration_ns)]
              for e in line.events]
      out['devices'].append(device)
    elif plane.name.startswith('/host:'):
      for line in plane.lines:
        for e in line.events:
          if e.name.startswith(ANNOTATION_PREFIX):
            out['host'].append([e.name, int(e.start_ns), int(e.duration_ns)])
  return out


def union_ns(intervals):
  """Total length of the union of ``(start, end)`` intervals."""
  total, end = 0, None
  for lo, hi in sorted(intervals):
    if end is None or lo > end:
      total += hi - lo
      end = hi
    elif hi > end:
      total += hi - end
      end = hi
  return total


def _label_gap(lo, hi, host):
  """What the host was doing in the idle gap ``[lo, hi)``: the
  annotation that covers most of it."""
  cover = collections.Counter()
  for name, start, dur in host:
    overlap = min(hi, start + dur) - max(lo, start)
    if overlap > 0:
      cover[name] += overlap
  step, loader = cover['chipbench.step_fn'], cover['chipbench.loader_next']
  if step * 2 >= hi - lo:
    return 'inside a step call'
  if loader * 2 >= hi - lo:
    return 'between step calls, loader producing'
  return 'between step calls'


def _reduce_device(device, host):
  kernel_names = sorted({n for n, op, _, _ in device['ops']
                         if op == KERNEL})
  ops = [(n, s, s + d) for n, op, s, d in device['ops']
         if d > 0 and op not in CONTAINERS]
  steps = sorted((s, s + d) for n, s, d in device['modules']
                 if n.startswith(STEP_MODULE_PREFIX))
  if not ops or not steps:
    return None
  # The traced window: whole step programs only, first start to last end.
  t0, t1 = steps[0][0], steps[-1][1]
  ops = [(n, max(s, t0), min(e, t1)) for n, s, e in ops if e > t0 and s < t1]
  busy = union_ns([(s, e) for _, s, e in ops])
  per_op = collections.Counter()
  for n, s, e in ops:
    per_op[n] += e - s
  kernel_ns = sum(per_op[n] for n in kernel_names)
  # Busy time inside each step program, and the gap to the next one.
  by_start = sorted((s, e) for _, s, e in ops)
  step_busy, gaps = [], []
  for lo, hi in steps:
    step_busy.append(union_ns([(max(s, lo), min(e, hi))
                               for s, e in by_start if e > lo and s < hi]))
  for (_, end), (start, _) in zip(steps, steps[1:]):
    gaps.append((end, start))
  # All idle gaps of the window (inside programs too), longest first.
  idle, end = [], t0
  for s, e in by_start:
    if s > end:
      idle.append((end, s))
    end = max(end, e)
  if t1 > end:
    idle.append((end, t1))
  idle.sort(key=lambda g: g[0] - g[1])
  return {
      'plane': device['plane'], 'window_ns': t1 - t0, 'busy_ns': busy,
      'steps': len(steps), 'step_busy_ns': step_busy,
      'step_ns': [hi - lo for lo, hi in steps],
      'step_gap_ns': [b - a for a, b in gaps],
      'kernel_ns': kernel_ns,
      'kernel_names': kernel_names,
      'device_ops': [[n, t / 1e9] for n, t in per_op.most_common(TOP)],
      'idle_gaps': [[_label_gap(a, b, host), (b - a) / 1e9]
                    for a, b in idle[:TOP]],
  }


def reduce(events):
  """The reduced trace, or None when no device plane held a step."""
  devices = [r for r in (_reduce_device(d, events['host'])
                         for d in events['devices']) if r]
  if not devices:
    return None
  n = len(devices)
  fullest = max(devices, key=lambda r: r['busy_ns'])
  return {
      'devices': devices,
      'busy_s': sum(r['busy_ns'] for r in devices) / n / 1e9,
      'window_s': sum(r['window_ns'] for r in devices) / n / 1e9,
      'breakdown': {'device_ops': fullest['device_ops'],
                    'idle_gaps': fullest['idle_gaps']},
  }
