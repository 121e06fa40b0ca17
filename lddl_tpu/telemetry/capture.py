"""What a profiler capture says about the train loop, made by the program.

When :class:`~.profiling.StepProfiler` stops a trace it hands the
directory to :func:`summarize_capture`, which reads the ``.xplane.pb``
the profiler just wrote and answers the two questions an operator
otherwise needs xprof for:

  - **Where did the chip wait for the host?** Every idle gap between two
    ``jit_step`` programs is laid over the loop's own phases
    (``train.data_wait``, ``train.dispatch``, ``train.loss_read``,
    ``train.after_step``, ``train.epoch_turn``: the
    ``jax.profiler.TraceAnnotation`` sink of ``tracer.phase``, see
    :mod:`.trace`), which sit on a ``/host:CPU`` thread line on the
    device's clock. A gap under several phases is split by overlap; what
    no phase covers is ``unattributed``. Beside the phases, and never
    one of them: what the feed's thread did meanwhile, and how much of
    the gap lay under a pause of Python's collector (the ``host.gc``
    spans of :mod:`.stalls`, from any thread).
  - **Which part of the model was the chip busy with?** Every device
    operation inside a step program is billed to a module class
    (:data:`CLASSES`) and, independently, to a pass (:data:`PASSES`),
    from the ``op_name`` XLA keeps for it: flax's module path plus the
    ``jax.named_scope`` s of what is no module (``loss``, ``optimizer``,
    ``grad_norm``, ``gelu``, ``residual``, ...).

Two stages, so that the second can be checked on a small recorded file:
:func:`extract` reads the trace into plain lists, :func:`summarize`
turns those into the numbers.

How a TPU trace carries ``op_name`` (looked at by hand in a chip trace,
PERF.md section 3): not in the event's name (the HLO text, printed
without metadata) and not among the event's own stats
(``device_offset_ps``, ``device_duration_ps``), but in the stats of the
event's *metadata* record, as ``tf_op`` (``jit(step)/jvp(Bert..)/
encoder/.../attention/query/dot_general:``), beside ``hlo_category``
(``while`` for a loop, which spans its body and is left out of every
sum) and the short instruction name as ``display_name``.
``jax.profiler.ProfileData`` shows an event's own stats only, so
:func:`extract` reads the file's protobuf wire format itself: the
few fields of ``XSpace``/``XPlane``/``XLine``/``XEvent``/
``XEventMetadata``/``XStat`` named below, nothing else.
"""

import bisect
import collections
import glob
import json
import os
import re
import statistics
import time

from .stalls import GC_SPAN  # a collector's pause: no phase of the loop

DEVICE_PLANE_PREFIX = '/device:TPU:'
HOST_PLANE_PREFIX = '/host:'
OPS_LINE = 'XLA Ops'
MODULES_LINE = 'XLA Modules'
STEP_MODULE_PREFIX = 'jit_step'
CONTAINERS = ('while', 'conditional', 'call')  # hlo_category of a container
PHASE_PREFIXES = ('train.', 'loader.')
PARENT_PHASE = 'train.step'
# The TPU runtime's own host span around handing a program to the device
# queue: no program starts on the device before it (see _device_clock_shift).
ENQUEUE = 'DoEnqueueProgram'
DISPATCH = 'train.dispatch'
EPOCH_TURN = 'train.epoch_turn'
UNATTRIBUTED = 'unattributed'

# Module classes, first match wins, tried on the op_name's path elements
# with their jvp()/transpose()/jit() wrappers taken off. Dropout first:
# its draws sit under whichever module drew them (attention/Dropout_0).
CLASSES = ('attention', 'ffn', 'norms', 'dropout', 'embed', 'head_loss',
           'optimizer', 'scan_carry', 'unscoped')
_DROPOUT = re.compile(
    r'[Dd]ropout|threefry|_bernoulli|random_bits|rng_bit_generator')
_CLASS_RULES = (
    ('optimizer', re.compile(r'^(optimizer|grad_norm)$')),
    ('head_loss', re.compile(r'^(loss$|mlm_|nsp|pool)')),
    ('attention', re.compile(r'^attention$')),
    ('ffn', re.compile(r'^(intermediate|output|gelu)$')),
    ('norms', re.compile(r'^(attention_norm|output_norm|residual)$')),
    ('embed', re.compile(r'^(embed$|embed_norm$|\w+_embeddings)')),
)
# The layer scan's own traffic (stacking what the backward pass needs,
# slicing the stacked weights, summing stacked gradients): ops directly
# under the encoder's while body, inside no layer module.
_SCAN_CARRY = re.compile(r'(^|/)encoder(/while/(body|cond))?/[^/]*$')
PASSES = ('forward', 'backward', 'recompute', 'update')
_WRAPPER = re.compile(r'^\w+\((.*)\)$')


def classify(op_name):
  """``(module class, pass)`` of one device operation's ``op_name``."""
  if 'rematted_computation' in op_name:
    pass_ = 'recompute'
  elif 'transpose(jvp(' in op_name:
    pass_ = 'backward'
  elif 'jvp(' in op_name:
    pass_ = 'forward'
  else:
    pass_ = 'update'
  if not op_name:
    return 'unscoped', pass_
  if _DROPOUT.search(op_name):
    return 'dropout', pass_
  elements = []
  for part in op_name.split('/')[:-1]:  # the last one is the primitive
    while True:
      inner = _WRAPPER.match(part)
      if inner is None:
        break
      part = inner.group(1)
    elements.append(part)
  for name, rule in _CLASS_RULES:
    if any(rule.match(e) for e in elements):
      return name, pass_
  if _SCAN_CARRY.search(op_name):
    return 'scan_carry', pass_
  return 'unscoped', pass_


# ----------------------------------------------------------------------------
# stage 1: the trace file -> plain lists

# Field numbers of tsl/profiler/protobuf/xplane.proto.
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_LINES, _PLANE_EVENT_META, _PLANE_STAT_META = 2, 3, 4, 5
_LINE_NAME, _LINE_TIMESTAMP_NS, _LINE_EVENTS = 2, 3, 4
_EVENT_META_ID, _EVENT_OFFSET_PS, _EVENT_DURATION_PS, _EVENT_STATS = 1, 2, 3, 4
_META_ID, _META_NAME, _META_DISPLAY_NAME, _META_STATS = 1, 2, 4, 5
_STAT_META_ID, _STAT_UINT64, _STAT_INT64, _STAT_STR, _STAT_REF = 1, 3, 4, 5, 7
_MAP_VALUE = 2


def _varint(buf, pos):
  value = buf[pos]
  pos += 1
  if value & 0x80:
    value &= 0x7f
    shift = 7
    while True:
      byte = buf[pos]
      pos += 1
      value |= (byte & 0x7f) << shift
      if not byte & 0x80:
        break
      shift += 7
  return value, pos


def _fields(buf, pos, end):
  """``(field number, value)`` of one protobuf message in ``buf[pos:end]``:
  an int for a varint or fixed field, ``(start, end)`` for a
  length-delimited one."""
  while pos < end:
    key, pos = _varint(buf, pos)
    wire = key & 7
    if wire == 0:
      value, pos = _varint(buf, pos)
    elif wire == 2:
      size, pos = _varint(buf, pos)
      value = (pos, pos + size)
      pos += size
    elif wire == 1:
      value = int.from_bytes(buf[pos:pos + 8], 'little')
      pos += 8
    elif wire == 5:
      value = int.from_bytes(buf[pos:pos + 4], 'little')
      pos += 4
    else:
      raise ValueError(f'protobuf wire type {wire} at byte {pos}')
    yield key >> 3, value
  if pos != end:
    raise ValueError('protobuf message overruns its length')


def _text(buf, span):
  return buf[span[0]:span[1]].decode('utf-8', 'replace')


def _read_stats(buf, spans, stat_names):
  """``{stat name: value}`` of the ``XStat`` messages at ``spans``
  (integers, strings and references to a stat's name; the rest is not
  read)."""
  out = {}
  for span in spans:
    name = value = None
    for field, v in _fields(buf, *span):
      if field == _STAT_META_ID:
        name = stat_names.get(v)
      elif field in (_STAT_UINT64, _STAT_INT64):
        value = v
      elif field == _STAT_STR:
        value = _text(buf, v)
      elif field == _STAT_REF:
        value = stat_names.get(v)
    if name is not None:
      out[name] = value
  return out


def _planes(buf):
  """``(name, line spans, {metadata id: span}, {stat id: name})`` of each
  plane of a serialized ``XSpace``."""
  for field, span in _fields(buf, 0, len(buf)):
    if field != _SPACE_PLANES:
      continue
    name, lines, metas, stat_names = '', [], {}, {}
    for field, v in _fields(buf, *span):
      if field == _PLANE_NAME:
        name = _text(buf, v)
      elif field == _PLANE_LINES:
        lines.append(v)
      elif field in (_PLANE_EVENT_META, _PLANE_STAT_META):
        for f2, v2 in _fields(buf, *v):  # a map entry: key, value
          if f2 != _MAP_VALUE:
            continue
          if field == _PLANE_STAT_META:
            entry = dict(_fields(buf, *v2))
            stat_names[entry.get(_META_ID, 0)] = _text(
                buf, entry.get(_META_NAME, (0, 0)))
          else:
            metas[next((v3 for f3, v3 in _fields(buf, *v2)
                        if f3 == _META_ID), 0)] = v2
    yield name, lines, metas, stat_names


def _metadata(buf, span, stat_names=None):
  """``name``, ``display_name`` and (given the plane's stat names)
  ``stats`` of one ``XEventMetadata``."""
  meta = {'name': '', 'display_name': '', 'stats': {}}
  stats = []
  for field, v in _fields(buf, *span):
    if field == _META_NAME:
      meta['name'] = _text(buf, v)
    elif field == _META_DISPLAY_NAME:
      meta['display_name'] = _text(buf, v)
    elif field == _META_STATS:
      stats.append(v)
  if stat_names is not None:
    meta['stats'] = _read_stats(buf, stats, stat_names)
  return meta


def _line(buf, span):
  """``(name, timestamp_ns, event spans)`` of one ``XLine``."""
  name, t0_ns, events = '', 0, []
  for field, v in _fields(buf, *span):
    if field == _LINE_NAME:
      name = _text(buf, v)
    elif field == _LINE_TIMESTAMP_NS:
      t0_ns = v
    elif field == _LINE_EVENTS:
      events.append(v)
  return name, t0_ns, events


def _event(buf, span, t0_ns):
  """``(metadata id, start_ns, duration_ns, own stats spans)`` of one
  ``XEvent``; whole nanoseconds on the file's one clock."""
  meta_id = offset_ps = duration_ps = 0
  stats = []
  for field, v in _fields(buf, *span):
    if field == _EVENT_META_ID:
      meta_id = v
    elif field == _EVENT_OFFSET_PS:
      offset_ps = v
    elif field == _EVENT_DURATION_PS:
      duration_ps = v
    elif field == _EVENT_STATS:
      stats.append(v)
  start_ps = t0_ns * 1000 + offset_ps
  return (meta_id, start_ps // 1000,
          (start_ps + duration_ps) // 1000 - start_ps // 1000, stats)


def find_xplane(trace_dir):
  """The newest ``.xplane.pb`` under a capture directory, or None."""
  paths = sorted(glob.glob(os.path.join(trace_dir, '**', '*.xplane.pb'),
                           recursive=True))
  return max(paths, key=os.path.getmtime) if paths else None


def _extract_device(buf, name, lines, metas, stat_names):
  device = {'plane': name, 'names': [], 'ops': [], 'modules': []}
  index = {}
  for span in lines:
    line_name, t0_ns, events = _line(buf, span)
    if line_name == MODULES_LINE:
      for e in events:
        meta_id, start, dur, _ = _event(buf, e, t0_ns)
        module = _metadata(buf, metas[meta_id])['name']
        device['modules'].append([module, start, dur])
    elif line_name == OPS_LINE:
      for e in events:
        meta_id, start, dur, _ = _event(buf, e, t0_ns)
        i = index.get(meta_id)
        if i is None:
          m = _metadata(buf, metas[meta_id], stat_names)
          op_name = m['stats'].get('tf_op') or ''
          if ':' in op_name:  # "<op_name>:<op type>", the type empty
            op_name = op_name.rsplit(':', 1)[0]
          i = index[meta_id] = len(device['names'])
          device['names'].append([
              m['display_name'] or m['name'].partition(' ')[0].lstrip('%'),
              m['stats'].get('hlo_category') or '', op_name])
        device['ops'].append([i, start, dur])
  return device


def _extract_host(buf, lines, metas, stat_names):
  """The thread lines that hold a phase, a collector's pause or the
  runtime's enqueue span. A host plane holds every call the profiler's
  Python tracer saw, so an event is read past its first field only when
  that names one of them."""
  phases = {}
  for meta_id, span in metas.items():
    name = _metadata(buf, span)['name']
    if name.startswith(PHASE_PREFIXES) or name in (ENQUEUE, GC_SPAN):
      phases[meta_id] = name
  out = []
  if not phases:
    return out
  first_field = _EVENT_META_ID << 3  # metadata_id, a varint, comes first
  for span in lines:
    line_name, t0_ns, events = _line(buf, span)
    rows = []
    for e in events:
      if buf[e[0]] == first_field and _varint(buf, e[0] + 1)[0] not in phases:
        continue
      meta_id, start, dur, stats = _event(buf, e, t0_ns)
      if meta_id in phases:
        step = _read_stats(buf, stats, stat_names).get('step')
        rows.append([phases[meta_id], start, dur, step])
    if rows:
      out.append({'line': line_name, 'events': rows})
  return out


def extract(path):
  """The events :func:`summarize` needs, as plain lists (JSON-able).

  Per device plane: ``names`` (one ``[short name, hlo_category,
  op_name]`` per distinct operation), ``ops`` (``[index into names,
  start_ns, duration_ns]`` per executed operation) and ``modules``
  (``[name, start_ns, duration_ns]`` per executed program). From the host
  planes: every thread line that holds a phase (or the runtime's
  ``DoEnqueueProgram``, or a ``host.gc`` pause), as ``[name, start_ns,
  duration_ns, step or None]`` rows."""
  with open(path, 'rb') as f:
    buf = f.read()
  out = {'devices': [], 'host': []}
  for name, lines, metas, stat_names in _planes(buf):
    if name.startswith(DEVICE_PLANE_PREFIX):
      out['devices'].append(
          _extract_device(buf, name, lines, metas, stat_names))
    elif name.startswith(HOST_PLANE_PREFIX):
      out['host'].extend(_extract_host(buf, lines, metas, stat_names))
  return out


# ----------------------------------------------------------------------------
# stage 2: plain lists -> the summary


def _overlaps(spans, starts, lo, hi):
  """The ``(name, overlap_ns)`` of each span (sorted by start, with
  ``starts`` their starts) with ``[lo, hi)``."""
  # Spans of one thread nest or follow one another; a parent that began
  # long before `lo` is found by walking back while spans still reach it.
  i = bisect.bisect_left(starts, lo)
  while i > 0 and spans[i - 1][1] + spans[i - 1][2] > lo:
    i -= 1
  out = []
  while i < len(spans) and spans[i][1] < hi:
    name, start, dur, _ = spans[i]
    over = min(hi, start + dur) - max(lo, start)
    if over > 0:
      out.append((name, over))
    i += 1
  return out


def _threads(host):
  """``(main, feed, pauses)``: the phase spans of the thread that runs
  ``TrainLoop.run`` (the line that holds ``train.step``), those of the
  other threads (the device feed's producer), and the collector's pauses
  of every thread, each sorted by start."""
  main, feed, pauses = [], [], []
  for line in host:
    rows = [r for r in line['events'] if r[0] not in (ENQUEUE, GC_SPAN)]
    pauses.extend(r for r in line['events'] if r[0] == GC_SPAN)
    if not main and any(r[0] == PARENT_PHASE for r in rows):
      main = rows
    else:
      feed.extend(rows)
  return tuple(sorted(rows, key=lambda r: r[1])
               for rows in (main, feed, pauses))


def _device_clock_shift(steps, enqueues, main):
  """Nanoseconds to add to the device plane's times to put them on the
  host planes' clock, or None when the trace has no enqueue span.

  The profiler lays the two clocks over each other itself, but in the
  traces of this repo's chip runs (PERF.md section 3) every step program
  "starts" 0.4-1.6 ms before the host runtime's ``DoEnqueueProgram`` for
  it has begun, throughout a capture by the same amount. A program cannot
  run before it is enqueued, and an idle chip starts one within
  microseconds of its enqueue. ``TrainLoop.run`` keeps a step in flight, so
  most programs of a capture were enqueued a whole step before they start
  and the enqueue nearest to a program's start is the next step's; but
  ``StepProfiler`` starts a trace only once the loop has drained
  (:attr:`~.profiling.StepProfiler.at_edge`), so the capture's first step
  program is launched into an idle chip. The device's timeline is placed
  where that program starts as its own enqueue begins: the first enqueue
  from the start of the capture's first ``train.dispatch`` on."""
  if not enqueues:
    return None
  dispatched = next((r[1] for r in main if r[0] == DISPATCH), None)
  own = [t for t in enqueues if dispatched is None or t >= dispatched]
  if not own:
    return None
  return min(own) - steps[0][0]


def _summarize_device(device, main, feed, pauses, enqueues):
  names = [(n, cat, *classify(op_name)) for n, cat, op_name in
           device['names']]
  steps = sorted((s, s + d) for n, s, d in device['modules']
                 if n.startswith(STEP_MODULE_PREFIX))
  if not steps:
    return None
  shift = _device_clock_shift(steps, enqueues, main)
  to_host = shift or 0
  step_starts = [s for s, _ in steps]
  by_class = dict.fromkeys(CLASSES, 0)
  by_pass = dict.fromkeys(PASSES, 0)
  per_op = collections.Counter()
  busy = 0
  end = steps[0][0]
  for i, start, dur in sorted(device['ops'], key=lambda o: o[1]):
    name, category, cls, pass_ = names[i]
    if dur <= 0 or category in CONTAINERS:
      continue
    k = bisect.bisect_right(step_starts, start) - 1
    if k < 0 or start >= steps[k][1]:
      continue  # not inside a step program
    # Only the part no earlier operation covered: the sums are then the
    # union of the intervals, whatever overlaps.
    lo, hi = max(start, end), min(start + dur, steps[k][1])
    if hi > lo:
      by_class[cls] += hi - lo
      by_pass[pass_] += hi - lo
      per_op[(name, cls, pass_)] += hi - lo
      busy += hi - lo
      end = hi

  leaves = [r for r in main if r[0] != PARENT_PHASE]
  leaf_starts = [r[1] for r in leaves]
  parents = [r for r in main if r[0] == PARENT_PHASE]
  parent_starts = [r[1] for r in parents]
  first_step = parents[0] if parents else None
  feed_starts = [r[1] for r in feed]
  pause_starts = [r[1] for r in pauses]
  gaps = []
  for (_, lo), (hi, _) in zip(steps, steps[1:]):
    # Back-to-back programs (the next one was queued before this one
    # ended) still give a gap record, of length nought.
    lo, hi = lo + to_host, max(hi, lo) + to_host  # on the host's clock
    phases = collections.Counter()
    for name, over in _overlaps(leaves, leaf_starts, lo, hi):
      phases[name] += over
    phases[UNATTRIBUTED] = (hi - lo) - sum(phases.values())
    # The step this gap holds up: the train.step under which the next
    # program was dispatched.
    step = None
    k = bisect.bisect_right(parent_starts, hi) - 1
    if k >= 0 and parents[k][1] + parents[k][2] >= hi:
      step = parents[k][3]
    # What the feed's producer thread did meanwhile: Python there (the
    # loader's collate) holds the lock the main thread wakes up into.
    meanwhile = collections.Counter()
    for name, over in _overlaps(feed, feed_starts, lo, hi):
      meanwhile[name] += over
    gap = {
        'ns': hi - lo, 'step': step, 'phases': dict(phases),
        'feed': dict(meanwhile),
        'epoch_turn': phases.get(EPOCH_TURN, 0) > 0,
        'first_step': bool(
            first_step and
            first_step[1] < hi and first_step[1] + first_step[2] > lo),
    }
    if pauses:
      # Collections never overlap one another: the parts add up.
      gap['gc'] = sum(over for _, over in
                      _overlaps(pauses, pause_starts, lo, hi))
    gaps.append(gap)
  return {
      'plane': device['plane'], 'steps': len(steps),
      'step_programs_ns': sum(e - s for s, e in steps),
      'busy_ns': busy, 'classes': by_class, 'passes': by_pass,
      'device_clock_shift_ns': shift,
      'top_ops': [[n, cls, pass_, ns] for (n, cls, pass_), ns in
                  per_op.most_common(10)],
      'gaps': gaps,
  }


def summarize(events):
  """The capture summary of :func:`extract`'s output: per device, over
  whole ``jit_step`` programs, the gaps by phase and the busy time by
  module class and by pass."""
  main, feed, pauses = _threads(events['host'])
  enqueues = [row[1] for line in events['host'] for row in line['events']
              if row[0] == ENQUEUE]
  devices = [d for d in (_summarize_device(dev, main, feed, pauses, enqueues)
                         for dev in events['devices']) if d]
  return {'devices': devices,
          'phases_seen': sorted({r[0] for r in main})}


def summarize_capture(trace_dir):
  """Read the capture under ``trace_dir``, write ``summary.json`` beside
  it and return the summary; raises what the file gives rise to."""
  t0 = time.perf_counter()
  path = find_xplane(trace_dir)
  if path is None:
    raise FileNotFoundError(f'no .xplane.pb under {trace_dir}')
  summary = summarize(extract(path))
  summary['trace'] = path
  summary['trace_bytes'] = os.path.getsize(path)
  summary['seconds'] = time.perf_counter() - t0
  with open(os.path.join(trace_dir, 'summary.json'), 'w') as f:
    json.dump(summary, f)
  return summary


def format_table(summary):
  """The short table the loop prints after a capture."""
  lines = [f'capture summary ({summary.get("seconds", 0.0):.2f}s to read '
           f'{summary.get("trace_bytes", 0)} bytes):']
  if not summary['devices']:
    lines.append('  no step program on a TPU device plane in this trace')
    return '\n'.join(lines)
  for d in summary['devices']:
    lines.append(
        f'  {d["plane"]}: {d["steps"]} step programs, busy '
        f'{d["busy_ns"] / 1e6:.1f} ms of {d["step_programs_ns"] / 1e6:.1f}')
    shift = d['device_clock_shift_ns']
    lines.append(
        '  device clock: no enqueue span of the runtime in the trace, times '
        'as the profiler gives them' if shift is None else
        f'  device clock: moved by {shift / 1e6:+.3f} ms onto the host\'s '
        '(the first step program starts as its enqueue begins)')
    gaps = d['gaps']
    if gaps:
      total = sum(g['ns'] for g in gaps)
      lines.append(
          f'  gaps between step programs: {len(gaps)}, mean '
          f'{total / len(gaps) / 1e6:.3f} ms, median '
          f'{statistics.median(g["ns"] for g in gaps) / 1e6:.3f} ms; mean '
          'part under each phase:')
      names = sorted({p for g in gaps for p in g['phases']},
                     key=lambda p: (p == UNATTRIBUTED, p))
      for name in names:
        part = sum(g['phases'].get(name, 0) for g in gaps)
        lines.append(f'    {name:<18} {part / len(gaps) / 1e6:8.3f} ms '
                     f'{100.0 * part / total:5.1f} %')
      g = max(gaps, key=lambda g: g['ns'])
      parts = ', '.join(
          f'{n} {ns / 1e6:.3f}' for n, ns in
          sorted(g['phases'].items(), key=lambda kv: -kv[1]) if ns > 0)
      marks = ''.join(
          f' [{label}]' for flag, label in
          ((g['epoch_turn'], 'epoch turn'),
           (g['first_step'], "the capture's first step")) if flag)
      meanwhile = ', '.join(f'{n} {ns / 1e6:.3f}'
                            for n, ns in sorted(g['feed'].items()))
      collector = (f'; the collector: {g["gc"] / 1e6:.3f}' if 'gc' in g
                   else '')
      lines.append(f'    longest: {g["ns"] / 1e6:.3f} ms before step '
                   f'{g["step"]}{marks}: {parts}; the feed\'s thread '
                   f'meanwhile: {meanwhile or "no span"}{collector}')
    for key, order in (('classes', CLASSES), ('passes', PASSES)):
      shares = '  '.join(
          f'{name} {100.0 * d[key][name] / max(d["busy_ns"], 1):.1f}'
          for name in order)
      lines.append(f'  busy time by {key[:-2]}, %: {shares}')
  return '\n'.join(lines)
