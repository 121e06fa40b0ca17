"""One run of one cell of the benchmark.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The timed window is one uninterrupted call of ``TrainLoop.run``, built as
``lddl_tpu.training.pretrain.main`` builds it (compile cache, mesh, then
tokenizer, model configuration and ``TrainLoop.build`` by the model's
family), with the real loader,
``prefetch_to_device`` and the ``CompiledStepCache``. The benchmark adds
two taps of its own and nothing else: one around the host loader (what
each batch held) and one around ``loop.step_fn`` (when each step was
called, and at which call it was first found finished). The window holds
whole epochs, so that every seed fills it with the same work in another
order: it opens when the last step before the first epoch start at or
after warm-up is found finished, and closes when the last step of an
epoch is, ``--seconds`` later or more (one epoch where an epoch is
longer); it is ended through the loop's own stop path (the preemption
notice). ``setup_s`` ends at the first step call after warm-up, wherever
the epoch stands.

Everything that belongs to one cell is data: the configuration file, the
traffic file, the limits file and one reader per per-layer metric, all
found by the names in ``BENCHMARK.json`` (or, for rehearsal cells that
are not part of the benchmark, in ``chipbench/rehearsal/``). Everything
that knows a model is its family's (``chipbench/families/``), found by
the configuration file's key ``family``.

The last line of standard output is the result. Exit codes: 0 a result
was printed; 2 bad arguments or files; 3 no TPU, or fewer chips than the
cell asks for.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import hashlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import statistics  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
  sys.path.insert(0, REPO)

WORK = os.path.join(REPO, '.chipbench_work')
COMPARED_STEPS = 3


def say(*parts):
  print(f'[chipbench {time.perf_counter() - T_START:6.1f}s]', *parts,
        file=sys.stderr, flush=True)


def fail(code, message):
  say(f'FAILED: {message}')
  raise SystemExit(code)


def load_json(path):
  with open(path) as f:
    return json.load(f)


# ----------------------------------------------------------------------------
# the cell, from data files


def find_cell(name):
  """The cell's entry, configuration (with its family), traffic and
  limits, by name."""
  from chipbench import families
  bench_path = os.path.join(REPO, 'BENCHMARK.json')
  if not os.path.exists(bench_path):
    fail(2, 'no BENCHMARK.json at the root of the checkout')
  bench = load_json(bench_path)
  cells = {w['name']: dict(w, official=True) for w in bench['workloads']}
  configs = {c['name']: c['file'] for c in bench['configs']}
  for path in sorted(glob.glob(os.path.join(HERE, 'rehearsal', '*.json'))):
    entry = load_json(path)
    cells.setdefault(entry['name'], dict(entry, official=False))
    configs.setdefault(entry['config'], entry['config_file'])
  if name not in cells:
    fail(2, f'no workload {name!r}; known: {sorted(cells)}')
  cell = cells[name]
  cell['config_data'] = load_json(os.path.join(REPO, configs[cell['config']]))
  traffic_path = os.path.join(HERE, 'traffic', cell['traffic'] + '.json')
  cell['traffic_data'] = load_json(traffic_path)
  cell['limits'] = load_json(os.path.join(HERE, 'limits', name + '.json'))
  cell['bench'] = bench
  try:
    cell['family'] = families.load(cell['config_data'])
  except families.Refused as e:
    fail(2, str(e))
  return cell


def require_device(cell):
  """Name the device; an official cell runs on a TPU with enough chips
  or not at all."""
  import jax
  devices = jax.devices()
  device = {'platform': devices[0].platform,
            'kind': devices[0].device_kind, 'count': len(devices)}
  say(f'jax {jax.__version__}; platform={device["platform"]} '
      f'device_kind={device["kind"]!r} devices={device["count"]}')
  if device['count'] < cell['chips']:
    fail(3, f'the cell asks for {cell["chips"]} chip(s), jax sees '
         f'{device["count"]}')
  if cell['official'] and device['platform'] != 'tpu':
    fail(3, f'no TPU: platform is {device["platform"]!r} (JAX_PLATFORMS='
         f'{os.environ.get("JAX_PLATFORMS")!r}); a cell of the benchmark '
         'never falls back to another backend')
  return device


# ----------------------------------------------------------------------------
# traffic: corpus -> shards, by the program's own CLIs, cached per corpus


def _cli(*args):
  cmd = [sys.executable, '-m', 'lddl_tpu.cli', *map(str, args)]
  say('+ ' + ' '.join(cmd[1:]))
  # These stages never import jax (every back-end is pinned to the host);
  # the variable makes sure a child could not reach for the chip anyway.
  env = dict(os.environ, JAX_PLATFORMS='cpu')
  subprocess.run(cmd, check=True, cwd=REPO, env=env, stdout=sys.stderr)


def prepare_data(traffic, vocab_file):
  """Balanced shards for this traffic file's corpus; made once per
  checkout (users pay preprocessing once per corpus), found again by the
  hash of what decides their bytes."""
  from chipbench.corpus import write_corpus
  recipe = {k: traffic[k] for k in ('corpus', 'preprocess', 'balance')}
  recipe['vocab_file'] = os.path.relpath(vocab_file, REPO)
  digest = hashlib.sha256(
      json.dumps(recipe, sort_keys=True).encode()).hexdigest()[:16]
  final = os.path.join(WORK, 'data', digest)
  if os.path.exists(os.path.join(final, 'DONE')):
    say(f'shards: cached under {os.path.relpath(final, REPO)}')
    return os.path.join(final, 'balanced')
  t0 = time.perf_counter()
  from lddl_tpu.native.build import build_library
  say(f'native library: {os.path.basename(build_library())} '
      f'({time.perf_counter() - t0:.1f}s)')
  os.makedirs(os.path.dirname(final), exist_ok=True)
  tmp = tempfile.mkdtemp(prefix=digest + '.', dir=os.path.dirname(final))
  corpus = traffic['corpus']
  mb = write_corpus(os.path.join(tmp, 'source'), corpus['target_mb'],
                    num_shards=corpus['num_shards'],
                    seed=corpus['corpus_seed'],
                    doc_sentences=corpus.get('doc_sentences'))
  say(f'corpus: {mb:.1f} MB from corpus_seed {corpus["corpus_seed"]}')
  pre = traffic['preprocess']
  _cli(pre['cli'], '--source', os.path.join(tmp, 'source'), '--sink',
       os.path.join(tmp, 'shards'), '--vocab-file', vocab_file, *pre['args'])
  _cli('balance_shards', '--indir', os.path.join(tmp, 'shards'), '--outdir',
       os.path.join(tmp, 'balanced'), '--num-shards',
       traffic['balance']['num_shards'])
  shutil.rmtree(os.path.join(tmp, 'source'))
  shutil.rmtree(os.path.join(tmp, 'shards'))
  with open(os.path.join(tmp, 'DONE'), 'w') as f:
    f.write(json.dumps(recipe, sort_keys=True))
  try:
    os.rename(tmp, final)
  except OSError:  # another run of this checkout got there first
    shutil.rmtree(tmp, ignore_errors=True)
  say(f'shards: made in {time.perf_counter() - t0:.1f}s')
  return os.path.join(final, 'balanced')


# ----------------------------------------------------------------------------
# the two taps


class LoaderTap:
  """The host loader, with what each batch held (the family's
  ``batch_facts``), its padded shape and the index at which each epoch
  starts written down. Iteration runs on the prefetch thread, as the
  loader's own would; ``TrainLoop.run`` enters it once an epoch."""

  def __init__(self, inner, keep_first, batch_facts):
    self._inner = inner
    self._keep_first = keep_first
    self._batch_facts = batch_facts
    self.facts = []
    self.shapes = []
    self.epoch_starts = []
    self.first = []

  def __getattr__(self, name):
    return getattr(self._inner, name)

  def __iter__(self):
    import jax
    import numpy as np
    it = iter(self._inner)
    self.epoch_starts.append(len(self.facts))
    while True:
      with jax.profiler.TraceAnnotation('chipbench.loader_next'):
        try:
          batch = next(it)
        except StopIteration:
          return
      if len(self.first) < self._keep_first:
        self.first.append({k: np.array(v) for k, v in batch.items()})
      self.facts.append(self._batch_facts(batch))
      self.shapes.append(max((np.shape(v) for v in batch.values()), key=len))
      yield batch


def make_step_tap(step_fn, window):
  """``loop.step_fn`` as the program's own ``CompiledStepCache``, with the
  time of every call written down, and each step's loss kept until a
  later call finds it ready (the loop runs ahead of the device: a call
  returns before its step has run)."""
  import jax

  from lddl_tpu.training.pretrain import CompiledStepCache

  class StepTap(CompiledStepCache):

    def __call__(self, params, opt_state, rng, batch):
      now = time.perf_counter()
      if window.recording:
        window.on_call(now, params, opt_state, self)
      with jax.profiler.TraceAnnotation('chipbench.step_fn'):
        out = super().__call__(params, opt_state, rng, batch)
      if window.recording:
        window.pending.append(out[2]['loss'])
      return out

  return StepTap(step_fn)


class Window:
  """Whole epochs of finished steps. ``done[j]`` is the time of the first
  step call that found step ``j`` finished: with one step in flight that
  is call ``j + 2``, made as soon as the host has read step ``j``'s loss,
  while step ``j + 1`` runs. The window opens at ``done[lo - 1]``, ``lo``
  the first epoch start at or after ``open_at`` (warm-up, in a traced run
  the traced steps too), and closes at ``done[hi - 1]``, ``hi`` the first
  epoch start with ``seconds`` or more between the two: the device ran
  steps ``lo`` to ``hi - 1`` in that time, whole epochs, and nothing else.
  (Between the *calls* ``lo`` and ``hi`` it ran steps ``lo - 1`` to
  ``hi - 2``: another epoch's last step for this one's, which in a cell
  of long unlike steps moved ``tokens_per_s`` by 1.9 % with the seed.)
  Between the two, nothing but reading the clock. Set-up ends at
  ``calls[open_at]``. ``epoch_starts`` is the loader tap's list: the index
  of each epoch's first step, there before that step is called."""

  HISTOGRAMS = ('train.data_wait_seconds', 'train.compute_seconds',
                'train.step_seconds', 'train.h2d_seconds')

  def __init__(self, cell, seed, seconds, trace_dir):
    self.cell, self.seed, self.seconds = cell, seed, seconds
    self.trace_dir = trace_dir
    plan = cell['traffic_data']['window']
    self.warmup = plan['warmup_steps']
    if self.warmup <= COMPARED_STEPS:
      fail(2, f'warmup_steps must exceed the {COMPARED_STEPS} compared steps')
    # A traced run traces `trace_steps` steady steps after warm-up and
    # opens the window two calls after the last of them, so that stopping
    # the trace (seconds of host time) lies before the window, not in it.
    self.trace_steps = plan['trace_steps'] if trace_dir else 0
    self.open_at = self.warmup + (self.trace_steps + 2 if trace_dir else 0)
    self.recording = False
    self.calls = []
    self.pending = collections.deque()  # losses of steps not yet found ready
    self.done = []
    self.epoch_starts = []
    self.open_index = self.close_index = None
    self.misses = {}
    self.telemetry = {}
    self.grad_norms = self.change_norms = None

  def _telemetry(self):
    from lddl_tpu.telemetry import get_telemetry
    tele = get_telemetry()
    if not tele.enabled:
      return None
    return {name: (tele.histogram(name).sum, tele.histogram(name).count)
            for name in self.HISTOGRAMS}

  def on_call(self, now, params, opt_state, tap):
    i = len(self.calls)
    self.calls.append(now)
    while self.pending and self.pending[0].is_ready():
      self.pending.popleft()
      self.on_done(now, tap)
    if i == 1:
      # The state after one step holds the first gradient, as the
      # optimizer got it.
      self.grad_norms = self.cell['family'].first_gradient_norms(opt_state)
    elif i == COMPARED_STEPS:
      # The parameters after the compared steps, before this call
      # donates them.
      self.change_norms = self.cell['family'].change_norms(
          self.cell['config_data'], self.seed, params)
    elif i == self.warmup and self.trace_dir:
      # Armed now, the loop's own profiler hook starts the trace once this
      # step is done and stops it `trace_steps` steps later.
      from lddl_tpu.telemetry.profiling import get_step_profiler
      get_step_profiler().arm(self.trace_steps, out_dir=self.trace_dir)

  def on_done(self, now, tap):
    """The next step was found finished at ``now``; where the step after
    it starts an epoch, the window may open or close."""
    self.done.append(now)
    i = len(self.done)
    if (i < self.open_at or self.close_index is not None or
        i not in self.epoch_starts):
      return
    if self.open_index is None:
      self.open_index = i
      self.misses['open'] = tap.misses
      self.telemetry['open'] = self._telemetry()
    elif now - self.done[self.open_index - 1] >= self.seconds:
      self.close_index = i
      self.misses['close'] = tap.misses
      self.telemetry['close'] = self._telemetry()
      # The loop's own stop path: a preemption notice, acted on at the
      # next step boundary. No checkpoint directory, so nothing is saved.
      os.kill(os.getpid(), signal.SIGTERM)


# ----------------------------------------------------------------------------
# building the loop as pretrain.main does


def build_loop(cell, shards, seed, window):
  import jax
  import jax.numpy as jnp

  from chipbench import families
  from lddl_tpu.core.compile_cache import use_compile_cache
  from lddl_tpu.loader.device import make_global_batch
  from lddl_tpu.parallel import make_mesh, mesh_summary

  family = cell['family']
  train = cell['traffic_data']['train']
  if train['prng'] != 'threefry':
    jax.config.update('jax_default_prng_impl', train['prng'])
  say(f'compile cache: {use_compile_cache()}')
  jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
  mesh = make_mesh(**train['mesh'])
  say(f'mesh: {mesh_summary(mesh)}')
  try:
    loop = family.build_loop(cell, shards, seed, mesh)
  except families.Refused as e:
    fail(2, str(e))
  say('TrainLoop.build done')
  loop.loader = LoaderTap(loop.loader, COMPARED_STEPS, family.batch_facts)
  window.epoch_starts = loop.loader.epoch_starts
  tap = make_step_tap(loop.step_fn, window)
  loop.step_fn = tap

  # Every shape the loader can yield is compiled now, through the loop's
  # own step object and the program's own placement, on a batch of
  # nothing; the state these steps leave is thrown away.
  for seq in family.bin_lengths(shards, train):
    placed = make_global_batch(family.fake_batch(train, seq), mesh)
    t0 = time.perf_counter()
    loop.params, loop.opt_state, metrics = tap(
        loop.params, loop.opt_state, loop.rng, placed)
    float(metrics['loss'])
    say(f'warmed [{train["batch_size"]}, {seq}] in '
        f'{time.perf_counter() - t0:.1f}s')
  for key, executable in getattr(tap, '_compiled', {}).items():
    analysis = getattr(executable, 'memory_analysis', lambda: None)()
    if analysis is not None:
      shape = max((k[1] for k in key), key=len)  # [batch_size, seq]
      total = (analysis.argument_size_in_bytes +
               analysis.output_size_in_bytes +
               analysis.temp_size_in_bytes - analysis.alias_size_in_bytes)
      say(f'memory_analysis {list(shape)}: arguments '
          f'{analysis.argument_size_in_bytes} output '
          f'{analysis.output_size_in_bytes} temp '
          f'{analysis.temp_size_in_bytes} alias '
          f'{analysis.alias_size_in_bytes} -> live {total} bytes')

  # The weights the reference will start from, in the program's tree;
  # Adam's state starts at nought.
  # The old weights go first, so that the chip never holds both and the
  # peak it reports is a training job's.
  like = jax.tree.map(
      lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
      loop.params)
  jax.tree.map(lambda x: x.delete(), loop.params)
  loop.params = family.seeded_params(cell['config_data'], seed, like)
  loop.opt_state = jax.jit(
      lambda t: jax.tree.map(jnp.zeros_like, t), donate_argnums=0)(
          loop.opt_state)
  jax.block_until_ready((loop.params, loop.opt_state))
  say('seeded weights in place')
  return loop, tap


# ----------------------------------------------------------------------------
# metrics


def device_memory(device):
  """The peak a chip held: the allocator's buffers (``peak_bytes_in_use``:
  state, batches) plus what it reserved for the programs' scratch
  (``peak_bytes_reserved``: activations, gradients, temporaries), which
  ``peak_bytes_in_use`` leaves out (PERF.md section 7)."""
  stats = device.memory_stats() or {}
  out = {k: int(stats.get(k, 0)) for k in
         ('peak_bytes_in_use', 'peak_bytes_reserved', 'bytes_limit')}
  out['peak_bytes'] = out['peak_bytes_in_use'] + out['peak_bytes_reserved']
  return out


def read_per_layer(names, ctx):
  """One reader file per metric; a reader that finds nothing to read
  returns None and the metric is left out."""
  out = {}
  for name in names:
    path = os.path.join(HERE, 'metrics', name + '.py')
    spec = importlib.util.spec_from_file_location(
        'chipbench_metric_' + name.replace('.', '_'), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    value = module.read(ctx)
    if value is not None:
      out[name] = float(value)
  return out


def wanted(metric, cell_name):
  return 'workloads' not in metric or cell_name in metric['workloads']


def main(argv=None):
  parser = argparse.ArgumentParser(description=__doc__)
  parser.add_argument('--workload', required=True)
  parser.add_argument('--seed', type=int, required=True)
  parser.add_argument('--seconds', type=float, required=True)
  parser.add_argument('--trace', type=int, choices=(0, 1), default=0)
  args = parser.parse_args(argv)
  # Any whole number up to a little over 2**31 is a seed; the program's
  # key and loader want one that 31 bits hold.
  seed = args.seed % 2147483629

  cell = find_cell(args.workload)
  if not os.path.isdir(os.path.join(REPO, 'lddl_tpu')):
    fail(2, 'the program (lddl_tpu/) is not in this checkout: there is '
         'nothing to measure')
  device = require_device(cell)
  on_tpu = device['platform'] == 'tpu'
  traffic = cell['traffic_data']
  train = traffic['train']

  import jax

  from chipbench import compare, required_work, trace_reduce
  peaks = required_work.load_peaks(device['kind']) if on_tpu else None

  say('imports done')
  shards = prepare_data(traffic, cell['family'].VOCAB_FILE)
  trace_dir = None
  if args.trace:
    from lddl_tpu.telemetry import enable
    enable()
    trace_dir = tempfile.mkdtemp(prefix='chipbench_trace_')
  window = Window(cell, seed, args.seconds, trace_dir)
  loop, tap = build_loop(cell, shards, seed, window)
  say(f'set-up before the loop: {time.perf_counter() - T_START:.1f}s')

  # --- the one call of TrainLoop.run: warm-up steps, then the window ---
  window.recording = True
  losses = loop.run(traffic['window']['max_steps'], log_every=0)
  window.recording = False
  if window.close_index is None:
    fail(2, f'the loop ended after {len(window.calls)} steps before an '
         'epoch start closed the window: raise window.max_steps in the '
         'traffic file')
  if args.trace:
    from lddl_tpu.telemetry.profiling import get_step_profiler
    get_step_profiler().close()

  lo, hi = window.open_index, window.close_index
  t_open, t_close = window.done[lo - 1], window.done[hi - 1]
  wall = t_close - t_open
  loader_facts = loop.loader.facts
  facts = loader_facts[lo:hi]
  epochs = sum(lo <= start < hi for start in window.epoch_starts)
  steps_per_shape = dict(collections.Counter(
      'x'.join(map(str, shape)) for shape in loop.loader.shapes[lo:hi]))
  intervals_ms = [1e3 * (b - a) for a, b in
                  zip(window.done[lo - 1:hi - 1], window.done[lo:hi])]
  tokens = sum(sum(f['rows']) for f in facts)
  compiles = window.misses['close'] - window.misses['open']
  finite = all(math.isfinite(x) for x in losses)
  first_batches = loop.loader.first
  program = {'losses': losses[:COMPARED_STEPS],
             'grad_norms': window.grad_norms,
             'change_norms': window.change_norms}
  memory = max((device_memory(d) for d in jax.local_devices()),
               key=lambda m: m['peak_bytes'])
  say(f'window: {epochs} whole epochs, {hi - lo} steps '
      f'{steps_per_shape}, {tokens} real tokens in {wall:.3f}s; '
      f'loss {losses[lo]:.4f} -> {losses[hi - 1]:.4f}; compiles inside '
      f'{compiles}; peak_bytes_in_use {memory["peak_bytes_in_use"]} + '
      f'peak_bytes_reserved {memory["peak_bytes_reserved"]} = '
      f'{memory["peak_bytes"]} of {memory["bytes_limit"]}')

  if hi - lo <= 64:  # a cell of few long steps: every one of them
    say('ms between steps found finished: ' +
        ' '.join(f'{x:.1f}' for x in intervals_ms))

  # --- free the program's state, then follow its first steps ---
  del loop, tap
  gc.collect()
  t0 = time.perf_counter()
  ref = cell['family'].follow(cell['config_data'], train, seed,
                              first_batches)
  values = compare.numbers(program, ref)
  values['compiles_in_window'] = compiles
  correct, compared, observed = compare.judge(values, cell['limits'])
  correct = correct and finite
  say(f'reference followed {COMPARED_STEPS} steps in '
      f'{time.perf_counter() - t0:.1f}s; program losses '
      f'{program["losses"]} reference {ref["losses"]}; worst leaves: grad '
      f'{values["_grad_gap_at"]}, change {values["_change_gap_at"]}; left '
      f'out of the change: {values["_left_out_of_change"]}')

  bench = cell['bench']
  ctx = {
      'cell': cell, 'family': cell['family'],
      'config': cell['config_data'], 'train': train,
      'chips': cell['chips'], 'peaks': peaks, 'wall_s': wall,
      'steps': facts, 'intervals_ms': intervals_ms,
      'compiles_in_window': compiles, 'memory': memory,
      'telemetry': None, 'trace': None,
      'traced_steps': loader_facts[window.warmup + 1:
                                   window.warmup + 1 + window.trace_steps],
  }
  end_to_end = {
      'tokens_per_s': (tokens / wall, 'tokens/s'),
      'step_ms_p90': (statistics.quantiles(intervals_ms, n=10)[8]
                      if len(intervals_ms) >= 2 else None, 'ms'),
      'setup_s': (window.calls[window.open_at] - T_START, 's'),
  }
  device_out = dict(device, memory_peak_bytes=memory['peak_bytes'])
  result = {'correct': bool(correct), 'attempted': hi - lo,
            'failed': sum(not math.isfinite(x) for x in losses[lo:hi]),
            'window': {'epochs': epochs, 'steps': hi - lo, 'seconds': wall,
                       'step_ms_median': statistics.median(intervals_ms),
                       'step_ms_max': max(intervals_ms),
                       'real_tokens': tokens,
                       'steps_per_shape': steps_per_shape}}
  metrics = {}
  if args.trace:
    opened, closed = window.telemetry['open'], window.telemetry['close']
    ctx['telemetry'] = {
        name: {'sum': closed[name][0] - opened[name][0],
               'count': closed[name][1] - opened[name][1]}
        for name in opened}
    paths = glob.glob(os.path.join(trace_dir, '**', '*.xplane.pb'),
                      recursive=True)
    if paths:
      t0 = time.perf_counter()
      ctx['trace'] = trace_reduce.reduce(trace_reduce.extract(paths[0]))
      say(f'trace of {os.path.getsize(paths[0])} bytes reduced in '
          f'{time.perf_counter() - t0:.1f}s')
    shutil.rmtree(trace_dir, ignore_errors=True)
    if on_tpu:
      units = {m['name']: m['unit'] for m in bench['per_layer']}
      names = [m['name'] for m in bench['per_layer']
               if wanted(m, cell['name']) or not cell['official']]
      for name, value in read_per_layer(names, ctx).items():
        metrics[name] = {'value': value, 'unit': units[name]}
    if ctx['trace']:
      device_out['busy_s'] = ctx['trace']['busy_s']
      device_out['window_s'] = ctx['trace']['window_s']
      result['breakdown'] = ctx['trace']['breakdown']
  elif on_tpu:
    for m in bench['end_to_end']:
      value, unit = end_to_end[m['name']]
      if wanted(m, cell['name']) and value is not None:
        metrics[m['name']] = {'value': value, 'unit': unit}
  if not on_tpu:
    # Not a chip: counts only, and never under the name of a device metric.
    result['rehearsal'] = {
        'note': f'platform {device["platform"]}: no device metric',
        'per_layer_readers': sorted(read_per_layer(
            [m['name'] for m in bench['per_layer']], ctx)),
    }
  result.update(metrics=metrics, device=device_out, observed=observed,
                compared=compared)
  for name, value in observed.items():
    say(f'observed {name} = {value:.6g} (not compared)')
  for name, pair in compared.items():
    say(f'compared {name} = {pair["value"]:.6g} (limit {pair["limit"]:g})')
  say(f'correct = {result["correct"]}')
  print(json.dumps(result), flush=True)


if __name__ == '__main__':
  main()
