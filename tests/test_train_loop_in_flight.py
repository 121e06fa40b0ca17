"""``TrainLoop.run`` keeps one step in flight: step k+1 is launched before
step k's loss is read, and the observers of a step run one step late.

Four contracts, on the CPU with step functions made for the purpose: the
order of launches and loss reads; the same numbers as a plain serial loop
(losses, state digest, checkpoints, the ledger's ``step`` records) across
epoch turns and checkpoint boundaries; stops that come at most one step
late and leave a checkpoint at ``loop.step``; and the per-step values an
observer reports, which are those of the step it observes.
"""

import json
import os
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lddl_tpu.training.pretrain import CompiledStepCache, TrainLoop

BATCH = 6  # not a multiple of the 8 virtual devices: every batch on one device
WIDTH = 4


class Loader:
  """``per_epoch`` batches an epoch, every batch made from its epoch and
  index alone; iterating again starts the next epoch."""

  batch_size = BATCH

  def __init__(self, per_epoch, segments=False):
    self.per_epoch = per_epoch
    self.epoch = 0
    self.segments = segments

  def __iter__(self):
    epoch, self.epoch = self.epoch, self.epoch + 1
    for i in range(self.per_epoch):
      rng = np.random.default_rng(1000 * epoch + i)
      batch = {'x': rng.standard_normal((BATCH, WIDTH)).astype(np.float32),
               'y': rng.standard_normal((BATCH,)).astype(np.float32)}
      if self.segments:
        batch['input_ids'] = np.zeros((BATCH, 128), np.int32)
        batch['segment_ids'] = np.zeros((BATCH, 128), np.int32)
      yield batch


def make_loop(step_fn, per_epoch=5, **loader_kwargs):
  return TrainLoop(
      model=None, tx=None, mesh=None, loader=Loader(per_epoch, **loader_kwargs),
      params={'w': jnp.zeros((WIDTH,), jnp.float32)},
      opt_state={'count': jnp.zeros((), jnp.int32),
                 'mu': jnp.zeros((WIDTH,), jnp.float32)},
      rng=jax.random.key(3), step_fn=step_fn)


# ----------------------------------------------------------------------------
# a step whose loss says when it is read


class RecordedLoss:

  def __init__(self, log, step, value):
    self.log, self.step, self.value = log, step, value

  def __float__(self):
    self.log.append(('read', self.step))
    return self.value


class RecordingStep:
  """Counts its calls and hands back a loss that writes down when it is
  converted; ``on_launch(k)`` runs inside call ``k``."""

  def __init__(self, values=None, on_launch=None):
    self.log = []
    self.values = values or {}
    self.on_launch = on_launch
    self.launched = 0

  def __call__(self, params, opt_state, rng, batch):
    k, self.launched = self.launched, self.launched + 1
    self.log.append(('launch', k))
    if self.on_launch is not None:
      self.on_launch(k)
    return params, opt_state, {
        'loss': RecordedLoss(self.log, k, self.values.get(k, 1.0 + k))}


def in_flight_order(n):
  """launch 0, launch 1, read 0, launch 2, read 1, ..., read n-1."""
  order = [('launch', 0)]
  for k in range(1, n):
    order += [('launch', k), ('read', k - 1)]
  return order + [('read', n - 1)]


@pytest.mark.parametrize('steps', [1, 2, 5, 7])
def test_step_k_plus_1_is_launched_before_step_k_is_read(steps):
  step = RecordingStep()
  loop = make_loop(step, per_epoch=3)  # 5 and 7 steps turn the epoch
  losses = loop.run(steps, log_every=0)
  assert step.log == in_flight_order(steps)
  assert losses == [1.0 + k for k in range(steps)]  # the drain included
  assert loop.step == steps and loop.samples_seen == steps * BATCH
  assert loop.stop_reason is None


def test_a_second_run_call_goes_on_where_the_first_stopped():
  step = RecordingStep()
  loop = make_loop(step)
  assert loop.run(2, log_every=0) == [1.0, 2.0]
  assert loop.run(4, log_every=0) == [3.0, 4.0]
  assert step.log == [('launch', 0), ('launch', 1), ('read', 0), ('read', 1),
                      ('launch', 2), ('launch', 3), ('read', 2), ('read', 3)]
  assert loop.run(4, log_every=0) == []  # nothing to do, nothing launched
  assert step.launched == 4


# The switch that once turned the cache off, in two pieces: the tree is
# searched for the name whole, and is to hold it nowhere.
_OLD_SWITCH = 'LDDL_STEP' + '_CACHE'


@pytest.mark.parametrize('env', [None, '0', '1'])
def test_the_step_always_runs_through_the_step_cache(monkeypatch, env):
  """``run`` wraps ``step_fn`` in a ``CompiledStepCache`` whatever the
  environment holds, and leaves one that is a cache already (a subclass,
  as the benchmark's tap is) as it found it."""
  if env is not None:
    monkeypatch.setenv(_OLD_SWITCH, env)
  step = RecordingStep()
  loop = make_loop(step)
  loop.run(2, log_every=0)
  assert type(loop.step_fn) is CompiledStepCache
  assert loop.step_fn.inner is step and step.launched == 2
  assert (loop.step_fn.misses, loop.step_fn.hits) == (1, 1)

  class Tap(CompiledStepCache):
    pass

  tap = Tap(RecordingStep())
  loop = make_loop(tap)
  loop.run(2, log_every=0)
  assert loop.step_fn is tap and tap.inner.launched == 2


def test_a_checkpoint_boundary_is_a_sync(tmp_path):
  # ckpt_every=2: steps 1 and 3 are drained before steps 2 and 4 launch,
  # so that the state saved at 2 and 4 is the state of loop.step.
  step = RecordingStep()
  loop = make_loop(step)
  loop.run(5, ckpt_dir=str(tmp_path / 'ckpt'), ckpt_every=2, log_every=0,
           async_ckpt=False)
  assert step.log == [
      ('launch', 0), ('launch', 1), ('read', 0), ('read', 1),
      ('launch', 2), ('launch', 3), ('read', 2), ('read', 3),
      ('launch', 4), ('read', 4)]
  assert sorted(int(d) for d in os.listdir(tmp_path / 'ckpt')
                if d.isdigit()) == [2, 4, 5]


@pytest.mark.parametrize('traced', [1, 2, 3])
def test_a_profiler_capture_holds_whole_steps(tmp_path, monkeypatch, traced):
  """Armed while step 2 is being launched (as the benchmark's tap arms it,
  or the monitor's thread), the capture starts once step 2 is drained and
  stops once the last traced step is: the trace holds ``traced`` whole
  steps, the first launched into an idle chip, and the steps between are
  launched one ahead as everywhere else."""
  import lddl_tpu.telemetry.profiling as profiling
  profiling._reset_for_tests()
  profiler = profiling.get_step_profiler()
  step = RecordingStep(
      on_launch=lambda k: k == 2 and profiler.arm(traced,
                                                  out_dir=str(tmp_path)))
  monkeypatch.setattr(jax.profiler, 'start_trace',
                      lambda d: step.log.append(('trace', 'start')))
  monkeypatch.setattr(jax.profiler, 'stop_trace',
                      lambda: step.log.append(('trace', 'stop')))
  monkeypatch.setattr(profiling, '_summarize', lambda d: None)
  loop = make_loop(step)
  try:
    loop.run(traced + 5, log_every=0)
  finally:
    profiling._reset_for_tests()
  last = 2 + traced
  inside = [('launch', 3)]
  for k in range(4, last + 1):
    inside += [('launch', k), ('read', k - 1)]
  assert step.log == [
      ('launch', 0), ('launch', 1), ('read', 0), ('launch', 2), ('read', 1),
      ('read', 2), ('trace', 'start'), *inside, ('read', last),
      ('trace', 'stop'),
      ('launch', last + 1), ('launch', last + 2), ('read', last + 1),
      ('read', last + 2)]


# ----------------------------------------------------------------------------
# the same numbers as a serial loop


@jax.jit
def sgd_step(params, opt_state, rng, batch):
  """A real step in small: a loss, its gradient, a momentum and a draw
  keyed by the optimizer's count, as the train step keys its dropout."""
  noise = 0.01 * jax.random.normal(
      jax.random.fold_in(rng, opt_state['count']), params['w'].shape)

  def loss_fn(p):
    return jnp.mean(jnp.square(batch['x'] @ (p['w'] + noise) - batch['y']))

  loss, grads = jax.value_and_grad(loss_fn)(params)
  mu = 0.9 * opt_state['mu'] + grads['w']
  return ({'w': params['w'] - 0.05 * mu},
          {'count': opt_state['count'] + 1, 'mu': mu},
          {'loss': loss, 'grad_norm': jnp.sqrt(jnp.sum(jnp.square(grads['w'])))})


def serial_reference(loop, max_steps, ckpt_dir, ckpt_every):
  """The loop as it was: launch, read the loss, count, save; one step at a
  time. Written out here so that it cannot change with ``run()``."""
  losses = []
  while loop.step < max_steps:
    for batch in loop.loader:
      if loop.step >= max_steps:
        break
      loop.params, loop.opt_state, metrics = loop.step_fn(
          loop.params, loop.opt_state, loop.rng, jax.device_put(batch))
      loss = float(metrics['loss'])
      losses.append(loss)
      loop._last_loss = loss
      loop.step += 1
      loop.samples_seen += BATCH
      if loop.step % ckpt_every == 0:
        loop.save(ckpt_dir)
  if loop._last_saved != loop.step:
    loop.save(ckpt_dir)
  return losses


def with_ledger(directory, fn):
  import lddl_tpu.telemetry.ledger as ledger_mod
  ledger_mod._active = None
  ledger_mod.enable_ledger(directory=str(directory), rank=0)
  try:
    return fn()
  finally:
    ledger_mod.disable_ledger()


def step_records(directory):
  from lddl_tpu.telemetry import audit
  records = audit.load_run(str(directory))[0]['records']
  return [(r['step'], r['samples'], r['loss'], r['digest'])
          for r in records if r['boundary'] == 'step']


def checkpoints(ckpt_dir):
  """``{step: (meta, leaves of the saved state)}`` of every checkpoint."""
  import orbax.checkpoint as ocp
  mngr = ocp.CheckpointManager(os.path.abspath(ckpt_dir))
  out = {}
  for step in mngr.all_steps():
    got = mngr.restore(step, args=ocp.args.Composite(
        state=ocp.args.StandardRestore(), meta=ocp.args.JsonRestore()))
    leaves, treedef = jax.tree_util.tree_flatten(got['state'])
    out[step] = (got['meta'], str(treedef), [np.asarray(x) for x in leaves])
  mngr.close()
  return out


@pytest.mark.parametrize('async_ckpt', [False, True])
def test_same_numbers_as_a_serial_loop(tmp_path, async_ckpt):
  # 13 steps of 5-batch epochs: two epoch turns, checkpoints at 4, 8, 12
  # inside the loop and the trailing one at 13.
  steps, every = 13, 4
  ref = make_loop(sgd_step)
  ref_losses = with_ledger(
      tmp_path / 'led_ref',
      lambda: serial_reference(ref, steps, str(tmp_path / 'ckpt_ref'), every))
  loop = make_loop(sgd_step)
  losses = with_ledger(
      tmp_path / 'led_run',
      lambda: loop.run(steps, ckpt_dir=str(tmp_path / 'ckpt_run'),
                       ckpt_every=every, log_every=0, async_ckpt=async_ckpt))
  assert losses == ref_losses and len(losses) == steps  # to the last bit
  assert loop.state_digest() == ref.state_digest()
  assert (loop.step, loop.samples_seen) == (ref.step, ref.samples_seen)
  assert loop._last_loss == ref._last_loss
  want = step_records(tmp_path / 'led_ref')
  assert [r[0] for r in want] == [4, 8, 12, 13]
  assert step_records(tmp_path / 'led_run') == want
  got, want = (checkpoints(str(tmp_path / d)) for d in ('ckpt_run',
                                                        'ckpt_ref'))
  assert sorted(got) == sorted(want) == [8, 12, 13]  # keep=3
  for step in want:
    assert got[step][0] == want[step][0] == {
        'step': step, 'samples_seen': step * BATCH}
    assert got[step][1] == want[step][1] and len(got[step][2]) == 4
    for a, b in zip(got[step][2], want[step][2]):
      np.testing.assert_array_equal(a, b)
  assert TrainLoop.latest_meta(str(tmp_path / 'ckpt_run')) == (13, 13 * BATCH)


# ----------------------------------------------------------------------------
# stops


@pytest.mark.parametrize('bad', [0, 2, 5])
def test_a_nonfinite_loss_stops_at_most_one_step_late(tmp_path, bad):
  step = RecordingStep(values={bad: float('nan')})
  loop = make_loop(step, per_epoch=3)
  ckpt = str(tmp_path / 'ckpt')
  losses = loop.run(10, ckpt_dir=ckpt, log_every=0)
  assert loop.stop_reason == 'nonfinite_loss'
  # Step bad+1 was on the device when step bad's loss was read: it is
  # drained and counted, and nothing is launched after it.
  assert step.launched == bad + 2
  assert step.log == in_flight_order(bad + 2)
  assert len(losses) == loop.step == bad + 2
  assert np.isnan(losses[bad]) and np.isfinite(losses[bad + 1])
  assert TrainLoop.latest_meta(ckpt) == (loop.step, loop.step * BATCH)


def test_a_nonfinite_loss_in_a_drained_step_stops_at_once(tmp_path):
  # Step 3 is drained before step 4 launches (ckpt_every=4): its loss is
  # seen with nothing in flight, and the boundary's checkpoint is the
  # emergency one.
  step = RecordingStep(values={3: float('inf')})
  loop = make_loop(step)
  ckpt = str(tmp_path / 'ckpt')
  losses = loop.run(10, ckpt_dir=ckpt, ckpt_every=4, log_every=0,
                    async_ckpt=False)
  assert loop.stop_reason == 'nonfinite_loss'
  assert step.launched == len(losses) == loop.step == 4
  assert TrainLoop.latest_meta(ckpt) == (4, 4 * BATCH)


def test_nonfinite_ignore_keeps_its_meaning(monkeypatch):
  monkeypatch.setenv('LDDL_NONFINITE', 'ignore')
  step = RecordingStep(values={1: float('nan')})
  loop = make_loop(step)
  losses = loop.run(4, log_every=0)
  assert loop.stop_reason is None and len(losses) == 4


def test_sigterm_launches_nothing_further_and_drains(tmp_path):
  # The notice arrives while step 2 is being launched: step 2 is the last
  # launch, its loss is read in the drain, the checkpoint is at 3.
  before = signal.getsignal(signal.SIGTERM)
  step = RecordingStep(
      on_launch=lambda k: k == 2 and os.kill(os.getpid(), signal.SIGTERM))
  loop = make_loop(step)
  ckpt = str(tmp_path / 'ckpt')
  losses = loop.run(10, ckpt_dir=ckpt, ckpt_every=100, log_every=0)
  assert signal.getsignal(signal.SIGTERM) == before
  assert loop.stop_reason == 'preempted'
  assert step.launched == 3
  assert step.log == in_flight_order(3)
  assert losses == [1.0, 2.0, 3.0] and loop.step == 3
  assert TrainLoop.latest_meta(ckpt) == (3, 3 * BATCH)


def test_a_notice_before_the_first_launch_launches_nothing(tmp_path,
                                                           monkeypatch):
  notice = tmp_path / 'notice'
  notice.write_text('maintenance')
  monkeypatch.setenv('LDDL_PREEMPTION_FILE', str(notice))
  step = RecordingStep()
  loop = make_loop(step)
  assert loop.run(10, log_every=0) == []
  assert loop.stop_reason == 'preempted' and step.launched == 0


class Membership:
  """A fleet event at the poll after ``steps`` observed steps."""

  interval = 0.0

  def __init__(self, steps):
    self.steps, self.polls = steps, 0

  def publish_signals(self, signals):
    assert signals['steps_per_sec'] >= 0

  def poll(self):
    self.polls += 1
    return 'peer_dead' if self.polls == self.steps else None


def test_a_membership_verdict_stops_one_step_late(tmp_path):
  step = RecordingStep()
  loop = make_loop(step)
  ckpt = str(tmp_path / 'ckpt')
  losses = loop.run(10, ckpt_dir=ckpt, log_every=0,
                    membership=Membership(steps=3))
  assert loop.stop_reason == 'peer_dead'
  assert step.launched == len(losses) == loop.step == 4
  assert TrainLoop.latest_meta(ckpt) == (4, 4 * BATCH)


# ----------------------------------------------------------------------------
# what the observers report


def test_per_step_values_travel_with_the_step(monkeypatch):
  """With telemetry on: one observation per step in every per-step
  instrument, the step's interval is pull to pull (so the intervals do not
  overlap and data wait + compute is the interval), the new
  ``train.loss_read_seconds`` lies inside it, and what is read from a
  step's batch (the packed rows' tile count, the MFU numerator's shape)
  was taken before the next pull deleted the batch."""
  import time

  import lddl_tpu.telemetry as telemetry
  from lddl_tpu.ops.flash_attention import count_skippable_tiles
  monkeypatch.setenv('LDDL_PEAK_TFLOPS', '0.5')
  tele = telemetry.enable()
  seen = []

  def flops_fn(b, s):
    seen.append((b, s))
    return 1e6

  def step(params, opt_state, rng, batch):
    time.sleep(0.01)
    return params, opt_state, {'loss': jnp.float32(1.0)}

  loop = make_loop(step, per_epoch=4, segments=True)
  loop.flops_fn = flops_fn
  t0 = time.perf_counter()
  assert len(loop.run(6, log_every=0)) == 6
  wall = time.perf_counter() - t0
  hist = {n: tele.histogram(f'train.{n}_seconds')
          for n in ('step', 'loss_read', 'data_wait', 'compute')}
  assert [h.count for h in hist.values()] == [6, 6, 6, 6]
  assert hist['step'].sum <= wall  # no interval counted twice
  assert hist['step'].sum == pytest.approx(
      hist['data_wait'].sum + hist['compute'].sum)
  assert 0 <= hist['loss_read'].sum < hist['step'].sum
  assert tele.counter('train.steps').total == 6
  assert tele.gauge('train.mfu').count == 6
  assert seen == [(BATCH, 128)] * 6
  total, skipped = count_skippable_tiles(np.zeros((BATCH, 128), np.int32))
  assert tele.counter('train.attn_tiles_total').total == 6 * total
  assert tele.counter('train.attn_tiles_skipped').total == 6 * skipped


def test_host_busy_reader(monkeypatch):
  """``chipbench/metrics/loop.host_busy_ms.py``: (step - loss read) /
  count from the program's histograms; None without the second one (the
  parent of PR 26) or with telemetry off."""
  import importlib.util

  import lddl_tpu.telemetry as telemetry
  here = os.path.dirname(os.path.abspath(__file__))
  spec = importlib.util.spec_from_file_location(
      'host_busy', os.path.join(os.path.dirname(here), 'chipbench', 'metrics',
                                'loop.host_busy_ms.py'))
  reader = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(reader)
  telemetry.disable()
  assert reader.read({}) is None
  tele = telemetry.enable()
  for seconds in (0.060, 0.062):
    tele.histogram('train.step_seconds').observe(seconds)
  assert reader.read({}) is None  # a program without the loss-read histogram
  for seconds in (0.057, 0.059):
    tele.histogram('train.loss_read_seconds').observe(seconds)
  assert reader.read({}) == pytest.approx(3.0)
  bench = json.load(open(os.path.join(os.path.dirname(here),
                                      'BENCHMARK.json')))
  (entry,) = [m for m in bench['per_layer']
              if m['name'] == 'loop.host_busy_ms']
  assert entry == {
      'name': 'loop.host_busy_ms', 'unit': 'ms', 'better': 'lower',
      'source': 'program_span', 'layer': 'train loop',
      'moves': 'tokens_per_s'}
