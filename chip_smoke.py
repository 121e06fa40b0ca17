"""Chip smoke: the pretrain path, end to end, on the attached TPU.

    python chip_smoke.py

Runs shards -> loader -> host-to-device feed -> jitted train step ->
checkpoint -> resume through the entry points a user calls, at the full
size of BERT-base (d=768, 12 layers, 12 heads of 64, V=30,528; random
seeded weights), and exits non-zero on the first thing that is wrong:

  1. name the device (versions, backend, device_kind, count) and refuse
     anything but a TPU;
  2. build the native tokenizer library from source on this machine;
  3. host stages through the CLIs: synthetic corpus -> masked + binned
     pair shards -> balance, and packed s=2048 shards -> balance;
  4. flash-attention kernels, compiled, against a dense float32
     reference on the chip: forward and gradients, plain (s=512, ragged
     s=200) and block-diagonal (s=2048, four documents + padding);
  5. pairs leg: ``pretrain_bert --model base --attention dense`` at
     s=128, b=64 over both length bins with an in-loop checkpoint, then
     the same command with ``--resume``;
  6. packed leg: ``pretrain_bert --attention flash --data-format packed
     --block-diagonal`` at s=2048, b=2, and the executable that ran must
     hold the Mosaic custom call;
  7. with four or more devices, the same path over the meshes the repo
     claims: the default ``data=N`` fold (legs 5-6), ``fsdp=2 x tensor=2``
     and ``tensor=2 x seq=2`` ring_flash at s=8192.

After every training leg the per-device bytes of params, optimizer state
and a batch are compared with what the mesh's sharding rules say.

One process holds the chip for the whole run; the host-stage children
never import jax. Compile seconds and wall time are printed per leg so a
cold and a warm compile cache can be told apart (observations, not
metrics). The last line of stdout is the JSON verdict.
"""

import contextlib
import gc
import hashlib
import io
import json
import math
import os
import re
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
VOCAB = os.path.join(REPO, 'benchmarks', 'assets', 'bench_vocab_30522.txt')

MODEL = 'base'
# ln(V) for a uniform MLM guess over the padded vocab + ln 2 for NSP: what
# a normal(0.02) init must give. bf16 on the chip has to land where the
# float32 CPU tests do.
INIT_LOSS = math.log(30528) + math.log(2)
INIT_LOSS_TOL = 0.3
# A packed batch is a handful of rows, all labelled "next sentence": the
# NSP term alone moves its first loss by +-0.2.
PACKED_INIT_LOSS_TOL = 0.5
# The loss must fall by more than step-to-step noise: adjacent steps
# differ by up to ~0.2 at this batch size, and the same leg falls ~1.5
# in 16 steps.
MIN_LOSS_DROP = 0.5
# Flash vs dense float32 on identical bf16 inputs: max |a - b| / max |b|.
# The kernel's outputs and gradients are rounded to bf16 (2^-8 relative)
# after float32 accumulation over up to 2048 keys.
FWD_TOL = 2e-2
GRAD_TOL = 3e-2
# Same weights, same batch, another mesh: only the bf16 reduction order
# changes.
MESH_LOSS_TOL = 1e-2

PAIRS = dict(seq=128, bin=64, batch=64, steps=16, ckpt_every=8,
             resume_steps=20)
PACKED = dict(seq=2048, batch=2, max_pred=400, steps=4)
RING = dict(seq=8192, batch=2, max_pred=1359, steps=3)


def check(ok, message):
  if not ok:
    raise SystemExit(f'chip_smoke: FAILED: {message}')


def banner(title):
  print(f'\n== {title}', flush=True)


# ---------------------------------------------------------------------------
# 1. the device


def require_tpu():
  from importlib import metadata

  import jax
  import jaxlib
  try:
    libtpu = metadata.version('libtpu')
  except metadata.PackageNotFoundError:
    libtpu = 'not installed'
  backend = jax.default_backend()
  devices = jax.devices()
  device = {'platform': devices[0].platform,
            'kind': devices[0].device_kind, 'count': len(devices)}
  print(f'jax {jax.__version__}, jaxlib {jaxlib.__version__}, '
        f'libtpu {libtpu}; backend={backend} '
        f'device_kind={device["kind"]!r} devices={device["count"]}')
  check(backend == 'tpu',
        f'no TPU: jax.default_backend() is {backend!r} '
        f'(JAX_PLATFORMS={os.environ.get("JAX_PLATFORMS")!r}); this smoke '
        'never falls back to another backend')
  return device


# ---------------------------------------------------------------------------
# 2-3. host stages


def build_native():
  from lddl_tpu.native.build import build_library
  from lddl_tpu.tokenization.wordpiece import load_bert_tokenizer
  t0 = time.perf_counter()
  path = build_library()
  tok = load_bert_tokenizer(vocab_file=VOCAB, backend='native')
  check(tok.native is not None, 'native tokenizer did not load')
  print(f'native library {os.path.basename(path)} built/loaded in '
        f'{time.perf_counter() - t0:.1f}s; native tokenizer: loaded')


def cli(*args):
  """One ``python -m lddl_tpu.cli`` child. These stages never import jax
  (the mask and tokenizer back-ends are pinned to host/native), so they
  cannot reach for the chip this process holds."""
  cmd = [sys.executable, '-m', 'lddl_tpu.cli', *map(str, args)]
  print('+ ' + ' '.join(cmd[1:]), flush=True)
  subprocess.run(cmd, check=True, cwd=REPO)


def _digest(directory):
  h = hashlib.sha256()
  for name in sorted(os.listdir(directory)):
    with open(os.path.join(directory, name), 'rb') as f:
      h.update(name.encode() + f.read())
  return h.hexdigest()[:16]


def prepare_data(work, ring):
  from lddl_tpu.core.synth import write_corpus
  src = os.path.join(work, 'source')
  mb = write_corpus(src, 3, num_shards=4, seed=1234)
  print(f'corpus: {mb:.1f} MB, seed 1234')
  # Every back-end that decides shard bytes is pinned, so the shards (and
  # with them every loss below) are the same on every machine.
  pinned = ('--vocab-file', VOCAB, '--tokenizer-backend', 'native',
            '--sentence-backend', 'rules', '--num-blocks', 8,
            '--sample-ratio', 1.0, '--seed', 42)
  cli('preprocess_bert_pretrain', '--source', src, '--sink',
      os.path.join(work, 'pairs'), '--masking', '--mask-backend', 'host',
      '--target-seq-length', PAIRS['seq'], '--bin-size', PAIRS['bin'],
      *pinned)
  cli('balance_shards', '--indir', os.path.join(work, 'pairs'),
      '--outdir', os.path.join(work, 'pairs_balanced'), '--num-shards', 4)
  packed = [('packed', PACKED['seq'])] + ([('ring', RING['seq'])]
                                         if ring else [])
  for name, seq in packed:
    cli('preprocess_packed_pretrain', '--source', src, '--sink',
        os.path.join(work, name), '--target-seq-length', seq, *pinned)
    cli('balance_shards', '--indir', os.path.join(work, name),
        '--outdir', os.path.join(work, f'{name}_balanced'),
        '--num-shards', 4)
  print('shard digests (same bytes on every machine): ' + ', '.join(
      f'{d} {_digest(os.path.join(work, d))}'
      for d in sorted(os.listdir(work)) if d.endswith('_balanced')))


# ---------------------------------------------------------------------------
# 4. the kernels, on the chip, against dense float32


def _dense_reference(q, k, v, mask, seg):
  import jax
  import jax.numpy as jnp
  q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
  scores = jnp.einsum('bhqd,bhkd->bhqk', q, k,
                      precision='highest') / math.sqrt(q.shape[-1])
  keep = (mask != 0)[:, None, None, :]
  if seg is not None:
    keep = keep & (seg[:, None, :, None] == seg[:, None, None, :])
  probs = jax.nn.softmax(jnp.where(keep, scores, -1e9), axis=-1)
  return jnp.einsum('bhqk,bhkd->bhqd', probs, v, precision='highest')


def flash_parity():
  """Forward and gradients of the Pallas kernels vs the dense reference.
  Query rows that are padding are excluded from the forward comparison
  (the kernel writes zeros there, dense writes a mean of V; nothing
  reads either) and get a zero cotangent, so every gradient is compared
  in full — including the all-padding tiles' ``-1e9``/``_L_FLOOR``
  handling, which must leave no NaN behind."""
  import jax
  import jax.numpy as jnp
  import numpy as np

  from lddl_tpu.ops.flash_attention import flash_attention

  def case(name, s, lengths, docs=None):
    b, h, d = len(lengths), 4, 64
    rng = np.random.default_rng(s)
    q, k, v = (jnp.asarray(rng.standard_normal((b, h, s, d)),
                           jnp.bfloat16) for _ in range(3))
    cols = np.arange(s)[None, :]
    mask = (cols < np.asarray(lengths)[:, None]).astype(np.int32)
    seg = None
    if docs is not None:
      seg = np.full((b, s), -1, np.int32)
      for row, bounds in enumerate(docs):
        for doc, (lo, hi) in enumerate(zip(bounds[:-1], bounds[1:])):
          seg[row, lo:hi] = doc
      seg = jnp.asarray(seg)
    mask = jnp.asarray(mask)
    real = (mask != 0)[:, None, :, None]
    cot = jnp.where(real, jnp.asarray(
        rng.standard_normal((b, h, s, d)), jnp.float32), 0.0)

    def flash(q, k, v):
      return flash_attention(q, k, v, mask, seg, seg)

    def dense(q, k, v):
      return _dense_reference(q, k, v, mask, seg)

    flash_jit = jax.jit(flash)
    check('tpu_custom_call' in flash_jit.lower(q, k, v).as_text(),
          f'{name}: the lowered flash call holds no Mosaic custom call')

    def err(a, ref):
      a, ref = np.asarray(a, np.float32), np.asarray(ref, np.float32)
      check(np.isfinite(a).all(), f'{name}: non-finite values from flash')
      return float(np.abs(a - ref).max() / np.abs(ref).max())

    out = flash_jit(q, k, v)
    check(np.isfinite(np.asarray(out, np.float32)).all(),
          f'{name}: non-finite forward output (padding rows included)')
    errs = {'fwd': err(jnp.where(real, out, 0.0),
                       jnp.where(real, jax.jit(dense)(q, k, v), 0.0))}
    grads = [
        jax.jit(jax.grad(
            lambda q, k, v, f=f: jnp.sum(f(q, k, v).astype(jnp.float32)
                                         * cot), argnums=(0, 1, 2)))(q, k, v)
        for f in (flash, dense)
    ]
    for which, got, ref in zip(('dq', 'dk', 'dv'), *grads):
      errs[which] = err(got, ref)
    print(f'flash parity {name}: ' +
          ' '.join(f'{key}={val:.2e}' for key, val in errs.items()))
    check(errs['fwd'] <= FWD_TOL, f'{name}: forward error {errs["fwd"]:.2e} '
          f'> {FWD_TOL}')
    worst = max(errs['dq'], errs['dk'], errs['dv'])
    check(worst <= GRAD_TOL,
          f'{name}: gradient error {worst:.2e} > {GRAD_TOL}')

  print(f'tolerances: forward {FWD_TOL}, gradients {GRAD_TOL} '
        '(max |flash - dense| / max |dense|, bf16 inputs, d=64)')
  case('plain s=512', 512, [512, 512])
  case('ragged s=200', 200, [200, 150])
  case('segmented s=2048', 2048, [1913, 2048],
       docs=[[0, 300, 1000, 1513, 1913], [0, 517, 1024, 1500, 2048]])


# ---------------------------------------------------------------------------
# 5-7. training legs through pretrain_bert


class _Tee(io.TextIOBase):

  def __init__(self, *sinks):
    self._sinks = sinks

  def write(self, text):
    for sink in self._sinks:
      sink.write(text)
    return len(text)

  def flush(self):
    for sink in self._sinks:
      sink.flush()


def pretrain_bert(*args):
  """``pretrain_bert`` in this process (it holds the chip). Returns the
  finished loop and the ``(step, loss, samples_seen)`` lines it logged."""
  from lddl_tpu.training import pretrain
  gc.collect()  # the previous leg's state must be off the chip by now
  argv = ['--vocab-file', VOCAB, '--model', MODEL, '--log-every', '1',
          *map(str, args)]
  print('+ pretrain_bert ' + ' '.join(argv), flush=True)
  log = io.StringIO()
  t0 = time.perf_counter()
  with contextlib.redirect_stdout(_Tee(sys.stdout, log)):
    loop = pretrain.main(argv)
  wall = time.perf_counter() - t0
  logged = [(int(step), float(loss), int(seen)) for step, loss, seen in
            re.findall(r'^step=(\d+) loss=(\S+) samples_seen=(\d+)',
                       log.getvalue(), re.M)]
  check(logged, 'pretrain_bert logged no step')
  losses = [loss for _, loss, _ in logged]
  check(all(math.isfinite(x) for x in losses), f'non-finite loss: {losses}')
  cache = loop.step_fn
  numerator = 'XLA cost_analysis' if cache.last_costs else 'analytic formula'
  print(f'leg: wall {wall:.1f}s, compile {cache.retrace_seconds:.1f}s over '
        f'{cache.misses} shape(s), {cache.hits} cache hit(s); first loss '
        f'{losses[0]:.4f}, last {losses[-1]:.4f}; MFU numerator: '
        f'{numerator}', flush=True)
  return loop, logged


def _device_bytes(tree):
  """{device id: bytes resident} over every array leaf of ``tree``."""
  import jax
  held = {}
  for leaf in jax.tree_util.tree_leaves(tree):
    for shard in leaf.addressable_shards:
      held[shard.device.id] = (held.get(shard.device.id, 0)
                               + shard.data.nbytes)
  return held


def check_spread(loop):
  """Params, optimizer state and a batch sit on the devices as the mesh
  says — after the steps ran, so an output layout the compiler chose
  differently (state piled on device 0, a replicated batch) would show.
  The state follows the model's sharding rules; a batch is split over
  (data, fsdp) and, where it has a sequence axis, over seq."""
  import jax
  import numpy as np

  from lddl_tpu.loader.device import make_global_batch
  from lddl_tpu.parallel.train import state_shardings
  mesh = loop.mesh
  leaves = jax.tree_util.tree_leaves

  def by_rule(tree, shardings):
    return sum(int(np.prod(sh.shard_shape(x.shape))) * x.dtype.itemsize
               for x, sh in zip(leaves(tree), leaves(shardings)))

  p_rule, o_rule = state_shardings(mesh, loop.params, loop.opt_state)
  batch = make_global_batch(next(iter(loop.loader)), mesh)
  dp = mesh.shape['data'] * mesh.shape['fsdp']
  batch_bytes = sum(
      x.nbytes // (dp * (mesh.shape['seq'] if x.ndim > 1 else 1))
      for x in leaves(batch))
  for name, tree, expect in (
      ('params', loop.params, by_rule(loop.params, p_rule)),
      ('opt_state', loop.opt_state, by_rule(loop.opt_state, o_rule)),
      ('batch', batch, batch_bytes)):
    held = _device_bytes(tree)
    print(f'{name}: {min(held.values()) / 1e6:.2f}-'
          f'{max(held.values()) / 1e6:.2f} MB on each of {len(held)} '
          f'device(s); the mesh says {expect / 1e6:.2f} MB')
    check(len(held) == mesh.devices.size
          and all(b == expect for b in held.values()),
          f'{name} is not spread as the mesh says ({expect} bytes on each '
          f'of {mesh.devices.size} devices): {held}')
  total = sum(x.nbytes for x in leaves(loop.params))
  print(f'params total {total / 1e6:.1f} MB; one device holds '
        f'{by_rule(loop.params, p_rule) / total:.2f} of it')


def pairs_leg(work, mesh_args=()):
  from lddl_tpu.training.pretrain import TrainLoop
  p = PAIRS
  ckpt = tempfile.mkdtemp(prefix='ckpt_', dir=work)
  common = ('--path', os.path.join(work, 'pairs_balanced'), '--attention',
            'dense', '--max-seq-length', p['seq'], '--bin-size', p['bin'],
            '--batch-size', p['batch'], '--masking', 'static',
            '--learning-rate', '2e-4', '--warmup-steps', 2,
            '--checkpoint-dir', ckpt, *mesh_args)
  loop, logged = pretrain_bert(*common, '--steps', p['steps'],
                               '--checkpoint-every', p['ckpt_every'])
  first, last = logged[0][1], logged[-1][1]
  check(abs(first - INIT_LOSS) <= INIT_LOSS_TOL,
        f'first loss {first:.4f} is not within {INIT_LOSS_TOL} of '
        f'ln 30528 + ln 2 = {INIT_LOSS:.3f}')
  check(last < first - MIN_LOSS_DROP,
        f'loss did not fall: first {first:.4f}, last {last:.4f}')
  bins = -(-p['seq'] // p['bin'])
  cache = loop.step_fn
  check(cache.misses == bins and cache.hits == p['steps'] - bins,
        f'step cache: {cache.misses} misses / {cache.hits} hits over '
        f'{p["steps"]} steps and {bins} bin shapes')
  saved = os.listdir(ckpt)
  check(str(p['ckpt_every']) in saved and str(p['steps']) in saved,
        f'checkpoints on disk: {saved}')
  check_spread(loop)
  step0, seen0 = TrainLoop.latest_meta(ckpt)
  check((step0, seen0) == (p['steps'], p['steps'] * p['batch']),
        f'saved meta {(step0, seen0)}')
  del loop

  banner('pairs leg, resumed')
  loop, logged = pretrain_bert(*common, '--resume', '--steps',
                               p['resume_steps'])
  check(logged[0][0] == step0 + 1 and logged[0][2] == seen0 + p['batch'],
        f'resume did not continue at step {step0} / samples_seen {seen0}: '
        f'first logged line is {logged[0]}')
  check((loop.step, loop.samples_seen) ==
        (p['resume_steps'], p['resume_steps'] * p['batch']),
        f'resumed run ended at {(loop.step, loop.samples_seen)}')
  check(logged[-1][1] < first, 'resumed loss is back above the first loss')
  shutil.rmtree(ckpt)  # three BERT-base checkpoints, ~4 GB
  return first


def flash_leg(work, data, cfg, attention, mesh_args=()):
  loop, logged = pretrain_bert(
      '--path', os.path.join(work, f'{data}_balanced'), '--attention',
      attention, '--data-format', 'packed', '--block-diagonal',
      '--max-seq-length', cfg['seq'], '--batch-size', cfg['batch'],
      '--max-predictions', cfg['max_pred'], '--steps', cfg['steps'],
      '--learning-rate', '2e-4', '--warmup-steps', 2, *mesh_args)
  first = logged[0][1]
  check(abs(first - INIT_LOSS) <= PACKED_INIT_LOSS_TOL,
        f'first loss {first:.4f} is not within {PACKED_INIT_LOSS_TOL} of '
        f'{INIT_LOSS:.3f}')
  require_compiled_kernel(loop)
  check_spread(loop)
  return first


def require_compiled_kernel(loop):
  """The executable that just ran must hold the Mosaic custom call: an
  interpreted Pallas kernel lowers to plain HLO and cannot pass."""
  for key, executable in loop.step_fn._compiled.items():
    check('tpu_custom_call' in executable.as_text(),
          f'the compiled step for {key} holds no Mosaic custom call')
  print('compiled step holds the Mosaic custom call (tpu_custom_call)')


def main():
  t_start = time.perf_counter()
  banner('device')
  device = require_tpu()
  from lddl_tpu.core.compile_cache import use_compile_cache
  print(f'compile cache: {use_compile_cache()} (JAX_COMPILATION_CACHE_DIR '
        f'{"set" if os.environ.get("JAX_COMPILATION_CACHE_DIR") else "unset"})')
  meshes = device['count'] >= 4

  with tempfile.TemporaryDirectory(prefix='lddl_chip_smoke_') as work:
    banner('host stages')
    build_native()
    prepare_data(work, ring=meshes)

    banner('flash kernels vs dense float32, on the chip')
    flash_parity()

    banner(f'pairs leg: dense s={PAIRS["seq"]} b={PAIRS["batch"]}')
    firsts = {'pairs': pairs_leg(work)}

    # A batch must divide over the mesh's (data, fsdp) devices.
    packed = dict(PACKED, batch=max(PACKED['batch'], device['count']))
    banner(f'packed leg: flash block-diagonal s={packed["seq"]} '
           f'b={packed["batch"]}')
    firsts['packed'] = flash_leg(work, 'packed', packed, 'flash')

    if meshes:
      # Legs 5-6 above ran on the default data=N fold; every other mesh
      # must give the same first-step loss on the same batch.
      mesh = ('--fsdp', 2, '--tp', 2)
      ring = dict(RING, batch=device['count'] // 2)
      banner('mesh leg: fsdp=2 x tensor=2, pairs')
      firsts['pairs fsdp2xtp2'] = pairs_leg(work, mesh)
      banner('mesh leg: fsdp=2 x tensor=2, packed flash')
      firsts['packed fsdp2xtp2'] = flash_leg(work, 'packed', packed, 'flash',
                                             mesh)
      banner(f'mesh leg: tensor=2 x seq=2 ring_flash s={ring["seq"]} '
             f'b={ring["batch"]}')
      firsts['ring tp2xsp2'] = flash_leg(work, 'ring', ring, 'ring_flash',
                                         ('--tp', 2, '--sp', 2))
      banner(f'mesh leg: fsdp=2 x tensor=2 flash s={ring["seq"]} (the ring '
             "leg's reference)")
      firsts['ring-data fsdp2xtp2'] = flash_leg(work, 'ring', ring, 'flash',
                                                mesh)
      for a, b in (('pairs', 'pairs fsdp2xtp2'),
                   ('packed', 'packed fsdp2xtp2'),
                   ('ring-data fsdp2xtp2', 'ring tp2xsp2')):
        check(abs(firsts[a] - firsts[b]) <= MESH_LOSS_TOL,
              f'first-step loss differs between meshes: {a} {firsts[a]:.4f}'
              f' vs {b} {firsts[b]:.4f}')

  banner('passed')
  print('first-step losses: ' +
        ', '.join(f'{k} {v:.4f}' for k, v in firsts.items()))
  print(f'total wall {time.perf_counter() - t_start:.0f}s')
  print(json.dumps({'ok': True, 'device': device}))


if __name__ == '__main__':
  main()
