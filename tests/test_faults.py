"""Kill-a-worker fault injection: a hard-killed (SIGKILL) loader worker
or comm rank must surface a named-rank error on the survivors within
seconds — the difference between a 2-minute diagnosis and a silent
multi-hour stall (SURVEY §5 failure detection; the reference gets the
same property from Dask's worker heartbeats)."""

import multiprocessing
import os
import signal
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lddl_tpu.comm import FileBackend


class TestLoaderWorkerDeath:

  def test_sigkill_worker_raises_named_error(self, tmp_path):
    """SIGKILL one of two collate workers mid-epoch; the parent iterator
    must raise naming the dead worker, not hang."""
    import __graft_entry__ as g
    from lddl_tpu.loader import get_bert_pretrain_data_loader

    bal, vocab_file, _ = g.build_tiny_dataset(str(tmp_path), num_shards=4)
    before = {p.pid for p in multiprocessing.active_children()}
    loader = get_bert_pretrain_data_loader(
        bal, batch_size_per_rank=2, bin_size=8, max_seq_length=32,
        vocab_file=vocab_file, masking='static', num_workers=2, base_seed=5)
    it = iter(loader)
    next(it)
    next(it)
    workers = [p for p in multiprocessing.active_children()
               if p.pid not in before]
    assert len(workers) == 2, 'expected exactly the two collate workers'
    os.kill(workers[0].pid, signal.SIGKILL)
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match=r'loader worker \d died'):
      # keep consuming: the parent drains any already-queued batches from
      # the dead worker, then must fail fast on its empty queue
      for _ in it:
        pass
    assert time.monotonic() - t0 < 30.0, 'detection took longer than the fail-fast bound'
    # The parent owns every shm slot-ring segment name and unlinks in its
    # iterator cleanup, so even a SIGKILLed worker cannot leak one.
    from conftest import own_shm_segments
    assert own_shm_segments() == [], 'SIGKILLed worker leaked shm segments'

  def test_abandoned_consumer_leaks_no_shm_segments(self, tmp_path):
    """A consumer that walks away mid-epoch (generator close, no epoch
    drain) must still leave /dev/shm clean."""
    import __graft_entry__ as g
    from lddl_tpu.loader import get_bert_pretrain_data_loader
    from conftest import own_shm_segments

    bal, vocab_file, _ = g.build_tiny_dataset(str(tmp_path), num_shards=4)
    loader = get_bert_pretrain_data_loader(
        bal, batch_size_per_rank=2, bin_size=8, max_seq_length=32,
        vocab_file=vocab_file, masking='static', num_workers=2, base_seed=5,
        transport='shm')
    it = iter(loader)
    next(it)
    assert own_shm_segments(), \
        'shm transport should have live slot rings mid-epoch'
    it.close()
    assert own_shm_segments() == [], 'abandoned consumer leaked shm segments'


def _fb_rank(rendezvous, rank, world, die_at, q):
  """One FileBackend rank looping collectives; rank `world-1` SIGKILLs
  itself before entering collective #die_at."""
  try:
    be = FileBackend(rendezvous, rank, world, timeout=60.0, run_id='fault')
    for i in range(die_at + 10):
      if rank == world - 1 and i == die_at:
        os.kill(os.getpid(), signal.SIGKILL)  # no cleanup, no sentinel
      be.allgather_object(('payload', rank, i))
    q.put((rank, 'completed', None))
  except BaseException as e:  # noqa: BLE001 - report everything
    q.put((rank, 'error', f'{type(e).__name__}: {e}'))


class TestCommRankDeath:

  def test_sigkill_rank_fails_fast_on_survivors(self, tmp_path):
    """SIGKILL one FileBackend rank mid-run: both survivors must raise a
    RuntimeError naming the dead rank well before the 60s collective
    timeout (same-host liveness beacon, comm/backend.py)."""
    world, die_at = 3, 3
    ctx = multiprocessing.get_context('spawn')
    q = ctx.Queue()
    procs = [
        ctx.Process(target=_fb_rank,
                    args=(str(tmp_path), r, world, die_at, q), daemon=True)
        for r in range(world)
    ]
    t0 = time.monotonic()
    for p in procs:
      p.start()
    results = {}
    while len(results) < world - 1 and time.monotonic() - t0 < 55.0:
      try:
        rank, kind, detail = q.get(timeout=1.0)
        results[rank] = (kind, detail)
      except Exception:
        pass
    elapsed = time.monotonic() - t0
    for p in procs:
      p.terminate()
      p.join(timeout=30)
    assert set(results) == {0, 1}, f'survivors did not report: {results}'
    for rank, (kind, detail) in results.items():
      assert kind == 'error', f'rank {rank} should have failed: {kind}'
      assert f'rank {world - 1}' in detail and 'died' in detail, detail
      assert 'RuntimeError' in detail, detail
    assert elapsed < 30.0, (
        f'survivors took {elapsed:.0f}s — the liveness fast-path should '
        'beat the 60s timeout by a wide margin')


def _publish_and_exit(rendezvous, rank, world):
  """Write the liveness beacon + this rank's collective-#0 payload, then
  exit — the 'last rank of a finishing job' shape."""
  import pickle
  be = FileBackend(rendezvous, rank, world, timeout=60.0, run_id='race')
  be._write_atomic(pickle.dumps(f'r{rank}'), be._path(0, rank))


class TestPeerDeathPublishRace:

  def test_dead_peer_with_published_payload_does_not_raise(self, tmp_path):
    """A peer whose last act was publishing its payload for collective
    #N and exiting cleanly must not trip the survivors' fail-fast path:
    the payload re-check in _check_peer_alive (comm/backend.py) closes
    the stat-poll/liveness-probe race. A collective the peer never
    published still fails fast."""
    world = 2
    ctx = multiprocessing.get_context('spawn')
    p = ctx.Process(target=_publish_and_exit,
                    args=(str(tmp_path), 1, world))
    p.start()
    p.join(timeout=60)
    assert p.exitcode == 0
    be = FileBackend(str(tmp_path), 0, world, timeout=10.0, run_id='race')
    # rank 1 is positively dead, but its op0 payload exists: no raise.
    be._check_peer_alive(1, 0)
    # ...while a collective it never entered still names the dead rank.
    with pytest.raises(RuntimeError, match=r'rank 1 .* died'):
      be._check_peer_alive(1, 1)
    # and rank 0's side of collective #0 completes normally.
    assert be.allgather_object('r0') == ['r0', 'r1']


# ---------------------------------------------------------------------------
# elastic executor: dead-rank re-execution, restart resume, lease revocation


def _write_shard(out_dir, sec, seed, gi):
  """Deterministic shard writer: output is a pure function of
  (task, global_index), the contract the elastic byte-identity
  guarantee rides on."""
  import pyarrow as pa

  from lddl_tpu.pipeline.parquet_io import write_shard_file
  time.sleep(sec)
  table = pa.table(
      {'v': pa.array([seed * 1000 + gi * 10 + k for k in range(20)])})
  write_shard_file(table, os.path.join(out_dir, f'part.{gi}.parquet'))
  return ('ok', gi, seed)


def _reference_shards(out_dir, tasks):
  """Fault-free single-process reference run (static stride)."""
  import functools

  from lddl_tpu.pipeline.executor import Executor
  os.makedirs(out_dir, exist_ok=True)
  with Executor(num_local_workers=1) as ex:  # NullBackend: static path
    return ex.map(functools.partial(_write_shard, out_dir, 0.0), tasks,
                  label='ref')


def _elastic_rank(rendezvous, rank, world, out_dir, tasks, env, q):
  """One elastic rank: barrier (so both ranks are claiming before any
  fault fires), then a lease-claimed map writing one shard per task."""
  import functools
  os.environ.update(env)
  try:
    from lddl_tpu.pipeline.executor import Executor
    be = FileBackend(rendezvous, rank, world, timeout=60.0, run_id='el')
    be.barrier()
    with Executor(comm=be, num_local_workers=1) as ex:
      out = ex.map(functools.partial(_write_shard, out_dir, 0.2), tasks,
                   label='shards')
    q.put((rank, 'completed', out))
  except BaseException as e:  # noqa: BLE001 - report everything
    q.put((rank, 'error', f'{type(e).__name__}: {e}'))


class TestElasticRankDeath:

  def test_sigkill_rank_survivor_completes_byte_identical(self, tmp_path):
    """SIGKILL rank 1 at the start of its first claimed partition: the
    survivor must revoke the orphaned lease via the positive death
    probe (the 60s staleness timeout would blow the deadline), finish
    ALL partitions, and produce shards byte-identical to a fault-free
    static-stride run."""
    from lddl_tpu.testing import hash_parquets
    tasks = list(range(8))
    out_dir = str(tmp_path / 'out')
    ref_dir = str(tmp_path / 'ref')
    os.makedirs(out_dir)
    expected = _reference_shards(ref_dir, tasks)
    env = {
        'LDDL_LEASE_TIMEOUT': '60',  # force the death-probe path
        'LDDL_COMM_HEARTBEAT': '0.2',
    }
    ctx = multiprocessing.get_context('spawn')
    q = ctx.Queue()
    procs = []
    for r in range(2):
      renv = dict(env)
      renv['LDDL_FAULTS'] = ('kill:elastic.task:rank=1,nth=1'
                             if r == 1 else '')
      procs.append(ctx.Process(
          target=_elastic_rank,
          args=(str(tmp_path / 'rdv'), r, 2, out_dir, tasks, renv, q),
          daemon=True))
    t0 = time.monotonic()
    for p in procs:
      p.start()
    rank, kind, out = q.get(timeout=120)
    elapsed = time.monotonic() - t0
    for p in procs:
      p.join(timeout=30)
    assert rank == 0 and kind == 'completed', (rank, kind, out)
    assert out == expected  # gather saw every partition, task-ordered
    assert procs[1].exitcode == -signal.SIGKILL
    assert hash_parquets(out_dir) == hash_parquets(ref_dir), \
        'surviving-rank shards diverged from the fault-free run'
    assert elapsed < 60.0, (
        f'survivor took {elapsed:.0f}s — dead-rank re-execution must ride '
        'the death probe, not the lease timeout')


def _resume_rank(rendezvous, out_dir, tasks, env, q):
  """World-1 elastic run for the kill-then-restart resume test."""
  import functools
  os.environ.update(env)
  try:
    from lddl_tpu.pipeline.executor import Executor
    be = FileBackend(rendezvous, 0, 1, timeout=60.0, run_id='resume')
    with Executor(comm=be, num_local_workers=1) as ex:
      out = ex.map(functools.partial(_write_shard, out_dir, 0.0), tasks,
                   label='shards')
    q.put(('completed', out))
  except BaseException as e:  # noqa: BLE001 - report everything
    q.put(('error', f'{type(e).__name__}: {e}'))


class TestElasticRestartResume:

  def test_killed_run_resumes_skipping_manifested_partitions(self,
                                                             tmp_path):
    """Kill a world-1 elastic preprocess on its third partition, restart
    it with the same run id: already-manifested partitions must be
    skipped (shard files untouched — same inode and mtime), the killed
    partition re-executed, and the final output byte-identical to a
    fault-free run."""
    from lddl_tpu.testing import hash_parquets
    tasks = list(range(6))
    out_dir = str(tmp_path / 'out')
    ref_dir = str(tmp_path / 'ref')
    rdv = str(tmp_path / 'rdv')
    os.makedirs(out_dir)
    expected = _reference_shards(ref_dir, tasks)
    env = {
        # 'once': the marker in LDDL_FAULTS_DIR survives the restart, so
        # the SAME spec is armed in both incarnations but fires in one.
        'LDDL_FAULTS': 'kill:elastic.task:nth=3,once',
        'LDDL_FAULTS_DIR': str(tmp_path / 'faults'),
        'LDDL_WRITE_BACK': '0',  # synchronous shards+manifests: the
        # manifested set at death is exactly the finished partitions
        'LDDL_COMM_HEARTBEAT': '0.2',
    }
    os.makedirs(env['LDDL_FAULTS_DIR'])
    ctx = multiprocessing.get_context('spawn')
    q = ctx.Queue()
    p1 = ctx.Process(target=_resume_rank,
                     args=(rdv, out_dir, tasks, env, q), daemon=True)
    p1.start()
    p1.join(timeout=120)
    assert p1.exitcode == -signal.SIGKILL, \
        'first incarnation should have been killed by the injected fault'
    survivors = {
        name: (st.st_ino, st.st_mtime_ns)
        for name in os.listdir(out_dir)
        for st in [os.stat(os.path.join(out_dir, name))]
        if name.endswith('.parquet')
    }
    assert len(survivors) == 2, (
        f'two partitions should have completed before the kill: '
        f'{sorted(survivors)}')
    p2 = ctx.Process(target=_resume_rank,
                     args=(rdv, out_dir, tasks, env, q), daemon=True)
    p2.start()
    kind, out = q.get(timeout=120)
    p2.join(timeout=30)
    assert kind == 'completed', out
    assert out == expected
    assert hash_parquets(out_dir) == hash_parquets(ref_dir), \
        'resumed shards diverged from the fault-free run'
    for name, (ino, mtime) in survivors.items():
      st = os.stat(os.path.join(out_dir, name))
      assert (st.st_ino, st.st_mtime_ns) == (ino, mtime), (
          f'{name} was manifested before the kill but rewritten by the '
          'resume — manifest skipping is not working')

  def test_killed_restart_ledger_audits_against_reference(self, tmp_path):
    """The determinism-ledger drill on the kill/restart path: both
    incarnations of the faulted run append shard fingerprints to ONE
    rank ledger (crash-durable O_APPEND), a fault-free reference run
    writes its own, and ``lddl-audit verify`` proves the recovered
    output byte-identical — then a tampered digest makes it fail with
    the damaged shard's coordinate."""
    from lddl_tpu.telemetry import audit
    tasks = list(range(6))
    out_dir, ref_out = str(tmp_path / 'out'), str(tmp_path / 'refout')
    led_dir, ref_led = str(tmp_path / 'led'), str(tmp_path / 'refled')
    for d in (out_dir, ref_out):
      os.makedirs(d)
    base = {'LDDL_WRITE_BACK': '0', 'LDDL_COMM_HEARTBEAT': '0.2',
            'LDDL_LEDGER': '1'}
    ctx = multiprocessing.get_context('spawn')
    q = ctx.Queue()
    ref = ctx.Process(
        target=_resume_rank,
        args=(str(tmp_path / 'rdv_ref'), ref_out, tasks,
              dict(base, LDDL_TELEMETRY_DIR=ref_led), q), daemon=True)
    ref.start()
    kind, out = q.get(timeout=120)
    ref.join(timeout=30)
    assert kind == 'completed', out

    env = dict(base, LDDL_TELEMETRY_DIR=led_dir,
               LDDL_FAULTS='kill:elastic.task:nth=3,once',
               LDDL_FAULTS_DIR=str(tmp_path / 'faults'))
    os.makedirs(env['LDDL_FAULTS_DIR'])
    rdv = str(tmp_path / 'rdv')
    p1 = ctx.Process(target=_resume_rank,
                     args=(rdv, out_dir, tasks, env, q), daemon=True)
    p1.start()
    p1.join(timeout=120)
    assert p1.exitcode == -signal.SIGKILL
    p2 = ctx.Process(target=_resume_rank,
                     args=(rdv, out_dir, tasks, env, q), daemon=True)
    p2.start()
    kind, out = q.get(timeout=120)
    p2.join(timeout=30)
    assert kind == 'completed', out

    # Recovery verified: the kill lost no shard records (the restart
    # re-executed the killed partition), every common coordinate agrees.
    assert audit.main(['verify', led_dir, ref_led]) == 0
    run = audit.load_run(led_dir)
    shard_table = audit.index_records(run[0])[0]['shard']
    assert len(shard_table) == len(tasks)

    # The auditor catches real corruption: tamper one recorded shard
    # digest and verify must fail naming that shard.
    led_path = os.path.join(led_dir, 'ledger.rank0.jsonl')
    tampered_dir = str(tmp_path / 'tampered')
    os.makedirs(tampered_dir)
    import json as _json
    with open(led_path) as f, \
        open(os.path.join(tampered_dir, 'ledger.rank0.jsonl'), 'w') as g:
      damaged = False
      for line in f:
        rec = _json.loads(line)
        if not damaged and rec.get('boundary') == 'shard' and \
            rec.get('path') == 'part.4.parquet':
          rec['digest'] = rec['digest'][::-1]
          damaged = True
          line = _json.dumps(rec) + '\n'
        g.write(line)
    assert damaged
    assert audit.main(['verify', tampered_dir, ref_led]) == 1
    result = audit.audit_verify(audit.load_run(tampered_dir),
                                audit.load_run(ref_led))
    assert result['first']['boundary'] == 'shard'
    assert result['first']['key'] == {'path': 'part.4.parquet'}


class TestLeaseRevokeDeterminism:

  def test_all_survivors_reach_same_revoke_decision(self, tmp_path):
    """Two survivors observing the same orphaned claim (owner never
    heartbeats, beacon absent) must both decide to revoke after the
    lease timeout, agree on the generation, and race the re-claim down
    to exactly one winner via CAS."""
    from lddl_tpu.pipeline.executor import _LeaseClaimer
    be0 = FileBackend(str(tmp_path), 0, 3, timeout=60.0, run_id='rv')
    be1 = FileBackend(str(tmp_path), 1, 3, timeout=60.0, run_id='rv')
    s0 = be0.lease_store('ph.0')
    s1 = be1.lease_store('ph.0')
    # Orphaned claim: partition 5 owned by rank 2, which never started
    # (no beacon, no heartbeat) — only the staleness path can free it.
    s0.publish('claim.5.g0', b'2')
    c0 = _LeaseClaimer(s0, [5], timeout=0.5)
    c1 = _LeaseClaimer(s1, [5], timeout=0.5)
    assert c0.next_claim() is None and c1.next_claim() is None
    # First sweep only *records* the silent heartbeat: a survivor that
    # just arrived must not revoke on zero observation time.
    assert c0.observe() is False and c1.observe() is False
    time.sleep(0.7)
    assert c0.observe() is True and c1.observe() is True
    assert c0._gen[5] == c1._gen[5] == 1, \
        'survivors diverged on the claim generation'
    revokes = [k for k in s0.list('revoke.') if k.startswith('revoke.5.')]
    assert revokes == ['revoke.5.g0'], \
        'the revoke CAS must leave exactly one revocation record'
    wins = [c for c in (c0, c1) if c.next_claim() == 5]
    assert len(wins) == 1, 're-claim after revocation must have one winner'


# ---------------------------------------------------------------------------
# elastic training: dead-rank detection, emergency checkpoint, resharded resume


def _train_rank(rdv, rank, world, bal, vocab_file, ckpt_dir, env, q):
  """One elastic train rank in its own 2-device CPU jax world, sharing a
  FileBackend membership store; the injected fault SIGKILLs rank 1
  mid-training and rank 0 must detect the death via the pid probe,
  land a final checkpoint, and stop with a dead_rank verdict."""
  os.environ.update(env)
  try:
    import jax.numpy as jnp

    from lddl_tpu.models import BertConfig
    from lddl_tpu.parallel import make_mesh
    from lddl_tpu.tokenization.wordpiece import load_bert_tokenizer
    from lddl_tpu.training.elastic import RankMembership
    from lddl_tpu.training.pretrain import TrainLoop

    be = FileBackend(rdv, rank, world, timeout=60.0, run_id='train')
    tok = load_bert_tokenizer(vocab_file=vocab_file, backend='hf')
    cfg = BertConfig(
        vocab_size=((tok.vocab_size + 63) // 64) * 64, hidden_size=32,
        num_layers=2, num_heads=2, intermediate_size=64,
        max_position_embeddings=64, dropout_rate=0.0, dtype=jnp.float32)
    loop = TrainLoop.build(
        bal, tok, model_cfg=cfg, mesh=make_mesh(), learning_rate=1e-3,
        warmup_steps=2, total_steps=100, batch_size_per_rank=4,
        bin_size=8, max_seq_length=32, seed=5, dp_rank=rank,
        dp_world=world, loader_kwargs={'shuffle_buffer_size': 16})
    membership = RankMembership(
        be.lease_store('train.membership'), rank, world).start()
    be.barrier()  # both ranks are members before any fault can fire
    try:
      # max_steps is unreachable: only a membership event can end rank
      # 0's run (a hang here fails the parent's queue timeout).
      losses = loop.run(100, ckpt_dir=(ckpt_dir if rank == 0 else None),
                        ckpt_every=2, log_every=0, membership=membership)
    finally:
      membership.stop()
    q.put((rank, 'completed',
           {'stop_reason': loop.stop_reason, 'step': loop.step,
            'samples_seen': loop.samples_seen, 'steps_run': len(losses)}))
  except BaseException as e:  # noqa: BLE001 - report everything
    q.put((rank, 'error', f'{type(e).__name__}: {e}'))


class TestTrainRankDeath:

  def test_sigkill_train_rank_fleet_checkpoints_and_resumes(self, tmp_path):
    """SIGKILL one of two train ranks mid-run: the survivor detects the
    dead rank through the lease membership (positive death probe — the
    60s staleness timeout would blow the deadline), checkpoints, and
    stops with a dead_rank stop_reason; the parent then resumes the
    checkpoint at world size 1 (different mesh, preserved global batch)
    and two independent restores agree on parameters AND the forward
    bin-draw sequence."""
    import itertools

    import jax
    import numpy as np

    import __graft_entry__ as g
    from lddl_tpu.tokenization.wordpiece import load_bert_tokenizer
    from lddl_tpu.training.pretrain import TrainLoop

    bal, vocab_file, _ = g.build_tiny_dataset(str(tmp_path), num_shards=4)
    ckpt_dir = str(tmp_path / 'ckpt')
    rdv = str(tmp_path / 'rdv')
    base_env = {
        'JAX_PLATFORMS': 'cpu',
        'XLA_FLAGS': '--xla_force_host_platform_device_count=2',
        'LDDL_LEASE_TIMEOUT': '60',  # force the death-probe path
        'LDDL_COMM_HEARTBEAT': '0.2',
    }
    ctx = multiprocessing.get_context('spawn')
    q = ctx.Queue()
    procs = []
    for r in range(2):
      env = dict(base_env)
      if r == 0:
        env['LDDL_ASYNC_CKPT'] = '1'  # the background checkpoint lane
      else:
        env['LDDL_FAULTS'] = 'kill:train.step:rank=1,nth=6'
      procs.append(ctx.Process(
          target=_train_rank,
          args=(rdv, r, 2, bal, vocab_file, ckpt_dir, env, q),
          daemon=True))
    t0 = time.monotonic()
    for p in procs:
      p.start()
    rank, kind, info = q.get(timeout=300)
    elapsed = time.monotonic() - t0
    for p in procs:
      p.join(timeout=60)
    assert procs[1].exitcode == -signal.SIGKILL
    assert (rank, kind) == (0, 'completed'), (rank, kind, info)
    assert str(info['stop_reason']).startswith('dead_rank:'), info
    # The survivor made progress and stopped on the verdict, not a hang
    # (rank 0 steps slower than the doomed rank — it owns checkpointing
    # — so its step count at detection is small but nonzero).
    assert info['steps_run'] >= 1, info
    assert elapsed < 240.0, (
        f'survivor took {elapsed:.0f}s — detection must ride the death '
        'probe, not the lease timeout')
    # The emergency checkpoint is complete and current.
    meta = TrainLoop.latest_meta(ckpt_dir)
    assert meta == (info['step'], info['samples_seen'])

    # Resharding resume: restore at world size 1 on THIS process's
    # 8-device mesh, per-rank batch 8 keeping the global batch at
    # 4 x 2 = 8, so the data position replays identically.
    import jax.numpy as jnp

    from lddl_tpu.models import BertConfig
    from lddl_tpu.parallel import make_mesh
    tok = load_bert_tokenizer(vocab_file=vocab_file, backend='hf')
    cfg = BertConfig(
        vocab_size=((tok.vocab_size + 63) // 64) * 64, hidden_size=32,
        num_layers=2, num_heads=2, intermediate_size=64,
        max_position_embeddings=64, dropout_rate=0.0, dtype=jnp.float32)

    def resume():
      loop = TrainLoop.build(
          bal, tok, model_cfg=cfg, mesh=make_mesh(), learning_rate=1e-3,
          warmup_steps=2, total_steps=100, batch_size_per_rank=8,
          bin_size=8, max_seq_length=32, seed=5,
          samples_seen=meta[1], dp_rank=0, dp_world=1,
          loader_kwargs={'shuffle_buffer_size': 16})
      return loop.restore(ckpt_dir)

    a, b = resume(), resume()
    assert a.step == meta[0] and a.samples_seen == meta[1]
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x),
                                                   np.asarray(y)),
        a.params, b.params)
    seq_a = [bt['input_ids'].shape[1]
             for bt in itertools.islice(iter(a.loader), 4)]
    seq_b = [bt['input_ids'].shape[1]
             for bt in itertools.islice(iter(b.loader), 4)]
    assert seq_a == seq_b, 'resumed loader positions diverged'


class TestTrainMembershipPrimitives:

  def test_injected_heartbeat_fault_is_absorbed(self, tmp_path,
                                                monkeypatch):
    """A transient error inside the membership pump's republish attempt
    (injected at train.heartbeat) is absorbed: the next beat retries
    and the counter keeps advancing for observers."""
    from lddl_tpu.comm import HeartbeatPump
    from lddl_tpu.core import faults
    faults.reset()
    monkeypatch.setenv('LDDL_FAULTS', 'raise:train.heartbeat:nth=1')
    be = FileBackend(str(tmp_path), 0, 1, timeout=10.0, run_id='hb')
    store = be.lease_store('train.membership')
    pump = HeartbeatPump(store, 0.05, fault_site='train.heartbeat')
    try:
      t0 = time.monotonic()
      while store.read_heartbeat(0) < 2 and time.monotonic() - t0 < 10.0:
        time.sleep(0.05)
      assert store.read_heartbeat(0) >= 2, \
          'heartbeat counter stalled after the injected republish fault'
    finally:
      pump.stop()
      faults.reset()

  def test_shed_verdict_cas_unique(self, tmp_path):
    """Both ranks score the same published signals; the shed verdict is
    CAS-arbitrated, so exactly one record lands and every rank's poll()
    obeys the record (not its own local computation)."""
    from lddl_tpu.training.elastic import RankMembership
    be0 = FileBackend(str(tmp_path), 0, 2, timeout=10.0, run_id='shed')
    be1 = FileBackend(str(tmp_path), 1, 2, timeout=10.0, run_id='shed')
    m0 = RankMembership(be0.lease_store('train.membership'), 0, 2,
                        interval=0.1, timeout=30.0, shed_score=2.0).start()
    m1 = RankMembership(be1.lease_store('train.membership'), 1, 2,
                        interval=0.1, timeout=30.0, shed_score=2.0).start()
    try:
      m0.publish_signals({'steps_per_sec': 10.0})
      m1.publish_signals({'steps_per_sec': 1.0})  # 5.5x the median: shed
      assert m0.poll() == m1.poll() == 'shed:rank1'
      fresh = be0.lease_store('train.membership')
      assert fresh.list('shed.rank') == ['shed.rank1'], \
          'the shed CAS must leave exactly one verdict record'
    finally:
      m0.stop()
      m1.stop()


class TestCommRetryAndKnobs:

  def test_injected_write_error_is_retried(self, tmp_path, monkeypatch):
    """A transient OSError out of the atomic-write path (first attempt
    only) must be absorbed by the bounded retry, invisibly to the
    caller."""
    from lddl_tpu.core import faults
    from lddl_tpu.telemetry import disable, enable
    faults.reset()
    monkeypatch.setenv('LDDL_FAULTS', 'raise:comm.write:nth=1')
    tele = enable()
    retries = tele.counter('comm.io_retries')
    before = retries.total
    be = FileBackend(str(tmp_path), 0, 1, timeout=10.0, run_id='retry')
    assert be.allgather_object('payload') == ['payload']
    assert retries.total > before, \
        'the injected first-attempt failure should have counted a retry'
    faults.reset()
    disable()

  def test_timeout_and_heartbeat_env_knobs(self, tmp_path, monkeypatch):
    from lddl_tpu.comm import comm_heartbeat_interval, comm_timeout
    monkeypatch.setenv('LDDL_COMM_TIMEOUT', '7.5')
    monkeypatch.setenv('LDDL_COMM_HEARTBEAT', '0.25')
    assert comm_timeout() == 7.5
    assert comm_heartbeat_interval() == 0.25
    be = FileBackend(str(tmp_path), 0, 1, run_id='knobs')
    assert be._timeout == 7.5
    assert be._liveness_interval == 0.25
    monkeypatch.setenv('LDDL_COMM_HEARTBEAT', '0.0001')
    assert comm_heartbeat_interval() == 0.05  # clamped: probe floor
    monkeypatch.setenv('LDDL_COMM_TIMEOUT', 'junk')
    assert comm_timeout() == 120.0
