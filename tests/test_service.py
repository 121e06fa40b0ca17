"""Failure-matrix tests for the network data service (loader/service.py).

The acceptance contract, exercised end to end:

  - wire frames and the packed batch spec round-trip byte-identically;
  - a single network client drains the exact serial sequence, and
    ``MultiprocessLoader(transport='network')`` keeps the epoch/resume
    contract of the process transports;
  - kill-server-mid-epoch: the client degrades to the local loader at
    its deterministic position and delivers the identical sequence, and
    re-attaches when a server answers again;
  - kill-one-of-two-clients (SIGKILL via the ``client.pull`` fault
    site): the survivor revokes the dead client's serve leases and the
    *union* of delivered batches is byte-identical to a
    single-consumer run — no loss, no duplicates;
  - a slow consumer never grows the server's buffered window past
    ``window`` (bounded memory by construction);
  - clean stop leaves no threads, sockets, or announce files; a
    SIGKILLed server's stale announce is provably dead to discovery
    and folds into lddl-monitor's error list.
"""

import hashlib
import json
import multiprocessing
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from lddl_tpu.core import faults
from lddl_tpu.loader.service import (DataServer, NetworkBatchSource,
                                     ProtocolError, _recv_frame,
                                     _send_frame, discover_data_servers,
                                     pack_batch, resolve_endpoint,
                                     unpack_batch)
from lddl_tpu.testing import SyntheticBatchLoader

BS, SEQ = 4, 16


def _loader(steps):
  return SyntheticBatchLoader(batch_size=BS, seq_len=SEQ, steps=steps)


def _digest(batch):
  h = hashlib.sha256()
  for k in sorted(batch):
    h.update(k.encode())
    h.update(np.ascontiguousarray(batch[k]).tobytes())
  return h.hexdigest()


def _reference(steps):
  """{gi: digest} of the single-consumer serial run."""
  return {gi: _digest(b) for gi, b in _loader(steps).iter_steps((0, 1))}


# ---------------------------------------------------------------------------
# wire + spec round trips


def test_pack_roundtrip_byte_identical():
  _, batch = next(_loader(2).iter_steps((0, 1)))
  spec, payload = pack_batch(batch)
  out = unpack_batch(spec, payload)
  assert sorted(out) == sorted(batch)
  for k in batch:
    assert np.array_equal(out[k], batch[k])
    assert out[k].dtype == batch[k].dtype


def test_frame_roundtrip_over_socketpair():
  a, b = socket.socketpair()
  a.settimeout(5)
  b.settimeout(5)
  try:
    _send_frame(a, {'op': 'batch', 'gi': 3}, b'payload-bytes')
    header, body = _recv_frame(b)
    assert header == {'op': 'batch', 'gi': 3}
    assert bytes(body) == b'payload-bytes'
  finally:
    a.close()
    b.close()


def test_frame_bad_magic_is_protocol_error():
  a, b = socket.socketpair()
  a.settimeout(5)
  b.settimeout(5)
  try:
    a.sendall(b'HTTP/1.1 200 OK\r\n' + b'\x00' * 16)
    with pytest.raises(ProtocolError):
      _recv_frame(b)
  finally:
    a.close()
    b.close()


# ---------------------------------------------------------------------------
# healthy-path drains


def test_single_client_drains_exact_serial_sequence(monkeypatch):
  srv = DataServer(_loader(6), window=3, epochs=1).start()
  monkeypatch.setenv('LDDL_DATA_SERVER', srv.url)
  try:
    got = list(NetworkBatchSource(timeout=10, retries=1).iter_steps(0))
  finally:
    srv.stop()
  assert [gi for gi, _ in got] == list(range(6))
  assert {gi: _digest(b) for gi, b in got} == _reference(6)


def test_multiprocess_loader_network_transport(monkeypatch):
  from lddl_tpu.loader.workers import MultiprocessLoader
  srv = DataServer(_loader(6), window=4, epochs=2).start()
  monkeypatch.setenv('LDDL_DATA_SERVER', srv.url)
  kwargs = dict(batch_size=BS, seq_len=SEQ, steps=6)
  loader = MultiprocessLoader(
      kwargs, num_workers=2, transport='network',
      factory=('lddl_tpu.testing', 'get_synthetic_batch_loader'))
  try:
    e0 = [_digest(b) for b in loader]
    assert loader.epoch == 1  # same epoch bump as the process transports
    e1 = [_digest(b) for b in loader]
  finally:
    srv.stop()
  ref = _reference(6)
  assert e0 == [ref[gi] for gi in range(6)]
  assert len(e1) == 6  # epoch 1 re-served (same synthetic stream)


def test_network_transport_resumes_mid_epoch(monkeypatch):
  """The serial loader's ``_batches_consumed`` position steers the
  network drain exactly like it steers the process transports."""
  from lddl_tpu.loader.workers import MultiprocessLoader
  srv = DataServer(_loader(8), window=8, epochs=1).start()
  monkeypatch.setenv('LDDL_DATA_SERVER', srv.url)
  kwargs = dict(batch_size=BS, seq_len=SEQ, steps=8)
  loader = MultiprocessLoader(
      kwargs, num_workers=0, transport='network',
      factory=('lddl_tpu.testing', 'get_synthetic_batch_loader'))
  loader._serial._batches_consumed = 5  # checkpoint-restore shape
  try:
    got = [_digest(b) for b in loader]
  finally:
    srv.stop()
  ref = _reference(8)
  assert got == [ref[gi] for gi in range(5, 8)]


def test_retry_absorbs_transient_wire_fault(monkeypatch):
  """A raise-spec on ``wire.write`` breaks the first frame send; the
  bounded-backoff retry path reconnects and the drain still delivers
  the exact sequence."""
  srv = DataServer(_loader(4), window=4, epochs=1).start()
  monkeypatch.setenv('LDDL_DATA_SERVER', srv.url)
  monkeypatch.setenv('LDDL_FAULTS', 'raise:wire.write:nth=1')
  faults.reset()
  try:
    got = list(NetworkBatchSource(timeout=10, retries=2).iter_steps(0))
  finally:
    monkeypatch.delenv('LDDL_FAULTS')
    faults.reset()
    srv.stop()
  assert {gi: _digest(b) for gi, b in got} == _reference(4)


# ---------------------------------------------------------------------------
# server death: degraded-mode fallback + re-attach


def test_server_death_falls_back_to_local_mid_epoch(monkeypatch):
  from lddl_tpu.telemetry import enable, get_telemetry
  enable()
  srv = DataServer(_loader(8), window=8, epochs=1).start()
  monkeypatch.setenv('LDDL_DATA_SERVER', srv.url)
  src = NetworkBatchSource(
      build_kwargs=dict(batch_size=BS, seq_len=SEQ, steps=8),
      factory=('lddl_tpu.testing', 'get_synthetic_batch_loader'),
      timeout=2, retries=1)
  it = src.iter_steps(0)
  got = [next(it) for _ in range(3)]
  srv.stop()  # server dies mid-epoch
  got.extend(it)
  assert [gi for gi, _ in got] == list(range(8))
  assert {gi: _digest(b) for gi, b in got} == _reference(8)
  assert get_telemetry().counter('serve.fallbacks').total >= 1


def test_client_reattaches_when_server_returns(monkeypatch):
  from lddl_tpu.telemetry import enable, get_telemetry
  enable()
  monkeypatch.setenv('LDDL_DATA_REATTACH_EVERY', '2')
  srv1 = DataServer(_loader(12), window=12, epochs=1).start()
  monkeypatch.setenv('LDDL_DATA_SERVER', srv1.url)
  src = NetworkBatchSource(
      build_kwargs=dict(batch_size=BS, seq_len=SEQ, steps=12),
      factory=('lddl_tpu.testing', 'get_synthetic_batch_loader'),
      timeout=2, retries=0)
  it = src.iter_steps(0)
  got = [next(it) for _ in range(3)]
  srv1.stop()
  # Degraded: next pulls come from the local loader...
  got.append(next(it))
  # ...then a new server announces and the probe re-attaches to it.
  srv2 = DataServer(_loader(12), window=12, epochs=1).start()
  monkeypatch.setenv('LDDL_DATA_SERVER', srv2.url)
  try:
    got.extend(it)
  finally:
    srv2.stop()
  assert [gi for gi, _ in got] == list(range(12))
  assert {gi: _digest(b) for gi, b in got} == _reference(12)
  assert get_telemetry().counter('serve.reattaches').total >= 1


# ---------------------------------------------------------------------------
# determinism ledger on the service path: tx/rx fingerprints + audit


def _with_ledger(directory, fn):
  import lddl_tpu.telemetry.ledger as ledger_mod
  ledger_mod._active = None
  ledger_mod.enable_ledger(directory=str(directory), rank=0)
  try:
    return fn()
  finally:
    ledger_mod.disable_ledger()


def test_fallback_run_ledger_verifies_against_healthy_reference(
    monkeypatch, tmp_path):
  """The determinism-ledger drill on the degraded-fallback path: the
  server fingerprints every frame pre-send (serve.tx), the client
  re-fingerprints post-receive (serve.rx); a run that lost its server
  mid-epoch recorded only the frames actually served — a strict subset
  of the healthy reference, every common coordinate byte-identical —
  so ``lddl-audit verify`` exits 0 on the recovery."""
  from lddl_tpu.telemetry import audit

  def drain(dirname, stop_after=None):
    def go():
      srv = DataServer(_loader(8), window=8, epochs=1).start()
      monkeypatch.setenv('LDDL_DATA_SERVER', srv.url)
      src = NetworkBatchSource(
          build_kwargs=dict(batch_size=BS, seq_len=SEQ, steps=8),
          factory=('lddl_tpu.testing', 'get_synthetic_batch_loader'),
          timeout=2, retries=1)
      it = src.iter_steps(0)
      if stop_after is None:
        got = list(it)
        srv.stop()
        return got
      got = [next(it) for _ in range(stop_after)]
      srv.stop()  # server dies mid-epoch; the client degrades locally
      got.extend(it)
      return got
    return _with_ledger(tmp_path / dirname, go)

  ref = drain('ref')
  faulted = drain('run', stop_after=3)
  for got in (ref, faulted):
    assert [gi for gi, _ in got] == list(range(8))
    assert {gi: _digest(b) for gi, b in got} == _reference(8)

  assert audit.main(['verify', str(tmp_path / 'run'),
                     str(tmp_path / 'ref')]) == 0
  run = audit.load_run(str(tmp_path / 'run'))
  indexed = audit.index_records(run[0])[0]
  # post-fallback batches came from the local loader, not the wire:
  # the faulted run's serve.rx stream is a genuine subset
  ref_rx = audit.index_records(
      audit.load_run(str(tmp_path / 'ref'))[0])[0]['serve.rx']
  assert len(ref_rx) == 8
  assert 0 < len(indexed['serve.rx']) < 8
  assert not audit.wire_mismatches(run)


def test_injected_wire_corruption_caught_with_exact_frame(
    monkeypatch, tmp_path, capsys):
  """The silent-data-corruption drill: ``corrupt:ledger.corrupt`` flips
  one byte of the third packed frame AFTER the server hashed it — the
  client receives (and consumes) damaged bytes, and the audit names
  the exact frame from ONE run's ledger, no reference needed."""
  from lddl_tpu.telemetry import audit

  def go():
    monkeypatch.setenv('LDDL_FAULTS', 'corrupt:ledger.corrupt:nth=3')
    faults.reset()
    srv = DataServer(_loader(6), window=6, epochs=1).start()
    monkeypatch.setenv('LDDL_DATA_SERVER', srv.url)
    try:
      return list(NetworkBatchSource(timeout=10, retries=1).iter_steps(0))
    finally:
      srv.stop()
      monkeypatch.delenv('LDDL_FAULTS')
      faults.reset()
  got = _with_ledger(tmp_path / 'run', go)

  # the damage is real: the delivered batch differs from the reference
  ref = _reference(6)
  digs = {gi: _digest(b) for gi, b in got}
  assert digs[2] != ref[2]
  assert all(digs[gi] == ref[gi] for gi in (0, 1, 3, 4, 5))

  run_dir = str(tmp_path / 'run')
  mismatches = audit.wire_mismatches(audit.load_run(run_dir))
  assert [m['key'] for m in mismatches] == [{'epoch': 0, 'gi': 2}]
  assert audit.main(['diff', run_dir, run_dir]) == 1
  out = capsys.readouterr().out
  assert 'wire' in out and 'gi=2' in out
  capsys.readouterr()
  assert audit.main(['show', run_dir]) == 0
  assert 'wire mismatch' in capsys.readouterr().out


# ---------------------------------------------------------------------------
# two clients, one SIGKILLed: lease re-serve + union byte-identity


def _union_client(rank, rdv, run_id, url, out_path, faults_spec,
                  hold_until=None):
  """Spawned client: drain epoch 0, appending one JSONL record per
  delivered batch (flushed immediately, so a SIGKILLed client's
  delivered set survives it). ``hold_until=(path, n)`` delays the drain
  until the peer writing ``path`` has delivered ``n`` batches — without
  it, a client that starts first on a loaded box can drain the whole
  epoch before its peer reaches the pull that kills it."""
  os.environ['LDDL_DATA_SERVER'] = url
  os.environ['LDDL_COMM_HEARTBEAT'] = '0.1'
  os.environ['LDDL_LEASE_TIMEOUT'] = '10'
  if faults_spec:
    os.environ['LDDL_FAULTS'] = faults_spec
  import hashlib as _hl

  import numpy as _np

  from lddl_tpu.comm import FileBackend
  from lddl_tpu.loader.service import NetworkBatchSource

  def digest(batch):
    h = _hl.sha256()
    for k in sorted(batch):
      h.update(k.encode())
      h.update(_np.ascontiguousarray(batch[k]).tobytes())
    return h.hexdigest()

  comm = FileBackend(rdv, rank=rank, world_size=2, run_id=run_id)
  src = NetworkBatchSource(comm=comm, timeout=10, retries=2)
  if hold_until is not None:
    path, n = hold_until
    deadline = time.monotonic() + 60
    while len(_read_records(path)) < n and time.monotonic() < deadline:
      time.sleep(0.05)
  with open(out_path, 'w') as f:
    for gi, batch in src.iter_steps(0):
      f.write(json.dumps({'gi': gi, 'digest': digest(batch)}) + '\n')
      f.flush()


def _read_records(path):
  if not os.path.exists(path):
    return {}
  out = {}
  with open(path) as f:
    for line in f:
      line = line.strip()
      if line:
        rec = json.loads(line)
        out[rec['gi']] = rec['digest']
  return out


@pytest.mark.parametrize('kill_spec', [None, 'kill:client.pull:nth=3'])
def test_two_client_union_byte_identity(tmp_path, kill_spec):
  """Two lease-claiming clients drain one serve stream. Healthy: the
  claim split is disjoint and the union is the single-consumer run.
  With client 1 SIGKILLed before its 3rd pull: the survivor revokes its
  unmanifested leases (positive pid death) and the union is *still*
  byte-identical — the dead client's batches are re-served, its
  already-manifested ones are not duplicated."""
  steps, run_id = 12, 'svc'
  rdv = str(tmp_path / 'rdv')
  from lddl_tpu.comm.backend import FileLeaseStore
  store = FileLeaseStore(os.path.join(rdv, f'{run_id}.elastic.serve'),
                         rank=-1)
  srv = DataServer(_loader(steps), window=4, epochs=1,
                   lease_store=store).start()
  ctx = multiprocessing.get_context('spawn')
  outs = [str(tmp_path / f'client{r}.jsonl') for r in range(2)]
  procs = [
      ctx.Process(target=_union_client,
                  args=(r, rdv, run_id, srv.url, outs[r],
                        kill_spec if r == 1 else None,
                        # The kill fires on client 1's 3rd pull: client 0
                        # holds off until client 1 has delivered two.
                        (outs[1], 2) if kill_spec and r == 0 else None))
      for r in range(2)
  ]
  try:
    for p in procs:
      p.start()
    deadline = time.monotonic() + 120
    for p in procs:
      p.join(timeout=max(1.0, deadline - time.monotonic()))
      assert p.exitcode is not None, 'client did not finish in time'
  finally:
    for p in procs:
      if p.is_alive():
        p.kill()
        p.join(timeout=10)
    srv.stop()
  if kill_spec:
    assert procs[1].exitcode == -signal.SIGKILL
  recs = [_read_records(o) for o in outs]
  overlap = set(recs[0]) & set(recs[1])
  assert not overlap, f'both clients delivered {sorted(overlap)}'
  union = {**recs[0], **recs[1]}
  assert union == _reference(steps)
  if kill_spec:
    # The survivor picked up the dead client's share.
    assert len(recs[0]) > len(recs[1])


# ---------------------------------------------------------------------------
# backpressure: slow consumer bounds server memory


def _stat(url, timeout=5.0):
  host, _, port = url.rpartition(':')
  with socket.create_connection((host, int(port)), timeout=timeout) as s:
    s.settimeout(timeout)
    _send_frame(s, {'op': 'hello'})
    _recv_frame(s)
    _send_frame(s, {'op': 'stat'})
    header, _ = _recv_frame(s)
  return header


def test_slow_consumer_backpressure_bounds_window(monkeypatch):
  window, steps = 2, 12
  srv = DataServer(_loader(steps), window=window, epochs=1).start()
  monkeypatch.setenv('LDDL_DATA_SERVER', srv.url)
  src = NetworkBatchSource(timeout=10, retries=1)
  it = src.iter_steps(0)
  try:
    for pulled in range(4):
      next(it)
      time.sleep(0.15)  # let the producer run as far ahead as it can
      stat = _stat(srv.url)
      assert stat['backlog'] <= window, (
          f'after {pulled + 1} pulls the server buffered '
          f'{stat["backlog"]} batches (window {window})')
    rest = list(it)
  finally:
    srv.stop()
  assert 4 + len(rest) == steps


# ---------------------------------------------------------------------------
# lifecycle hygiene: clean stop, SIGKILL, discovery, monitor folding


def _serve_threads():
  return [t.name for t in threading.enumerate()
          if t.name.startswith('lddl-serve')]


def test_stop_leaves_no_threads_sockets_or_announce(tmp_path):
  announce_dir = str(tmp_path / 'mon')
  srv = DataServer(_loader(4), window=4, epochs=1,
                   announce_dir=announce_dir).start()
  url = srv.url
  found = discover_data_servers(announce_dir)
  assert [i['url'] for i in found] == [url]
  assert not found[0]['dead']
  assert resolve_endpoint(announce_dir=announce_dir) is not None
  srv.stop()
  assert _serve_threads() == []
  assert discover_data_servers(announce_dir) == []
  host, _, port = url.rpartition(':')
  with pytest.raises(OSError):
    socket.create_connection((host, int(port)), timeout=1.0).close()
  srv.stop()  # idempotent


def test_sigkilled_server_announce_is_provably_dead(tmp_path):
  announce_dir = str(tmp_path / 'mon')
  env = dict(os.environ, LDDL_MONITOR_DIR=announce_dir,
             JAX_PLATFORMS='cpu')
  proc = subprocess.Popen(
      [sys.executable, '-m', 'lddl_tpu.cli', 'lddl-data-server',
       '--synthetic', '--steps', '4', '--batch-size', '2',
       '--max-seq-length', '8', '--window', '64'],
      env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
  try:
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
      live = discover_data_servers(announce_dir)
      if live:
        break
      assert proc.poll() is None, proc.stdout.read().decode()
      time.sleep(0.1)
    assert live and not live[0]['dead']
    proc.kill()  # SIGKILL: no teardown, the announce file stays behind
    proc.wait(timeout=30)
    found = discover_data_servers(announce_dir)
    assert found and found[0]['dead']
    # The dead announce is not a resolvable endpoint...
    assert resolve_endpoint(announce_dir=announce_dir) is None
    # ...and lddl-monitor folds it into fleet errors instead of polling
    # a corpse (exit 1: no live ranks either, which is the point).
    from lddl_tpu.telemetry.monitor import main as monitor_main
    import io
    from contextlib import redirect_stdout
    buf = io.StringIO()
    with redirect_stdout(buf):
      rc = monitor_main(['--dir', announce_dir, '--once', '--json'])
    assert rc == 1
    payload = json.loads(buf.getvalue())
    assert any('data server' in err and 'dead' in err
               for err in payload['errors'].values())
    assert payload['data_servers'][0]['dead']
  finally:
    if proc.poll() is None:
      proc.kill()
      proc.wait(timeout=30)


# ---------------------------------------------------------------------------
# telemetry: the serve verdict block


def test_serve_block_in_live_verdict(monkeypatch):
  from lddl_tpu.telemetry import enable
  from lddl_tpu.telemetry.live import SnapshotWindow, live_verdict
  enable()
  window = SnapshotWindow()
  window.sample()
  srv = DataServer(_loader(5), window=5, epochs=1).start()
  monkeypatch.setenv('LDDL_DATA_SERVER', srv.url)
  try:
    got = list(NetworkBatchSource(timeout=10, retries=1).iter_steps(0))
  finally:
    srv.stop()
  assert len(got) == 5
  window.sample()
  verdict = live_verdict(window)
  serve = verdict['serve']
  assert serve is not None
  assert serve['batches_served'] == 5
  assert serve['client_pulls'] >= 5
  assert serve['reserves'] == 0
  # A registry with no serve activity keeps the dashboard quiet.
  from lddl_tpu.telemetry import Telemetry
  fresh = Telemetry()
  quiet = SnapshotWindow()
  fresh.counter('train.steps').add(1)
  quiet.sample(telemetry=fresh)
  fresh.counter('train.steps').add(1)
  quiet.sample(telemetry=fresh)
  assert live_verdict(quiet)['serve'] is None
