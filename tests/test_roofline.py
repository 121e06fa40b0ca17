"""Roofline-aware device observability: XLA cost capture, the windowed
bound-class verdict, HBM gauges, on-demand profiling, and the no-op
guarantees.

The load-bearing contracts:

  - ``compiled.cost_analysis()`` FLOPs/bytes are captured once per
    (bin, shape) entry at CompiledStepCache compile time and billed per
    step as counters — hits pay two adds, never a re-analysis;
  - the roofline verdict classifies compute- vs memory- vs input-bound
    from pure windowed arithmetic (input-bound takes precedence), and
    rides ``live_verdict`` / ``/snapshot`` / the monitor dashboard;
  - HBM gauges sample ``device.memory_stats()`` at the scrape cadence
    and degrade to absent (never an error) on backends without memory
    stats — i.e. this CPU test suite;
  - ``/profile?steps=N`` arms the step profiler; unarmed, the hook adds
    zero threads and zero sockets with ``LDDL_MONITOR`` unset;
  - stale announce files (SIGKILLed monitors) are provably-dead-skipped
    by discovery instead of polled into timeouts.
"""

import json
import multiprocessing as mp
import os
import socket
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

import lddl_tpu.telemetry.profiling as profiling
import lddl_tpu.telemetry.roofline as roofline
from lddl_tpu.telemetry import enable, get_telemetry
from lddl_tpu.telemetry.live import SnapshotWindow, goodput_meters, live_status, live_verdict
from lddl_tpu.telemetry.report import merge_metric_lines
from lddl_tpu.telemetry.roofline import (compiled_step_costs, resolve_peaks,
                                         roofline_verdict, sample_hbm)
from lddl_tpu.telemetry.server import maybe_start_monitor, stop_monitor

from test_monitor import _counter, _gauge, _hist, _meta  # noqa: F401


# ---------------------------------------------------------------------------
# cost extraction from compiled executables


def _compile_matmul(n=64):
  import jax
  import jax.numpy as jnp

  @jax.jit
  def f(a, b):
    return a @ b

  x = jnp.ones((n, n), jnp.float32)
  return f.lower(x, x).compile()


class TestCompiledStepCosts:

  def test_real_compiled_executable_reports_costs(self):
    costs = compiled_step_costs(_compile_matmul(64))
    assert costs is not None
    flops, nbytes = costs
    # 64x64x64 matmul: 2*n^3 FLOPs (XLA counts multiply-add as 2).
    assert flops == pytest.approx(2 * 64 ** 3, rel=0.5)
    assert nbytes > 0

  def test_objects_without_cost_model_return_none(self):
    assert compiled_step_costs(object()) is None

    class _Raises:
      def cost_analysis(self):
        raise RuntimeError('no cost model on this backend')

    class _Empty:
      def cost_analysis(self):
        return []

    class _NoFlops:
      def cost_analysis(self):
        return [{'bytes accessed': 10.0}]

    assert compiled_step_costs(_Raises()) is None
    assert compiled_step_costs(_Empty()) is None
    assert compiled_step_costs(_NoFlops()) is None

  def test_plain_dict_analysis_accepted(self):
    class _Dict:
      def cost_analysis(self):
        return {'flops': 123.0, 'bytes accessed': 456.0}

    assert compiled_step_costs(_Dict()) == (123.0, 456.0)


# ---------------------------------------------------------------------------
# peak resolution


class TestResolvePeaks:

  def test_cpu_without_overrides_has_no_axes(self, monkeypatch):
    monkeypatch.delenv('LDDL_PEAK_TFLOPS', raising=False)
    monkeypatch.delenv('LDDL_PEAK_HBM_GBPS', raising=False)
    peaks = resolve_peaks(refresh=True)
    assert peaks['flops_per_sec'] is None
    assert peaks['hbm_bytes_per_sec'] is None
    assert peaks['balance'] is None
    assert peaks['local_devices'] == 8  # the forced virtual mesh

  def test_env_overrides_scale_by_local_devices(self, monkeypatch):
    monkeypatch.setenv('LDDL_PEAK_TFLOPS', '100')
    monkeypatch.setenv('LDDL_PEAK_HBM_GBPS', '1000')
    peaks = resolve_peaks(refresh=True)
    assert peaks['flops_per_sec'] == pytest.approx(100e12 * 8)
    assert peaks['hbm_bytes_per_sec'] == pytest.approx(1000e9 * 8)
    # Balance is a per-device ridge point; the device-count factor
    # cancels.
    assert peaks['balance'] == pytest.approx(100.0)

  def test_resolution_is_cached_until_refresh(self, monkeypatch):
    monkeypatch.setenv('LDDL_PEAK_TFLOPS', '100')
    monkeypatch.setenv('LDDL_PEAK_HBM_GBPS', '1000')
    first = resolve_peaks(refresh=True)
    monkeypatch.setenv('LDDL_PEAK_TFLOPS', '999')
    assert resolve_peaks() is first
    assert resolve_peaks(refresh=True)['flops_per_sec'] == \
        pytest.approx(999e12 * 8)

  def test_chip_table_has_matching_hbm_entries(self):
    from lddl_tpu.models.flops import (machine_balance,
                                       peak_flops_per_device,
                                       peak_hbm_bytes_per_device)

    class _Fake:
      device_kind = 'TPU v4'

    assert peak_flops_per_device(_Fake()) == pytest.approx(275e12)
    assert peak_hbm_bytes_per_device(_Fake()) == pytest.approx(1228e9)
    assert machine_balance(_Fake()) == pytest.approx(275e12 / 1228e9)

    class _V5e:
      device_kind = 'TPU v5 lite'

    # The lite entry must win over the plain-'v5' (= v5p) fallback.
    assert peak_flops_per_device(_V5e()) == pytest.approx(197e12)
    assert peak_hbm_bytes_per_device(_V5e()) == pytest.approx(819e9)

  def test_unknown_accelerator_raises_but_cpu_is_none(self):
    from lddl_tpu.models.flops import (machine_balance,
                                       peak_flops_per_device,
                                       peak_hbm_bytes_per_device)

    class _Unknown:
      platform = 'tpu'
      device_kind = 'TPU v99'

    with pytest.raises(ValueError, match='TPU v99'):
      peak_flops_per_device(_Unknown())
    with pytest.raises(ValueError, match='TPU v99'):
      peak_hbm_bytes_per_device(_Unknown())

    class _Cpu:
      platform = 'cpu'
      device_kind = 'cpu'

    assert peak_flops_per_device(_Cpu()) is None
    assert machine_balance(_Cpu()) is None


# ---------------------------------------------------------------------------
# the windowed verdict (pure arithmetic over merged metrics)


def _merged(flops, nbytes, wait=0.0, compute=10.0):
  lines = [_meta(0.0), _counter('train.xla_flops', flops),
           _counter('train.xla_bytes', nbytes)]
  if wait or compute:
    lines.append(_hist('train.data_wait_seconds', 10, wait))
    lines.append(_hist('train.compute_seconds', 10, compute))
  return merge_metric_lines([lines])


_PEAKS = {'flops_per_sec': 100e12, 'hbm_bytes_per_sec': 1e12,
          'balance': 100.0, 'device_kind': 'fake', 'local_devices': 1}


class TestRooflineVerdict:

  def test_compute_bound(self):
    # AI = 1e12/5e9 = 200 FLOPs/byte > balance 100.
    v = roofline_verdict(_merged(1e12, 5e9), 10.0, peaks=_PEAKS)
    assert v['bound'] == 'compute-bound'
    assert v['arithmetic_intensity'] == pytest.approx(200.0)
    assert v['flops_per_sec'] == pytest.approx(1e11)
    assert v['flops_frac'] == pytest.approx(1e11 / 100e12)
    assert 'machine balance 100' in v['detail']

  def test_memory_bound(self):
    # AI = 1e12/5e10 = 20 < balance 100.
    v = roofline_verdict(_merged(1e12, 5e10), 10.0, peaks=_PEAKS)
    assert v['bound'] == 'memory-bound'
    assert v['bw_frac'] == pytest.approx(5e9 / 1e12)

  def test_input_bound_takes_precedence(self):
    # Compute-bound by AI, but 50% of step time is data wait.
    v = roofline_verdict(_merged(1e12, 5e9, wait=10.0, compute=10.0),
                         10.0, peaks=_PEAKS)
    assert v['bound'] == 'input-bound'
    assert v['wait_frac'] == pytest.approx(0.5)

  def test_unknown_without_cost_counters(self):
    v = roofline_verdict(merge_metric_lines([[_meta(0.0)]]), 10.0,
                         peaks=_PEAKS)
    assert v['bound'].startswith('unknown')

  def test_unknown_without_peaks(self):
    nopeaks = dict(_PEAKS, flops_per_sec=None, hbm_bytes_per_sec=None,
                   balance=None)
    v = roofline_verdict(_merged(1e12, 5e9), 10.0, peaks=nopeaks)
    assert v['bound'].startswith('unknown')
    assert 'LDDL_PEAK_TFLOPS' in v['bound']
    # The achieved axes still report even when the peaks are unknown.
    assert v['flops_per_sec'] == pytest.approx(1e11)
    assert v['flops_frac'] is None


# ---------------------------------------------------------------------------
# cost capture through CompiledStepCache


class TestStepCacheCostCapture:

  def _cache(self):
    import jax
    import jax.numpy as jnp

    from lddl_tpu.training.pretrain import CompiledStepCache

    @jax.jit
    def step(params, opt_state, rng, batch):
      loss = jnp.sum(params @ batch['x'])
      return params, opt_state, {'loss': loss}

    cache = CompiledStepCache(step)
    params = jnp.ones((16, 16), jnp.float32)
    batch = {'x': np.ones((16, 16), np.float32)}
    rng = jax.random.key(0)
    return cache, params, batch, rng

  def test_costs_captured_once_and_billed_per_step(self):
    tele = enable()
    cache, params, batch, rng = self._cache()
    cache(params, None, rng, batch)
    assert cache.misses == 1
    assert cache.last_costs is not None
    flops_1 = tele.counter('train.xla_flops').total
    bytes_1 = tele.counter('train.xla_bytes').total
    assert flops_1 > 0 and bytes_1 > 0
    # Whole-process accounting: 8 local devices run the (replicated)
    # module, so the billed total is per-device cost x 8.
    per_step = cache.last_costs[0]
    assert flops_1 == pytest.approx(per_step)
    cache(params, None, rng, batch)
    assert cache.hits == 1
    assert tele.counter('train.xla_flops').total == \
        pytest.approx(2 * per_step)

  def test_uncompiled_fallback_reports_no_costs(self):
    from lddl_tpu.training.pretrain import CompiledStepCache

    def plain_step(params, opt_state, rng, batch):
      return params, opt_state, {'loss': 0.0}

    tele = enable()
    cache = CompiledStepCache(plain_step)
    cache(1, None, None, {'x': np.zeros((2, 2))})
    assert cache.last_costs is None
    assert tele.counter('train.xla_flops').total == 0


# ---------------------------------------------------------------------------
# HBM sampling


class TestSampleHbm:

  def test_cpu_backend_degrades_to_absent(self):
    tele = enable()
    assert sample_hbm(tele) is None  # CPU devices expose no memory_stats
    lines = tele.snapshot_lines(rank=0)
    assert not any(l.get('name', '').startswith('hbm.') for l in lines)

  def test_fake_devices_sum_and_headroom(self, monkeypatch):
    import jax

    class _Dev:
      def __init__(self, used, peak, limit):
        self._s = {'bytes_in_use': used, 'peak_bytes_in_use': peak,
                   'bytes_limit': limit}

      def memory_stats(self):
        return self._s

    monkeypatch.setattr(jax, 'local_devices',
                        lambda: [_Dev(100, 900, 1000), _Dev(300, 500, 1000)])
    roofline._reset_for_tests()
    tele = enable()
    summary = sample_hbm(tele)
    assert summary['bytes_in_use'] == 400
    assert summary['peak_bytes_in_use'] == 1400
    assert summary['bytes_limit'] == 2000
    # Headroom is the WORST device: 1 - 900/1000.
    assert summary['headroom_frac'] == pytest.approx(0.1)
    assert tele.gauge('hbm.bytes_in_use').value == 400
    assert tele.gauge('hbm.headroom_frac').value == pytest.approx(0.1)

  def test_unsupported_probe_is_cached(self, monkeypatch):
    import jax
    calls = []

    def _devices():
      calls.append(1)
      return []

    roofline._reset_for_tests()
    monkeypatch.setattr(jax, 'local_devices', _devices)
    assert sample_hbm(get_telemetry()) is None
    assert sample_hbm(get_telemetry()) is None
    assert len(calls) == 1  # second call short-circuits on the probe


# ---------------------------------------------------------------------------
# live integration: verdict, goodput, /snapshot


class TestLiveIntegration:

  def test_live_verdict_carries_roofline(self, monkeypatch):
    monkeypatch.setenv('LDDL_PEAK_TFLOPS', '100')
    monkeypatch.setenv('LDDL_PEAK_HBM_GBPS', '1')  # balance 100e3
    roofline._reset_for_tests()
    w = SnapshotWindow()
    w.push([_meta(0.0), _counter('train.xla_flops', 0),
            _counter('train.xla_bytes', 0),
            _hist('train.compute_seconds', 1, 1.0)])
    w.push([_meta(10.0), _counter('train.xla_flops', int(1e12)),
            _counter('train.xla_bytes', int(5e9)),
            _hist('train.compute_seconds', 11, 9.0)])
    v = live_verdict(w)
    roof = v['roofline']
    # AI 200 < balance 100e3 with these peaks -> memory-bound.
    assert roof['bound'] == 'memory-bound'
    assert roof['window_sec'] == pytest.approx(10.0)

  def test_warming_window_has_none_roofline(self):
    assert live_verdict(SnapshotWindow())['roofline'] is None

  def test_goodput_meters_hbm_and_device_live(self):
    lines = [_meta(0.0), _gauge('hbm.bytes_in_use', 4e9),
             _gauge('hbm.headroom_frac', 0.25),
             _gauge('loader.device_live_bytes', 2e6),
             _gauge('loader.device_live_batches', 2.0),
             _gauge('train.mfu', 0.41)]
    good = goodput_meters(merge_metric_lines([lines]))
    assert good['hbm']['bytes_in_use']['mean'] == pytest.approx(4e9)
    assert good['hbm']['headroom_frac']['mean'] == pytest.approx(0.25)
    assert good['device_live_bytes']['mean'] == pytest.approx(2e6)
    assert good['device_live_batches']['mean'] == pytest.approx(2.0)
    assert good['mfu']['mean'] == pytest.approx(0.41)

  def test_goodput_meters_absent_without_instrumentation(self):
    good = goodput_meters(merge_metric_lines([[_meta(0.0)]]))
    assert good['hbm'] is None
    assert good['device_live_bytes'] is None

  def test_live_status_has_roofline_and_hbm_keys(self):
    tele = enable()
    w = SnapshotWindow()
    status = live_status(w, rank=0, telemetry=tele)
    assert 'hbm' in status  # None on CPU, but the key is always there
    tele.counter('train.steps').add(1)
    status = live_status(w, rank=0, telemetry=tele)
    assert 'roofline' in status['verdict']


# ---------------------------------------------------------------------------
# prefetcher live-byte accounting


class TestDeviceLiveBytes:

  def test_gauges_track_and_zero_on_close(self):
    from lddl_tpu.loader.device import prefetch_to_device
    tele = enable()
    batches = [{'x': np.ones((8, 4), np.float32)} for _ in range(4)]
    out = list(prefetch_to_device(iter(batches), size=2))
    assert len(out) == 4
    g_bytes = tele.gauge('loader.device_live_bytes')
    g_batches = tele.gauge('loader.device_live_batches')
    # During the drain at least one batch was live on device...
    assert g_batches.max >= 1
    assert g_bytes.max >= 8 * 4 * 4
    # ...and the closed stream accounts everything back down to zero.
    assert g_bytes.value == 0
    assert g_batches.value == 0


# ---------------------------------------------------------------------------
# the step profiler + /profile endpoint


class _FakeJaxProfiler:

  def __init__(self, monkeypatch):
    import jax
    self.events = []
    monkeypatch.setattr(jax.profiler, 'start_trace',
                        lambda d: self.events.append(('start', d)))
    monkeypatch.setattr(jax.profiler, 'stop_trace',
                        lambda: self.events.append(('stop', None)))


class TestStepProfiler:

  def test_trace_capture_noop_without_dir(self):
    with profiling.trace_capture(None) as d:
      assert d is None

  def test_trace_capture_real_roundtrip(self, tmp_path):
    # Real jax.profiler on the CPU backend: proves the shared code path
    # bench uses actually drives the profiler API.
    target = str(tmp_path / 'trace')
    with profiling.trace_capture(target) as d:
      assert d == target
      np.dot(np.ones((8, 8)), np.ones((8, 8)))
    assert os.path.isdir(target)

  def test_arm_then_window_then_stop(self, monkeypatch, tmp_path):
    fake = _FakeJaxProfiler(monkeypatch)
    prof = profiling.StepProfiler()
    assert prof.on_step() is None  # unarmed: nothing happens
    assert fake.events == []
    out = prof.arm(2, out_dir=str(tmp_path))
    assert out == str(tmp_path)
    assert prof.armed
    assert prof.on_step() is None           # starts the trace
    assert fake.events == [('start', str(tmp_path / 'capture0000'))]
    assert prof.on_step() is None           # 1 of 2 steps done
    done = prof.on_step()                   # 2 of 2: stops, reports dir
    assert done == str(tmp_path / 'capture0000')
    assert fake.events[-1] == ('stop', None)
    assert not prof.armed
    # A later capture lands in a fresh numbered directory.
    prof.arm(1, out_dir=str(tmp_path))
    prof.on_step()
    assert prof.on_step() == str(tmp_path / 'capture0001')

  def test_finished_capture_leaves_summary_and_file(self, tmp_path):
    # Real jax.profiler on the CPU backend, through the singleton the
    # phase helper looks at: the phases land in the trace with their step,
    # the summary is kept and written beside the trace. (A CPU trace has
    # no TPU device plane, so the summary's device list is empty.)
    import jax
    import jax.numpy as jnp

    from lddl_tpu.telemetry import capture
    from lddl_tpu.telemetry.trace import disable_trace, get_tracer
    disable_trace()  # the annotations need no LDDL_TRACE
    prof = profiling.get_step_profiler()
    assert prof.last_summary is None
    prof.arm(2, out_dir=str(tmp_path))
    assert prof.on_step() is None  # starts the trace
    phase = get_tracer().phase
    done = None
    for step in (5, 6):
      with phase('train.step', step):
        with phase('train.dispatch', step):
          jax.jit(lambda x: x @ x)(jnp.ones((8, 8))).block_until_ready()
        done = prof.on_step()
    assert done == str(tmp_path / 'capture0000')
    summary = prof.last_summary
    assert summary['devices'] == []
    assert summary['phases_seen'] == ['train.dispatch', 'train.step']
    assert summary['seconds'] > 0 and summary['trace_bytes'] > 0
    with open(os.path.join(done, 'summary.json')) as f:
      assert json.load(f) == summary
    # Stage one on the same file: the spans, their steps, one thread.
    (line,) = capture.extract(summary['trace'])['host']
    rows = {(name, step) for name, _, _, step in line['events']}
    assert ('train.dispatch', 5) in rows and ('train.dispatch', 6) in rows
    assert ('train.step', 5) in rows
    assert 'capture summary' in capture.format_table(summary)

  @pytest.mark.parametrize('leave', ['nothing', 'garbage', 'truncated'])
  def test_missing_or_unreadable_trace_leaves_none(self, monkeypatch,
                                                   tmp_path, caplog, leave):
    _FakeJaxProfiler(monkeypatch)
    prof = profiling.StepProfiler()
    prof.last_summary = {'stale': True}
    prof.arm(1, out_dir=str(tmp_path))
    prof.on_step()
    run_dir = tmp_path / 'capture0000' / 'plugins' / 'profile' / 'run'
    if leave != 'nothing':
      run_dir.mkdir(parents=True)
      # A length-delimited field that claims more bytes than there are,
      # or one whose length never ends.
      (run_dir / 'host.xplane.pb').write_bytes(
          b'\x0a\x7fnot a profile' if leave == 'garbage' else b'\x0a\xff')
    with caplog.at_level('WARNING', logger='lddl_tpu'):
      assert prof.on_step() == str(tmp_path / 'capture0000')  # no raise
    assert prof.last_summary is None
    assert not os.path.exists(tmp_path / 'capture0000' / 'summary.json')
    assert 'no summary of the capture' in caplog.text

  def test_close_stops_inflight_trace(self, monkeypatch, tmp_path):
    fake = _FakeJaxProfiler(monkeypatch)
    prof = profiling.StepProfiler()
    prof.arm(5, out_dir=str(tmp_path))
    prof.on_step()
    prof.close()
    assert fake.events[-1] == ('stop', None)
    assert not prof.armed
    prof.close()  # idempotent

  def test_default_dir_follows_telemetry_dir(self, monkeypatch):
    monkeypatch.setenv('LDDL_TELEMETRY_DIR', '/tmp/t')
    assert profiling.default_profile_dir() == '/tmp/t/profiles'

  def test_profile_endpoint_arms_the_singleton(self, monkeypatch,
                                               tmp_path):
    monkeypatch.setenv('LDDL_MONITOR', '1')
    monkeypatch.setenv('LDDL_MONITOR_DIR', str(tmp_path))
    stop_monitor()
    enable()
    mon = maybe_start_monitor(rank=0)
    with urllib.request.urlopen(mon.url + '/profile?steps=3',
                                timeout=10) as resp:
      payload = json.loads(resp.read().decode('utf-8'))
    assert payload['armed_steps'] == 3
    assert profiling.get_step_profiler().armed
    with pytest.raises(urllib.error.HTTPError) as exc:
      urllib.request.urlopen(mon.url + '/profile?steps=zero', timeout=10)
    assert exc.value.code == 400
    stop_monitor()

  def test_404_lists_all_endpoints(self, monkeypatch, tmp_path):
    monkeypatch.setenv('LDDL_MONITOR', '1')
    monkeypatch.setenv('LDDL_MONITOR_DIR', str(tmp_path))
    stop_monitor()
    enable()
    mon = maybe_start_monitor(rank=0)
    with pytest.raises(urllib.error.HTTPError) as exc:
      urllib.request.urlopen(mon.url + '/nope', timeout=10)
    assert exc.value.code == 404
    body = exc.value.read().decode('utf-8')
    for endpoint in ('/snapshot', '/metrics', '/healthz', '/profile'):
      assert endpoint in body
    stop_monitor()

  def test_monitor_cli_profile_command(self, monkeypatch, tmp_path):
    from lddl_tpu.telemetry.monitor import main as monitor_main
    monkeypatch.setenv('LDDL_MONITOR', '1')
    monkeypatch.setenv('LDDL_MONITOR_DIR', str(tmp_path))
    stop_monitor()
    enable()
    mon = maybe_start_monitor(rank=0)
    assert monitor_main(['--url', mon.url, '--profile', '2']) == 0
    assert profiling.get_step_profiler().armed
    assert monitor_main(['--url', mon.url, '--profile', '0']) == 2
    stop_monitor()


class TestProfileNoopDiscipline:

  def test_unset_monitor_profile_hook_adds_no_threads_or_sockets(
      self, monkeypatch):
    """The satellite acceptance test: with LDDL_MONITOR unset, the
    /profile machinery (the step-profiler singleton + the per-step
    hook) creates zero threads and zero sockets."""
    monkeypatch.delenv('LDDL_MONITOR', raising=False)
    stop_monitor()
    profiling._reset_for_tests()
    created = []
    real_socket = socket.socket

    class _RecordingSocket(real_socket):

      def __init__(self, *a, **k):
        created.append((a, k))
        super().__init__(*a, **k)

    monkeypatch.setattr(socket, 'socket', _RecordingSocket)
    threads_before = set(threading.enumerate())

    mon = maybe_start_monitor(rank=0)
    assert not mon.enabled
    prof = profiling.get_step_profiler()
    for _ in range(10_000):
      assert prof.on_step() is None

    assert created == []
    leaked = set(threading.enumerate()) - threads_before
    assert not leaked, f'leaked threads: {leaked}'


# ---------------------------------------------------------------------------
# stale-endpoint discovery


def _exit_now():
  os._exit(0)


class TestStaleEndpointDiscovery:

  def _announce(self, tmp_path, rank, pid, pidns, starttime, url=None):
    path = tmp_path / f'monitor.rank{rank}.pid{pid}.json'
    path.write_text(json.dumps({
        'url': url or f'http://127.0.0.1:{9000 + rank}', 'rank': rank,
        'pid': pid, 'pidns': pidns, 'pid_starttime': starttime}))
    return path

  def test_dead_pid_skipped_live_pid_kept(self, tmp_path):
    from lddl_tpu.comm.backend import FileBackend
    from lddl_tpu.telemetry.monitor import (discover_announcements,
                                            discover_endpoints)
    pidns = FileBackend._pid_namespace()
    if not pidns:
      pytest.skip('no /proc pid namespace introspection on this platform')
    # A provably-dead pid: spawn a child, record identity, let it exit.
    proc = mp.get_context('spawn').Process(target=_exit_now)
    proc.start()
    dead_pid = proc.pid
    dead_start = FileBackend._pid_starttime(dead_pid)
    proc.join()
    self._announce(tmp_path, 0, os.getpid(), pidns,
                   FileBackend._pid_starttime(os.getpid()),
                   url='http://127.0.0.1:9100')
    self._announce(tmp_path, 1, dead_pid, pidns, dead_start,
                   url='http://127.0.0.1:9101')
    infos = discover_announcements(str(tmp_path))
    assert [i['dead'] for i in infos] == [False, True]
    assert discover_endpoints(str(tmp_path)) == ['http://127.0.0.1:9100']
    assert discover_endpoints(str(tmp_path), include_dead=True) == \
        ['http://127.0.0.1:9100', 'http://127.0.0.1:9101']

  def test_old_format_announces_never_flagged(self, tmp_path):
    from lddl_tpu.telemetry.monitor import discover_endpoints
    # Pre-PR announce files carry no pid identity: absence of proof is
    # not death.
    (tmp_path / 'monitor.rank0.pid999999.json').write_text(json.dumps(
        {'url': 'http://127.0.0.1:9102', 'rank': 0, 'pid': 999999}))
    assert discover_endpoints(str(tmp_path)) == ['http://127.0.0.1:9102']

  def test_live_server_announce_carries_identity(self, monkeypatch,
                                                 tmp_path):
    from lddl_tpu.comm.backend import FileBackend
    monkeypatch.setenv('LDDL_MONITOR', '1')
    monkeypatch.setenv('LDDL_MONITOR_DIR', str(tmp_path))
    stop_monitor()
    enable()
    maybe_start_monitor(rank=0)
    announce = list(tmp_path.glob('monitor.rank0.pid*.json'))
    assert len(announce) == 1
    info = json.loads(announce[0].read_text())
    assert info['pid'] == os.getpid()
    assert info['pidns'] == FileBackend._pid_namespace()
    assert info['pid_starttime'] == \
        FileBackend._pid_starttime(os.getpid())
    from lddl_tpu.telemetry.monitor import discover_endpoints
    assert discover_endpoints(str(tmp_path)) == [info['url']]
    stop_monitor()
