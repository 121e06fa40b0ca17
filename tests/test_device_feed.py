"""Host->device feed tests: prefetch placement/donation/error propagation,
the per-bin compiled step cache, and fused-columnar shard byte-identity."""

import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding

from lddl_tpu.loader.device import (SeqlenAwarePrefetcher, prefetch_to_device)
from lddl_tpu.pipeline.executor import Executor
from lddl_tpu.preprocess import bert, codebert
from lddl_tpu.preprocess.readers import read_code, read_corpus
from lddl_tpu.training.pretrain import CompiledStepCache


def _batches(n, batch=8, seq=8):
  for i in range(n):
    yield {
        'input_ids': np.full((batch, seq), i, dtype=np.int32),
        'attention_mask': np.ones((batch, seq), dtype=np.int32),
    }


class TestPrefetchToDevice:

  def test_error_propagates_to_consumer(self):
    def bad_iter():
      yield {'x': np.zeros((2, 4), np.float32)}
      raise RuntimeError('loader exploded')

    stream = prefetch_to_device(bad_iter(), size=2)
    next(stream)
    with pytest.raises(RuntimeError, match='loader exploded'):
      for _ in stream:
        pass

  def test_mesh_placement_named_sharding(self):
    mesh = Mesh(np.asarray(jax.local_devices()[:1]), ('data',))
    out = list(prefetch_to_device(_batches(3), mesh=mesh, size=2,
                                  donate=False))
    assert len(out) == 3
    for item in out:
      for v in item.values():
        assert isinstance(v.sharding, NamedSharding)
        assert v.sharding.mesh.axis_names == ('data',)
        assert v.sharding.spec[0] in ('data', ('data',))

  def test_default_mesh_placement(self):
    # mesh=None dict batches still land as global arrays with the
    # canonical batch-dim NamedSharding over the local-devices mesh.
    out = list(prefetch_to_device(_batches(2), size=2, donate=False))
    for item in out:
      for v in item.values():
        assert isinstance(v.sharding, NamedSharding)
        assert v.sharding.mesh.axis_names == ('data',)

  def test_non_divisible_batch_falls_back(self):
    # A dim-0 the device count does not divide cannot use the default
    # mesh; the batch must still arrive (plain device_put fallback).
    n = len(jax.local_devices())
    it = iter([{'x': np.zeros((n + 1 if n > 1 else 3, 2), np.float32)}])
    (item,) = list(prefetch_to_device(it, size=1, donate=False))
    assert item['x'].shape[0] in (n + 1, 3)

  def test_donation_deletes_previous_batch(self):
    stream = prefetch_to_device(_batches(3), size=2, donate=True)
    first = next(stream)
    assert not any(v.is_deleted() for v in first.values())
    second = next(stream)
    # Pulling batch k+1 deleted batch k's device buffers.
    assert all(v.is_deleted() for v in first.values())
    assert not any(v.is_deleted() for v in second.values())
    stream.close()

  def test_donate_false_keeps_batches(self):
    stream = prefetch_to_device(_batches(3), size=2, donate=False)
    first = next(stream)
    next(stream)
    assert not any(v.is_deleted() for v in first.values())
    stream.close()


class TestSeqlenAwarePrefetcherClose:

  def test_close_closes_wrapped_generator(self):
    stream = prefetch_to_device(_batches(8), size=2, donate=False)
    pf = SeqlenAwarePrefetcher(stream, lambda b: b['input_ids'].shape[1])
    assert pf.next_seqlen() == 8  # seq dim of _batches
    next(pf)
    pf.close()
    with pytest.raises(StopIteration):
      next(stream)

  def test_close_without_pull(self):
    pf = SeqlenAwarePrefetcher(iter([]), lambda b: 0)
    pf.close()  # plain iterators (no close()) are fine


class TestCompiledStepCache:

  @staticmethod
  def _make_step():
    def step(params, opt_state, rng, batch):
      del rng
      loss = jnp.sum(batch['x']) * params
      return params, opt_state, {'loss': loss}

    return jax.jit(step)

  def test_hits_misses_and_zero_retrace_after_warmup(self):
    cache = CompiledStepCache(self._make_step())
    params = jnp.float32(2.0)
    opt = jnp.float32(0.0)
    rng = jax.random.PRNGKey(0)
    small = {'x': np.ones((2, 8), np.float32)}
    large = {'x': np.ones((2, 16), np.float32)}
    for b in (small, large):  # warmup: one compile per bin
      cache(params, opt, rng, b)
    assert (cache.misses, cache.hits) == (2, 0)
    assert cache.retrace_seconds > 0.0
    for _ in range(3):  # bin switches after warmup: zero retraces
      for b in (small, large):
        cache(params, opt, rng, b)
    assert (cache.misses, cache.hits) == (2, 6)
    _, _, metrics = cache(params, opt, rng, small)
    assert float(metrics['loss']) == pytest.approx(2.0 * 16)

  def test_telemetry_counters(self):
    from lddl_tpu.telemetry import enable
    tele = enable()
    cache = CompiledStepCache(self._make_step())
    params, opt, rng = jnp.float32(1.0), jnp.float32(0.0), jax.random.PRNGKey(0)
    batch = {'x': np.ones((2, 4), np.float32)}
    cache(params, opt, rng, batch)
    cache(params, opt, rng, batch)
    snap = {
        line['name']: line
        for line in tele.snapshot_lines() if line['kind'] != 'meta'
    }
    assert snap['train.step_cache_misses']['total'] == 1
    assert snap['train.step_cache_hits']['total'] == 1
    assert snap['train.retrace_seconds']['count'] == 1

  def test_plain_callable_step_fn(self):
    calls = []

    def step(params, opt_state, rng, batch):
      calls.append(1)
      return params, opt_state, {'loss': np.float32(0.0)}

    cache = CompiledStepCache(step)
    batch = {'x': np.ones((2, 4), np.float32)}
    cache(None, None, None, batch)
    cache(None, None, None, batch)
    assert len(calls) == 2


def _hash_dir(path):
  out = {}
  for fn in sorted(os.listdir(path)):
    p = os.path.join(path, fn)
    if os.path.isfile(p):
      with open(p, 'rb') as f:
        out[fn] = hashlib.sha256(f.read()).hexdigest()
  return out


class TestColumnarByteIdentity:

  def test_bert_gate_on_off(self, tmp_path, tmp_corpus, tiny_vocab,
                            monkeypatch):
    hashes = {}
    for gate in ('1', '0'):
      monkeypatch.setenv('LDDL_NATIVE_COLUMNAR', gate)
      sink = str(tmp_path / f'sink_{gate}')
      cfg = bert.BertPretrainConfig(
          vocab_file=tiny_vocab, target_seq_length=32, duplicate_factor=2,
          masking=True, bin_size=8, seed=42, sentence_backend='rules',
          engine='fast', tokenizer_backend='hf', mask_backend='host')
      corpus = read_corpus(tmp_corpus, num_blocks=4, sample_ratio=1.0)
      bert.run(corpus, sink, cfg, executor=Executor(num_local_workers=1))
      hashes[gate] = _hash_dir(sink)
    assert hashes['1'] and hashes['1'] == hashes['0']

  def test_codebert_gate_on_off(self, tmp_path, tiny_vocab, monkeypatch):
    src = tmp_path / 'code_src'
    src.mkdir()
    import random
    from conftest import WORDS
    r = random.Random(5)
    with open(src / '0.txt', 'w', newline='') as f:
      for i in range(16):
        doc = '\n'.join(
            ' '.join(r.choice(WORDS) for _ in range(r.randrange(3, 8)))
            for _ in range(r.randrange(0, 3)))
        code = '\n'.join(
            ' '.join(r.choice(WORDS) for _ in range(r.randrange(4, 10)))
            for _ in range(r.randrange(3, 12)))
        f.write(f'fn-{i}<CODESPLIT>{doc}<CODESPLIT>{code}\r\n')
    hashes = {}
    for gate in ('1', '0'):
      monkeypatch.setenv('LDDL_NATIVE_COLUMNAR', gate)
      sink = str(tmp_path / f'csink_{gate}')
      cfg = codebert.CodebertPretrainConfig(
          vocab_file=tiny_vocab, target_seq_length=64, bin_size=16, seed=11,
          duplicate_factor=2)
      corpus = read_code(str(src), num_blocks=2)
      codebert.run(corpus, sink, cfg, executor=Executor(num_local_workers=1))
      hashes[gate] = _hash_dir(sink)
    assert hashes['1'] and hashes['1'] == hashes['0']
