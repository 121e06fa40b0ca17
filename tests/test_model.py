import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from lddl_tpu.models import BertConfig, BertForPretraining, spec_for_param
from lddl_tpu.parallel import make_mesh, ring_attention
from lddl_tpu.parallel.ring import make_ring_attention
from lddl_tpu.parallel.train import (
    init_params,
    make_train_step,
    pretrain_loss,
    shard_batch,
)

TINY = BertConfig(
    vocab_size=64,
    hidden_size=32,
    num_layers=2,
    num_heads=4,
    intermediate_size=64,
    max_position_embeddings=64,
    dropout_rate=0.0,
    dtype=jnp.float32,
)


def _dense_reference(q, k, v, mask):
  scale = 1.0 / np.sqrt(q.shape[-1])
  s = np.einsum('bhqd,bhkd->bhqk', q, k) * scale
  s = s + np.where(mask[:, None, None, :], 0.0, -1e9)
  p = np.exp(s - s.max(-1, keepdims=True))
  p = p / p.sum(-1, keepdims=True)
  return np.einsum('bhqk,bhkd->bhqd', p, v)


class TestRingAttention:

  @pytest.mark.parametrize('ring_size', [1, 4, 8])
  def test_matches_dense(self, ring_size):
    mesh = make_mesh(data=1, fsdp=1, tensor=1, seq=ring_size,
                     devices=jax.devices()[:ring_size])
    rng = np.random.default_rng(0)
    b, h, s, d = 2, 2, 32, 8
    q = rng.standard_normal((b, h, s, d), dtype=np.float32)
    k = rng.standard_normal((b, h, s, d), dtype=np.float32)
    v = rng.standard_normal((b, h, s, d), dtype=np.float32)
    mask = np.ones((b, s), dtype=bool)
    mask[:, -7:] = False  # padding tail
    from jax.sharding import PartitionSpec as P
    fn = make_ring_attention(
        mesh,
        q_spec=P(None, None, 'seq', None),
        mask_spec=P(None, 'seq'))
    out = np.asarray(fn(q, k, v, mask, None))
    ref = _dense_reference(q, k, v, mask)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


class TestSpecs:

  def test_spec_rules(self):
    from jax.sharding import PartitionSpec as P
    assert spec_for_param(('word_embeddings', 'embedding'),
                          (64, 32)) == P('tensor', 'fsdp')
    # scanned layer param: leading layer axis is replicated
    assert spec_for_param(
        ('encoder', 'layers', 'attention', 'query', 'kernel'),
        (2, 32, 32)) == P(None, 'fsdp', 'tensor')
    assert spec_for_param(('embed_norm', 'scale'), (32,)) == P(None)


class TestBertModel:

  @pytest.fixture(scope='class')
  def mesh(self):
    return make_mesh(data=2, fsdp=2, tensor=2, seq=1)

  @pytest.fixture(scope='class')
  def params(self, mesh):
    model = BertForPretraining(TINY)
    return init_params(model, mesh, jax.random.key(0), seq_len=32, batch=2)

  def test_params_sharded(self, mesh, params):
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    assert flat  # non-empty
    qk = [l for p, l in flat if 'query' in str(p) and 'kernel' in str(p)][0]
    # [layers, hidden, hidden] sharded over fsdp x tensor
    assert qk.shape == (2, 32, 32)
    spec = qk.sharding.spec
    assert tuple(spec) == (None, 'fsdp', 'tensor')

  def test_forward_and_loss(self, mesh, params):
    model = BertForPretraining(TINY)
    b, s = 4, 32
    rng = np.random.default_rng(1)
    batch = {
        'input_ids': rng.integers(0, 64, (b, s)).astype(np.int32),
        'token_type_ids': np.zeros((b, s), np.int32),
        'attention_mask': np.ones((b, s), np.int32),
        'labels': np.full((b, s), -100, np.int32),
        'next_sentence_labels': rng.integers(0, 2, (b,)).astype(np.int32),
    }
    batch['labels'][:, 3] = 5  # one masked position per row
    batch = shard_batch(batch, mesh)
    loss, metrics = jax.jit(
        lambda p, bt: pretrain_loss(model, p, bt))(params, batch)
    assert np.isfinite(float(loss))
    assert 0.0 <= float(metrics['mlm_acc']) <= 1.0

  def test_train_step_updates(self, mesh, params):
    model = BertForPretraining(TINY)
    tx = optax.adamw(1e-3)
    opt_state = tx.init(params)
    step = make_train_step(model, tx, mesh)
    b, s = 4, 32
    rng = np.random.default_rng(2)
    batch = shard_batch(
        {
            'input_ids': rng.integers(0, 64, (b, s)).astype(np.int32),
            'token_type_ids': np.zeros((b, s), np.int32),
            'attention_mask': np.ones((b, s), np.int32),
            'labels': np.where(
                rng.random((b, s)) < 0.15,
                rng.integers(0, 64, (b, s)), -100).astype(np.int32),
            'next_sentence_labels': rng.integers(0, 2,
                                                 (b,)).astype(np.int32),
        }, mesh)
    old = jax.tree_util.tree_leaves(params)[0]
    old_val = np.asarray(old)
    params2, opt_state, metrics = step(params, opt_state, jax.random.key(1),
                                       batch)
    new_val = np.asarray(jax.tree_util.tree_leaves(params2)[0])
    assert np.isfinite(float(metrics['loss']))
    assert not np.array_equal(old_val, new_val)

  def test_step_keeps_state_layout(self, mesh):
    """The step hands the state back laid out as it came in, so an
    AOT-compiled step (CompiledStepCache) accepts its own output on the
    next call. Left to the partitioner, replicated-by-rule leaves
    (biases, norms) came back split over fsdp and the second call of
    the compiled step raised on this fsdp x tensor mesh."""
    model = BertForPretraining(TINY)
    params = init_params(model, mesh, jax.random.key(0), seq_len=32)
    tx = optax.adamw(1e-3)
    opt_state = tx.init(params)
    rng = np.random.default_rng(3)
    b, s = 4, 32
    batch = shard_batch(
        {
            'input_ids': rng.integers(0, 64, (b, s)).astype(np.int32),
            'token_type_ids': np.zeros((b, s), np.int32),
            'attention_mask': np.ones((b, s), np.int32),
            'labels': np.where(
                rng.random((b, s)) < 0.15,
                rng.integers(0, 64, (b, s)), -100).astype(np.int32),
            'next_sentence_labels': rng.integers(0, 2,
                                                 (b,)).astype(np.int32),
        }, mesh)
    key = jax.random.key(1)
    from lddl_tpu.parallel.train import state_shardings
    state = (params, opt_state)
    want = jax.tree_util.tree_leaves(state_shardings(mesh, *state))
    compiled = make_train_step(model, tx, mesh).lower(
        *state, key, batch).compile()
    for _ in range(2):
      *state, metrics = compiled(*state, key, batch)
    assert np.isfinite(float(metrics['loss']))
    for x, sharding in zip(jax.tree_util.tree_leaves(state), want):
      assert x.sharding.is_equivalent_to(sharding, x.ndim)

  def test_init_divides_its_dummy_batch_over_the_mesh(self):
    """flash/ring attention run under shard_map, which refuses a batch
    the (data, fsdp) axes do not divide — at init as at every step; the
    old fixed two-row dummy failed on any mesh with data x fsdp > 2."""
    import dataclasses
    mesh = make_mesh(data=4, fsdp=2, tensor=1, seq=1)
    model = BertForPretraining(
        dataclasses.replace(TINY, attention_impl='flash'), mesh=mesh)
    params = init_params(model, mesh, jax.random.key(0), seq_len=32)
    assert jax.tree_util.tree_leaves(params)

  def test_ring_model_matches_dense(self, mesh):
    # Same params, attention_impl dense vs ring on a seq-sharded mesh.
    seq_mesh = make_mesh(data=2, fsdp=1, tensor=1, seq=4)
    dense_model = BertForPretraining(TINY)
    ring_model = BertForPretraining(
        BertConfig(**{**TINY.__dict__, 'attention_impl': 'ring'}),
        mesh=seq_mesh)
    params = init_params(dense_model, seq_mesh, jax.random.key(0),
                         seq_len=32, batch=2)
    b, s = 2, 32
    rng = np.random.default_rng(3)
    ids = rng.integers(0, 64, (b, s)).astype(np.int32)
    tt = np.zeros((b, s), np.int32)
    am = np.ones((b, s), np.int32)
    am[:, -5:] = 0
    out_d = dense_model.apply({'params': params}, ids, tt, am)
    out_r = ring_model.apply({'params': params}, ids, tt, am)
    np.testing.assert_allclose(
        np.asarray(out_d[0]), np.asarray(out_r[0]), rtol=2e-3, atol=2e-3)


class TestMaskedOnlyHead:
  """The masked-only MLM head must reproduce the full head's loss exactly
  (CE is only ever evaluated at masked positions) whenever P covers every
  row's masked count, and the accounting must bill the smaller head."""

  def _batch(self, b=4, s=32, max_masked=4, seed=3):
    rng = np.random.default_rng(seed)
    batch = {
        'input_ids': rng.integers(0, 64, (b, s)).astype(np.int32),
        'token_type_ids': np.zeros((b, s), np.int32),
        'attention_mask': np.ones((b, s), np.int32),
        'labels': np.full((b, s), -100, np.int32),
        'next_sentence_labels': rng.integers(0, 2, (b,)).astype(np.int32),
    }
    for i in range(b):
      cols = rng.choice(np.arange(1, s - 1), size=rng.integers(1, max_masked + 1),
                        replace=False)
      batch['labels'][i, cols] = rng.integers(0, 64, len(cols))
    return batch

  def test_loss_matches_full_head(self):
    mesh = make_mesh(data=1, fsdp=1, tensor=1, seq=1,
                     devices=jax.devices()[:1])
    model = BertForPretraining(TINY)
    params = init_params(model, mesh, jax.random.key(0), seq_len=32)
    batch = shard_batch(self._batch(), mesh)
    full, m_full = jax.jit(
        lambda p, bt: pretrain_loss(model, p, bt))(params, batch)
    gathered, m_gath = jax.jit(
        lambda p, bt: pretrain_loss(model, p, bt, max_predictions=6))(
            params, batch)
    np.testing.assert_allclose(float(full), float(gathered), rtol=1e-6)
    np.testing.assert_allclose(float(m_full['mlm_acc']),
                               float(m_gath['mlm_acc']), rtol=1e-6)

  def test_train_step_with_masked_only_head(self):
    mesh = make_mesh(data=1, fsdp=1, tensor=1, seq=1,
                     devices=jax.devices()[:1])
    model = BertForPretraining(TINY)
    params = init_params(model, mesh, jax.random.key(0), seq_len=32)
    tx = optax.adamw(1e-3)
    opt_state = tx.init(params)
    step = make_train_step(model, tx, mesh, max_predictions=6)
    batch = shard_batch(self._batch(seed=4), mesh)
    old = np.asarray(jax.tree_util.tree_leaves(params)[0])  # before donation
    params2, _, metrics = step(params, opt_state, jax.random.key(1), batch)
    assert np.isfinite(float(metrics['loss']))
    assert not np.array_equal(old,
                              np.asarray(jax.tree_util.tree_leaves(params2)[0]))

  def test_flops_accounting_shrinks(self):
    from lddl_tpu.models.flops import bert_pretrain_flops_per_step
    full = bert_pretrain_flops_per_step(TINY, 8, 128)
    gathered = bert_pretrain_flops_per_step(TINY, 8, 128, max_predictions=20)
    assert gathered < full
    d, v = TINY.hidden_size, TINY.vocab_size
    assert full - gathered == 3 * (2 * 8 * (128 - 20) * d * (d + v))

  def test_under_budget_warns(self):
    import warnings as w

    from lddl_tpu.parallel.train import check_max_predictions
    with w.catch_warnings(record=True) as rec:
      w.simplefilter('always')
      check_max_predictions(20, 128, 'static')   # budget 20: fine
      check_max_predictions(32, 128, 'dynamic')  # 19.2 + 4sd ~ 36: warns
      check_max_predictions(20, 512, 'dynamic')  # way under: warns
    msgs = [str(r.message) for r in rec]
    assert len(msgs) == 2 and all('silently drop' in m for m in msgs)
