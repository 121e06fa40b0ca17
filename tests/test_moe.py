"""Sparse experts (lddl_tpu/ops/moe.py) on the CPU: one chip's share of the
experts against the uncut reference layer, drop-free routing, the bias
step, the grouped-product kernel against XLA's ragged product, and the
rows' moves to and from the sorted buffer against a sort and scatter-add,
forward and backward."""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import lfm2_reference as reference
from lddl_tpu.ops import moe
from test_lfm2_parity import CONFIG, TOL


def test_four_expert_shares_add_up_to_the_whole_layer():
  """Four chips of 2 experts each, every one routing over all 8, sum to
  the uncut reference layer."""
  rng = np.random.default_rng(1)
  t, d, f, e = 48, 64, 32, 8
  u = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
  w = {k: jnp.asarray(0.1 * rng.standard_normal(s), jnp.float32)
       for k, s in (('router', (d, e)), ('e1', (e, d, f)), ('e3', (e, d, f)),
                    ('e2', (e, f, d)))}
  bias = jnp.asarray(0.01 * rng.standard_normal(e), jnp.float32)
  real = jnp.asarray(rng.random(t) < 0.9)
  logits = jnp.matmul(u, w['router'], precision='highest')
  experts, weights, _ = moe.route(logits, bias, 4)
  shares = sum(
      moe.held_experts_mix(u, experts, weights, real,
                           *(w[k][first:first + 2] for k in ('e1', 'e3', 'e2')),
                           first)[0]
      for first in (0, 2, 4, 6))
  with jax.default_matmul_precision('highest'):
    whole, load = reference._experts(u[None], {**w, 'bias': bias},
                                     real[None], dict(CONFIG, num_experts=e),
                                     'float32')
  np.testing.assert_allclose(np.asarray(shares), np.asarray(whole[0]), **TOL)
  assert int(load.sum()) == 4 * int(real.sum())
  np.testing.assert_array_equal(moe.expert_load(experts, real, e), load)


def test_routing_forced_to_one_expert_drops_nothing():
  """A router that sends every token to one held expert: that expert's
  group is every real token, and none is dropped."""
  rng = np.random.default_rng(2)
  t, d, f = 64, 16, 8
  u = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
  w1, w3 = (jnp.asarray(rng.standard_normal((2, d, f)), jnp.float32)
            for _ in range(2))
  w2 = jnp.asarray(rng.standard_normal((2, f, d)), jnp.float32)
  bias = jnp.zeros(8).at[5].set(100.0)  # expert 5 wins every token
  experts, weights, _ = moe.route(jnp.zeros((t, 8)), bias, 1)
  real = jnp.ones(t, bool)
  out, sizes = moe.held_experts_mix(u, experts, weights, real, w1, w3, w2, 4)
  assert sizes.tolist() == [0, t]
  h = jax.nn.silu(u @ w1[1]) * (u @ w3[1])
  # float32 sums of 16 and 8 terms, the layer's through a sort and two
  # gathers: round-off alone.
  np.testing.assert_allclose(np.asarray(out), np.asarray(h @ w2[1]),
                             rtol=1e-5, atol=1e-5)
  assert moe.expert_load(experts, real, 8).tolist()[5] == t


def test_bias_moves_toward_the_mean_load():
  bias = moe.balance_bias(jnp.zeros(4), jnp.array([10, 2, 4, 0]), 1e-3)
  assert bias.tolist() == pytest.approx([-1e-3, 1e-3, 0.0, 1e-3])


def test_the_kernel_computes_xlas_ragged_product():
  """megablox's gmm (interpreted here, compiled on the chip) against
  ``jax.lax.ragged_dot`` over the held groups, forward and backward; rows
  past the groups are left out of the comparison, as the layer selects
  them away."""
  rng = np.random.default_rng(4)
  x = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
  w = jnp.asarray(rng.standard_normal((3, 32, 16)), jnp.float32)
  sizes = jnp.array([20, 0, 28], jnp.int32)
  rows = np.arange(64) < 48

  def loss(kernel, x, w):
    y = moe._grouped(x, w, sizes, kernel=kernel)
    return jnp.sum(jnp.where(rows[:, None], y, 0) ** 2)

  got = jax.value_and_grad(lambda x, w: loss(True, x, w), (0, 1))(x, w)
  want = jax.value_and_grad(lambda x, w: loss(False, x, w), (0, 1))(x, w)
  # float32: the kernel's tiles sum the 32-wide contractions, and the
  # weight gradient's rows of a group, in another order than XLA's.
  np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
  np.testing.assert_allclose(np.asarray(got[1][0])[rows],
                             np.asarray(want[1][0])[rows], rtol=1e-4,
                             atol=1e-4)
  np.testing.assert_allclose(got[1][1], want[1][1], rtol=1e-4, atol=1e-4)


def scatter_mix(u, experts, weights, real, w1, w3, w2, first):
  """The layer as a sort and a scatter-add: the held assignments gathered
  into sorted rows, and their weighted results scattered back into their
  tokens, both moves left to JAX's autodiff."""
  t, k = experts.shape
  count = w1.shape[0]
  local = experts.reshape(-1) - first
  held = (local >= 0) & (local < count) & jnp.repeat(real, k)
  key = jnp.where(held, local, count)
  order = jnp.argsort(key, stable=True)
  sizes = jnp.bincount(key, length=count + 1)[:count].astype(jnp.int32)
  token = order // k
  kept = jnp.take(held, order)
  x = jnp.where(kept[:, None], jnp.take(u, token, axis=0), 0)
  y = moe.grouped_swiglu(x, w1, w3, w2, sizes)
  w = jnp.where(kept, jnp.take(weights.reshape(-1), order), 0.0)
  y = jnp.where(kept[:, None], y.astype(jnp.float32), 0.0) * w[:, None]
  out = jnp.zeros((t, u.shape[-1]), jnp.float32).at[token].add(y)
  return out.astype(u.dtype), sizes


def nan_past_the_groups(grouped):
  """``grouped`` with every row past ``sum(group_sizes)`` NaN, in its output
  and in its gradient with respect to its input rows: what the kernel may
  leave there."""
  def fill(a, sizes):
    past = jnp.arange(a.shape[0]) >= jnp.sum(sizes)
    return jnp.where(past[:, None], jnp.nan, a)

  @jax.custom_vjp
  def f(x, w1, w3, w2, sizes):
    return fill(grouped(x, w1, w3, w2, sizes), sizes)

  def fwd(x, w1, w3, w2, sizes):
    y, pull = jax.vjp(lambda *a: grouped(*a, sizes), x, w1, w3, w2)
    return fill(y, sizes), (pull, sizes)

  def bwd(res, dy):
    pull, sizes = res
    dx, d1, d3, d2 = pull(dy)
    return fill(dx, sizes), d1, d3, d2, None

  f.defvjp(fwd, bwd)
  return f


def routed_case(case, t=40, d=16, f=8, e=8):
  """``(u, experts, weights, real, w1, w3, w2, first)`` of one case: the
  held share ``first`` of 8 experts under a random router, a batch half of
  padding, every token on one held expert, or no assignment held."""
  rng = np.random.default_rng(zlib.crc32(case.encode()))
  u = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
  w1, w3 = (jnp.asarray(0.3 * rng.standard_normal((2, d, f)), jnp.float32)
            for _ in range(2))
  w2 = jnp.asarray(0.3 * rng.standard_normal((2, f, d)), jnp.float32)
  logits = jnp.asarray(rng.standard_normal((t, e)), jnp.float32)
  real = jnp.asarray(rng.random(t) < 0.9)
  bias, top_k, first = jnp.zeros(e), 4, 2
  if case.startswith('share'):
    first = int(case[-1])
  elif case == 'padding':
    real = jnp.arange(t) < t // 2
  elif case == 'one expert':
    bias, top_k, first = bias.at[5].set(100.0), 1, 4
    real = jnp.ones(t, bool)
  elif case == 'none held':
    bias, first = bias.at[:4].set(100.0), 6  # experts 0-3 win every token
  experts, weights, _ = moe.route(logits, bias, top_k)
  return u, experts, weights, real, w1, w3, w2, first


@pytest.mark.parametrize('case', ['share 0', 'share 2', 'share 4', 'share 6',
                                  'padding', 'one expert', 'none held'])
def test_gathers_match_the_scatter_formulation(case, monkeypatch):
  """The layer's forward and its VJP with respect to the tokens, the
  routing weights and the three expert weights equal the sort and
  scatter-add formulation's, with NaN in every row the kernel leaves past
  the groups: none of it reaches an output or a gradient."""
  monkeypatch.setattr(moe, 'grouped_swiglu',
                      nan_past_the_groups(moe.grouped_swiglu))
  u, experts, weights, real, w1, w3, w2, first = routed_case(case)
  ct = jnp.asarray(np.random.default_rng(7).standard_normal(u.shape),
                   jnp.float32)

  def run(mix):
    def f(u, weights, w1, w3, w2):
      return mix(u, experts, weights, real, w1, w3, w2, first)
    (out, sizes), pull = jax.vjp(f, u, weights, w1, w3, w2)
    return out, sizes, pull((ct, np.zeros(sizes.shape, jax.dtypes.float0)))

  out, sizes, grads = run(moe.held_experts_mix)
  want_out, want_sizes, want_grads = run(scatter_mix)
  held = {'one expert': [0, 40], 'none held': [0, 0]}.get(case)
  if held is not None:
    assert sizes.tolist() == held
  np.testing.assert_array_equal(sizes, want_sizes)
  for got, want in zip((out, *grads), (want_out, *want_grads)):
    assert np.isfinite(np.asarray(got)).all()
    # float32: the two sum a token's k rows in other orders.
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-6)


def scatters_of(jaxpr):
  """Every scatter primitive in ``jaxpr`` and the jaxprs inside it, as
  ``(name, operand shape)``."""
  found = []
  for eqn in jaxpr.eqns:
    if eqn.primitive.name.startswith('scatter'):
      found.append((eqn.primitive.name, eqn.invars[0].aval.shape))
    for value in eqn.params.values():
      for sub in value if isinstance(value, (list, tuple)) else (value,):
        sub = getattr(sub, 'jaxpr', sub)
        if hasattr(sub, 'eqns'):
          found += scatters_of(sub)
  return found


def test_no_row_moves_as_a_scatter_forward_or_backward():
  """The jaxpr of the layer's forward and VJP holds no scatter of [t, d]
  or [t * k, d] rows: every move of rows, both ways, is a gather. The
  scatter formulation holds two such, which the search must find."""
  u, experts, weights, real, w1, w3, w2, first = routed_case('share 2')
  t, d = u.shape
  rows = {(t, d), (t * experts.shape[1], d)}

  def row_scatters(mix):
    def f(u, weights, w1, w3, w2):
      return mix(u, experts, weights, real, w1, w3, w2, first)[0]

    def step(*args):
      out, pull = jax.vjp(f, *args)
      return out, pull(jnp.ones_like(out))

    jaxpr = jax.make_jaxpr(step)(u, weights, w1, w3, w2).jaxpr
    return [s for s in scatters_of(jaxpr) if s[1] in rows]

  assert len(row_scatters(scatter_mix)) == 2
  assert row_scatters(moe.held_experts_mix) == []
