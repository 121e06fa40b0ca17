"""Sparse experts (lddl_tpu/ops/moe.py) on the CPU: one chip's share of the
experts against the uncut reference layer, drop-free routing, the bias
step, and the grouped-product kernel against XLA's ragged product."""

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
  # float32 sums of 16 and 8 terms, the layer's through a sort and a
  # scatter-add: round-off alone.
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
