"""``ops.attention.attend``: the one dispatch over the attention
back-ends, against a dense float32 reference written out here (the Pallas
kernels run interpreted on the CPU; ring's cases are in ``test_model.py``,
``test_packed.py`` and ``test_block_diagonal.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lddl_tpu.ops.attention import ATTENTION_IMPLS, attend
from lddl_tpu.parallel import make_mesh

B, H, S, D = 8, 2, 64, 32  # the batch divides over the eight CPU devices


def _inputs(seed, two_documents):
  rng = np.random.default_rng(seed)
  q, k, v = (jnp.asarray(rng.standard_normal((B, H, S, D), dtype=np.float32))
             for _ in range(3))
  mask = np.ones((B, S), bool)
  mask[0, -9:] = False  # a padded tail on one row
  seg = None
  if two_documents:
    seg = np.where(np.arange(S)[None, :] < 37, 0, 1).repeat(B, 0)
    seg = jnp.asarray(np.where(mask, seg, -1), jnp.int32)
  return q, k, v, jnp.asarray(mask), seg


def _reference(q, k, v, mask, seg):
  scores = jnp.einsum('bhqd,bhkd->bhqk', q, k, precision='highest') / D**0.5
  keep = mask[:, None, None, :]
  if seg is not None:
    keep = keep & (seg[:, None, :, None] == seg[:, None, None, :])
  probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
  return jnp.einsum('bhqk,bhkd->bhqd', jnp.nan_to_num(probs), v,
                    precision='highest')


@pytest.mark.parametrize('with_mesh', [False, True], ids=['no-mesh', 'mesh'])
@pytest.mark.parametrize('two_documents', [False, True],
                         ids=['one-document', 'two-documents'])
@pytest.mark.parametrize('impl', ['dense', 'flash'])
def test_attend_matches_the_dense_reference(impl, two_documents, with_mesh):
  q, k, v, mask, seg = _inputs(5, two_documents)
  mesh = make_mesh() if with_mesh else None  # data=8

  @jax.jit
  def run(q, k, v, mask, seg):
    return attend(q, k, v, mask, seg, impl=impl, mesh=mesh,
                  dtype=jnp.float32)

  out = run(q, k, v, mask, seg)
  assert out.shape == (B, H, S, D) and out.dtype == jnp.float32
  real = np.asarray(mask)[:, None, :, None]  # padding rows carry no contract
  np.testing.assert_allclose(np.asarray(out) * real,
                             np.asarray(_reference(q, k, v, mask, seg)) * real,
                             rtol=2e-5, atol=2e-5)


def test_an_unknown_impl_is_refused():
  q, k, v, mask, seg = _inputs(6, False)
  with pytest.raises(ValueError) as err:
    attend(q, k, v, mask, seg, impl='flsh', mesh=None, dtype=jnp.float32)
  assert 'flsh' in str(err.value)
  assert all(name in str(err.value) for name in ATTENTION_IMPLS)
