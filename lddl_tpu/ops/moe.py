"""Sparse experts: sigmoid routing over every expert, the held share
computed with no dropped token.

A chip holds a contiguous range of the layer's experts
(``held = [first, first + count)``) and routes each token over *all* of
them. Only the assignments that land on a held expert are computed here:
they are sorted by expert and run through three grouped products
(megablox's ``gmm``, a Pallas kernel over ragged expert batches), and the
results are summed back into their tokens with the routing weights. An assignment to
an expert another chip holds is that chip's part of the sum; nothing
here stands in for it.

Routing (DeepSeek-V3's aux-loss-free balancing, arXiv:2412.19437
§2.1.2, as LFM2-8B-A1B's configuration has it): scores
``s = sigmoid(W_r u)`` in float32, experts chosen by the top ``k`` of
``s + b`` where ``b`` is a per-expert bias that no gradient moves, weights
``g_i = s_i / (sum over the chosen s + 1e-6) * scale``. After each step
the bias moves by ``rate * sign(mean load - load_i)``
(:func:`balance_bias`), from this chip's counts over all experts.

The buffer of sorted assignments is ``tokens * k`` rows whatever the
routing, so the shape is static and no token is ever dropped: every
assignment can be held at worst. Rows past the held assignments belong
to no group: the kernel visits none of their tiles and leaves them as the
memory held them, so they are selected away on both sides of the
products, forward and backward.

Rows move to and from the sorted buffer by gathers alone, forward and
backward. The stable sort's ``order`` (the assignment at each sorted row)
and its inverse ``inv`` (the sorted row of each assignment, from the
groups' offsets and a running count per expert) are one permutation and
its transpose: the dispatch gathers token rows by ``order`` and its
backward sums the sorted rows' gradients back by ``inv``; the combine
sums each token's held rows by ``inv`` and its backward gathers by
``order``. No ``[rows, hidden]`` array is scattered: on a v5e a
scatter-add of 32,768 rows into 8,192 of 2,048 took 2.5 ms (PERF.md).

On a v5e the grouped SwiGLU triple of a chunk of 32,768 rows of which
8,192 are held (hidden 2,048, expert width 1,792) took 5.23 ms forward
and backward with ``gmm`` at tiles of (512, 1024, 1024), 7.11 ms with
``jax.lax.ragged_dot`` and 47.3 ms with ``gmm``'s default 128-cubed
tiles; with every row held 14.4 / 19.6 ms (PERF.md, PR 41).

Names for the capture summary (``telemetry/capture.py``): the layer is a
flax module named ``moe``; the grouped products and the gate between
them sit under the scope ``experts``, which the summary also counts on
its own (``moe_experts_roofline``), and the moves of rows under
``dispatch`` and ``combine``.
"""

import collections
import math

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

# Added to the chosen scores' sum before the weights are normalised
# (the source's ``norm_topk_prob``).
NORM_EPS = 1e-6


def route(logits, bias, top_k, scale=1.0):
  """``(experts [t, k] int32, weights [t, k] float32, scores [t, e])`` of
  float32 router ``logits`` [t, e]: the top ``k`` of ``sigmoid(logits) +
  bias``, weighted by their own normalised scores."""
  scores = jax.nn.sigmoid(logits.astype(jnp.float32))
  _, experts = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), top_k)
  chosen = jnp.take_along_axis(scores, experts, axis=-1)
  weights = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + NORM_EPS)
  return experts, weights * scale, scores


def expert_load(experts, real, num_experts):
  """Assignments per expert, over every expert: int32 ``[e]``; tokens
  where ``real`` is False count nowhere."""
  hits = jax.nn.one_hot(experts, num_experts, dtype=jnp.int32)
  return jnp.sum(hits * real[:, None, None].astype(jnp.int32), axis=(0, 1))


def balance_bias(bias, load, rate):
  """The bias after one step: each expert's moves by ``rate`` toward the
  mean load, up where the expert took less than the mean."""
  load = load.astype(jnp.float32)
  mean = jnp.mean(load, axis=-1, keepdims=True)
  return bias + rate * jnp.sign(mean - load)


# Rows, contraction and output columns of one tile of the grouped product.
_TILE = (512, 1024, 1024)


def _grouped(x, w, group_sizes, kernel=None):
  """``group_sizes[i]`` consecutive rows of ``x`` times ``w[i]``: megablox's
  ``gmm`` kernel, or on the ``cpu`` backend XLA's ``ragged_dot`` (the same
  product; the kernel's interpreter runs its grid as a loop of small ops,
  15 s a step of the tiny test model). ``kernel`` forces the choice."""
  if kernel is None:
    kernel = jax.default_backend() != 'cpu'
  if not kernel:
    return jax.lax.ragged_dot(x, w, group_sizes)
  m, k = x.shape
  tiling = (math.gcd(m, _TILE[0]), min(k, _TILE[1]), min(w.shape[2], _TILE[2]))
  return megablox.gmm(x, w, group_sizes, x.dtype, tiling, None, None, False,
                      jax.default_backend() == 'cpu')


def grouped_swiglu(x, w1, w3, w2, group_sizes):
  """``w2(silu(w1 x) * w3 x)`` of rows sorted by expert: ``group_sizes[i]``
  consecutive rows go through expert ``i``; rows past the groups hold
  whatever the kernel left there."""
  with jax.named_scope('experts'):
    gate = _grouped(x, w1, group_sizes)
    up = _grouped(x, w3, group_sizes)
    h = (jax.nn.silu(gate.astype(jnp.float32)) *
         up.astype(jnp.float32)).astype(x.dtype)
    return _grouped(h, w2, group_sizes)


# Both directions of the data movement are gathers: ``order[p]`` is the
# assignment at sorted row ``p`` and ``inv[t, j]`` the sorted row of
# assignment ``t * k + j``, so a gather by one is the transpose of a gather
# by the other. JAX would transpose each gather into a scatter-add of
# [rows, hidden], so the two moves below carry their own backward passes.
# Each token's k rows are gathered as k arrays of [t, d]: one [t, k, d]
# array would be laid out with k padded to the tile's 8 rows.


@jax.custom_vjp
def _dispatch(u, held, order, inv):
  """Sorted row ``p`` is token ``order[p] // k``'s row where that
  assignment is held, else 0."""
  kept = held.reshape(-1)[order]
  return jnp.where(kept[:, None], u[order // held.shape[1]], 0)


def _dispatch_fwd(u, held, order, inv):
  return _dispatch(u, held, order, inv), (held, order, inv)


def _dispatch_bwd(res, dx):
  held, order, inv = res
  # A select, not a product: the rows past the groups (whatever the kernels
  # left in them) are zeroed before any token gathers them.
  dx = jnp.where(held.reshape(-1)[order][:, None], dx, 0)
  du = sum(dx[inv[:, j]].astype(jnp.float32) for j in range(inv.shape[1]))
  return du.astype(dx.dtype), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(y, weights, held, order, inv):
  """Each token's held assignments' sorted rows of ``y``, weighted and
  summed in float32. A select, not a product, leaves out the rows past
  the groups, which the assignments not held point at."""
  out = 0.0
  for j in range(held.shape[1]):
    rows = y[inv[:, j]].astype(jnp.float32) * weights[:, j, None]
    out = out + jnp.where(held[:, j, None], rows, 0.0)
  return out


def _combine_fwd(y, weights, held, order, inv):
  return _combine(y, weights, held, order, inv), (y, weights, held, order,
                                                   inv)


def _combine_bwd(res, dout):
  y, weights, held, order, inv = res
  kept = held.reshape(-1)[order]
  rows = dout[order // held.shape[1]]
  dy = jnp.where(kept[:, None], rows * weights.reshape(-1)[order][:, None],
                 0.0).astype(y.dtype)
  # Each sorted row's weight gradient, then moved to its assignment: a
  # gather of [t * k] scalars where a gather of rows would be.
  dw = jnp.sum(rows * y.astype(jnp.float32), axis=-1)
  return dy, jnp.where(held, dw[inv], 0.0), None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def held_experts_mix(u, experts, weights, real, w1, w3, w2, first):
  """This chip's part of the experts' sum for tokens ``u`` [t, d]: the
  assignments to experts ``[first, first + len(w1))`` are sorted by
  expert, run through :func:`grouped_swiglu` and summed back into their
  tokens with their ``weights``. Returns ``(out [t, d], group sizes)``."""
  t, k = experts.shape
  count = w1.shape[0]
  with jax.named_scope('dispatch'):
    local = experts - first
    held = (local >= 0) & (local < count) & real[:, None]
    key = jnp.where(held, local, count).reshape(-1)  # not held: last
    order = jnp.argsort(key, stable=True)
    # The stable sort's inverse with no second sort: an assignment's row is
    # its key's offset plus the assignments of that key before it.
    hits = key[None] == jnp.arange(count + 1, dtype=key.dtype)[:, None]
    seen = jnp.cumsum(hits, axis=1, dtype=jnp.int32)  # [count + 1, t * k]
    sizes = seen[:, -1]
    offsets = jnp.cumsum(sizes) - sizes
    inv = jnp.sum(jnp.where(hits, seen - 1 + offsets[:, None], 0), axis=0)
    inv = inv.reshape(t, k)
    x = _dispatch(u, held, order, inv)
  y = grouped_swiglu(x, w1, w3, w2, sizes[:count])
  with jax.named_scope('combine'):
    out = _combine(y, weights, held, order, inv)
  return out.astype(u.dtype), sizes[:count]


_ROUTED = collections.deque(maxlen=4096)


def observe_load(tele, step, load, cfg):
  """The host's record of one step's routing, from its ``expert_load``
  ``[..., num_experts]`` (already on the host side of the step's loss
  read): the ``moe.load_max_over_mean`` histogram (the busiest held
  expert's assignments over the held experts' mean, the worst sparse
  layer of the step) and a row ``(step, assignments to held experts in
  each sparse layer)`` in :func:`routed_record`."""
  import numpy as np
  load = np.asarray(load).reshape(-1, cfg.num_experts)
  first = cfg.first_held_expert
  held = load[:, first:first + cfg.held_experts].astype(np.float64)
  mean = held.mean(axis=1)
  worst = max((h.max() / m for h, m in zip(held, mean) if m > 0),
              default=None)
  if worst is not None:
    tele.histogram('moe.load_max_over_mean').observe(float(worst))
  _ROUTED.append((int(step), [int(n) for n in held.sum(axis=1)]))


def routed_record():
  """The rows :func:`observe_load` kept, oldest first (the last 4096
  steps of a run with telemetry on)."""
  return list(_ROUTED)
