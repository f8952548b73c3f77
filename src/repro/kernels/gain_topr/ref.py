"""Pure-jnp oracle for the batched top-R gain selection (DESIGN.md §14).

Input is the Algorithm-1 candidate-gain tensor ``cand[b, i, j]`` — the
marginal benefits of operator *i*'s extra processors in scenario *b*
(masked/invalid entries are 0).  Only a row's positive gains count, not
where they sit in it: the decide passes the gain table masked in place
below each operator's minimal feasible allocation.  Each scenario hands
out ``budget[b]`` processors to the largest *positive* gains; because
each row's positive gains are non-increasing along k (convexity, paper
Ineq. 5) the result equals the scalar greedy's argmax walk, with
threshold ties resolved in operator-index order
(`allocator.greedy_increments`'s rule).

Selection = one threshold: ``take[b, i] = #{j : cand[b,i,j] > theta_b}``
with ``theta_b`` the budget-th largest positive gain, plus ties at
``theta_b`` distributed row-major until the budget is exact.
"""

from __future__ import annotations

import jax.numpy as jnp

__all__ = ["gain_topr"]


def gain_topr(cand, budget):
    """``cand [B, N, J]`` gains + ``budget [B]`` -> ``take [B, N]`` int32."""
    cand = jnp.asarray(cand)
    budget = jnp.asarray(budget, dtype=jnp.int32)
    b, n, j = cand.shape
    flat = cand.reshape(b, n * j)
    pos = flat > 0
    pos_row = (cand > 0).sum(axis=-1).astype(jnp.int32)
    total_pos = pos.sum(axis=-1).astype(jnp.int32)
    use_all = total_pos <= budget
    # theta = budget-th largest positive value (descending sort; non-
    # positive entries sink to -inf so they are never the threshold).
    vals = jnp.sort(jnp.where(pos, flat, -jnp.inf), axis=-1)[:, ::-1]
    idx = jnp.clip(budget - 1, 0, n * j - 1)
    thresh = jnp.take_along_axis(vals, idx[:, None], axis=-1)[:, 0]
    strict = ((cand > thresh[:, None, None]) & (cand > 0)).sum(-1).astype(jnp.int32)
    ties = ((cand == thresh[:, None, None]) & (cand > 0)).sum(-1).astype(jnp.int32)
    rem = budget - strict.sum(axis=-1)
    before = jnp.cumsum(ties, axis=-1) - ties
    extra = jnp.clip(jnp.minimum(ties, rem[:, None] - before), 0, None)
    take = jnp.where(use_all[:, None], pos_row, strict + extra)
    return jnp.where(budget[:, None] > 0, take, 0).astype(jnp.int32)
