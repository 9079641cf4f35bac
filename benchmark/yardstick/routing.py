"""Mixture-of-experts routing drawn from the seed, with integer arithmetic
only, so that the generator and the reference make the same counts bit
for bit in whatever program computes them.

Per (step, layer) the seed draws a permutation of the experts, the same
on every rank: the expert at place i of it has Zipf weight
``round(2**24 / (i + 1)**s)``. Each of a rank's tokens then takes ``topk``
distinct experts, one after another, each drawn in proportion to the
weight of the experts it has not taken yet: the Plackett-Luce draw that a
Gumbel top-k makes, done as an integer cumulative sum against a hashed
integer below the remaining weight. The result is the number of rows
(token, expert pairs) routed to each expert.
"""
from __future__ import annotations

import functools

import numpy as np

from .data import _mix, stream_key

#: the ``bucket`` words of the routing's hash streams, apart from the
#: data's (whose bucket is the layer)
PERM = 0x20000
PICK = 0x10000


def zipf_weights(experts: int, s: float) -> np.ndarray:
    """int32 weight of the expert at each place of the permutation."""
    i = np.arange(1, experts + 1, dtype=np.float64)
    return np.rint(2.0 ** 24 / i ** s).astype(np.int32)


def layer_counts(key, step, layer, rank, tokens: int, weights, topk: int):
    """int32 ``(experts,)``: rows rank ``rank`` routes to each expert at
    (``step``, ``layer``); traceable in key, step, layer and rank."""
    import jax.numpy as jnp
    from jax import lax
    experts = weights.shape[0]
    e = lax.iota(jnp.uint32, experts)
    order = jnp.argsort(_mix(stream_key(key, 0, PERM + layer, step)
                             ^ (e * jnp.uint32(0x9E3779B1))))
    place = jnp.zeros(experts, jnp.int32).at[order].set(
        lax.iota(jnp.int32, experts))
    w = jnp.broadcast_to(jnp.asarray(weights)[place], (tokens, experts))
    skey = stream_key(key, rank, PICK + layer, step)
    t = lax.iota(jnp.uint32, tokens) * jnp.uint32(topk)
    counts = jnp.zeros(experts, jnp.int32)
    for j in range(topk):
        total = jnp.sum(w, axis=1).astype(jnp.uint32)
        u = (_mix((t + jnp.uint32(j)) * jnp.uint32(0x85EBCA77) ^ skey)
             % total).astype(jnp.int32)
        pick = jnp.sum(jnp.cumsum(w, axis=1) <= u[:, None], axis=1)
        hit = lax.iota(jnp.int32, experts)[None, :] == pick[:, None]
        counts = counts + jnp.sum(hit, axis=0, dtype=jnp.int32)
        w = jnp.where(hit, 0, w)
    return counts


@functools.lru_cache(maxsize=None)
def _step_fn(layers: int, tokens: int, topk: int, keep: int):
    import jax
    import jax.numpy as jnp

    def bench_route(key, rank, step, weights):
        return tuple(layer_counts(key, step, jnp.uint32(l), rank, tokens,
                                  weights, topk)[:keep]
                     for l in range(layers))
    return jax.jit(bench_route)


class Router:
    """One rank's routing of every layer of a step, in one jitted call on
    its device: a tuple of int32 ``(keep,)`` counts per layer, the
    experts held by the ranks present (``keep`` = their number)."""

    def __init__(self, key, rank: int, layers: int, tokens: int, weights,
                 topk: int, keep: int, device):
        import jax
        self.fn = _step_fn(layers, tokens, topk, keep)
        self.key = jax.device_put(key, device)
        self.rank = jax.device_put(np.uint32(rank), device)
        self.weights = jax.device_put(weights, device)
        self.device = device

    def __call__(self, step: int):
        import jax
        k = jax.device_put(np.uint32(step), self.device)
        return self.fn(self.key, self.rank, k, self.weights)
