"""Sequence-parallel ring attention on ucc_tpu collectives.

The long-context workload the framework must carry (SURVEY §5 long-context
note; the reference's analog machinery is msg-range switching + pipelined
fragmentation): the sequence axis is sharded across the mesh; each step a
rank computes attention of its local Q block against the K/V block currently
in hand, then the K/V blocks rotate one hop around the ring
(``ops.ring_shift`` == lax.ppermute on ICI neighbors). Communication of
block k+1 overlaps compute of block k under XLA's scheduler — bandwidth-
optimal context parallelism with O(seq/n) memory per chip.

Numerically stable streaming softmax (flash-attention style running max /
normalizer) so the result is exact, not an approximation.

Also provided: ``alltoall_seq_attention`` — the Ulysses-style alternative
that swaps the sequence sharding for a head sharding with two
``ops.alltoall`` calls around full local attention.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import ops




def _ring_attention_shard(q, k, v, axis_name: str):
    """Shard-local ring attention.

    q, k, v: (heads, seq_local, d). Returns (heads, seq_local, d) — exact
    attention over the FULL (sharded) sequence.
    """
    n = ops.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    h, s_local, d = q.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, q.dtype))

    def step(i, carry):
        acc, m_run, l_run, k_cur, v_cur = carry
        scores = jnp.einsum("hqd,hkd->hqk", q, k_cur) * scale
        m_blk = jnp.max(scores, axis=-1)                   # (h, s_local)
        m_new = jnp.maximum(m_run, m_blk)
        p = jnp.exp(scores - m_new[..., None])             # (h, q, k)
        corr = jnp.exp(m_run - m_new)                      # rescale old acc
        l_new = l_run * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("hqk,hkd->hqd", p, v_cur)
        # rotate K/V to the next rank; XLA overlaps this with the next
        # step's compute (the ring attention pipeline)
        k_nxt = ops.ring_shift(k_cur, axis_name)
        v_nxt = ops.ring_shift(v_cur, axis_name)
        return acc, m_new, l_new, k_nxt, v_nxt

    acc0 = jnp.zeros_like(q)
    m0 = jnp.full((h, s_local), -jnp.inf, dtype=q.dtype)
    l0 = jnp.zeros((h, s_local), dtype=q.dtype)
    acc, m_run, l_run, _, _ = lax.fori_loop(
        0, n, step, (acc0, m0, l0, k, v))
    return acc / l_run[..., None]


def make_ring_attention(mesh: Mesh, axis_name: str = "sp"):
    """Jitted exact attention with the sequence axis sharded over *mesh*.

    Inputs (global): q, k, v of shape (heads, seq, d) with seq sharded on
    `axis_name`. Output: same sharding.
    """
    spec = P(None, axis_name, None)
    fn = functools.partial(_ring_attention_shard, axis_name=axis_name)
    return jax.jit(jax.shard_map(fn, mesh=mesh,
                                 in_specs=(spec, spec, spec),
                                 out_specs=spec, check_vma=False))


def _ulysses_shard(q, k, v, axis_name: str):
    """Ulysses/all-to-all sequence parallelism: trade seq-sharding for
    head-sharding with alltoall, run full local attention, trade back.

    q,k,v: (heads, seq_local, d); heads % n == 0 required.
    """
    n = ops.axis_size(axis_name)
    h, s_local, d = q.shape

    def seq2head(x):
        # (h, s_local, d) -> (h/n, n*s_local, d): each rank keeps its head
        # GROUP with the FULL sequence. Head group j goes to rank j; the
        # received pieces stack in source-rank order = sequence order.
        y = x.reshape(n, h // n, s_local, d)          # piece j = head grp j
        y = lax.all_to_all(y, axis_name, split_axis=0, concat_axis=0,
                           tiled=False)               # (n_src, h/n, s, d)
        return y.transpose(1, 0, 2, 3).reshape(h // n, n * s_local, d)

    def head2seq(x):
        # inverse: (h/n, n*s_local, d) -> (h, s_local, d). Seq block j goes
        # to rank j; sources stack in head-group order.
        y = x.reshape(h // n, n, s_local, d).transpose(1, 0, 2, 3)
        y = lax.all_to_all(y, axis_name, split_axis=0, concat_axis=0,
                           tiled=False)               # (n_src, h/n, s, d)
        return y.reshape(h, s_local, d)

    qh, kh, vh = seq2head(q), seq2head(k), seq2head(v)
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, q.dtype))
    scores = jnp.einsum("hqd,hkd->hqk", qh, kh) * scale
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hqk,hkd->hqd", probs, vh)
    return head2seq(out)


def make_ulysses_attention(mesh: Mesh, axis_name: str = "sp"):
    spec = P(None, axis_name, None)
    fn = functools.partial(_ulysses_shard, axis_name=axis_name)
    return jax.jit(jax.shard_map(fn, mesh=mesh,
                                 in_specs=(spec, spec, spec),
                                 out_specs=spec, check_vma=False))


def reference_attention(q, k, v):
    """Unsharded exact attention for validation."""
    d = q.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, q.dtype))
    scores = jnp.einsum("hqd,hkd->hqk", q, k) * scale
    return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(scores, -1), v)
