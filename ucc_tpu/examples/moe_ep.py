"""Reference workload: expert-parallel MoE token routing on ucc_tpu.

The expert-parallel (EP) strategy is alltoall-shaped: every device holds a
shard of the batch AND one expert; tokens are routed to the device owning
their assigned expert, processed, and routed back. The reference serves
exactly this traffic through its alltoallv machinery (the ucc_perftest MoE
traffic-matrix generator models it, ucc_pt_config.h:98-108); here the
dispatch/combine exchanges run through ``ucc_tpu.ops.alltoall`` inside one
jitted shard_map program (the ICI path).

Capacity-style routing keeps shapes static for XLA: every (src device,
expert) pair exchanges a fixed ``capacity`` slot block, padded with zeros —
the standard TPU MoE formulation (static shapes over dynamic token counts).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import ops


def make_moe_layer(mesh: Mesh, d_model: int, capacity: int,
                   axis: str = "ep"):
    """Build a jitted expert-parallel MoE layer over *mesh* (1-D, axis
    ``ep``): each device owns one expert (a distinct MLP) and a batch
    shard. Returns ``fn(x, w_up, w_dn, assign) -> y`` with
    x: P(ep) over (n*tokens_local, d); w_*: P(ep) over (n, d, h)-ish;
    assign: per-token expert id.
    """
    n = len(mesh.devices.reshape(-1))

    def layer(x, w_up, w_dn, assign):
        # x: (tokens_local, d); assign: (tokens_local,) int32
        # 1. pack tokens into per-expert capacity slots (static shapes)
        # position of each token within its expert's block
        onehot = jax.nn.one_hot(assign, n, dtype=jnp.int32)
        pos = (jnp.cumsum(onehot, axis=0) - 1) * onehot  # (tokens, n)
        pos = pos.sum(axis=1)
        keep = pos < capacity
        dispatch = jnp.zeros((n, capacity, x.shape[1]), x.dtype)
        dispatch = dispatch.at[assign, pos].add(
            jnp.where(keep[:, None], x, 0))
        # 2. route: alltoall over the ep axis (each expert receives its
        #    capacity block from every device)
        routed = ops.alltoall(
            dispatch.reshape(1, n * capacity * x.shape[1]), axis_name=axis)
        routed = routed.reshape(n, capacity, x.shape[1])
        # 3. expert MLP (this device's expert weights)
        h = jax.nn.gelu(jnp.einsum("ncd,dh->nch", routed, w_up[0]))
        out = jnp.einsum("nch,hd->ncd", h, w_dn[0])
        # 4. combine: route results back and unpack to token order
        combined = ops.alltoall(
            out.reshape(1, n * capacity * x.shape[1]), axis_name=axis)
        combined = combined.reshape(n, capacity, x.shape[1])
        y = combined[assign, pos] * keep[:, None].astype(x.dtype)
        return y

    return jax.jit(jax.shard_map(layer, mesh=mesh,
                                 in_specs=(P(axis), P(axis), P(axis), P(axis)),
                                 out_specs=P(axis), check_vma=False))


def reference_moe(x, w_up, w_dn, assign, capacity: int):
    """Unsharded reference: apply each token's assigned expert (tokens
    beyond an expert's per-source capacity produce zeros)."""
    import numpy as np
    n = w_up.shape[0]
    tokens_per_dev = x.shape[0] // n
    y = np.zeros_like(np.asarray(x))
    xs = np.asarray(x)
    for dev in range(n):
        counts = {}
        for i in range(tokens_per_dev):
            t = dev * tokens_per_dev + i
            e = int(assign[t])
            c = counts.get(e, 0)
            counts[e] = c + 1
            if c >= capacity:
                continue
            h = np.asarray(jax.nn.gelu(xs[t] @ np.asarray(w_up[e])))
            y[t] = h @ np.asarray(w_dn[e])
    return y
