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


# ---------------------------------------------------------------------------
# dropless expert parallelism over the eager API: a DeepSeek-V2 MoE layer
# ---------------------------------------------------------------------------

def init_moe_params(key, d_model: int, n_experts: int, inter: int,
                    n_shared: int) -> dict:
    """Seeded float32 weights of one DeepSeek-V2 MoE layer: the router,
    ``n_experts`` routed SwiGLU experts of width ``inter`` and the shared
    experts as one SwiGLU of width ``n_shared * inter``."""
    ks = jax.random.split(key, 7)
    s = d_model ** -0.5

    def w(k, shape, scale):
        return jax.random.normal(k, shape, jnp.float32) * scale

    return {
        "router": w(ks[0], (d_model, n_experts), s),
        "gate": w(ks[1], (n_experts, d_model, inter), s),
        "up": w(ks[2], (n_experts, d_model, inter), s),
        "down": w(ks[3], (n_experts, inter, d_model), inter ** -0.5),
        "shared_gate": w(ks[4], (d_model, n_shared * inter), s),
        "shared_up": w(ks[5], (d_model, n_shared * inter), s),
        "shared_down": w(ks[6], (n_shared * inter, d_model),
                         (n_shared * inter) ** -0.5),
    }


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def route(x, router, topk: int):
    """DeepSeek-V2's gate with ``scoring_func`` softmax, ``topk_method``
    greedy, ``norm_topk_prob`` false and ``routed_scaling_factor`` 1:
    softmax over all experts, the top-k scores as the weights, as they
    are. Returns (weights, experts), each (tokens, topk)."""
    scores = jax.nn.softmax(x @ router, axis=-1)
    return jax.lax.top_k(scores, topk)


def shared_experts(x, params):
    return _swiglu(x, params["shared_gate"], params["shared_up"],
                   params["shared_down"])


def moe_reference(x, params, topk: int, held=None, shared: bool = True):
    """The whole layer, plainly, in float32: every token's top-k routed
    experts weighted by their scores, plus the shared experts. ``held``
    keeps only the routed experts in it (one chip's share; the router
    still routes over all of them); ``shared=False`` leaves the shared
    experts out."""
    weights, experts = route(x, params["router"], topk)
    y = jnp.zeros_like(x)
    for e in range(params["router"].shape[1]) if held is None else held:
        w = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=-1)
        y = y + w[:, None] * _swiglu(x, params["gate"][e], params["up"][e],
                                     params["down"][e])
    return y + shared_experts(x, params) if shared else y


class DroplessMoE:
    """DeepSeek-V2's MoE layer under expert parallelism, every exchange
    posted through ucc_tpu's eager API (``collective_init`` -> ``post`` ->
    ``test`` -> ``finalize``), dropless: no capacity, no padding.

    Rank r of the team holds routed experts ``L*r .. L*r + L - 1`` (L =
    experts / ranks) and its own tokens. Per forward, on every rank: route
    over all experts; sort the (token, expert) pairs by expert, so the rows
    are grouped by destination rank; an ALLTOALL of the per-expert counts
    (the host reads the received ones); the dispatch ALLTOALLV of the rows
    into a buffer of ``ranks * tokens * topk`` rows, which no routing can
    exceed; the held experts on the rows received; the combine ALLTOALLV
    back; the rows unsorted, weighted and summed, plus the shared experts,
    which every rank computes for its own tokens.

    ``teams`` and ``contexts`` are the ranks' team and context objects in
    this process, ``devices`` their devices: the posts of one exchange are
    made for every rank before any is tested."""

    def __init__(self, teams, contexts, devices, params, topk: int):
        self.teams, self.contexts = teams, contexts
        self.devices, self.topk = devices, topk
        self.n = len(teams)
        experts = params["router"].shape[1]
        self.local = experts // self.n
        held = ("gate", "up", "down")

        def mine(r, k, v):        # a rank holds its routed experts only
            return v[r * self.local:(r + 1) * self.local] if k in held \
                else v
        self.params = [{k: jax.device_put(mine(r, k, v), d)
                        for k, v in params.items()}
                       for r, d in enumerate(devices)]

    def _exchange(self, argses):
        import time

        from .. import Status
        reqs = [t.collective_init(a) for t, a in zip(self.teams, argses)]
        for q in reqs:
            q.post()
        deadline = time.monotonic() + 60.0
        while any(q.test() == Status.IN_PROGRESS for q in reqs):
            for c in self.contexts:
                c.progress()
            if time.monotonic() > deadline:
                raise TimeoutError("MoE exchange did not complete")
        sts = [q.test() for q in reqs]
        for q in reqs:
            q.finalize()
        if any(s != Status.OK for s in sts):
            raise RuntimeError(f"MoE exchange failed: {sts}")
        return [a.dst.buffer for a in argses]

    def __call__(self, xs):
        """xs: each rank's tokens (tokens, d_model), on its device.
        Returns each rank's output."""
        import numpy as np

        from .. import (BufferInfo, BufferInfoV, CollArgs, CollType,
                        DataType, MemoryType)
        n, local, topk = self.n, self.local, self.topk
        tpu = MemoryType.TPU
        d = xs[0].shape[1]
        rows = xs[0].shape[0] * topk
        plans, sends, counts = [], [], []
        for x, p in zip(xs, self.params):
            weights, experts = route(x, p["router"], topk)
            order = jnp.argsort(experts.reshape(-1), stable=True)
            sends.append(x[order // topk].reshape(-1))
            counts.append(jnp.bincount(experts.reshape(-1),
                                       length=n * local).astype(jnp.int32))
            plans.append((weights, order))
        got = self._exchange([CollArgs(
            coll_type=CollType.ALLTOALL,
            src=BufferInfo(c, n * local, DataType.INT32, mem_type=tpu),
            dst=BufferInfo(None, n * local, DataType.INT32, mem_type=tpu))
            for c in counts])
        mine = [np.asarray(c).reshape(n, local) for c in counts]
        recv = [np.asarray(g).reshape(n, local) for g in got]

        def v(buf, per_rank):
            return BufferInfoV(buf, [int(c) * d for c in per_rank], None,
                               DataType.FLOAT32, mem_type=tpu)

        dispatched = self._exchange([CollArgs(
            coll_type=CollType.ALLTOALLV,
            src=v(s, m.sum(axis=1)),
            dst=v(jnp.zeros(n * rows * d, jnp.float32, device=dev),
                  r.sum(axis=1)))
            for s, m, r, dev in zip(sends, mine, recv, self.devices)])
        outs = []
        for buf, r, p in zip(dispatched, recv, self.params):
            x = buf.reshape(n * rows, d)
            # the rows from each source come grouped by the held experts
            which = np.full(n * rows, -1)
            which[:r.sum()] = np.concatenate(
                [np.repeat(np.arange(local), r[src]) for src in range(n)])
            which = jnp.asarray(which)
            y = jnp.zeros_like(x)
            for e in range(local):
                y = y + jnp.where((which == e)[:, None], _swiglu(
                    x, p["gate"][e], p["up"][e], p["down"][e]), 0.0)
            outs.append(y.reshape(-1))
        combined = self._exchange([CollArgs(
            coll_type=CollType.ALLTOALLV,
            src=v(y, r.sum(axis=1)),
            dst=v(jnp.zeros(rows * d, jnp.float32, device=dev),
                  m.sum(axis=1)))
            for y, m, r, dev in zip(outs, mine, recv, self.devices)])
        result = []
        for x, c, (weights, order), p in zip(xs, combined, plans,
                                              self.params):
            pairs = jnp.zeros((rows, d), jnp.float32).at[order].set(
                c.reshape(rows, d))
            routed = jnp.sum(pairs.reshape(-1, topk, d)
                             * weights[..., None], axis=1)
            result.append(routed + shared_experts(x, p))
        return result
