"""Reference workload: pipeline parallelism (GPipe-style) on ucc_tpu.

The PP strategy is point-to-point-shaped: each device owns one layer
(stage) and activations stream stage-to-stage while microbatches fill the
pipeline. The stage-to-stage transfer is ``ops.ring_shift`` (lax.ppermute
over ICI — the p2p primitive the reference serves through UCX tagged
send/recv between pipeline neighbors).

One jitted shard_map program runs the whole schedule: n_micro + n_stages
- 1 ticks inside ``lax.fori_loop``; at tick t stage s processes
microbatch t - s (masked when outside [0, n_micro)), the last stage banks
its result, everyone shifts right.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from .. import ops


def make_pipeline(mesh: Mesh, n_micro: int, axis: str = "pp"):
    """Forward pipeline over *mesh* (1-D, axis ``pp``): device s applies
    layer s (gelu(x @ w)). Returns ``fn(x, w) -> y`` with
    x: (n_micro, b, d) replicated input microbatches; w: P(pp) over
    (n_stages, d, d); y: (n_micro, b, d) outputs after all stages."""
    n = len(mesh.devices.reshape(-1))

    def stage_fn(x, w):
        return jax.nn.gelu(x @ w)

    def pipe(x, w):
        me = lax.axis_index(axis)
        w_local = w[0]                       # my stage's layer
        nm, b, d = x.shape
        outputs = jnp.zeros((nm, b, d), x.dtype)
        act = jnp.zeros((b, d), x.dtype)     # in-flight activation

        def tick(t, carry):
            act, outputs = carry
            # stage 0 ingests microbatch t; later stages use what arrived
            inject = lax.cond(
                t < nm,
                lambda: lax.dynamic_index_in_dim(x, jnp.minimum(t, nm - 1),
                                                 axis=0, keepdims=False),
                lambda: jnp.zeros((b, d), x.dtype))
            cur = jnp.where(me == 0, inject, act)
            # stage s is working on microbatch t - s
            mb = t - me
            active = (mb >= 0) & (mb < nm)
            y = jnp.where(active, stage_fn(cur, w_local), cur)
            # last stage banks its finished microbatch
            bank = active & (me == n - 1)
            outputs = lax.cond(
                bank,
                lambda o: lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(mb, 0, nm - 1), axis=0),
                lambda o: o, outputs)
            # activations flow to the next stage (ppermute ring; the
            # wraparound n-1 -> 0 arrival is masked out by `me == 0`
            # selecting the injected microbatch instead)
            act = ops.ring_shift(y, axis_name=axis, shift=1)
            return act, outputs

        act, outputs = lax.fori_loop(0, nm + n - 1, tick, (act, outputs))
        # only the last stage banked results (others hold zeros): the sum
        # across the pp axis IS the replicated output
        return ops.allreduce(outputs, axis_name=axis)

    fn = jax.shard_map(pipe, mesh=mesh,
                       in_specs=(P(None), P(axis)),
                       out_specs=P(None), check_vma=False)
    return jax.jit(fn)


def reference_pipeline(x, w):
    """Sequential reference: every microbatch through every layer."""
    import numpy as np
    y = np.asarray(x)
    for s in range(w.shape[0]):
        y = np.asarray(jax.nn.gelu(jnp.asarray(y) @ jnp.asarray(w[s])))
    return y
