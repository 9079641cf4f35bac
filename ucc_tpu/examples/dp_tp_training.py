"""Reference workload: a DP×TP sharded training step on ucc_tpu collectives.

UCC is a collectives library — its "flagship model" is the collective
engine under a real consumer. This module is that consumer: a two-layer
MLP trained with data parallelism × tensor parallelism where every
communication goes through ``ucc_tpu.ops`` (the compiled/ICI path):

  - TP: activations reduced across the tensor axis with ``ops.allreduce``
    (the row-parallel matmul psum)
  - DP: gradients synchronized across the data axis with ``ops.allreduce``
    (AVG), the NCCL-allreduce-in-the-optimizer pattern the reference serves
    via torch-ucc

The driver's ``dryrun_multichip`` jits this over an N-device mesh with real
dp/tp shardings and runs one step on tiny shapes.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..constants import ReductionOp
from .. import ops


def init_params(d_model: int, d_hidden: int, key=None):
    key = key if key is not None else jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    w1 = jax.random.normal(k1, (d_model, d_hidden), jnp.float32) * 0.02
    w2 = jax.random.normal(k2, (d_hidden, d_model), jnp.float32) * 0.02
    return {"w1": w1, "w2": w2}


def make_train_step(mesh: Mesh, lr: float = 1e-2):
    """Build the jitted DP×TP train step for *mesh* with axes (dp, tp).

    Shardings: x: P('dp', None); w1: P(None, 'tp') (column-parallel);
    w2: P('tp', None) (row-parallel); outputs replicated.
    """
    def step_shard(w1, w2, x, y):
        # forward: column-parallel w1 -> local gelu -> row-parallel w2
        h = jnp.dot(x, w1)                      # (b_local, hid/tp)
        h = jax.nn.gelu(h)
        out_partial = jnp.dot(h, w2)            # partial sum over tp
        out = ops.allreduce(out_partial, ReductionOp.SUM, axis_name="tp")
        diff = out - y
        # local loss; mean over the dp axis via our collective
        loss_local = jnp.mean(diff ** 2)[None, None]
        loss = ops.allreduce(loss_local, ReductionOp.AVG, axis_name="dp")

        # backward (hand-rolled so the collective placement is explicit,
        # mirroring how megatron-style TP places its psums)
        dout = 2.0 * diff / diff.size
        dh = jnp.dot(dout, w2.T)
        dw2 = jnp.dot(h.T, dout)
        dpre = dh * _gelu_grad(jnp.dot(x, w1))
        dw1 = jnp.dot(x.T, dpre)
        # DP gradient sync: average over the data axis
        dw1 = ops.allreduce(dw1, ReductionOp.AVG, axis_name="dp")
        dw2 = ops.allreduce(dw2, ReductionOp.AVG, axis_name="dp")
        w1 = w1 - lr * dw1
        w2 = w2 - lr * dw2
        return w1, w2, loss

    in_specs = (P(None, "tp"), P("tp", None), P("dp", None), P("dp", None))
    out_specs = (P(None, "tp"), P("tp", None), P(None, None))
    return jax.jit(jax.shard_map(step_shard, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


def _gelu_grad(x):
    c = jnp.sqrt(2.0 / jnp.pi)
    t = jnp.tanh(c * (x + 0.044715 * x ** 3))
    return 0.5 * (1 + t) + 0.5 * x * (1 - t ** 2) * c * (1 + 3 * 0.044715 * x ** 2)


def run_one_step(mesh: Mesh, batch: int = 8, d_model: int = 16,
                 d_hidden: int = 32):
    """Place sharded inputs and execute a single step (dryrun driver)."""
    params = init_params(d_model, d_hidden)
    x = jnp.ones((batch, d_model), jnp.float32)
    y = jnp.zeros((batch, d_model), jnp.float32)
    step = make_train_step(mesh)
    put = partial(jax.device_put)
    w1 = put(params["w1"], NamedSharding(mesh, P(None, "tp")))
    w2 = put(params["w2"], NamedSharding(mesh, P("tp", None)))
    xs = put(x, NamedSharding(mesh, P("dp", None)))
    ys = put(y, NamedSharding(mesh, P("dp", None)))
    w1, w2, loss = step(w1, w2, xs, ys)
    jax.block_until_ready(loss)
    return float(loss[0, 0])
