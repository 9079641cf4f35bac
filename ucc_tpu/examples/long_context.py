"""Long-context training step: SP ring attention × DP gradient sync.

The end-to-end shape of the long-context workload the framework must
carry (task brief: ring attention / sequence parallelism first-class):
a single-head-block attention "model" whose sequence axis is sharded
over the `sp` mesh axis and whose batch is sharded over `dp` —

  - attention runs as ``fused_attention.ring_flash_attention`` with
    ``fused=None`` (auto): on real hardware the FUSED Pallas kernel
    runs on this multi-axis ('dp','sp') mesh too (dict MESH device ids
    address the sp-ring neighbor within the dp group — round 4); only
    interpret mode (this CPU dryrun) takes the lax ring schedule, whose
    discharge rule is 1-axis-only — same ring math and gradients,
    O(seq/n_sp) activation memory per chip. 1-axis fused-kernel
    coverage lives in ``make_ring_flash_attention`` and
    tests/test_ring_attention.py;
  - gradients flow through the kernel's custom_vjp (lax ring-schedule
    backward, flash-style recompute);
  - DP gradient synchronization is ``ops.allreduce(AVG)`` — the
    NCCL-allreduce-in-the-optimizer role.

`dryrun`-able on the virtual CPU mesh (interpret-mode kernel) and the
pattern scales to a real pod by growing the mesh axes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import ops
from ..constants import ReductionOp
from ..fused_attention import ring_flash_attention


def init_params(heads: int, d: int, key=None):
    key = key if key is not None else jax.random.PRNGKey(0)
    kq, kk, kv, ko = jax.random.split(key, 4)
    mk = lambda k: jax.random.normal(k, (heads, d, d), jnp.float32) * 0.1
    return {"wq": mk(kq), "wk": mk(kk), "wv": mk(kv), "wo": mk(ko)}


def _make_step(mesh: Mesh, make_loss, xspec, pspec, lr: float):
    """Shared SGD scaffolding for the train-step variants: per-shard
    loss -> value_and_grad -> joint-axis (sp x dp) gradient mean ->
    update. ``make_loss(params..., x, y)`` returns the per-shard scalar
    loss fn; weight grads are PER-RANK partials (the ring backward only
    aggregates activation grads dK/dV, never weight grads), so the
    global-mean loss needs the mean over BOTH mesh axes — one joint-axis
    collective per weight. Verified exact vs a dense single-device
    reference in tests/test_ring_attention.py::test_grads_match_dense."""

    def step_shard(wq, wk, wv, wo, x, y):
        loss_fn = make_loss(x, y)
        loss, grads = jax.value_and_grad(loss_fn, argnums=(0, 1, 2, 3))(
            wq, wk, wv, wo)
        grads = [ops.allreduce(g, ReductionOp.AVG, axis_name=("sp", "dp"))
                 for g in grads]
        new = [p - lr * g for p, g in zip((wq, wk, wv, wo), grads)]
        return (loss, *new)

    fn = jax.shard_map(step_shard, mesh=mesh,
                       in_specs=(pspec, pspec, pspec, pspec, xspec, xspec),
                       out_specs=(P(), pspec, pspec, pspec, pspec),
                       check_vma=False)
    return jax.jit(fn)


def make_train_step(mesh: Mesh, lr: float = 1e-2, causal: bool = True):
    """Jitted train step over mesh axes ('dp', 'sp').

    x, y: (batch, heads, seq, d) with batch sharded on 'dp' and seq on
    'sp'; params replicated.
    """

    def make_loss(x, y):
        def loss_fn(wq, wk, wv, wo):
            # per-head projections on the local (batch, seq) block
            q = jnp.einsum("bhsd,hde->bhse", x, wq)
            k = jnp.einsum("bhsd,hde->bhse", x, wk)
            v = jnp.einsum("bhsd,hde->bhse", x, wv)
            # fused ring attention: heads are independent in the kernel,
            # so the local batch folds into the head axis (no vmap over
            # the pallas_call needed)
            b, h, s_loc, e = q.shape
            attn = ring_flash_attention(
                q.reshape(b * h, s_loc, e), k.reshape(b * h, s_loc, e),
                v.reshape(b * h, s_loc, e), axis_name="sp",
                causal=causal,
                # auto: fused kernel on real chips (dict MESH device
                # ids serve the ('dp','sp') mesh), lax ring under
                # interpret (its discharge rule is 1-axis-only)
                fused=None).reshape(b, h, s_loc, e)
            out = jnp.einsum("bhse,hed->bhsd", attn, wo)
            local = jnp.mean((out - y) ** 2)
            # mean over data AND sequence shards in ONE collective (the
            # loss is a global scalar; every rank holds seq/n_sp tokens)
            return ops.allreduce(local[None], ReductionOp.AVG,
                                 axis_name=("sp", "dp"))[0]
        return loss_fn

    return _make_step(mesh, make_loss, P("dp", None, "sp", None),
                      P(None, None, None), lr)


def run_one_step(mesh: Mesh, batch: int, heads: int, seq: int, d: int,
                 causal: bool = True):
    """Convenience: init, shard, run one step; returns the loss."""
    params = init_params(heads, d)
    kx, ky = jax.random.split(jax.random.PRNGKey(7))
    x = jax.random.normal(kx, (batch, heads, seq, d), jnp.float32)
    y = jax.random.normal(ky, (batch, heads, seq, d), jnp.float32)
    xs = NamedSharding(mesh, P("dp", None, "sp", None))
    x, y = jax.device_put(x, xs), jax.device_put(y, xs)
    step = make_train_step(mesh, causal=causal)
    out = step(params["wq"], params["wk"], params["wv"], params["wo"],
               x, y)
    return float(jax.device_get(out[0]))


# ---------------------------------------------------------------------------
# GQA variant: standard token-stream block (round 5)
# ---------------------------------------------------------------------------

def init_gqa_params(dm: int, heads: int, kv_heads: int, e: int, key=None):
    """Token-stream projections: wq (dm, heads*e), wk/wv (dm, kv_heads*e),
    wo (heads*e, dm) — the LLM GQA shape (fewer K/V than Q heads)."""
    key = key if key is not None else jax.random.PRNGKey(0)
    kq, kk, kv, ko = jax.random.split(key, 4)
    s = 0.1
    return {
        "wq": jax.random.normal(kq, (dm, heads * e), jnp.float32) * s,
        "wk": jax.random.normal(kk, (dm, kv_heads * e), jnp.float32) * s,
        "wv": jax.random.normal(kv, (dm, kv_heads * e), jnp.float32) * s,
        "wo": jax.random.normal(ko, (heads * e, dm), jnp.float32) * s,
    }


def make_gqa_train_step(mesh: Mesh, heads: int, kv_heads: int, e: int,
                        lr: float = 1e-2, causal: bool = True):
    """Jitted GQA train step over mesh axes ('dp', 'sp').

    x, y: (batch, seq, dm) — batch on 'dp', seq on 'sp'; params
    replicated. The ring rotates only kv_heads K/V blocks per step
    (heads/kv_heads less ICI traffic than MHA at the same query width),
    and the batch folds into the head axis EXACTLY compatibly with the
    kernel's grouping: folded q index bi*heads + hi maps to folded kv
    index (bi*heads + hi) // (heads/kv_heads) = bi*kv_heads + hi//g.
    """
    g = heads // kv_heads
    assert heads == kv_heads * g, "heads must divide by kv_heads"

    def make_loss(x, y):
        def loss_fn(wq, wk, wv, wo):
            b, s_loc, dm = x.shape
            q = (x @ wq).reshape(b, s_loc, heads, e)
            k = (x @ wk).reshape(b, s_loc, kv_heads, e)
            v = (x @ wv).reshape(b, s_loc, kv_heads, e)
            # (b, s, h, e) -> (b*h, s, e): heads independent in-kernel
            fold = lambda t, h: t.transpose(0, 2, 1, 3).reshape(
                b * h, s_loc, e)
            attn = ring_flash_attention(
                fold(q, heads), fold(k, kv_heads), fold(v, kv_heads),
                axis_name="sp", causal=causal, fused=None)
            out = attn.reshape(b, heads, s_loc, e).transpose(0, 2, 1, 3) \
                .reshape(b, s_loc, heads * e) @ wo
            local = jnp.mean((out - y) ** 2)
            return ops.allreduce(local[None], ReductionOp.AVG,
                                 axis_name=("sp", "dp"))[0]
        return loss_fn

    return _make_step(mesh, make_loss, P("dp", "sp", None),
                      P(None, None), lr)
