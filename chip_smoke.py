"""Chip smoke: drive ucc_tpu's device path once on a TPU and check it.

    python chip_smoke.py              # one chip (what the driver runs)
    python chip_smoke.py --chips 4    # the cross-chip phase only

One process owns every chip it uses. Each phase compares with a plain
JAX/numpy reference; any mismatch or exception exits non-zero. Progress
and numbers go to earlier lines; the last line of stdout is one JSON
object naming the device. There is no CPU branch: without a TPU this exits
non-zero and prints no result.

One chip:
  - eager API (init -> Context -> Team -> persistent collective_init/post/
    test) on 256 MiB bf16 jax.Arrays: allreduce, reduce_scatter,
    allgather, alltoall, bcast; once with default selection, once with
    TL/XLA forced;
  - the Pallas reduction executor (EcTpu.reduce), k in {2, 9} sources of
    64 MiB: f32 SUM, bf16 MAX, f32 AVG (alpha);
  - the in-jit DP x TP step (examples/dp_tp_training) at Llama-3-8B MLP
    widths, 5 steps against the same step with the ops calls removed.
Four chips (--chips 4):
  - a 4-rank eager job (one context per chip) running TL/XLA allreduce,
    reduce_scatter, allgather and alltoall at 64 MiB per chip, each
    against the raw lax collective on the same mesh;
  - the DP x TP step on a (2, 2) mesh against the 1-device reference.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

MIB = 1 << 20
#: meta-llama/Meta-Llama-3-8B config.json: hidden_size, intermediate_size
D_MODEL, D_HIDDEN = 4096, 14336
TOKENS = 16384
STEPS = 5
LR = 0.1
SEED = 0
REPO = os.path.dirname(os.path.abspath(__file__))


def log(msg: str) -> None:
    print(msg, flush=True)


class Failed(Exception):
    """A phase's result disagreed with its reference."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise Failed(what)


# ---------------------------------------------------------------------------
# eager API
# ---------------------------------------------------------------------------

def make_job(n: int, **lib_overrides):
    """n ranks in this process, one context each (rank r claims local
    device r), and one team over all of them."""
    import ucc_tpu
    from ucc_tpu import ContextParams, Status, TeamParams, ThreadOobWorld

    world = ThreadOobWorld(n)
    libs = [ucc_tpu.init(**lib_overrides) for _ in range(n)]
    ctxs: list = [None] * n
    errs: list = []

    def mk(r):
        try:
            ctxs[r] = ucc_tpu.Context(
                libs[r], ContextParams(oob=world.endpoint(r)))
        except Exception as e:  # noqa: BLE001 - re-raised below
            errs.append(e)

    ths = [threading.Thread(target=mk, args=(r,)) for r in range(n)]
    for t in ths:
        t.start()
    for t in ths:
        t.join(timeout=300)
    if errs:
        raise errs[0]
    tw = ThreadOobWorld(n)
    teams = [c.create_team_post(TeamParams(oob=tw.endpoint(i)))
             for i, c in enumerate(ctxs)]
    deadline = time.monotonic() + 300
    while True:
        sts = [t.create_test() for t in teams]
        for c in ctxs:
            c.progress()
        if all(s == Status.OK for s in sts):
            return ctxs, teams
        bad = [s for s in sts if s.is_error]
        check(not bad and time.monotonic() < deadline,
              f"team create failed: {bad}")


def run_posts(ctxs, reqs, posts: int):
    """Post every request ``posts`` times; returns per-post seconds,
    each ending at readiness of every result."""
    import jax

    from ucc_tpu import Status

    times = []
    for _ in range(posts):
        t0 = time.perf_counter()
        for rq in reqs:
            rq.post()
        while True:
            sts = [rq.test() for rq in reqs]
            if all(s != Status.IN_PROGRESS for s in sts):
                break
            for c in ctxs:
                c.progress()
        check(all(s == Status.OK for s in sts), f"collective: {sts}")
        jax.block_until_ready([result(rq.task.args) for rq in reqs])
        times.append(time.perf_counter() - t0)
    return times


def result(args):
    from ucc_tpu import CollType
    return args.src.buffer if args.coll_type == CollType.BCAST \
        else args.dst.buffer


def coll_args(coll: str, src, count: int, n: int):
    from ucc_tpu import (BufferInfo, CollArgs, CollArgsFlags, CollType,
                         DataType, MemoryType, ReductionOp)
    ct = {"allreduce": CollType.ALLREDUCE,
          "reduce_scatter": CollType.REDUCE_SCATTER,
          "allgather": CollType.ALLGATHER, "alltoall": CollType.ALLTOALL,
          "bcast": CollType.BCAST}[coll]
    dst_count = {"reduce_scatter": count // n,
                 "allgather": count * n}.get(coll, count)

    def buf(b, c):
        return BufferInfo(b, c, DataType.BFLOAT16, mem_type=MemoryType.TPU)

    return CollArgs(
        coll_type=ct, src=buf(src, count),
        dst=None if ct == CollType.BCAST else buf(None, dst_count),
        op=ReductionOp.SUM if coll in ("allreduce", "reduce_scatter")
        else None,
        root=0, flags=CollArgsFlags.PERSISTENT)


def fallback_count() -> float:
    from ucc_tpu.obs import metrics
    return sum(metrics.snapshot()["counters"]
               .get("coll_fallback_runtime", {}).values())


def eager_one_chip(nbytes: int, posts: int = 3) -> None:
    """1-rank world on this chip: each collective is a copy, so the
    reference is the source itself."""
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    count = nbytes // 2
    src = jax.random.normal(jax.random.PRNGKey(SEED), (count,),
                            jnp.bfloat16)
    for label, overrides in (("default selection", {}),
                             ("TL/XLA forced", {"TLS": "xla,shm"})):
        ctxs, teams = make_job(1, **overrides)
        check("xla" in ctxs[0].tl_contexts,
              f"TL/XLA context missing: {sorted(ctxs[0].tl_contexts)}")
        for coll in ("allreduce", "reduce_scatter", "allgather",
                     "alltoall", "bcast"):
            args = coll_args(coll, src, count, 1)
            rq = teams[0].collective_init(args)
            times = run_posts(ctxs, [rq], posts)
            out = result(args)
            tl = type(rq.task).__module__.rsplit(".", 1)[-1]
            check(isinstance(out, jax.Array) and out.devices() == {dev},
                  f"{coll}: result not a jax.Array on {dev}")
            check(bool(jnp.array_equal(out, src)),
                  f"{coll} ({label}): result differs from the source")
            if overrides:
                check(tl == "xla", f"{coll}: ran on {tl}, not TL/XLA")
            log(f"eager {coll:14s} {label:17s} alg={rq.task.alg_name} "
                f"tl={tl} {nbytes // MIB} MiB bf16 first={times[0]*1e3:.3f} "
                f"ms repost={min(times[1:])*1e3:.3f} ms ok")
            rq.finalize()
        for t in teams:
            t.destroy()
        for c in ctxs:
            c.destroy()
    check(fallback_count() == 0, "coll_fallback_runtime != 0")
    log("eager: coll_fallback_runtime=0")


def eager_four_chips(nbytes: int, posts: int = 3) -> None:
    """4-rank job, one context per chip; each collective against the raw
    lax collective on the team's own mesh."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    n = 4
    devs = jax.devices()[:n]
    count = nbytes // 2
    keys = jax.random.split(jax.random.PRNGKey(SEED), n)
    srcs = [jax.device_put(jax.random.normal(keys[r], (count,),
                                             jnp.bfloat16), devs[r])
            for r in range(n)]
    ctxs, teams = make_job(n)
    mesh = Mesh(np.array(devs), ("r",))
    garr = jax.make_array_from_single_device_arrays(
        (n * count,), NamedSharding(mesh, P("r")), srcs)
    raw_bodies = {
        "allreduce": lambda x: jax.lax.psum(x, "r"),
        "reduce_scatter": lambda x: jax.lax.psum_scatter(
            x, "r", scatter_dimension=0, tiled=True),
        "allgather": lambda x: jax.lax.all_gather(x, "r", tiled=True),
        "alltoall": lambda x: jax.lax.all_to_all(
            x, "r", split_axis=0, concat_axis=0, tiled=True),
    }
    for coll, body in raw_bodies.items():
        raw = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("r"),
                                    out_specs=P("r"), check_vma=False))
        ref = raw(garr)
        t0 = time.perf_counter()
        ref = jax.block_until_ready(raw(garr))
        raw_s = time.perf_counter() - t0
        ref_by_dev = {s.device: s.data for s in ref.addressable_shards}
        argses = [coll_args(coll, srcs[r], count, n) for r in range(n)]
        reqs = [teams[r].collective_init(argses[r]) for r in range(n)]
        times = run_posts(ctxs, reqs, posts)
        on = []
        for r in range(n):
            out = result(argses[r])
            check(isinstance(out, jax.Array) and out.devices() == {devs[r]},
                  f"{coll}: rank {r} result not on chip {devs[r].id}")
            on.append(next(iter(out.devices())).id)
            want = ref_by_dev[devs[r]]
            check(out.shape == want.shape,
                  f"{coll}: rank {r} shape {out.shape} != {want.shape}")
            err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                        - want.astype(jnp.float32))))
            check(err <= 0.0625, f"{coll}: rank {r} max |ucc - lax| "
                                 f"= {err}")
        check(len(set(on)) == n, f"{coll}: results on chips {on}")
        tl = type(reqs[0].task).__module__.rsplit(".", 1)[-1]
        check(tl == "xla", f"{coll}: ran on {tl}, not TL/XLA")
        log(f"eager4 {coll:14s} alg={reqs[0].task.alg_name} tl={tl} "
            f"{nbytes // MIB} MiB/chip bf16 chips={on} "
            f"ucc first={times[0]*1e3:.3f} ms repost={min(times[1:])*1e3:.3f}"
            f" ms raw lax={raw_s*1e3:.3f} ms ok")
        for rq in reqs:
            rq.finalize()
    check(fallback_count() == 0, "coll_fallback_runtime != 0")
    for t in teams:
        t.destroy()
    for c in ctxs:
        c.destroy()
    log("eager4: coll_fallback_runtime=0")


# ---------------------------------------------------------------------------
# reduction executor
# ---------------------------------------------------------------------------

def executor(nbytes: int) -> None:
    import jax
    import jax.numpy as jnp

    from ucc_tpu import DataType, ReductionOp
    from ucc_tpu.ec.tpu import EcTpu

    ec = EcTpu()
    cases = (("f32 SUM", jnp.float32, DataType.FLOAT32, ReductionOp.SUM,
              False),
             ("bf16 MAX", jnp.bfloat16, DataType.BFLOAT16, ReductionOp.MAX,
              False),
             ("f32 AVG", jnp.float32, DataType.FLOAT32, ReductionOp.AVG,
              True))
    for k in (2, 9):
        for label, jdt, dt, op, avg in cases:
            count = nbytes // jnp.dtype(jdt).itemsize
            keys = jax.random.split(jax.random.PRNGKey(SEED + k), k)
            srcs = [jax.random.normal(kk, (count,), jdt) for kk in keys]
            stack = jnp.stack(srcs)
            if op == ReductionOp.MAX:
                want = jnp.max(stack, axis=0)
            else:
                want = jnp.sum(stack.astype(jnp.float32), axis=0)
                if avg:
                    want = want / k
            alpha = 1.0 / k if avg else None
            got = ec.reduce(None, srcs, count, dt, op, alpha=alpha).array
            jax.block_until_ready(got)
            t0 = time.perf_counter()
            got = jax.block_until_ready(
                ec.reduce(None, srcs, count, dt, op, alpha=alpha).array)
            secs = time.perf_counter() - t0
            check(ec.interpret is False, "EcTpu ran in interpret mode")
            err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
            tol = 0.0 if op == ReductionOp.MAX else 1e-5 * k
            check(got.shape == (count,) and err <= tol,
                  f"executor {label} k={k}: max err {err} > {tol}")
            moved = (k + 1) * nbytes
            log(f"executor {label:8s} k={k} {nbytes // MIB} MiB/source "
                f"interpret={ec.interpret} max_err={err:.3g} "
                f"time={secs*1e3:.3f} ms ({moved / secs / 1e9:.1f} GB/s "
                f"read+write incl. host dispatch) ok")


# ---------------------------------------------------------------------------
# in-jit DP x TP step
# ---------------------------------------------------------------------------

def plain_step(w1, w2, x, y, lr: float = LR):
    """examples/dp_tp_training's step with the ops calls removed."""
    import jax
    import jax.numpy as jnp

    from ucc_tpu.examples.dp_tp_training import _gelu_grad

    h = jax.nn.gelu(x @ w1)
    diff = h @ w2 - y
    loss = jnp.mean(diff ** 2)[None, None]
    dout = 2.0 * diff / diff.size
    dw2 = h.T @ dout
    dw1 = x.T @ ((dout @ w2.T) * _gelu_grad(x @ w1))
    return w1 - lr * dw1, w2 - lr * dw2, loss


def step_inputs(tokens: int, d_model: int, d_hidden: int):
    import jax

    from ucc_tpu.examples.dp_tp_training import init_params

    kp, kx, ky = jax.random.split(jax.random.PRNGKey(SEED), 3)
    p = init_params(d_model, d_hidden, kp)
    x = jax.random.normal(kx, (tokens, d_model))
    y = jax.random.normal(ky, (tokens, d_model)) * 0.5
    return p["w1"], p["w2"], x, y


def max_rel(a, b) -> float:
    import jax.numpy as jnp
    return float(jnp.max(jnp.abs(a - b)) / jnp.maximum(
        jnp.max(jnp.abs(b)), 1e-30))


def train_step_phase(dp: int, tp: int, tokens: int, d_model: int,
                     d_hidden: int, steps: int, rtol: float) -> None:
    """make_train_step on a (dp, tp) mesh, each step against plain_step on
    one device fed the same inputs."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ucc_tpu.examples.dp_tp_training import make_train_step

    devs = jax.devices()
    mesh = jax.sharding.Mesh(np.array(devs[:dp * tp]).reshape(dp, tp),
                             ("dp", "tp"))
    step = make_train_step(mesh, lr=LR)
    ref = jax.jit(plain_step)
    w1, w2, x, y = step_inputs(tokens, d_model, d_hidden)

    def put(a, spec):
        return jax.device_put(a, NamedSharding(mesh, spec))

    xs, ys = put(x, P("dp", None)), put(y, P("dp", None))
    flops = 12 * tokens * d_model * d_hidden
    losses = []
    for i in range(steps):
        t0 = time.perf_counter()
        nw1, nw2, loss = jax.block_until_ready(
            step(put(w1, P(None, "tp")), put(w2, P("tp", None)), xs, ys))
        secs = time.perf_counter() - t0
        rw1, rw2, rloss = ref(w1, w2, x, y)
        errs = [max_rel(nw1, rw1), max_rel(nw2, rw2), max_rel(loss, rloss)]
        loss_v = float(loss[0, 0])
        check(np.isfinite(loss_v), f"step {i}: loss {loss_v}")
        check(max(errs) <= rtol, f"step {i}: max rel err {errs} > {rtol}")
        losses.append(loss_v)
        rate = "" if i == 0 else f" ({flops / secs / 1e12:.1f} TFLOP/s)"
        log(f"step ({dp},{tp}) {i}: loss={loss_v!r} "
            f"rel_err(w1,w2,loss)={[f'{e:.2e}' for e in errs]} "
            f"time={secs*1e3:.3f} ms{' incl. compile' if i == 0 else ''}"
            f"{rate}")
        w1, w2 = rw1, rw2
    check(all(b < a for a, b in zip(losses, losses[1:])),
          f"loss did not fall: {losses}")
    log(f"step ({dp},{tp}): {steps} steps d_model={d_model} "
        f"d_hidden={d_hidden} tokens={tokens}, loss falls, matches the "
        f"plain step ok")


# ---------------------------------------------------------------------------

def native_status() -> None:
    """The native matcher is built by make from the committed sources
    (ucc_tpu/native.py); say whether it loaded and is fresh."""
    from ucc_tpu import native
    loaded = native.available()
    stale = native._stale()
    log(f"native matcher: {'loaded' if loaded else 'NOT loaded'}, "
        f"stale={stale} ({native._SO_PATH})")
    check(not (loaded and stale), "native matcher loaded from a stale .so")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    from ucc_tpu.utils.backend import enable_compile_cache
    cache = enable_compile_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s), found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 2
    log(f"devices: {len(devs)} x {devs[0].device_kind} "
        f"({devs[0].platform}); compile cache: {cache}")
    from ucc_tpu.obs import metrics
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    metrics.enable(file=os.path.join(out_dir, "chip_smoke_stats.json"))
    native_status()
    t0 = time.perf_counter()
    if args.chips == 4:
        eager_four_chips(64 * MIB)
        train_step_phase(2, 2, TOKENS, D_MODEL, D_HIDDEN, steps=2,
                         rtol=2e-3)
    else:
        eager_one_chip(256 * MIB)
        executor(64 * MIB)
        train_step_phase(1, 1, TOKENS, D_MODEL, D_HIDDEN, steps=STEPS,
                         rtol=1e-4)
    metrics.disable()
    log(f"all phases ok in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
