"""ucc_perftest — collective benchmark CLI.

Mirrors /root/reference/tools/perf (ucc_perftest, ucc_pt_config.h:34-75,
ucc_pt_benchmark.cc:139-171, 392-397): exponential size sweep ``-b..-e``,
warmup + iterations, per-size min/avg/max latency reduced across ranks, and
Bus Bandwidth with ``-F``. Bootstrap differs TPU-natively: instead of
MPI/UCX bootstrap, ranks are either in-process (``-p N``, the default — one
rank per chip via TL/XLA or host ranks via TL/SHM) or multi-process via the
TCP store (``--store host:port --rank R --np N``).

Examples::

    python -m ucc_tpu.tools.perftest -c allreduce -b 8 -e 1M -p 4
    python -m ucc_tpu.tools.perftest -c alltoall -m tpu -F
    python -m ucc_tpu.tools.perftest -c allreduce --store h:29500 --rank 0 --np 8
    python -m ucc_tpu.tools.perftest -c allreduce -O          # one-sided
"""
from __future__ import annotations

import argparse
import gc
import sys
import threading
import time
from typing import List, Optional

import numpy as np

import ucc_tpu
from ucc_tpu import (BufferInfo, CollArgs, CollArgsFlags, CollType, Context,
                     ContextParams, DataType, MemoryType, ReductionOp, Status,
                     TcpStoreOob, TeamParams, ThreadOobWorld)
from ucc_tpu.constants import coll_type_str, dt_numpy, dt_size
from ucc_tpu.utils.config import memunits_str, parse_memunits

COLLS = {coll_type_str(c): c for c in CollType}
#: executor-op benchmarks (ucc_pt_config.h:55-57 MEMCPY/REDUCEDT/
#: REDUCEDT_STRIDED): time the EC component directly, no team involved
OP_BENCHES = ("memcpy", "reducedt", "reducedt_strided")
_TRAFFIC_MATRIX = None


def gen_traffic_matrix(kind: str, n: int, count: int, seed: int):
    """Per-(src,dst) element counts. 'moe' draws a skewed expert-routing
    style distribution (few hot destinations per source), 'uniform' splits
    evenly — the reference's matrix generators (ucc_pt_config.h:98-108)."""
    rng = np.random.default_rng(seed)
    if kind == "moe":
        m = np.zeros((n, n), dtype=np.int64)
        for src in range(n):
            hot = rng.choice(n, size=max(1, n // 4), replace=False)
            weights = rng.dirichlet(np.ones(len(hot)) * 0.5)
            for h, w in zip(hot, weights):
                m[src][h] = int(round(w * count * n))
        return m
    return np.full((n, n), count, dtype=np.int64)
OPS = {o.name.lower(): o for o in ReductionOp}
DTS = {d.name.lower(): d for d in DataType}


def lat_stats(lats) -> dict:
    """avg/min/max plus p50/p99 (microseconds) from second-samples.
    p99 is linearly interpolated (np.percentile default) — with few
    iterations it converges to max, which is the honest reading."""
    a = np.asarray(lats, dtype=np.float64) * 1e6
    return {"avg_us": float(a.mean()), "min_us": float(a.min()),
            "max_us": float(a.max()),
            "p50_us": float(np.percentile(a, 50)),
            "p99_us": float(np.percentile(a, 99))}


def busbw_factor(coll: CollType, n: int) -> float:
    """Bus-bandwidth factors (ucc_pt_benchmark.cc bus bw computation)."""
    if n <= 1:
        return 1.0
    if coll == CollType.ALLREDUCE:
        return 2.0 * (n - 1) / n
    if coll in (CollType.ALLGATHER, CollType.ALLGATHERV,
                CollType.REDUCE_SCATTER, CollType.REDUCE_SCATTERV):
        return float(n - 1) / n
    if coll in (CollType.ALLTOALL, CollType.ALLTOALLV):
        return float(n - 1) / n
    return 1.0


def make_args(coll: CollType, rank: int, n: int, count: int, dt: DataType,
              op: ReductionOp, mem: MemoryType, inplace: bool, root: int,
              persistent: bool, devices=None) -> CollArgs:
    nd = dt_numpy(dt)
    flags = CollArgsFlags(0)
    if inplace:
        flags |= CollArgsFlags.IN_PLACE
    if persistent:
        flags |= CollArgsFlags.PERSISTENT

    def host(shape_count):
        return np.ones(shape_count, dtype=nd)

    def buf(shape_count):
        if mem == MemoryType.TPU:
            import jax
            arr = jax.device_put(host(shape_count),
                                 devices[rank] if devices else None)
            return BufferInfo(arr, shape_count, dt, mem_type=MemoryType.TPU)
        return BufferInfo(host(shape_count), shape_count, dt,
                          mem_type=MemoryType.HOST)

    def out(shape_count):
        if mem == MemoryType.TPU:
            return BufferInfo(None, shape_count, dt, mem_type=MemoryType.TPU)
        return BufferInfo(np.zeros(shape_count, dtype=nd), shape_count, dt,
                          mem_type=MemoryType.HOST)

    from ucc_tpu import BufferInfoV

    def bufv(counts, displs=None):
        total = sum(counts) or 1
        if mem == MemoryType.TPU:
            import jax
            arr = jax.device_put(host(total),
                                 devices[rank] if devices else None)
            return BufferInfoV(arr, list(counts), displs, dt,
                               mem_type=MemoryType.TPU)
        return BufferInfoV(host(total), list(counts), displs, dt,
                           mem_type=MemoryType.HOST)

    def outv(counts, displs=None):
        total = sum(counts) or 1
        if mem == MemoryType.TPU:
            return BufferInfoV(None, list(counts), displs, dt,
                               mem_type=MemoryType.TPU)
        return BufferInfoV(np.zeros(total, dtype=nd), list(counts), displs,
                           dt, mem_type=MemoryType.HOST)

    if coll == CollType.ALLTOALLV:
        # per-pair counts from the traffic matrix (row = what I send)
        if inplace:
            raise SystemExit("perftest: -i is not supported for alltoallv")
        m = _TRAFFIC_MATRIX
        scounts = [int(c) for c in m[rank]]
        rcounts = [int(m[p][rank]) for p in range(n)]
        sdispl = list(np.cumsum([0] + scounts[:-1]))
        rdispl = list(np.cumsum([0] + rcounts[:-1]))
        return CollArgs(
            coll_type=coll, flags=flags,
            src=bufv(scounts, displs=sdispl),
            dst=outv(rcounts, displs=rdispl))
    if coll in (CollType.BARRIER, CollType.FANIN, CollType.FANOUT):
        return CollArgs(coll_type=coll, flags=flags)
    if coll == CollType.ALLREDUCE:
        a = CollArgs(coll_type=coll, op=op, flags=flags)
        if inplace:
            a.dst = buf(count)
            a.src = a.dst
        else:
            a.src = buf(count)
            a.dst = out(count)
        return a
    if coll == CollType.ALLGATHER:
        return CollArgs(coll_type=coll, src=buf(count), dst=out(count * n),
                        flags=flags)
    if coll == CollType.ALLTOALL:
        return CollArgs(coll_type=coll, src=buf(count * n),
                        dst=out(count * n), flags=flags)
    if coll == CollType.BCAST:
        return CollArgs(coll_type=coll, root=root, src=buf(count),
                        flags=flags)
    if coll == CollType.REDUCE:
        return CollArgs(coll_type=coll, root=root, op=op, src=buf(count),
                        dst=out(count) if rank == root else None, flags=flags)
    if coll == CollType.REDUCE_SCATTER:
        return CollArgs(coll_type=coll, op=op, src=buf(count * n),
                        dst=out(count), flags=flags)
    if coll == CollType.GATHER:
        return CollArgs(coll_type=coll, root=root, src=buf(count),
                        dst=out(count * n) if rank == root else None,
                        flags=flags)
    if coll == CollType.SCATTER:
        return CollArgs(coll_type=coll, root=root,
                        src=buf(count * n) if rank == root else None,
                        dst=out(count), flags=flags)
    # v-colls: equal per-rank blocks of `count` (the counts vector is
    # what exercises the v machinery; ucc_perftest does the same)
    if coll == CollType.ALLGATHERV:
        return CollArgs(coll_type=coll, src=buf(count),
                        dst=outv([count] * n), flags=flags)
    if coll == CollType.GATHERV:
        # counts vector on every rank (the device TL derives the launch
        # shape from it); dst buffer lands at root only
        return CollArgs(coll_type=coll, root=root, src=buf(count),
                        dst=outv([count] * n), flags=flags)
    if coll == CollType.SCATTERV:
        return CollArgs(coll_type=coll, root=root,
                        src=bufv([count] * n) if rank == root else None,
                        dst=out(count), flags=flags)
    if coll == CollType.REDUCE_SCATTERV:
        return CollArgs(coll_type=coll, op=op, src=buf(count * n),
                        dst=outv([count] * n), flags=flags)
    raise SystemExit(f"perftest: coll {coll_type_str(coll)} not wired")


def run_op_bench(args) -> int:
    """Executor-op benchmark path (ucc_pt_op_{memcpy,reduce,
    reduce_strided}.cc): times the EC component's copy/reduce tasks
    directly — no team, no transport. BW formulas match the reference:
    memcpy 2*S/t (read+write); reduce (nbufs+1)*S/t (nbufs reads + one
    write)."""
    from ..ec.base import (EXECUTOR_NUM_BUFS, MULTI_OP_NUM_BUFS,
                           create_executor)

    dt = DTS[args.dtype]
    op = OPS[args.op]
    mem = MemoryType.parse(args.mem)
    esz = dt_size(dt)
    nd = dt_numpy(dt)
    nbufs = args.nbufs if args.nbufs is not None else \
        (1 if args.coll == "memcpy" else 2)
    if args.coll == "memcpy":
        # copy_multi's vector cap (ucc_ec_base.h:83) is 7, tighter than
        # the 9-source reduce cap
        if not 1 <= nbufs <= MULTI_OP_NUM_BUFS:
            raise SystemExit("perftest: memcpy needs 1 <= nbufs <= "
                             f"{MULTI_OP_NUM_BUFS}")
    elif not 2 <= nbufs <= EXECUTOR_NUM_BUFS:
        raise SystemExit("perftest: reducedt needs 2 <= nbufs <= "
                         f"{EXECUTOR_NUM_BUFS}")

    if mem == MemoryType.TPU:
        from ..utils.backend import setup_backend
        setup_backend(virtual_cpu_devices=1)
        import jax
        import jax.numpy as jnp
    ec = create_executor(mem)

    def alloc(count):
        if mem == MemoryType.TPU:
            return jnp.ones((count,), jnp.dtype(nd.str)
                            if nd.name != "bfloat16" else jnp.bfloat16)
        return np.ones(count, nd)

    def block(task):
        if mem == MemoryType.TPU:
            import jax
            jax.block_until_ready(task.array)

    if not args.json:
        print(f"# ucc_perftest: {args.coll} {args.dtype}"
              + (f" {args.op}" if args.coll != "memcpy" else "")
              + f" mem={args.mem} nbufs={nbufs}")
        hdr = f"{'count':>12} {'size':>10} {'time avg(us)':>14} " \
              f"{'min(us)':>10} {'max(us)':>10} {'p50(us)':>10} " \
              f"{'p99(us)':>10}"
        if args.full:
            hdr += f" {'bw(GB/s)':>10}"
        print(hdr)

    size = max(parse_memunits(args.begin), esz)
    bmax = parse_memunits(args.end)
    while size <= bmax:
        count = max(1, size // esz)
        nbytes = count * esz
        if args.coll == "memcpy":
            srcs = [alloc(count) for _ in range(nbufs)]
            dsts = [alloc(count) for _ in range(nbufs)]

            def round_fn():
                if nbufs == 1:
                    return ec.copy(dsts[0], srcs[0], nbytes)
                return ec.copy_multi(list(zip(dsts, srcs,
                                              [nbytes] * nbufs)))
            # reference sums ALL copy_multi vectors before the x2
            # read+write factor (ucc_pt_op_memcpy.cc get_bw)
            factor = 2.0 * nbufs
        elif args.coll == "reducedt":
            srcs = [alloc(count) for _ in range(nbufs)]
            dst = alloc(count)

            def round_fn():
                return ec.reduce(dst, srcs, count, dt, op)
            factor = float(nbufs + 1)
        else:                                    # reducedt_strided
            src1 = alloc(count)
            base = alloc(count * (nbufs - 1))
            dst = alloc(count)

            def round_fn():
                return ec.reduce_strided(dst, src1, base, nbytes,
                                         nbufs - 1, count, dt, op)
            factor = float(nbufs + 1)

        lats = []
        for i in range(args.warmup + args.iters):
            t0 = time.perf_counter()
            block(round_fn())
            t1 = time.perf_counter()
            if i >= args.warmup:
                lats.append(t1 - t0)
        st = lat_stats(lats)
        bw = factor * nbytes / (st["avg_us"] / 1e6) / 1e9
        if args.json:
            import json
            rec = {"bench": "op", "op": args.coll, "dtype": args.dtype,
                   "mem": args.mem, "nbufs": nbufs, "count": count,
                   "size_bytes": nbytes,
                   **{k: round(v, 3) for k, v in st.items()},
                   "detail": {"transport": "local"}}
            if args.full:
                rec["bw_GBps"] = round(bw, 3)
            print(json.dumps(rec), flush=True)
        else:
            line = f"{count:>12} {memunits_str(nbytes):>10} " \
                   f"{st['avg_us']:>14.2f} {st['min_us']:>10.2f} " \
                   f"{st['max_us']:>10.2f} {st['p50_us']:>10.2f} " \
                   f"{st['p99_us']:>10.2f}"
            if args.full:
                line += f" {bw:>10.3f}"
            print(line)
        size *= 2
    return 0


def run_sweep_mode(args, job, coll, dt, op, mem, bmin, bmax, n,
                   devices) -> int:
    """--sweep: msg-size x algorithm sweep. Every score-map candidate of
    (coll, mem) is force-selected per size and timed; one JSON line per
    (size, algorithm) in the autotuner's measurement-file format, so
    offline tuning data can come from perftest runs too::

        ucc_perftest -c allreduce --sweep -p 4 > sweep.jsonl
        ucc_tune --from sweep.jsonl -p 4
    """
    import json

    from ..api.types import coll_args_msgsize
    from ..score import cost
    from ..score.tuner import (cand_label, measure_candidate,
                               measurement_record, sweep_candidates)
    # a previously fitted cost model adds a predicted_us column to
    # generated candidates' rows — sweep output doubles as
    # model-calibration data (compare predicted vs p50 per row)
    cost_model = cost.load_model()
    esz = dt_size(dt)
    size = max(bmin, esz)
    while size <= bmax:
        count = max(1, size // esz)
        if coll == CollType.ALLTOALLV:
            global _TRAFFIC_MATRIX
            _TRAFFIC_MATRIX = gen_traffic_matrix(args.matrix or "uniform",
                                                 n, count, args.seed)
        argses = [make_args(coll, r, n, count, dt, op, mem, False,
                            args.root, True, devices) for r in range(n)]
        msgsize = coll_args_msgsize(argses[0], n, 0)
        cands = sweep_candidates(job.teams[0], coll, mem, msgsize)
        for idx in range(len(cands)):
            comp, alg = cand_label(cands[idx])
            lats = measure_candidate(job.teams, job.contexts, argses, coll,
                                     mem, msgsize, idx, args.iters,
                                     args.warmup)
            if lats is None:
                continue    # candidate refused these args / failed / hung
            rec = measurement_record(
                args.coll, mem, n, (comp, alg), size, count, args.iters,
                lat_stats(lats), precision=cands[idx].precision,
                gen=cands[idx].gen,
                predicted_us=cost.predict_for_record(
                    cost_model, cands[idx].gen, n, size))
            rec["detail"] = {"transport": _job_tier(job)}
            print(json.dumps(rec), flush=True)
        size *= 2
    return 0


# ---------------------------------------------------------------------------
# --quant mode: wire-vs-logical busbw + measured error (ISSUE 6 satellite)
# ---------------------------------------------------------------------------

def _quant_verify(job, coll, n, count, dt, mem, devices, budget, seed=5):
    """One verification round on RANDOM data (the timed loops run ones,
    which int8 encodes exactly): returns (selected alg, error-stats
    dict, measured wire bytes). The round runs under
    ``quant.verify.MeasuredBytes`` so the reported wire bytes are the
    transport's actual ``bytes_sent``, not a formula. In-process jobs
    only."""
    from ucc_tpu.constants import dt_numpy as _dtn
    from ucc_tpu.quant.verify import MeasuredBytes, error_stats
    nd = _dtn(dt)
    rng = np.random.default_rng(seed)
    hosts = [(((rng.random(count).astype(np.float32)) - 0.5) * 4)
             .astype(nd) for _ in range(n)]

    def buf(r, arr):
        cnt = arr.size
        if mem == MemoryType.TPU:
            import jax
            a = jax.device_put(arr, devices[r] if devices else None)
            return BufferInfo(a, cnt, dt, mem_type=MemoryType.TPU)
        return BufferInfo(arr.copy(), cnt, dt, mem_type=MemoryType.HOST)

    def out(cnt):
        if mem == MemoryType.TPU:
            return BufferInfo(None, cnt, dt, mem_type=MemoryType.TPU)
        return BufferInfo(np.zeros(cnt, nd), cnt, dt,
                          mem_type=MemoryType.HOST)

    if coll == CollType.ALLREDUCE:
        argses = [CollArgs(coll_type=coll, op=ReductionOp.SUM,
                           src=buf(r, hosts[r]), dst=out(count))
                  for r in range(n)]
        exact = np.sum(np.stack([h.astype(np.float64) for h in hosts]),
                       axis=0)
    else:                                   # ALLGATHER
        argses = [CollArgs(coll_type=coll, src=buf(r, hosts[r]),
                           dst=out(count * n)) for r in range(n)]
        exact = np.concatenate([h.astype(np.float64) for h in hosts])
    with MeasuredBytes() as mb:
        reqs = job.init_reqs(argses)
        alg = str(getattr(reqs[0].task, "alg_name", "") or "")
        job.post_and_wait(reqs)
    stats = error_stats(exact, [a.dst.buffer for a in argses], budget)
    for rq in reqs:
        try:
            rq.finalize()
        except Exception:  # noqa: BLE001 - verification teardown
            pass
    return alg, stats, mb.total


def _quant_detail(job, coll, n, count, dt, mem, devices, bw):
    """The ``detail.quant`` record: effective (wire) vs logical busbw
    plus the measured error and measured wire bytes of one random-data
    round (record shape from quant.verify)."""
    from ucc_tpu import quant as _q
    from ucc_tpu.quant.verify import base_detail
    params = _q.params_for(job.teams[0] if hasattr(job, "teams")
                           else job.team, coll)
    if params is None or coll not in _q.QUANT_COLLS:
        d = {"mode": params.mode if params else "off"}
        d["note"] = "collective not served by quantized variants"
        return d
    d = base_detail(params, coll, count, dt_size(dt), bw, n)
    try:
        alg, stats, wire_total = _quant_verify(job, coll, n, count, dt,
                                               mem, devices,
                                               params.budget)
        d["alg"] = alg
        d.update(stats)
        if wire_total > 0:      # 0 = path not transport-instrumented
            d["measured_wire_bytes_total"] = int(wire_total)
    except Exception as e:  # noqa: BLE001 - verification must not kill
        d["verify_error"] = str(e)
    return d


def run_storm_mode(args, n, dt, op) -> int:
    """``--teams N --storm``: multi-tenant small-collective storm
    (in-process only). N teams share one progress engine: team 0 is the
    latency class (priority 3), the rest are bulk (priority 0). Every
    round each bulk team posts a burst of small allreduces, then the
    latency team posts one — the probe measuring how long a
    high-priority tenant waits behind bulk traffic. Two configurations
    run back to back:

      fifo — every team at the default priority, coalescing off (the
             pre-multi-tenant engine: one lane, every queued burst task
             serviced on every pass)
      qos  — priority lanes + small-collective coalescing on

    Reports p50/p99 per class for each mode plus the high-priority p99
    improvement; one JSON line per mode (and a summary line) with
    ``--json``."""
    import json as _json

    from ..core import coalesce as _coal

    T = args.teams
    esz = dt_size(dt)
    size = max(parse_memunits(args.begin), esz)
    count = max(1, size // esz)
    K = args.storm_burst
    nd = dt_numpy(dt)
    out = {}

    def ar_args():
        return CollArgs(coll_type=CollType.ALLREDUCE, op=op,
                        src=BufferInfo(np.ones(count, nd), count, dt),
                        dst=BufferInfo(np.zeros(count, nd), count, dt))

    prev = (_coal.ENABLED, _coal.LIMIT_BYTES,
            round(_coal.WINDOW_S * 1e6), _coal.MAX_BATCH)
    try:
        for mode in ("fifo", "qos"):
            _coal.configure(enabled=(mode == "qos"))
            job = InProcJob(n)
            teams = []
            try:
                for t in range(T):
                    tw = ThreadOobWorld(n)
                    pr = (3 if t == 0 else 0) if mode == "qos" else None
                    per = [job.contexts[r].create_team_post(
                        TeamParams(oob=tw.endpoint(r), priority=pr))
                        for r in range(n)]
                    deadline = time.monotonic() + 120
                    # the list comprehension (vs a generator) matters:
                    # every rank's create state machine must step each
                    # pass, or the OOB exchange deadlocks
                    while not all([tm.create_test() == Status.OK
                                   for tm in per]):
                        for c in job.contexts:
                            c.progress()
                        if time.monotonic() > deadline:
                            raise SystemExit("storm: team create timed "
                                             "out")
                    teams.append(per)
                lat_hi, lat_bulk = [], []
                for it in range(args.warmup + args.iters):
                    # a gen-2 GC pause mid-probe is multi-ms — collect
                    # between rounds, hold collection during them (same
                    # treatment both modes)
                    gc.collect()
                    gc.disable()
                    t0 = time.perf_counter()
                    bulk = []
                    for t in range(1, T):
                        for _ in range(K):
                            for r in range(n):
                                rq = teams[t][r].collective_init(
                                    ar_args())
                                rq.post()
                                bulk.append(rq)
                    # per-probe latency: clock stops in the completion
                    # callback, not at drain-loop exit — the in-process
                    # driver keeps serving other ranks' bulk queues
                    # inside the same pass, and that trailing service
                    # must not pollute the probe's number (a real
                    # tenant's rank returns as soon as ITS collective
                    # completes)
                    hi_done = [0.0] * n
                    hi_t0 = [0.0] * n

                    def _stamp(i):
                        def _cb(_task, _st):
                            hi_done[i] = time.perf_counter()
                        return _cb

                    hi = []
                    for r in range(n):
                        a = ar_args()
                        a.cb = _stamp(r)
                        hi_t0[r] = time.perf_counter()
                        rq = teams[0][r].collective_init(a)
                        rq.post()
                        hi.append(rq)
                    while any([rq.test() == Status.IN_PROGRESS
                               for rq in hi]):
                        for c in job.contexts:
                            c.progress()
                    while any([rq.test() == Status.IN_PROGRESS
                               for rq in bulk]):
                        for c in job.contexts:
                            c.progress()
                    t3 = time.perf_counter()
                    gc.enable()
                    for rq in hi + bulk:
                        if rq.test().is_error:
                            raise SystemExit(
                                f"storm collective failed: {rq.test()}")
                    if it >= args.warmup:
                        lat_hi.extend(hi_done[r] - hi_t0[r]
                                      for r in range(n))
                        # bulk latency amortized per logical collective
                        lat_bulk.append((t3 - t0) /
                                        max(1, K * (T - 1)))
                rec = {"bench": "storm", "mode": mode, "teams": T,
                       "ranks": n, "burst": K, "size_bytes": size,
                       "iters": args.iters,
                       "detail": {"transport": _job_tier(job)},
                       "classes": {
                           "hi": {"priority": 3 if mode == "qos"
                                  else None,
                                  **{k: round(v, 3) for k, v in
                                     lat_stats(lat_hi).items()}},
                           "bulk": {"priority": 0 if mode == "qos"
                                    else None,
                                    **{k: round(v, 3) for k, v in
                                       lat_stats(lat_bulk).items()}}}}
                if mode == "qos":
                    rec["coalesce_fused_batches"] = sum(
                        tm.coalescer._fused_seq
                        for per in teams for tm in per
                        if tm.coalescer is not None)
                    rec["qos"] = \
                        job.contexts[0].progress_queue.qos_snapshot()
                out[mode] = rec
            finally:
                for per in teams:
                    for tm in per:
                        try:
                            tm.destroy()
                        except Exception:  # noqa: BLE001 - teardown
                            pass
                job.destroy()
    finally:
        _coal.configure(enabled=prev[0], limit=prev[1],
                        window_us=prev[2], max_batch=prev[3])

    imp = out["fifo"]["classes"]["hi"]["p99_us"] / \
        max(1e-9, out["qos"]["classes"]["hi"]["p99_us"])
    summary = {"bench": "storm_summary", "teams": T, "ranks": n,
               "burst": K, "size_bytes": size,
               "hi_p99_fifo_us": out["fifo"]["classes"]["hi"]["p99_us"],
               "hi_p99_qos_us": out["qos"]["classes"]["hi"]["p99_us"],
               "hi_p99_improvement": round(imp, 2),
               "ok": imp >= 2.0}
    if args.json:
        for mode in ("fifo", "qos"):
            print(_json.dumps(out[mode]), flush=True)
        print(_json.dumps(summary), flush=True)
    else:
        print(f"# ucc_perftest storm: {T} teams x {n} ranks, "
              f"burst {K} x {memunits_str(size)}")
        for mode in ("fifo", "qos"):
            for cls in ("hi", "bulk"):
                st = out[mode]["classes"][cls]
                print(f"  {mode:<5} {cls:<5} p50={st['p50_us']:.1f}us "
                      f"p99={st['p99_us']:.1f}us avg={st['avg_us']:.1f}us")
        print(f"  hi-priority p99 improvement: "
              f"{summary['hi_p99_improvement']}x "
              f"({'OK' if summary['ok'] else 'BELOW 2x'})")
    return 0 if summary["ok"] else 1


def transport_tier(team) -> str:
    """Classify the transport tier serving a team's host tag spaces:
    ``pooled`` (ipc arena with one-sided window traffic) > ``ipc``
    (cross-process arena) > ``socket`` > ``shm-thread`` (in-process
    native mailbox). Every JSON record carries this as
    ``detail.transport`` so BENCH deltas attribute the tier rather than
    guessing it from the rank layout."""
    tiers = set()
    pooled = False
    try:
        for _key, tr in team._tl_tag_spaces():
            if getattr(tr, "arena", None) is not None:
                tiers.add("ipc")
                if getattr(tr, "n_pooled", 0) > 0:
                    pooled = True
            elif "Socket" in type(tr).__name__:
                tiers.add("socket")
            else:
                tiers.add("shm-thread")
    except Exception:  # noqa: BLE001 - classification must not kill a run
        return "unknown"
    if pooled:
        return "pooled"
    for t in ("ipc", "socket", "shm-thread"):
        if t in tiers:
            return t
    return "shm-thread"


def _job_tier(job) -> str:
    team = job.teams[0] if getattr(job, "teams", None) else job.team
    return transport_tier(team)


def _free_port_pair() -> int:
    """A base port p where both p and p+1 bind (ctx store + team store)."""
    import socket as _socket
    for _ in range(64):
        s0 = _socket.socket()
        s0.setsockopt(_socket.SOL_SOCKET, _socket.SO_REUSEADDR, 1)
        s0.bind(("127.0.0.1", 0))
        port = s0.getsockname()[1]
        s1 = _socket.socket()
        s1.setsockopt(_socket.SOL_SOCKET, _socket.SO_REUSEADDR, 1)
        try:
            s1.bind(("127.0.0.1", port + 1))
        except OSError:
            continue
        finally:
            s0.close()
            s1.close()
        return port
    raise SystemExit("perftest: no adjacent free port pair")


def run_procs_mode(args, argv) -> int:
    """``--procs N``: self-fork N single-rank worker processes wired by a
    TCP store rendezvous — each child runs the existing ``--store`` path
    and rank 0 inherits stdout, so output (table or JSON lines) is
    identical to a hand-launched multi-process run. The parent is only a
    launcher + reaper. The transport tier the children land on follows
    the ambient UCC_TLS (the ipc arena TL wins by score where enabled)."""
    import os as _os
    import subprocess
    port = _free_port_pair()
    base = list(argv) if argv is not None else sys.argv[1:]
    child_argv = []
    skip = False
    for a in base:
        if skip:
            skip = False
            continue
        if a == "--procs":
            skip = True
            continue
        if a.startswith("--procs="):
            continue
        child_argv.append(a)
    procs = []
    for r in range(args.procs):
        cmd = [sys.executable, "-m", "ucc_tpu.tools.perftest",
               *child_argv, "--store", f"127.0.0.1:{port}",
               "--rank", str(r), "--np", str(args.procs)]
        # host-memory children stay off the chip (one process per chip)
        procs.append(subprocess.Popen(
            cmd, env=dict(_os.environ, JAX_PLATFORMS="cpu"),
            stdout=None if r == 0 else subprocess.DEVNULL))
    rc = 0
    for pr in procs:
        rc = max(rc, pr.wait())
    return rc


def _wait_reqs(job, reqs) -> None:
    from ucc_tpu import Status as _St
    # listified on purpose — a short-circuiting any() would starve the
    # tail ranks' test()-driven work (the UCC_INTEGRITY=verify digest
    # exchange) behind a still-running head rank until its abandon
    # timeout, turning the sampled iterations into 60s stalls
    while any([rq.test() == _St.IN_PROGRESS for rq in reqs]):
        for c in job.contexts:
            c.progress()
    for rq in reqs:
        if rq.test().is_error:
            raise SystemExit(f"collective failed: {rq.test()}")


# ---------------------------------------------------------------------------
# one-sided mode (-O): mem_map + handle exchange (the test/mpi -o role)
# ---------------------------------------------------------------------------

ONESIDED_TUNE = {
    CollType.ALLREDUCE: "allreduce:@sliding_window",
    CollType.ALLTOALL: "alltoall:@onesided",
    CollType.ALLTOALLV: "alltoallv:@onesided",
}


def _allgather_handles(team, handle: bytes, n: int, pad: int = 2048):
    """Distribute exported memh handles across a multi-process team via a
    fixed-size padded allgather (the public-API rkey-exchange shape)."""
    assert len(handle) <= pad - 8
    blob = np.zeros(pad, np.uint8)
    blob[:8] = np.frombuffer(np.int64(len(handle)).tobytes(), np.uint8)
    blob[8:8 + len(handle)] = np.frombuffer(handle, np.uint8)
    out = np.zeros(pad * n, np.uint8)
    req = team.collective_init(CollArgs(
        coll_type=CollType.ALLGATHER,
        src=BufferInfo(blob, pad, DataType.UINT8),
        dst=BufferInfo(out, pad * n, DataType.UINT8)))
    req.post()
    req.wait(timeout=120)
    hs = []
    for p in range(n):
        seg = out[p * pad:(p + 1) * pad]
        ln = int(np.frombuffer(seg[:8].tobytes(), np.int64)[0])
        hs.append(seg[8:8 + ln].tobytes())
    return hs


def attach_onesided(job, argses, coll, ranks, n):
    """mem_map each rank's buffers, exchange handles, and fill the
    global-memh coll args. Returns (ctx, handle) pairs to unmap."""
    to_unmap = []

    def map_exchange(get_bi):
        local = []
        for i, _ in enumerate(ranks):
            ctx = job.contexts[i] if len(job.contexts) > 1 \
                else job.contexts[0]
            h = ctx.mem_map(get_bi(argses[i]).buffer)
            local.append(h)
            to_unmap.append((ctx, h))
        if len(ranks) == n:
            return local                       # in-process: global view
        return _allgather_handles(job.team, local[0], n)

    dst_handles = map_exchange(lambda a: a.dst)
    for a in argses:
        a.dst_memh = list(dst_handles)
        a.flags |= CollArgsFlags.MEM_MAP_DST_MEMH
    if coll == CollType.ALLREDUCE:
        if argses[0].src is argses[0].dst:     # inplace: one mapping
            src_handles = dst_handles
        else:
            src_handles = map_exchange(lambda a: a.src)
        for a in argses:
            a.src_memh = list(src_handles)
            a.flags |= CollArgsFlags.MEM_MAP_SRC_MEMH
    if coll == CollType.ALLTOALLV:
        # onesided a2av displacements are TARGET-relative
        # (alltoallv_onesided.c convention; see tl/host/onesided.py)
        m = _TRAFFIC_MATRIX
        for i, r in enumerate(ranks):
            argses[i].dst.displacements = [
                int(sum(m[q][p] for q in range(r))) for p in range(n)]
    return to_unmap


class InProcJob:
    persistent_capable = True

    def __init__(self, n: int, lib_overrides: Optional[dict] = None,
                 create_timeout: float = 120.0):
        self.n = n
        world = ThreadOobWorld(n)
        self.libs = [ucc_tpu.init(**(lib_overrides or {}))
                     for _ in range(n)]
        self.contexts: List[Optional[Context]] = [None] * n
        errs: List[Exception] = []

        def mk(r):
            try:
                self.contexts[r] = Context(
                    self.libs[r], ContextParams(oob=world.endpoint(r)))
            except Exception as e:  # noqa: BLE001 - surfaced below
                errs.append(e)

        ths = [threading.Thread(target=mk, args=(r,)) for r in range(n)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=create_timeout)
        if errs:
            raise errs[0]
        if any(c is None for c in self.contexts):
            # a create thread is still wedged (e.g. a stuck TL probe):
            # report the timeout instead of crashing on the None later
            raise SystemExit("context create timed out")
        tw = ThreadOobWorld(n)
        self.teams = [c.create_team_post(TeamParams(oob=tw.endpoint(i)))
                      for i, c in enumerate(self.contexts)]
        deadline = time.monotonic() + create_timeout
        while True:
            sts = [t.create_test() for t in self.teams]
            if all(s == Status.OK for s in sts):
                break
            if any(s.is_error for s in sts) or \
                    time.monotonic() > deadline:
                raise SystemExit("team create failed")
            for c in self.contexts:
                c.progress()

    def destroy(self) -> None:
        self.destroy_ees()
        for t in self.teams:
            try:
                t.destroy()
            except Exception:  # noqa: BLE001 - teardown is best-effort
                pass
        for c in self.contexts:
            try:
                c.destroy()
            except Exception:  # noqa: BLE001
                pass

    def init_reqs(self, argses):
        return [self.teams[r].collective_init(argses[r])
                for r in range(self.n)]

    def post_and_wait(self, reqs) -> None:
        for rq in reqs:
            rq.post()
        # listified: every rank's test() must run each pass (it drives
        # the verify-mode attestation exchange; see _wait_reqs)
        while any([rq.test() == Status.IN_PROGRESS for rq in reqs]):
            for c in self.contexts:
                c.progress()
        for rq in reqs:
            if rq.test().is_error:
                raise SystemExit(f"collective failed: {rq.test()}")

    def run_round(self, argses) -> None:
        self.post_and_wait(self.init_reqs(argses))

    # -- triggered-post mode (ucc_pt_benchmark.cc:217-246) ---------------
    _ees = None

    def post_and_wait_triggered(self, reqs) -> None:
        """Post through execution engines: each rank's collective fires
        off a compute_complete event (ucc_collective_triggered_post), the
        timed region covering event signal -> EE dispatch -> completion."""
        from ucc_tpu.core.ee import Ee, UccEvent
        if self._ees is None:
            self._ees = [Ee(t) for t in self.teams]
        for r, rq in enumerate(reqs):
            ev = UccEvent("compute_complete")
            self._ees[r].triggered_post(ev, rq)
            self._ees[r].set_event(ev)
        while any([rq.test() in (Status.IN_PROGRESS,
                                 Status.OPERATION_INITIALIZED)
                   for rq in reqs]):
            for c in self.contexts:
                c.progress()
        for rq in reqs:
            if rq.test().is_error:
                raise SystemExit(f"collective failed: {rq.test()}")

    def destroy_ees(self) -> None:
        if self._ees:
            for ee in self._ees:
                ee.destroy()
            self._ees = None


class StoreJob:
    """One rank of a multi-process run."""

    def __init__(self, host: str, port: int, rank: int, n: int):
        self.n = 1
        self.rank = rank
        oob = TcpStoreOob(rank, n, host=host, port=port)
        self.lib = ucc_tpu.init()
        self.ctx = Context(self.lib, ContextParams(oob=oob))
        self.contexts = [self.ctx]
        team_oob = TcpStoreOob(rank, n, host=host, port=port + 1)
        self.team = self.ctx.create_team(TeamParams(oob=team_oob))
        self.world_n = n

    persistent_capable = True

    def init_reqs(self, argses):
        return [self.team.collective_init(argses[0])]

    def post_and_wait(self, reqs) -> None:
        reqs[0].post()
        reqs[0].wait(timeout=120)

    def run_round(self, argses) -> None:
        self.post_and_wait(self.init_reqs(argses))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="ucc_perftest")
    p.add_argument("-c", "--coll", default="allreduce",
                   choices=sorted(COLLS) + list(OP_BENCHES))
    p.add_argument("-b", "--begin", default="8", help="min size (bytes)")
    p.add_argument("-e", "--end", default="1M", help="max size (bytes)")
    p.add_argument("-n", "--iters", type=int, default=20)
    p.add_argument("-w", "--warmup", type=int, default=5)
    p.add_argument("-m", "--mem", default="host",
                   help="memory type: host/tpu (cuda aliases tpu)")
    p.add_argument("-d", "--dtype", default="float32", choices=sorted(DTS))
    p.add_argument("-o", "--op", default="sum", choices=sorted(OPS))
    p.add_argument("-r", "--root", type=int, default=0)
    p.add_argument("-i", "--inplace", action="store_true")
    p.add_argument("-F", "--full", action="store_true",
                   help="print bus bandwidth column")
    p.add_argument("--json", action="store_true",
                   help="one JSON line per size (machine-readable: "
                        "avg/min/max/p50/p99 us + busbw with -F) instead "
                        "of the latency table")
    p.add_argument("--sweep", action="store_true",
                   help="msg-size x algorithm sweep: force every "
                        "score-map candidate per size and emit one JSON "
                        "measurement line per (size, algorithm) — the "
                        "ucc_tune offline-tuning input format (compile "
                        "with `ucc_tune --from FILE`); in-process only")
    p.add_argument("--quant", nargs="?", const="env", default="",
                   choices=["env", "int8", "fp8"],
                   help="quantized mode (in-process only): report "
                        "effective (wire) vs logical busbw and the "
                        "measured max-abs/rel error of a random-data "
                        "round per point (detail.quant with --json). An "
                        "explicit int8/fp8 value sets UCC_QUANT for this "
                        "run; bare --quant uses the ambient UCC_QUANT "
                        "(defaulting to int8)")
    p.add_argument("--gen", nargs="?", const="all", default="",
                   metavar="FAMILIES",
                   help="register GENERATED candidates (ucc_tpu/dsl) "
                        "for this run: sets UCC_GEN=y before lib "
                        "creation; an optional value restricts the "
                        "family grids (UCC_GEN_FAMILIES syntax). With "
                        "--sweep, generated candidates are swept and "
                        "emitted in the same measurement-record format "
                        "(rows carry their gen family/parameter string)")
    p.add_argument("--gen-device", nargs="?", const="all", default="",
                   metavar="FAMILIES",
                   help="register GENERATED-DEVICE candidates "
                        "(ucc_tpu/dsl/lower_device) for this run: sets "
                        "UCC_GEN_DEVICE=y before lib creation; an "
                        "optional value restricts the device family "
                        "grids (UCC_GEN_DEVICE_FAMILIES syntax). With "
                        "--sweep -m tpu, gen_dev_* candidates are "
                        "swept alongside the monolithic lax programs "
                        "and their rows carry the gen param string + "
                        "origin provenance")
    p.add_argument("-p", "--nprocs", type=int, default=0,
                   help="in-process ranks (default: one per device for tpu "
                        "mem, else 4)")
    p.add_argument("--persistent", action="store_true",
                   help="persistent collectives (init once, post many)")
    p.add_argument("-S", "--streaming", action="store_true",
                   help="streaming mode: post every iteration before "
                        "waiting (throughput), vs default isolated mode "
                        "(per-op latency) — ucc_pt_config.h:72-75")
    p.add_argument("--matrix", default="", choices=["", "uniform", "moe"],
                   help="alltoallv traffic-matrix generator "
                        "(ucc_pt_config.h:98-108 MoE-style skew)")
    p.add_argument("-O", "--onesided", action="store_true",
                   help="one-sided algorithms over mem-mapped buffers "
                        "(host mem; allreduce->sliding_window, "
                        "alltoall(v)->onesided put — the test/mpi -o role)")
    p.add_argument("-T", "--triggered", action="store_true",
                   help="post through execution engines (triggered-post "
                        "lifecycle, ucc_pt_benchmark.cc:217-246; "
                        "in-process jobs only)")
    p.add_argument("--nbufs", type=int, default=None,
                   help="buffer count for the executor-op benchmarks "
                        "(memcpy/reducedt/reducedt_strided; default 1 "
                        "copy / 2 reduce sources; caps 7 copy / 9 "
                        "reduce, ucc_ec_base.h)")
    p.add_argument("--teams", type=int, default=0,
                   help="multi-tenant mode: number of concurrent teams "
                        "sharing the progress engine (with --storm)")
    p.add_argument("--storm", action="store_true",
                   help="multi-tenant small-collective storm (needs "
                        "--teams >= 2; in-process only): bulk teams "
                        "flood bursts of small allreduces while a "
                        "latency-class team posts probes; reports "
                        "p50/p99 per priority class for a FIFO/no-"
                        "coalesce baseline vs priority lanes + "
                        "coalescing, and the hi-priority p99 "
                        "improvement (exit 0 iff >= 2x)")
    p.add_argument("--storm-burst", type=int, default=24,
                   help="small allreduces each bulk team posts per "
                        "round in --storm (default 24 — deep enough "
                        "that FIFO head-of-line blocking dominates the "
                        "probe latency)")
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--store", default="", help="host:port for multi-process")
    p.add_argument("--rank", type=int, default=0)
    p.add_argument("--np", type=int, dest="world", default=1)
    p.add_argument("--procs", type=int, default=0,
                   help="spawn N worker PROCESSES (one rank each) wired "
                        "by an automatic TCP store rendezvous — the "
                        "multi-process twin of -p, exercising the "
                        "cross-process transport (ipc arena where "
                        "enabled, else socket). Rank 0's output is "
                        "printed; other ranks are silenced")
    args = p.parse_args(argv)

    if args.procs:
        if args.store:
            raise SystemExit("perftest: --procs and --store are exclusive "
                             "(--procs launches --store workers itself)")
        if args.sweep or args.storm or args.quant or args.gen \
                or args.gen_device:
            raise SystemExit("perftest: --procs is incompatible with the "
                             "in-process-only modes (--sweep/--storm/"
                             "--quant/--gen/--gen-device)")
        if MemoryType.parse(args.mem) == MemoryType.TPU:
            # one process per chip: N children cannot share one device
            raise SystemExit("perftest: --procs runs host memory only "
                             "(-m tpu needs one process per chip)")
        return run_procs_mode(args, argv)

    # shared across the collective and executor-op paths: negative
    # warmup skews the timed-round bookkeeping silently, zero iters
    # divides by zero
    if args.iters < 1:
        raise SystemExit("perftest: -n must be >= 1")
    if args.warmup < 0:
        raise SystemExit("perftest: -w must be >= 0")

    if args.coll in OP_BENCHES:
        return run_op_bench(args)

    if args.quant:
        # set the precision BEFORE lib/context creation: the quantized
        # candidates register at team create from the lib config
        import os as _os
        if args.quant in ("int8", "fp8"):
            _os.environ["UCC_QUANT"] = args.quant
        elif not _os.environ.get("UCC_QUANT"):
            _os.environ["UCC_QUANT"] = "int8"
        if args.store:
            raise SystemExit("perftest: --quant requires in-process mode")

    if args.gen:
        # same contract as --quant: generated candidates register at
        # team create from the lib config, so the env must be set first
        # — and only in-process, where every rank shares it (per-rank
        # env divergence would desync candidate tables and deadlock)
        import os as _os
        _os.environ["UCC_GEN"] = "y"
        if args.gen != "all":
            _os.environ["UCC_GEN_FAMILIES"] = args.gen
        if args.store:
            raise SystemExit("perftest: --gen requires in-process mode")

    if args.gen_device:
        # same register-before-lib-create contract as --gen/--quant
        import os as _os
        _os.environ["UCC_GEN_DEVICE"] = "y"
        if args.gen_device != "all":
            _os.environ["UCC_GEN_DEVICE_FAMILIES"] = args.gen_device
        if args.store:
            raise SystemExit("perftest: --gen-device requires "
                             "in-process mode")

    global _TRAFFIC_MATRIX
    coll = COLLS[args.coll]
    dt = DTS[args.dtype]
    op = OPS[args.op]
    mem = MemoryType.parse(args.mem)
    bmin = parse_memunits(args.begin)
    bmax = parse_memunits(args.end)
    esz = dt_size(dt)

    if args.onesided:
        if mem != MemoryType.HOST:
            raise SystemExit("perftest: -O/--onesided requires -m host "
                             "(no HBM RDMA window over DCN)")
        if coll not in ONESIDED_TUNE:
            raise SystemExit("perftest: -O supports "
                             + "/".join(coll_type_str(c)
                                        for c in ONESIDED_TUNE))
        if args.inplace and coll != CollType.ALLREDUCE:
            raise SystemExit("perftest: -O -i only for allreduce")
        if args.streaming or args.triggered:
            # concurrent one-sided rounds would overlap puts into the
            # same mapped segments; triggered rebuilds fresh buffers
            raise SystemExit("perftest: -O is incompatible with -S/-T")
        import os as _os
        for tl in ("SHM", "SOCKET"):
            _os.environ.setdefault(f"UCC_TL_{tl}_TUNE", ONESIDED_TUNE[coll])

    # initialise the backend once, before context threads race into
    # device discovery; JAX_PLATFORMS=cpu runs get a device per rank
    from ..utils.backend import setup_backend
    setup_backend(virtual_cpu_devices=max(args.nprocs, 8))

    devices = None
    if mem == MemoryType.TPU:
        import jax
        devices = jax.devices()

    if args.storm:
        if args.store:
            raise SystemExit("perftest: --storm requires in-process mode")
        if args.teams < 2:
            raise SystemExit("perftest: --storm needs --teams >= 2")
        return run_storm_mode(args, args.nprocs or 4, dt, op)

    if args.store:
        host, port_s = args.store.rsplit(":", 1)
        job = StoreJob(host, int(port_s), args.rank, args.world)
        n = job.world_n
        ranks = [args.rank]
        is_lead = args.rank == 0
    else:
        n = args.nprocs or (len(devices) if devices else 4)
        job = InProcJob(n)
        ranks = list(range(n))
        is_lead = True

    if args.sweep:
        if args.store:
            raise SystemExit("perftest: --sweep requires in-process mode "
                             "(each candidate is force-selected by score-"
                             "map index on every rank)")
        if args.onesided or args.streaming or args.triggered:
            raise SystemExit("perftest: --sweep is incompatible with "
                             "-O/-S/-T")
        return run_sweep_mode(args, job, coll, dt, op, mem, bmin, bmax, n,
                              devices)

    tier = _job_tier(job)
    if is_lead and not args.json:
        hdr = f"{'count':>12} {'size':>10} {'time avg(us)':>14} " \
              f"{'min(us)':>10} {'max(us)':>10} {'p50(us)':>10} " \
              f"{'p99(us)':>10}"
        if args.full:
            hdr += f" {'bus bw(GB/s)':>14}"
        print(f"# ucc_perftest: {args.coll} {args.dtype} {args.op} "
              f"mem={args.mem} ranks={n} transport={tier}")
        print(hdr)

    size = max(bmin, esz)
    while size <= bmax:
        count = max(1, size // esz)
        if coll == CollType.ALLTOALLV:
            _TRAFFIC_MATRIX = gen_traffic_matrix(args.matrix or "uniform",
                                                 n, count, args.seed)
        lats = []
        rounds = args.warmup + args.iters
        persistent_reqs = None
        os_argses = None
        os_unmap = []
        if args.persistent or args.onesided:
            # init once, post many (ucc.h:1674 persistent semantics);
            # measured time then excludes collective_init. One-sided mode
            # also builds args once per size: buffers are mem_mapped and
            # handles exchanged before the timed rounds (the rkey-exchange
            # setup cost is out-of-band, like the reference's onesided
            # benchmarks)
            argses = [make_args(coll, r, n, count, dt, op, mem,
                                args.inplace, args.root, args.persistent,
                                devices)
                      for r in ranks]
            if args.onesided:
                os_unmap = attach_onesided(job, argses, coll, ranks, n)
                os_argses = argses
            if args.persistent:
                persistent_reqs = job.init_reqs(argses)
        if args.streaming and persistent_reqs is None:
            # streaming: init+post everything, single wait at the end;
            # reported number is per-op amortized time
            all_argses = [[make_args(coll, r, n, count, dt, op, mem,
                                     args.inplace, args.root, False,
                                     devices) for r in ranks]
                          for _ in range(rounds)]
            all_reqs = [job.init_reqs(a) for a in all_argses[:args.warmup]]
            for reqs_ in all_reqs:
                job.post_and_wait(reqs_)
            t0 = time.perf_counter()
            inflight = [job.init_reqs(a) for a in all_argses[args.warmup:]]
            for reqs_ in inflight:
                for rq in reqs_:
                    rq.post()
            for reqs_ in inflight:
                _wait_reqs(job, reqs_)
            total = time.perf_counter() - t0
            lats = np.array([total / max(1, args.iters)])
        else:
            for it in range(rounds):
                t0 = time.perf_counter()
                if args.triggered:
                    # triggered-post lifecycle: fresh request dispatched
                    # by an execution engine on an event signal; a fresh
                    # request per round keeps the completion observable
                    # (OPERATION_INITIALIZED -> OK) without racing the EE
                    # thread (ucc_pt_benchmark.cc:217-246)
                    argses = [make_args(coll, r, n, count, dt, op, mem,
                                        args.inplace, args.root, False,
                                        devices) for r in ranks]
                    reqs_t = job.init_reqs(argses)
                    t0 = time.perf_counter()
                    job.post_and_wait_triggered(reqs_t)
                elif persistent_reqs is not None:
                    job.post_and_wait(persistent_reqs)
                else:
                    if os_argses is not None:
                        argses = os_argses
                    else:
                        argses = [make_args(coll, r, n, count, dt, op, mem,
                                            args.inplace, args.root, False,
                                            devices) for r in ranks]
                    t0 = time.perf_counter()
                    job.run_round(argses)
                dt_s = time.perf_counter() - t0
                if it >= args.warmup:
                    lats.append(dt_s)
        lats = np.array(lats)
        if is_lead:
            st = lat_stats(lats)
            bw = busbw_factor(coll, n) * size / lats.mean() / 1e9
            qd = None
            if args.quant:
                qd = _quant_detail(job, coll, n, count, dt, mem, devices,
                                   bw)
            if args.json:
                import json
                rec = {"bench": "coll", "coll": args.coll,
                       "dtype": args.dtype, "op": args.op, "mem": args.mem,
                       "ranks": n, "count": count, "size_bytes": size,
                       "iters": args.iters,
                       **{k: round(v, 3) for k, v in st.items()}}
                from .. import integrity as _integ
                if _integ.ENABLED:
                    # overhead numbers are meaningless without the mode
                    # that produced them on the record
                    rec["integrity"] = _integ.MODE
                if args.full:
                    rec["busbw_GBps"] = round(bw, 3)
                # tier re-sampled per size: pooled only shows once a
                # one-sided window variant has actually moved traffic
                rec["detail"] = {"transport": _job_tier(job)}
                if qd is not None:
                    rec["detail"]["quant"] = qd
                print(json.dumps(rec), flush=True)
            else:
                line = f"{count:>12} {memunits_str(size):>10} " \
                       f"{st['avg_us']:>14.2f} {st['min_us']:>10.2f} " \
                       f"{st['max_us']:>10.2f} {st['p50_us']:>10.2f} " \
                       f"{st['p99_us']:>10.2f}"
                if args.full:
                    line += f" {bw:>14.3f}"
                print(line, flush=True)
                if qd is not None and "wire_ratio" in qd:
                    print(f"#   quant[{qd['mode']}] alg={qd.get('alg', '?')}"
                          f" wire_ratio={qd['wire_ratio']}"
                          f" busbw_wire={qd.get('busbw_wire_GBps', 0)}GB/s"
                          f" max_rel_err={qd.get('max_rel_err', '?')}"
                          f" (budget {qd['error_budget']})", flush=True)
        for ctx, h in os_unmap:
            ctx.mem_unmap(h)
        size *= 2
    return 0


def cli() -> int:
    """Command-line entry: ``main`` with the persistent compile cache on
    (tests call ``main`` directly and keep JAX's default of no cache)."""
    from ..utils.backend import enable_compile_cache
    enable_compile_cache()
    return main()


if __name__ == "__main__":
    sys.exit(cli())
