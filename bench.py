"""Benchmark: collective bus bandwidth through the full ucc_tpu stack vs raw
jax.lax collectives on the same devices (BASELINE.md north star: within 10%
of raw psum). Prints ONE JSON line.

Runs on the TPU chips of this host and exits non-zero without one: a
CPU number is not a device measurement. Uses true persistent
collectives (init once, post many — ucc.h:1674) with HBM-resident jax
buffers: the TL's launch cache reuses the device-resident global array +
AOT-compiled program on every re-post, matching how
`ucc_perftest -c allreduce` measures the reference
(ucc_pt_benchmark.cc:139-171).

`python bench.py --sweep` additionally prints one JSON line per
(collective, size) point (allreduce 8B..64MiB + alltoall) for BASELINE.md.
"""
from __future__ import annotations

import json
import time

import numpy as np


def _busbw(coll: str, nbytes: int, n: int, seconds: float) -> float:
    """ucc_perftest bus-bandwidth formulas (ucc_pt_benchmark.cc:392):
    allreduce moves 2*(n-1)/n of the vector per chip; alltoall (n-1)/n."""
    if n <= 1:
        factor = 1.0
    elif coll == "alltoall":
        factor = (n - 1) / n
    else:
        factor = 2.0 * (n - 1) / n
    return factor * nbytes / seconds / 1e9


def _make_job(n):
    """Full-stack job: one lib/context per rank, one team over all ranks.
    Returns (ctxs, teams, create_s) — team-create latency rides every
    bench record's detail so the scale trajectory (ISSUE 8: bootstrap +
    activation cost) is tracked across rounds like busbw."""
    import threading

    import ucc_tpu
    from ucc_tpu import ContextParams, Status, TeamParams, ThreadOobWorld

    world = ThreadOobWorld(n)
    libs = [ucc_tpu.init() for _ in range(n)]
    ctxs: list = [None] * n

    def mk(r):
        ctxs[r] = ucc_tpu.Context(libs[r], ContextParams(oob=world.endpoint(r)))

    ths = [threading.Thread(target=mk, args=(r,)) for r in range(n)]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    t0 = time.perf_counter()
    tw = ThreadOobWorld(n)
    teams = [c.create_team_post(TeamParams(oob=tw.endpoint(i)))
             for i, c in enumerate(ctxs)]
    while True:
        sts = [t.create_test() for t in teams]
        for c in ctxs:
            c.progress()
        if all(s == Status.OK for s in sts):
            break
    return ctxs, teams, time.perf_counter() - t0


def _persistent_reqs(coll: str, teams, ctxs, srcs, count: int, n: int):
    from ucc_tpu import (BufferInfo, CollArgs, CollArgsFlags, CollType,
                         DataType, MemoryType, ReductionOp)
    ct = {"allreduce": CollType.ALLREDUCE,
          "alltoall": CollType.ALLTOALL}[coll]
    argses = [CollArgs(
        coll_type=ct,
        src=BufferInfo(srcs[r], count, DataType.FLOAT32,
                       mem_type=MemoryType.TPU),
        dst=BufferInfo(None, count, DataType.FLOAT32,
                       mem_type=MemoryType.TPU),
        op=ReductionOp.SUM if coll == "allreduce" else None,
        flags=CollArgsFlags.PERSISTENT) for r in range(n)]
    reqs = [teams[r].collective_init(argses[r]) for r in range(n)]
    return argses, reqs


def _measure_point(coll: str, count: int, ctxs, teams, devices, mesh,
                   iters: int, warmup: int):
    """Interleaved medians of (raw lax collective, full ucc stack) for one
    (collective, per-rank element count) point. Interleaving matters: this
    box's run-to-run drift (shared CPU, cache/thermal state) exceeds the
    effect being measured, so both sides must sample the same conditions."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ucc_tpu import Status

    n = len(devices)
    nbytes = count * 4

    # flat 1-D layout for the raw program too (measured equivalent to the
    # (n, count) 2-D form, and tiny counts avoid XLA sharding overrides)
    if coll == "allreduce":
        def body(x):          # x: (count,) flat shard
            return jax.lax.psum(x[None, :], "r")[0]
    else:
        def body(x):
            return jax.lax.all_to_all(x.reshape(n, count // n), "r",
                                      split_axis=0, concat_axis=0,
                                      tiled=False).reshape(count)

    raw = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("r"),
                                out_specs=P("r"), check_vma=False))
    garr = jax.make_array_from_single_device_arrays(
        (n * count,), NamedSharding(mesh, P("r")),
        [jax.device_put(jnp.ones((count,), jnp.float32), d)
         for d in devices])

    def raw_round():
        jax.block_until_ready(raw(garr))

    from ucc_tpu.mc.pool import host_pool
    point_start = host_pool().stats()
    srcs = [jax.device_put(jnp.ones((count,), jnp.float32), devices[r])
            for r in range(n)]
    argses, reqs = _persistent_reqs(coll, teams, ctxs, srcs, count, n)
    # which algorithm the score map selected for this point (ISSUE 5
    # satellite): read back from the dispatched task so BENCH_r*.json
    # trajectories can attribute busbw changes to selection changes.
    # Generated/searched programs additionally record their full
    # provenance (ISSUE 14 satellite): the family/parameter string and
    # the selection origin, so "gen_ring_c3[searched ring(chunks=3)]"
    # in detail.alg names the exact synthesized program that ran
    alg = str(getattr(reqs[0].task, "alg_name", "") or "")
    prog = getattr(reqs[0].task, "prog", None)
    if prog is not None and alg:
        origin = str(getattr(reqs[0].task, "gen_origin", "") or "")
        try:
            from ucc_tpu.constants import CollType as _CT
            from ucc_tpu.constants import MemoryType as _MT
            ct = {"allreduce": _CT.ALLREDUCE,
                  "alltoall": _CT.ALLTOALL}[coll]
            for cand in teams[0].score_map.lookup(ct, _MT.TPU, nbytes):
                if cand.alg_name != alg:
                    continue
                if not origin or origin == "tune-str":
                    # a TUNE pin overlays the registered range: keep
                    # walking for the registration origin (generated/
                    # generated-device/searched) — "gen_dev_ring_c2
                    # [generated-device ring(chunks=2)]" names how the
                    # program came to exist, not how it was selected
                    origin = cand.origin
                if origin and origin != "tune-str":
                    break
        except Exception:  # noqa: BLE001 - provenance is best-effort
            pass
        alg = f"{alg}[{origin or 'generated'} {prog.param_str}]"

    def one_round():
        for rq in reqs:
            rq.post()
        while any(rq.test() == Status.IN_PROGRESS for rq in reqs):
            for c in ctxs:
                c.progress()
        # device-mem collectives complete at dispatch (stream-ordered);
        # hard completion = readiness of the launch's global output — the
        # SAME object the raw loop blocks on (one block per process, which
        # is also the real per-process cost: the in-process 8-rank job
        # would otherwise pay 8x the block overhead no real deployment has)
        glob = getattr(reqs[0].task, "_out", None)
        jax.block_until_ready(
            glob if glob is not None else [a.dst.buffer for a in argses])

    for _ in range(warmup):
        raw_round()
        one_round()
    # memory behavior alongside busbw: pool misses that grow during the
    # timed (steady-state) loop are per-iteration allocations the mpool
    # failed to absorb — 0 is the healthy reading (ISSUE 3 satellite).
    # All numbers are PER-POINT deltas (a --sweep record must not carry
    # earlier points' cumulative hits in its hit_rate).
    pool0 = host_pool().stats()
    raw_samples, ucc_samples = [], []
    for _ in range(iters):
        t0 = time.perf_counter()
        raw_round()
        t1 = time.perf_counter()
        one_round()
        t2 = time.perf_counter()
        raw_samples.append(t1 - t0)
        ucc_samples.append(t2 - t1)
    pool1 = host_pool().stats()
    for rq in reqs:
        rq.finalize()
    raw_samples.sort()
    ucc_samples.sort()
    raw_time = raw_samples[len(raw_samples) // 2]
    ucc_time = ucc_samples[len(ucc_samples) // 2]
    hits = pool1["hits"] - point_start["hits"]
    misses = pool1["misses"] - point_start["misses"]
    lookups = hits + misses
    pool_stats = {
        "hit": hits, "miss": misses,
        "hit_rate": round(hits / lookups, 4) if lookups else 0.0,
        "steady_state_allocs": pool1["misses"] - pool0["misses"],
    }
    return (ucc_time, raw_time, _busbw(coll, nbytes, n, ucc_time),
            _busbw(coll, nbytes, n, raw_time), pool_stats, alg)


def _enable_quant() -> str:
    """--quant: arm UCC_QUANT (default int8) BEFORE lib/context creation
    and pin the device path to the quantized program (it registers below
    the exact default, tuner-promoted on real fabrics — the bench mode
    exists to measure it explicitly). Returns the mode."""
    import os
    mode = os.environ.get("UCC_QUANT", "").strip().lower()
    if mode not in ("int8", "fp8"):
        mode = "int8"
    os.environ["UCC_QUANT"] = mode
    os.environ.setdefault("UCC_TL_XLA_TUNE",
                          f"allreduce:@q{mode}#allgather:@q{mode}")
    return mode


def _quant_detail(teams, ctxs, devices, count: int, busbw: float) -> dict:
    """detail.quant for a bench record: the shared quant.verify record
    (same shape ucc_perftest --quant emits and the gate smoke reads)
    filled from one random-data verification round on device buffers
    (the timed loop runs ones, which int8 encodes exactly)."""
    import jax
    import jax.numpy as jnp

    import numpy as np
    from ucc_tpu import (BufferInfo, CollArgs, CollType, DataType,
                         MemoryType, ReductionOp, Status)
    from ucc_tpu import quant as _q
    from ucc_tpu.quant.verify import (MeasuredBytes, base_detail,
                                      error_stats)

    n = len(teams)
    params = _q.params_for(teams[0], CollType.ALLREDUCE)
    if params is None:
        return {"mode": "off"}
    d = base_detail(params, CollType.ALLREDUCE, count, 4, busbw, n)
    rng = np.random.default_rng(9)
    hosts = [((rng.random(count).astype(np.float32)) - 0.5) * 4
             for _ in range(n)]
    srcs = [jax.device_put(jnp.asarray(hosts[r]), devices[r])
            for r in range(n)]
    argses = [CollArgs(
        coll_type=CollType.ALLREDUCE,
        src=BufferInfo(srcs[r], count, DataType.FLOAT32,
                       mem_type=MemoryType.TPU),
        dst=BufferInfo(None, count, DataType.FLOAT32,
                       mem_type=MemoryType.TPU),
        op=ReductionOp.SUM) for r in range(n)]
    with MeasuredBytes() as mb:
        reqs = [teams[r].collective_init(argses[r]) for r in range(n)]
        d["alg"] = str(getattr(reqs[0].task, "alg_name", "") or "")
        for rq in reqs:
            rq.post()
        while any(rq.test() == Status.IN_PROGRESS for rq in reqs):
            for c in ctxs:
                c.progress()
    exact = np.sum(np.stack(hosts).astype(np.float64), axis=0)
    d.update(error_stats(exact, [np.asarray(a.dst.buffer)
                                 for a in argses], params.budget))
    if mb.total > 0:            # 0 = device path, not host-instrumented
        d["measured_wire_bytes_total"] = int(mb.total)
    for rq in reqs:
        rq.finalize()
    return d


def _enable_gen_device() -> None:
    """--gen-device: arm UCC_GEN_DEVICE BEFORE lib/context creation and
    pin the device allreduce to a generated-device ring (they register
    at a low score, tuner-promoted in production — the bench mode
    measures one explicitly; detail.alg then records the full
    provenance, e.g. ``gen_dev_ring_c2[generated-device
    ring(chunks=2)]``)."""
    import os
    os.environ["UCC_GEN_DEVICE"] = "y"
    # pin only when generated-device candidates will actually register
    # (2..MAX_DEVICE_RANKS devices): a TUNE string naming an
    # unregistered algorithm fails team CREATE — a 1-chip box (the real
    # TPU probe host) must fall back to the plain bench, not crash
    import jax
    from ucc_tpu.dsl.lower_device import MAX_DEVICE_RANKS
    if 2 <= len(jax.devices()) <= MAX_DEVICE_RANKS:
        os.environ.setdefault("UCC_TL_XLA_TUNE",
                              "allreduce:@gen_dev_ring_c2:inf")


def main(sweep: bool = False, quant: bool = False,
         gen_device: bool = False) -> None:
    import os
    if quant:
        _enable_quant()
    if gen_device:
        _enable_gen_device()
    # detail.quant rides every allreduce record whenever a precision is
    # armed — bare UCC_QUANT=int8 records the registered-but-not-forced
    # state (selection stays honest per fabric; --quant pins the
    # quantized program to measure it explicitly)
    quant = quant or os.environ.get("UCC_QUANT", "").strip().lower() in \
        ("int8", "fp8")
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench.py: no TPU (platform "
                         f"{devices[0].platform!r}); nothing measured")
    n = len(devices)
    mesh = jax.make_mesh((n,), ("r",))
    ctxs, teams, team_create_s = _make_job(n)
    team_create_ms = round(team_create_s * 1e3, 1)

    count = 16 << 20                                 # 64 MiB f32
    iters = 20

    if sweep:
        points = [("allreduce", c) for c in
                  (2, 256, 16 << 10, 256 << 10, 1 << 20, 16 << 20)
                  if c * 4 * n < (2 << 30)]
        points += [("alltoall", c) for c in
                   (16 << 10, 256 << 10, 1 << 20, 16 << 20)
                   if c * 4 * n < (2 << 30)]
        for coll, cnt in points:
            if coll == "alltoall" and cnt % n:
                cnt += n - cnt % n
            it = max(6, iters // (2 if cnt >= (1 << 20) else 1))
            ut, rt, ub, rb, pool, alg = _measure_point(coll, cnt, ctxs,
                                                       teams, devices,
                                                       mesh, it, warmup=4)
            plat = devices[0].platform
            if n > 1:
                rec = {
                    "metric": f"{coll}_busbw_GBps", "value": round(ub, 3),
                    "unit": "GB/s/chip",
                    "vs_baseline": round(ub / rb, 4) if rb else 0.0,
                    "detail": {"n_chips": n, "msg_bytes": cnt * 4,
                               "platform": plat, "alg": alg,
                               "ucc_lat_ms": round(ut * 1e3, 3),
                               "raw_lat_ms": round(rt * 1e3, 3),
                               "mc_pool": pool,
                               "team_create_ms": team_create_ms}}
            else:
                # 1 chip: busbw is identically 0 (the 2(n-1)/n factor) —
                # the honest per-size number is e2e latency vs raw
                # dispatch, same convention as the non-sweep 1-chip path
                rec = {
                    "metric": f"{coll}_e2e_latency_us",
                    "value": round(ut * 1e6, 2), "unit": "us (full stack)",
                    "vs_baseline": round(rt / ut, 4) if ut else 0.0,
                    "detail": {"n_chips": n, "msg_bytes": cnt * 4,
                               "platform": plat, "alg": alg,
                               "raw_lat_us": round(rt * 1e6, 2),
                               "mc_pool": pool,
                               "team_create_ms": team_create_ms}}
            if quant and coll == "allreduce" and n > 1:
                rec["detail"]["quant"] = _quant_detail(teams, ctxs,
                                                       devices, cnt, ub)
            print(json.dumps(rec))
        return

    ucc_time, raw_time, ucc_bw, raw_bw, pool, alg = _measure_point(
        "allreduce", count, ctxs, teams, devices, mesh, iters, warmup=5)
    nbytes = count * 4

    if n > 1:
        # north-star comparison (BASELINE.md): bus bandwidth vs raw psum
        result = {
            "metric": "allreduce_busbw_GBps",
            "value": round(ucc_bw, 3),
            "unit": "GB/s/chip",
            "vs_baseline": round(ucc_bw / raw_bw, 4),
            "detail": {
                "n_chips": n,
                "msg_bytes": nbytes,
                "platform": devices[0].platform,
                "alg": alg,
                "ucc_lat_ms": round(ucc_time * 1e3, 3),
                "raw_psum_lat_ms": round(raw_time * 1e3, 3),
                "raw_busbw_GBps": round(raw_bw, 3),
                "mc_pool": pool,
                "team_create_ms": team_create_ms,
            },
        }
        if quant:
            result["detail"]["quant"] = _quant_detail(teams, ctxs, devices,
                                                      count, ucc_bw)
    else:
        # single chip: a 1-rank allreduce is semantically a no-op, so bus
        # bandwidth is undefined; the honest hardware measurement is the
        # end-to-end through-stack latency vs the raw jitted call.
        # vs_baseline = raw/ours (>= 1.0 means the framework adds no
        # overhead over raw XLA dispatch).
        result = {
            "metric": "allreduce_e2e_latency_us",
            "value": round(ucc_time * 1e6, 2),
            "unit": "us (64MiB f32, 1 chip, full stack)",
            "vs_baseline": round(raw_time / ucc_time, 4),
            "detail": {
                "n_chips": n,
                "msg_bytes": nbytes,
                "platform": devices[0].platform,
                "alg": alg,
                "raw_psum_lat_us": round(raw_time * 1e6, 2),
                "mc_pool": pool,
                "note": "single-chip: latency comparison (busbw undefined); "
                        "multi-chip busbw path activates when >1 device",
            },
        }
    print(json.dumps(result))


# ---------------------------------------------------------------------------
# cross-process tier bench (--ipc): 2 procs x 2 rank-threads, ipc vs socket
# ---------------------------------------------------------------------------

def _xproc_rank_main(rank, size, port, lib, sizes, iters, warmup, q):
    """One rank (thread) of the cross-process tier bench: timed fresh
    allreduce rounds per size, rank 0 reports per-round latencies."""
    import time as _time

    import numpy as np

    import ucc_tpu
    from ucc_tpu import (BufferInfo, CollArgs, CollType, ContextParams,
                         DataType, ReductionOp, Status, TcpStoreOob,
                         TeamParams)
    ctx = None
    try:
        ctx = ucc_tpu.Context(lib, ContextParams(
            oob=TcpStoreOob(rank, size, port=port)))
        team = ctx.create_team(TeamParams(
            oob=TcpStoreOob(rank, size, port=port + 1)))
        from ucc_tpu.tools.perftest import transport_tier
        tier = transport_tier(team)
        for nbytes in sizes:
            count = nbytes // 4
            lats = []
            # the small cells are latency probes; the bandwidth-bound
            # >=4MiB cells have long rounds — fewer iterations keep the
            # sweep inside the driver budget
            it_n = iters if nbytes < (4 << 20) else max(6, iters // 2)
            for it in range(warmup + it_n):
                src = np.ones(count, np.float32)
                dst = np.zeros(count, np.float32)
                rq = team.collective_init(CollArgs(
                    coll_type=CollType.ALLREDUCE, op=ReductionOp.SUM,
                    src=BufferInfo(src, count, DataType.FLOAT32),
                    dst=BufferInfo(dst, count, DataType.FLOAT32)))
                deadline = _time.monotonic() + 120
                t0 = _time.perf_counter()
                rq.post()
                while rq.test() == Status.IN_PROGRESS:
                    ctx.progress()
                    # sched_yield: co-resident rank threads must get the
                    # GIL promptly or every handoff costs a full switch
                    # interval — that scheduler tax, identical for both
                    # tiers, buries the transport difference being
                    # measured
                    _time.sleep(0)
                    if _time.monotonic() > deadline:
                        raise RuntimeError(f"allreduce hung at {nbytes}B")
                t1 = _time.perf_counter()
                st = rq.test()
                rq.finalize()
                if st != Status.OK:
                    raise RuntimeError(f"allreduce failed: {st.name}")
                if dst[0] != float(size):
                    raise RuntimeError(f"allreduce wrong: {dst[0]}")
                if it >= warmup:
                    lats.append(t1 - t0)
            # re-sample after the rounds: the pooled classification keys
            # off the transport's pooled-op counter, which only moves
            # once a pooled-window collective has actually run
            tier = transport_tier(team)
            if rank == 0:
                q.put(("point", nbytes, lats, tier))
        if rank == 0:
            q.put(("done", None, None, tier))
        team.destroy()
    except Exception as e:  # noqa: BLE001 - surfaced to the driver
        q.put(("error", rank, f"{type(e).__name__}: {e}", None))
    finally:
        if ctx is not None:
            try:
                ctx.destroy()
            except Exception:  # noqa: BLE001
                pass


def _xproc_worker(ranks, size, port, env, sizes, iters, warmup, q):
    import os
    import sys as _sys
    import threading
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.update(env)
    # rank threads hand work to each other constantly; the default 5ms
    # GIL switch interval would quantize every handoff
    _sys.setswitchinterval(5e-4)
    import ucc_tpu
    # component discovery is not thread-re-entrant: init every rank's lib
    # on the main thread before the rank threads start
    libs = {r: ucc_tpu.init() for r in ranks}
    ths = [threading.Thread(target=_xproc_rank_main,
                            args=(r, size, port, libs[r], sizes, iters,
                                  warmup, q), daemon=True)
           for r in ranks]
    for t in ths:
        t.start()
    for t in ths:
        t.join(timeout=600)


def _parse_xproc_sizes(spec: str):
    """``64K,8M,32M`` -> byte tuple (the gate smoke trims the sweep)."""
    mult = {"k": 1 << 10, "m": 1 << 20, "g": 1 << 30}
    out = []
    for tok in spec.split(","):
        tok = tok.strip().lower()
        if not tok:
            continue
        m = mult.get(tok[-1], 1)
        out.append(int(tok[:-1] if tok[-1] in mult else tok) * m)
    return tuple(out)


def run_xproc_bench(n_procs: int = 2, ranks_per: int = 2,
                    sizes=(64 << 10, 1 << 20, 4 << 20, 8 << 20,
                           16 << 20, 32 << 20),
                    iters: int = 12, warmup: int = 3) -> int:
    """``--ipc``: the cross-process transport comparison. The same
    2-proc x 4-rank host allreduce runs over three tiers — the
    shared-memory arena with its default matched-message algorithms,
    the arena's pooled one-sided window variant, and the socket TL —
    one record per (tier, size) plus a summary with the per-size p50
    speedups of the best arena tier over socket. The tentpole claim
    rides the summary: arena p50 >= 3x socket at >=64KiB."""
    import multiprocessing as mp
    import os
    import queue as _q

    import numpy as np

    from ucc_tpu.tools.perftest import _free_port_pair

    # the gate's warn-only smoke trims the sweep to stay inside its
    # budget; the full default set is the committed BENCH evidence
    if os.environ.get("UCC_XPROC_SIZES"):
        sizes = _parse_xproc_sizes(os.environ["UCC_XPROC_SIZES"])
    if os.environ.get("UCC_XPROC_ITERS"):
        iters = int(os.environ["UCC_XPROC_ITERS"])
    size = n_procs * ranks_per
    splits = [tuple(range(p * ranks_per, (p + 1) * ranks_per))
              for p in range(n_procs)]
    mctx = mp.get_context("spawn")
    results = {}            # leg -> {nbytes: p50_us}
    # the matched-message arena path tops out at the largest block
    # class (8MiB single message); pooled windows bump-allocate from
    # the separate window region, so only the pooled and socket legs
    # measure the bandwidth-bound 16/32MiB cells
    small = tuple(s for s in sizes if s <= (8 << 20))
    legs = [
        ("ipc", {"UCC_TLS": "ipc,self"}, small),
        # the arena's one-sided tier: put+flag windows, no per-message
        # matching handoffs — the configuration the pooled tentpole ships
        ("pooled", {"UCC_TLS": "ipc,self", "UCC_GEN": "y",
                    "UCC_GEN_FAMILIES": "pooled(1,2)",
                    "UCC_TL_IPC_TUNE": "allreduce:@gen_pooled_c1",
                    "UCC_TL_IPC_WINDOW": "512M"}, sizes),
        ("socket", {"UCC_TLS": "socket,self"}, sizes),
    ]
    for leg, env, leg_sizes in legs:
        port = _free_port_pair()
        q = mctx.Queue()
        procs = [mctx.Process(target=_xproc_worker,
                              args=(splits[p], size, port, env,
                                    leg_sizes, iters, warmup, q))
                 for p in range(n_procs)]
        for p in procs:
            p.start()
        points, tier, err = {}, None, None
        deadline = time.monotonic() + 600
        while time.monotonic() < deadline:
            try:
                msg = q.get(timeout=10)
            except _q.Empty:
                if not any(p.is_alive() for p in procs):
                    err = err or "workers exited without reporting"
                    break
                continue
            if msg[0] == "point":
                points[msg[1]] = [s * 1e6 for s in msg[2]]
                tier = msg[3]
            elif msg[0] == "done":
                tier = msg[3]
                break
            elif msg[0] == "error":
                err = f"rank {msg[1]}: {msg[2]}"
                break
        for p in procs:
            p.join(timeout=30)
            if p.is_alive():
                p.kill()
        if err:
            print(json.dumps({"metric": "xproc_allreduce_p50_us",
                              "value": 0.0, "unit": "us",
                              "vs_baseline": 0.0,
                              "detail": {"transport": leg,
                                         "error": err}}))
            return 1
        results[leg] = {
            nb: float(np.percentile(ls, 50)) for nb, ls in points.items()}
        for nb in leg_sizes:
            p50 = results[leg][nb]
            print(json.dumps({
                "metric": "xproc_allreduce_p50_us",
                "value": round(p50, 1), "unit": "us",
                "vs_baseline": 0.0,
                "detail": {"transport": tier or leg, "procs": n_procs,
                           "ranks": size, "msg_bytes": nb,
                           "iters": iters}}), flush=True)
    # the claim compares the arena's best tier per size against socket:
    # matched-message ipc wins the small cells, the one-sided pooled
    # windows win the bandwidth-bound ones
    arena = {}
    for nb in sizes:
        vals = [results[l][nb] for l in ("ipc", "pooled")
                if results.get(l, {}).get(nb)]
        if vals and results.get("socket", {}).get(nb):
            arena[nb] = min(vals)
    ratios = {nb: round(results["socket"][nb] / arena[nb], 2)
              for nb in arena}
    best = max(ratios.values()) if ratios else 0.0
    print(json.dumps({
        "metric": "xproc_ipc_vs_socket_p50_speedup",
        "value": best, "unit": "x (socket p50 / arena p50)",
        "vs_baseline": best,
        "detail": {"transport": "ipc", "procs": n_procs, "ranks": size,
                   "per_size": {str(nb): r for nb, r in ratios.items()},
                   "ok": best >= 3.0}}), flush=True)
    return 0


if __name__ == "__main__":
    import sys as _sys
    if "--ipc" in _sys.argv:
        _sys.exit(run_xproc_bench())
    from ucc_tpu.utils.backend import enable_compile_cache
    enable_compile_cache()
    main(sweep="--sweep" in _sys.argv, quant="--quant" in _sys.argv,
         gen_device="--gen-device" in _sys.argv)
