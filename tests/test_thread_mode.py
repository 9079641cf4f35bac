"""Thread modes (ucc.h:493-497): MULTIPLE-mode world where every rank is
driven concurrently from its own OS thread (the deployment shape of a
one-process-per-host pod runner) over the MT progress queue."""
import threading

import numpy as np
import pytest

import ucc_tpu
from ucc_tpu import (BufferInfo, CollArgs, CollType, Context, ContextParams,
                     DataType, LibParams, ReductionOp, Status, TeamParams,
                     ThreadMode, ThreadOobWorld)
from ucc_tpu.schedule.progress import ProgressQueueMT


class TestThreadModeMultiple:
    def test_concurrent_rank_threads(self):
        n = 4
        iters = 5
        world = ThreadOobWorld(n)
        libs = [ucc_tpu.init(LibParams(thread_mode=ThreadMode.MULTIPLE))
                for _ in range(n)]
        ctxs = [None] * n

        def mk(r):
            ctxs[r] = Context(libs[r], ContextParams(oob=world.endpoint(r)))

        ths = [threading.Thread(target=mk, args=(r,)) for r in range(n)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        assert all(isinstance(c.progress_queue, ProgressQueueMT)
                   for c in ctxs)

        tw = ThreadOobWorld(n)
        teams = [None] * n
        errors = []
        results = [[None] * iters for _ in range(n)]

        def rank_main(r):
            try:
                team = ctxs[r].create_team(TeamParams(oob=tw.endpoint(r)))
                teams[r] = team
                count = 256
                for it in range(iters):
                    src = np.full(count, (r + 1) * (it + 1), np.float64)
                    dst = np.zeros(count, np.float64)
                    req = team.collective_init(CollArgs(
                        coll_type=CollType.ALLREDUCE,
                        src=BufferInfo(src, count, DataType.FLOAT64),
                        dst=BufferInfo(dst, count, DataType.FLOAT64),
                        op=ReductionOp.SUM))
                    req.post()
                    req.wait(timeout=60)
                    results[r][it] = float(dst[0])
            except Exception as e:  # noqa: BLE001
                errors.append((r, e))

        ths = [threading.Thread(target=rank_main, args=(r,))
               for r in range(n)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=120)
        assert not errors, errors
        for it in range(iters):
            expect = (it + 1) * n * (n + 1) / 2
            for r in range(n):
                assert results[r][it] == expect, (r, it)


class TestThreadModeStress:
    def test_concurrent_collectives_two_teams(self):
        """MULTIPLE-mode stress: every rank thread keeps TWO collectives
        in flight at once (one per team, posted before either is waited),
        across mixed coll types and several iterations — exercises the MT
        progress queue under genuine cross-thread concurrency."""
        n, iters = 4, 6
        world = ThreadOobWorld(n)
        libs = [ucc_tpu.init(LibParams(thread_mode=ThreadMode.MULTIPLE))
                for _ in range(n)]
        ctxs = [None] * n

        def mk(r):
            ctxs[r] = Context(libs[r], ContextParams(oob=world.endpoint(r)))

        ths = [threading.Thread(target=mk, args=(r,)) for r in range(n)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()

        tw_a, tw_b = ThreadOobWorld(n), ThreadOobWorld(n)
        errors = []
        sums = [[None] * iters for _ in range(n)]
        gathers = [[None] * iters for _ in range(n)]

        def rank_main(r):
            try:
                team_a = ctxs[r].create_team(TeamParams(oob=tw_a.endpoint(r)))
                team_b = ctxs[r].create_team(TeamParams(oob=tw_b.endpoint(r)))
                count = 128
                for it in range(iters):
                    src_a = np.full(count, (r + 1) * (it + 1), np.float64)
                    dst_a = np.zeros(count, np.float64)
                    req_a = team_a.collective_init(CollArgs(
                        coll_type=CollType.ALLREDUCE,
                        src=BufferInfo(src_a, count, DataType.FLOAT64),
                        dst=BufferInfo(dst_a, count, DataType.FLOAT64),
                        op=ReductionOp.SUM))
                    src_b = np.full(8, r * 10 + it, np.int64)
                    dst_b = np.zeros(8 * n, np.int64)
                    req_b = team_b.collective_init(CollArgs(
                        coll_type=CollType.ALLGATHER,
                        src=BufferInfo(src_b, 8, DataType.INT64),
                        dst=BufferInfo(dst_b, 8 * n, DataType.INT64)))
                    # both in flight before either completes
                    req_a.post()
                    req_b.post()
                    req_b.wait(timeout=90)
                    req_a.wait(timeout=90)
                    sums[r][it] = float(dst_a[0])
                    gathers[r][it] = dst_b.copy()
                    # interleave a barrier on team A while team B idles
                    bar = team_a.collective_init(CollArgs(
                        coll_type=CollType.BARRIER))
                    bar.post()
                    bar.wait(timeout=90)
            except Exception as e:  # noqa: BLE001
                import traceback
                errors.append((r, e, traceback.format_exc()))

        ths = [threading.Thread(target=rank_main, args=(r,))
               for r in range(n)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=240)
        assert not errors, errors[0]
        for it in range(iters):
            expect_sum = (it + 1) * n * (n + 1) / 2
            expect_g = np.concatenate(
                [np.full(8, p * 10 + it, np.int64) for p in range(n)])
            for r in range(n):
                assert sums[r][it] == expect_sum, (r, it)
                np.testing.assert_array_equal(gathers[r][it], expect_g)


class TestThreadModeFastLane:
    """MULTIPLE-mode stress of persistent re-posts on device buffers:
    every rank re-posts from its own OS thread, and the last depositor's
    thread launches and sets every local task's result (cross-thread
    set_result), while each owner completes its own task."""

    def test_concurrent_persistent_device_reposts(self):
        jax = pytest.importorskip("jax")
        import jax.numpy as jnp
        from ucc_tpu import CollArgsFlags, MemoryType

        n, iters, count = 4, 12, 64
        world = ThreadOobWorld(n)
        libs = [ucc_tpu.init(LibParams(thread_mode=ThreadMode.MULTIPLE))
                for _ in range(n)]
        ctxs = [None] * n

        def mk(r):
            ctxs[r] = Context(libs[r], ContextParams(oob=world.endpoint(r)))

        ths = [threading.Thread(target=mk, args=(r,)) for r in range(n)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()

        tw = ThreadOobWorld(n)
        errors = []
        results = [[None] * iters for _ in range(n)]
        barrier = threading.Barrier(n)

        def rank_main(r):
            try:
                team = ctxs[r].create_team(TeamParams(oob=tw.endpoint(r)))
                dev = ctxs[r].tl_contexts["xla"].obj.device
                src = jax.device_put(
                    jnp.full((count,), r + 1.0, jnp.float32), dev)
                args = CollArgs(
                    coll_type=CollType.ALLREDUCE,
                    src=BufferInfo(src, count, DataType.FLOAT32,
                                   mem_type=MemoryType.TPU),
                    dst=BufferInfo(None, count, DataType.FLOAT32,
                                   mem_type=MemoryType.TPU),
                    op=ReductionOp.SUM,
                    flags=CollArgsFlags.PERSISTENT)
                req = team.collective_init(args)
                for it in range(iters):
                    barrier.wait(timeout=60)   # maximize re-post overlap
                    req.post()
                    req.wait(timeout=60)
                    results[r][it] = float(
                        np.asarray(args.dst.buffer)[0])
                req.finalize()
            except Exception as e:  # noqa: BLE001
                errors.append((r, e))

        ths = [threading.Thread(target=rank_main, args=(r,))
               for r in range(n)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=180)
        assert not errors, errors
        expect = n * (n + 1) / 2
        for r in range(n):
            for it in range(iters):
                assert results[r][it] == expect, (r, it, results[r][it])


class TestThreadModeOneSided:
    """MULTIPLE-mode stress of the one-sided path: every rank drives
    sliding-window allreduce re-posts from its own OS thread — the
    segment registry and arrival counters take concurrent puts/gets
    under the registry lock while each owner reduces in its own
    thread."""

    def test_concurrent_sliding_window_reposts(self, monkeypatch):
        from ucc_tpu import CollArgsFlags
        monkeypatch.setenv("UCC_TL_SHM_TUNE", "allreduce:@sliding_window")
        monkeypatch.setenv("UCC_TL_SHM_ALLREDUCE_SW_WINDOW", "128")
        n, iters, count = 4, 10, 300
        world = ThreadOobWorld(n)
        libs = [ucc_tpu.init(LibParams(thread_mode=ThreadMode.MULTIPLE))
                for _ in range(n)]
        ctxs = [None] * n

        def mk(r):
            ctxs[r] = Context(libs[r], ContextParams(oob=world.endpoint(r)))

        ths = [threading.Thread(target=mk, args=(r,)) for r in range(n)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()

        tw = ThreadOobWorld(n)
        srcs = [np.arange(count, dtype=np.float64) * (r + 1)
                for r in range(n)]
        dsts = [np.zeros(count, dtype=np.float64) for _ in range(n)]
        sh = [ctxs[r].mem_map(srcs[r]) for r in range(n)]
        dh = [ctxs[r].mem_map(dsts[r]) for r in range(n)]
        errors = []
        barrier = threading.Barrier(n)

        def rank_main(r):
            try:
                team = ctxs[r].create_team(TeamParams(oob=tw.endpoint(r)))
                args = CollArgs(
                    coll_type=CollType.ALLREDUCE,
                    src=BufferInfo(srcs[r], count, DataType.FLOAT64),
                    dst=BufferInfo(dsts[r], count, DataType.FLOAT64),
                    op=ReductionOp.SUM,
                    src_memh=list(sh), dst_memh=list(dh),
                    flags=(CollArgsFlags.MEM_MAP_SRC_MEMH
                           | CollArgsFlags.MEM_MAP_DST_MEMH
                           | CollArgsFlags.PERSISTENT))
                req = team.collective_init(args)
                for _ in range(iters):
                    barrier.wait(timeout=60)
                    req.post()
                    req.wait(timeout=60)
                req.finalize()
            except Exception as e:  # noqa: BLE001
                errors.append((r, e))

        ths = [threading.Thread(target=rank_main, args=(r,))
               for r in range(n)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=180)
        assert not errors, errors
        expect = np.arange(count, dtype=np.float64) * sum(
            range(1, n + 1))
        for r in range(n):
            np.testing.assert_allclose(dsts[r], expect, rtol=1e-12)
