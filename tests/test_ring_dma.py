"""TL/RING_DMA — device-initiated ring collectives as Pallas remote-DMA
kernels (the tl/mlx5 / sliding-window role, VERDICT r1 missing #3).
Kernels run in Pallas interpret mode on the virtual CPU mesh; on real TPU
meshes the same kernels compile to ICI DMAs."""
import numpy as np
import pytest

import ucc_tpu
from ucc_tpu import (BufferInfo, CollArgs, CollType, DataType, MemoryType,
                     ReductionOp, Status)

from harness import UccJob

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

N = 4


@pytest.fixture(scope="module")
def job(request):
    import os
    os.environ["UCC_TL_RING_DMA_TUNE"] = \
        "allreduce:@ring_dma:inf#allgather:@ring_dma:inf" \
        "#reduce_scatter:@ring_dma:inf"
    j = UccJob(N)
    yield j
    j.cleanup()
    os.environ.pop("UCC_TL_RING_DMA_TUNE", None)


@pytest.fixture(scope="module")
def teams(job):
    return job.create_team()


def dev_buf(job, rank, np_arr, dt):
    dev = job.contexts[rank].tl_contexts["ring_dma"].obj.device
    arr = jax.device_put(jnp.asarray(np_arr), dev)
    return BufferInfo(arr, int(np.prod(np_arr.shape)), dt,
                      mem_type=MemoryType.TPU)


class TestRingDmaSelection:
    def test_registered(self):
        from ucc_tpu.core.components import get_tl
        tl = get_tl("ring_dma")
        assert tl.NAME == "ring_dma"

    def test_tune_selects_ring_dma(self, teams):
        cands = teams[0].score_map.lookup(CollType.ALLREDUCE,
                                          MemoryType.TPU, 1 << 10)
        assert cands[0].alg_name == "ring_dma"

    def test_info_lists_tl(self, capsys):
        from ucc_tpu.tools.info import print_algorithms
        print_algorithms()
        assert "ring_dma" in capsys.readouterr().out


class TestRingDmaAllreduce:
    @pytest.mark.parametrize("count", [16, 100, 1000])
    def test_sum(self, job, teams, count):
        srcs = [np.arange(count, dtype=np.float32) + r for r in range(N)]
        argses = [CollArgs(
            coll_type=CollType.ALLREDUCE,
            src=dev_buf(job, r, srcs[r], DataType.FLOAT32),
            dst=BufferInfo(None, count, DataType.FLOAT32,
                           mem_type=MemoryType.TPU),
            op=ReductionOp.SUM) for r in range(N)]
        job.run_coll(teams, lambda r: argses[r])
        expect = np.sum(srcs, axis=0)
        for r in range(N):
            np.testing.assert_allclose(np.asarray(argses[r].dst.buffer),
                                       expect, rtol=1e-6)

    def test_max(self, job, teams):
        count = 32
        srcs = [np.roll(np.arange(count, dtype=np.float32), r)
                for r in range(N)]
        argses = [CollArgs(
            coll_type=CollType.ALLREDUCE,
            src=dev_buf(job, r, srcs[r], DataType.FLOAT32),
            dst=BufferInfo(None, count, DataType.FLOAT32,
                           mem_type=MemoryType.TPU),
            op=ReductionOp.MAX) for r in range(N)]
        job.run_coll(teams, lambda r: argses[r])
        expect = np.max(srcs, axis=0)
        for r in range(N):
            np.testing.assert_array_equal(np.asarray(argses[r].dst.buffer),
                                          expect)

    def test_avg(self, job, teams):
        count = 24
        argses = [CollArgs(
            coll_type=CollType.ALLREDUCE,
            src=dev_buf(job, r, np.full(count, r + 1.0, np.float32),
                        DataType.FLOAT32),
            dst=BufferInfo(None, count, DataType.FLOAT32,
                           mem_type=MemoryType.TPU),
            op=ReductionOp.AVG) for r in range(N)]
        job.run_coll(teams, lambda r: argses[r])
        for r in range(N):
            np.testing.assert_allclose(np.asarray(argses[r].dst.buffer),
                                       2.5)


class TestRingDmaDataMovement:
    def test_allgather(self, job, teams):
        per = 8
        srcs = [np.arange(per, dtype=np.float32) + 10 * r for r in range(N)]
        argses = [CollArgs(
            coll_type=CollType.ALLGATHER,
            src=dev_buf(job, r, srcs[r], DataType.FLOAT32),
            dst=BufferInfo(None, per * N, DataType.FLOAT32,
                           mem_type=MemoryType.TPU)) for r in range(N)]
        job.run_coll(teams, lambda r: argses[r])
        expect = np.concatenate(srcs)
        for r in range(N):
            np.testing.assert_array_equal(np.asarray(argses[r].dst.buffer),
                                          expect)

    def test_reduce_scatter(self, job, teams):
        per = 4
        total = N * per
        srcs = [np.arange(total, dtype=np.float32) * (r + 1)
                for r in range(N)]
        argses = [CollArgs(
            coll_type=CollType.REDUCE_SCATTER,
            src=dev_buf(job, r, srcs[r], DataType.FLOAT32),
            dst=BufferInfo(None, per, DataType.FLOAT32,
                           mem_type=MemoryType.TPU),
            op=ReductionOp.SUM) for r in range(N)]
        job.run_coll(teams, lambda r: argses[r])
        expect = np.sum(srcs, axis=0)
        for r in range(N):
            np.testing.assert_allclose(np.asarray(argses[r].dst.buffer),
                                       expect[r * per:(r + 1) * per])

    def test_non_divisible_falls_back(self, job, teams):
        """count % n != 0 reduce_scatter: ring_dma rejects at init and
        selection falls through to TL/XLA's near-equal path."""
        from ucc_tpu.utils.mathutils import block_count, block_offset
        total = 10
        srcs = [np.arange(total, dtype=np.float32) for _ in range(N)]
        argses = [CollArgs(
            coll_type=CollType.REDUCE_SCATTER,
            src=dev_buf(job, r, srcs[r], DataType.FLOAT32),
            dst=BufferInfo(None, block_count(total, N, r), DataType.FLOAT32,
                           mem_type=MemoryType.TPU),
            op=ReductionOp.SUM) for r in range(N)]
        job.run_coll(teams, lambda r: argses[r])
        expect = np.sum(srcs, axis=0)
        for r in range(N):
            off = block_offset(total, N, r)
            np.testing.assert_allclose(
                np.asarray(argses[r].dst.buffer),
                expect[off:off + block_count(total, N, r)])


class TestRingDmaChunked:
    """Vectors beyond one VMEM working set split into independent ring
    passes; results must reassemble exactly per mode."""

    @pytest.mark.parametrize("coll,count", [
        ("allreduce", 40), ("allgather", 10), ("reduce_scatter", 24)])
    def test_chunked_paths(self, job, teams, coll, count, monkeypatch):
        from ucc_tpu.tl import ring_dma as rd
        monkeypatch.setattr(rd, "CHUNK_ELEMS", 8)   # force several chunks
        monkeypatch.setattr(rd, "TILE_BYTES", 4)   # 1-element f32 tiles
        ct = {"allreduce": CollType.ALLREDUCE,
              "allgather": CollType.ALLGATHER,
              "reduce_scatter": CollType.REDUCE_SCATTER}[coll]
        srcs = [np.arange(count, dtype=np.float32) * (r + 1)
                for r in range(N)]
        if coll == "allgather":
            dst_count = count * N
        elif coll == "reduce_scatter":
            dst_count = count // N
        else:
            dst_count = count
        argses = [CollArgs(
            coll_type=ct,
            src=dev_buf(job, r, srcs[r], DataType.FLOAT32),
            dst=BufferInfo(None, dst_count, DataType.FLOAT32,
                           mem_type=MemoryType.TPU),
            op=ReductionOp.SUM if coll != "allgather" else None)
            for r in range(N)]
        job.run_coll(teams, lambda r: argses[r])
        if coll == "allgather":
            expect = np.concatenate(srcs)
            for r in range(N):
                np.testing.assert_array_equal(
                    np.asarray(argses[r].dst.buffer), expect)
        elif coll == "reduce_scatter":
            full = np.sum(srcs, axis=0)
            blk = count // N
            for r in range(N):
                np.testing.assert_allclose(
                    np.asarray(argses[r].dst.buffer),
                    full[r * blk:(r + 1) * blk])
        else:
            expect = np.sum(srcs, axis=0)
            for r in range(N):
                np.testing.assert_allclose(
                    np.asarray(argses[r].dst.buffer), expect)


class TestRingDmaPersistent:
    def test_persistent_repost(self, job, teams):
        from ucc_tpu import CollArgsFlags
        count = 32
        srcs = [np.full(count, r + 1.0, np.float32) for r in range(N)]
        argses = [CollArgs(
            coll_type=CollType.ALLREDUCE,
            src=dev_buf(job, r, srcs[r], DataType.FLOAT32),
            dst=BufferInfo(None, count, DataType.FLOAT32,
                           mem_type=MemoryType.TPU),
            op=ReductionOp.SUM,
            flags=CollArgsFlags.PERSISTENT) for r in range(N)]
        reqs = [teams[r].collective_init(argses[r]) for r in range(N)]
        for _ in range(3):
            for rq in reqs:
                rq.post()
            job.progress_until(lambda: all(
                rq.test() != Status.IN_PROGRESS for rq in reqs))
            for r in range(N):
                assert reqs[r].test() == Status.OK
                np.testing.assert_allclose(
                    np.asarray(argses[r].dst.buffer), N * (N + 1) / 2)
        for rq in reqs:
            rq.finalize()


class TestRingDmaBcast:
    """Pipelined ring bcast — the tl/mlx5 mcast role (VERDICT r2 next #6).
    Symmetric step schedule (wrap-around into the root carries ignored
    data) so semaphores pair exactly."""

    @pytest.mark.parametrize("root", [0, 2])
    def test_bcast(self, job, teams, root, monkeypatch):
        monkeypatch.setenv("UCC_TL_RING_DMA_TUNE", "bcast:@ring_dma:inf")
        j = UccJob(N)
        try:
            tms = j.create_team()
            count = 40
            data = np.arange(count, dtype=np.float32) * 2 + 1
            argses = []
            for r in range(N):
                src = data if r == root else np.zeros(count, np.float32)
                dev = j.contexts[r].tl_contexts["ring_dma"].obj.device
                arr = jax.device_put(jnp.asarray(src), dev)
                argses.append(CollArgs(
                    coll_type=CollType.BCAST, root=root,
                    src=BufferInfo(arr, count, DataType.FLOAT32,
                                   mem_type=MemoryType.TPU)))
            j.run_coll(tms, lambda r: argses[r])
            for r in range(N):
                np.testing.assert_allclose(np.asarray(argses[r].src.buffer),
                                           data)
        finally:
            j.cleanup()

    def test_bcast_pipelined_subblocks(self, monkeypatch):
        """nsub > 1: the sub-block pipeline (root streams pieces, hops
        forward while receiving)."""
        import ucc_tpu.tl.ring_dma as rd
        from ucc_tpu.tl.ring_dma import build_bcast_program
        from jax.sharding import NamedSharding, PartitionSpec as P
        monkeypatch.setattr(rd, "CHUNK_ELEMS", 64)
        monkeypatch.setattr(rd, "TILE_BYTES", 4)   # 1-element f32 tiles
        n = 4
        mesh = jax.make_mesh((n,), ("r",))
        prog, padded = build_bcast_program(mesh, n, 1,
                                           np.dtype(np.float32), 500)
        assert padded // min(padded, 32) > 1   # really pipelined
        data = np.arange(padded, dtype=np.float32) + 7
        shards = [jax.device_put(
            jnp.asarray(data if r == 1 else np.zeros(padded, np.float32)),
            jax.devices()[r]) for r in range(n)]
        garr = jax.make_array_from_single_device_arrays(
            (n * padded,), NamedSharding(mesh, P("r")), shards)
        out = np.asarray(jax.block_until_ready(prog(garr)))
        np.testing.assert_allclose(out[:500], data[:500])


class TestRingDmaHbmChunked:
    """HBM-resident grid allreduce: the full vector stays in HBM, chunks
    stage through double-buffered VMEM inside the kernel schedule (lifts
    the old 2^27 cap; sliding-window role)."""

    def test_hbm_allreduce_multi_chunk(self, monkeypatch):
        import ucc_tpu.tl.ring_dma as rd
        from ucc_tpu.tl.ring_dma import build_hbm_allreduce_program
        from ucc_tpu.constants import ReductionOp as R
        from jax.sharding import NamedSharding, PartitionSpec as P
        monkeypatch.setattr(rd, "CHUNK_ELEMS", 64)
        monkeypatch.setattr(rd, "TILE_BYTES", 4)   # 1-element f32 tiles
        n = 4
        mesh = jax.make_mesh((n,), ("r",))
        prog, padded = build_hbm_allreduce_program(
            mesh, n, R.SUM, np.dtype(np.float32), 500)
        csize = max(n, (64 // n) * n)
        assert padded // csize >= 8            # genuinely multi-chunk
        shards = [jax.device_put(
            jnp.arange(padded, dtype=jnp.float32) * (r + 1),
            jax.devices()[r]) for r in range(n)]
        garr = jax.make_array_from_single_device_arrays(
            (n * padded,), NamedSharding(mesh, P("r")), shards)
        out = np.asarray(jax.block_until_ready(prog(garr)))
        expect = np.arange(padded, dtype=np.float32) * sum(
            range(1, n + 1))
        np.testing.assert_allclose(out.reshape(n, padded),
                                   np.tile(expect, (n, 1)))

    def test_hbm_allgather_multi_chunk_padding(self, monkeypatch):
        """HBM allgather with a count that is NOT a chunk multiple: the
        per-block padding circulates through the ring and is sliced off
        in the program body (end-padding would interleave garbage)."""
        import ucc_tpu.tl.ring_dma as rd
        from jax.sharding import NamedSharding, PartitionSpec as P
        monkeypatch.setattr(rd, "CHUNK_ELEMS", 64)
        monkeypatch.setattr(rd, "TILE_BYTES", 4)   # 1-element f32 tiles
        n, count = 4, 150                      # 3 chunks of 64, pad 42
        mesh = jax.make_mesh((n,), ("r",))
        prog, padded = rd.build_hbm_allgather_program(
            mesh, n, np.dtype(np.float32), count)
        assert padded == 192 and padded != count
        srcs = [np.arange(count, dtype=np.float32) * (r + 1)
                for r in range(n)]
        shards = [jax.device_put(
            jnp.pad(jnp.asarray(srcs[r]), (0, padded - count)),
            jax.devices()[r]) for r in range(n)]
        garr = jax.make_array_from_single_device_arrays(
            (n * padded,), NamedSharding(mesh, P("r")), shards)
        out = np.asarray(jax.block_until_ready(prog(garr)))
        np.testing.assert_array_equal(out, np.concatenate(srcs))

    def test_hbm_reduce_scatter_multi_chunk_padding(self, monkeypatch):
        """HBM reduce_scatter with per-rank blocks that are NOT a chunk
        multiple: the program re-pads PER BLOCK so boundaries align."""
        import ucc_tpu.tl.ring_dma as rd
        from ucc_tpu.constants import ReductionOp as R
        from jax.sharding import NamedSharding, PartitionSpec as P
        monkeypatch.setattr(rd, "CHUNK_ELEMS", 64)
        monkeypatch.setattr(rd, "TILE_BYTES", 4)   # 1-element f32 tiles
        n = 4
        blk0 = 40                              # cblk=16 -> blk_tot=48
        count = n * blk0
        mesh = jax.make_mesh((n,), ("r",))
        prog, padded = rd.build_hbm_reduce_scatter_program(
            mesh, n, R.SUM, np.dtype(np.float32), count)
        assert padded == n * 48 and padded != count
        srcs = [np.arange(count, dtype=np.float32) * (r + 1)
                for r in range(n)]
        shards = [jax.device_put(
            jnp.pad(jnp.asarray(srcs[r]), (0, padded - count)),
            jax.devices()[r]) for r in range(n)]
        garr = jax.make_array_from_single_device_arrays(
            (n * padded,), NamedSharding(mesh, P("r")), shards)
        out = np.asarray(jax.block_until_ready(prog(garr)))
        full = np.sum(srcs, axis=0)
        blk_tot = padded // n
        for r in range(n):
            np.testing.assert_allclose(
                out[r * blk_tot:r * blk_tot + blk0],
                full[r * blk0:(r + 1) * blk0])

    def test_large_count_selects_hbm_path(self, job, teams):
        """Counts beyond one VMEM pass route through the HBM builder via
        the task (no NOT_SUPPORTED above the old cap)."""
        from ucc_tpu.tl.ring_dma import CHUNK_ELEMS
        count = CHUNK_ELEMS + 1024      # > one pass, modest memory
        argses = []
        for r in range(N):
            argses.append(CollArgs(
                coll_type=CollType.ALLREDUCE,
                src=dev_buf(job, r, np.full(count, 1.0, np.float32),
                            DataType.FLOAT32),
                dst=BufferInfo(None, count, DataType.FLOAT32,
                               mem_type=MemoryType.TPU),
                op=ReductionOp.SUM))
        job.run_coll(teams, lambda r: argses[r], timeout=120)
        for r in range(N):
            np.testing.assert_allclose(np.asarray(argses[r].dst.buffer),
                                       N)


class TestRingDmaHbmBcastAlltoall:
    """HBM-resident bcast + alltoall grid kernels (round-3 verdict
    missing #4: AR/AG/RS got HBM-resident kernels, these two kept a
    whole-vector VMEM cap). local/out live in pl.ANY; chunks stage
    through VMEM inside the kernel schedule."""

    @pytest.mark.parametrize("count,root", [(500, 1), (96, 0)])
    def test_hbm_bcast_multi_subblock(self, count, root, monkeypatch):
        """count=500: several sub-blocks; count=96 (blk=32, nsub=3,
        n_steps=5 odd) exercises the even-step-count padding — the grid
        pairs ring steps, so an odd schedule gets one surplus padded
        sub-block that must land in the out padding region."""
        import ucc_tpu.tl.ring_dma as rd
        from jax.sharding import NamedSharding, PartitionSpec as P
        monkeypatch.setattr(rd, "CHUNK_ELEMS", 64)
        monkeypatch.setattr(rd, "TILE_BYTES", 4)   # 1-element f32 tiles
        n = 4
        mesh = jax.make_mesh((n,), ("r",))
        prog, padded = rd.build_hbm_bcast_program(
            mesh, n, root, np.dtype(np.float32), count)
        assert padded >= count and padded % 32 == 0
        data = np.arange(padded, dtype=np.float32) + 7
        shards = [jax.device_put(
            jnp.asarray(data if r == root
                        else np.zeros(padded, np.float32)),
            jax.devices()[r]) for r in range(n)]
        garr = jax.make_array_from_single_device_arrays(
            (n * padded,), NamedSharding(mesh, P("r")), shards)
        out = np.asarray(jax.block_until_ready(prog(garr)))
        np.testing.assert_allclose(out[:count], data[:count])

    def test_hbm_alltoall_multi_chunk_padding(self, monkeypatch):
        """Per-partner blocks that are NOT a chunk multiple: the program
        re-pads PER BLOCK (boundaries stay aligned) and slices the same
        layout back out."""
        import ucc_tpu.tl.ring_dma as rd
        from jax.sharding import NamedSharding, PartitionSpec as P
        monkeypatch.setattr(rd, "CHUNK_ELEMS", 64)
        monkeypatch.setattr(rd, "TILE_BYTES", 4)   # 1-element f32 tiles
        n, blk0 = 4, 25                    # cblk=10 -> blk_tot=30
        count = n * blk0
        mesh = jax.make_mesh((n,), ("r",))
        prog, padded = rd.build_hbm_alltoall_program(
            mesh, n, np.dtype(np.float32), count)
        assert padded == count             # launch-level padding only
        srcs = [np.arange(count, dtype=np.float32) + 1000 * r
                for r in range(n)]
        shards = [jax.device_put(jnp.asarray(srcs[r]), jax.devices()[r])
                  for r in range(n)]
        garr = jax.make_array_from_single_device_arrays(
            (n * padded,), NamedSharding(mesh, P("r")), shards)
        out = np.asarray(jax.block_until_ready(prog(garr)))
        for r in range(n):
            expect = np.concatenate(
                [srcs[p][r * blk0:(r + 1) * blk0] for p in range(n)])
            np.testing.assert_allclose(
                out[r * padded:(r + 1) * padded], expect)

    @pytest.mark.parametrize("coll", ["bcast", "alltoall"])
    def test_large_count_selects_hbm_path(self, coll, monkeypatch):
        """Counts beyond the old VMEM cap route through the HBM builders
        via the task (the NOT_SUPPORTED rejection is n==1-only now)."""
        from ucc_tpu.tl.ring_dma import CHUNK_ELEMS
        monkeypatch.setenv("UCC_TL_RING_DMA_TUNE", f"{coll}:@ring_dma:inf")
        j = UccJob(N)
        try:
            tms = j.create_team()
            count = CHUNK_ELEMS + N * 1024
            if coll == "alltoall":
                count -= count % N
            data = np.arange(count, dtype=np.float32)
            argses = []
            for r in range(N):
                dev = j.contexts[r].tl_contexts["ring_dma"].obj.device
                if coll == "bcast":
                    src = data if r == 1 else np.zeros(count, np.float32)
                    arr = jax.device_put(jnp.asarray(src), dev)
                    argses.append(CollArgs(
                        coll_type=CollType.BCAST, root=1,
                        src=BufferInfo(arr, count, DataType.FLOAT32,
                                       mem_type=MemoryType.TPU)))
                else:
                    arr = jax.device_put(jnp.asarray(data + 1000 * r), dev)
                    argses.append(CollArgs(
                        coll_type=CollType.ALLTOALL,
                        src=BufferInfo(arr, count, DataType.FLOAT32,
                                       mem_type=MemoryType.TPU),
                        dst=BufferInfo(None, count, DataType.FLOAT32,
                                       mem_type=MemoryType.TPU)))
            j.run_coll(tms, lambda r: argses[r], timeout=180)
            blk = count // N
            for r in range(N):
                if coll == "bcast":
                    np.testing.assert_allclose(
                        np.asarray(argses[r].src.buffer), data)
                else:
                    expect = np.concatenate(
                        [data + 1000 * p for p in range(N)]
                    ).reshape(N, count)[:, r * blk:(r + 1) * blk].reshape(-1)
                    np.testing.assert_allclose(
                        np.asarray(argses[r].dst.buffer), expect)
        finally:
            j.cleanup()


class TestRingDmaAlltoall:
    """Pairwise-exchange alltoall — the tl_mlx5 hardware-alltoall role
    (VERDICT r2 missing #3): at step s each rank DMAs its block for
    (me+s) DIRECTLY to that rank (arbitrary device_id) and receives
    from (me-s)."""

    def test_alltoall(self, job, teams, monkeypatch):
        monkeypatch.setenv("UCC_TL_RING_DMA_TUNE",
                           "alltoall:@ring_dma:inf")
        j = UccJob(N)
        try:
            tms = j.create_team()
            cands = tms[0].score_map.lookup(CollType.ALLTOALL,
                                            MemoryType.TPU, 1 << 10)
            assert cands[0].alg_name == "ring_dma"
            blk = 6
            total = N * blk
            srcs = [np.arange(total, dtype=np.float32) + 1000 * r
                    for r in range(N)]
            argses = [CollArgs(
                coll_type=CollType.ALLTOALL,
                src=dev_buf(j, r, srcs[r], DataType.FLOAT32),
                dst=BufferInfo(None, total, DataType.FLOAT32,
                               mem_type=MemoryType.TPU))
                for r in range(N)]
            j.run_coll(tms, lambda r: argses[r])
            for r in range(N):
                expect = np.concatenate(
                    [srcs[p][r * blk:(r + 1) * blk] for p in range(N)])
                np.testing.assert_allclose(
                    np.asarray(argses[r].dst.buffer), expect)
        finally:
            j.cleanup()

    # TPU compile coverage lives in tests/test_tpu_compile.py (alltoall
    # is one of its parametrized families)
