"""Alltoallv on device memory as rows: the planning in ``ops``
(``a2av_row_width``, ``a2av_plan``), TL/XLA's program keyed on buffer
shapes with the counts as runtime inputs, and TL/self placing a block at
its displacement on a 1-rank team."""
import numpy as np
import pytest

from ucc_tpu import (BufferInfo, BufferInfoV, CollArgs, CollType, DataType,
                     MemoryType, Status)
from ucc_tpu.utils import profiling

from harness import UccJob

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ucc_tpu import ops  # noqa: E402
from ucc_tpu.tl import xla as tl_xla  # noqa: E402

N = 4


@pytest.fixture(scope="module")
def job4():
    if len(jax.devices()) < N:
        pytest.skip("needs >= 4 virtual devices")
    j = UccJob(N)
    yield j, j.create_team()
    j.cleanup()


@pytest.fixture(scope="module")
def job1():
    j = UccJob(1)
    yield j, j.create_team()
    j.cleanup()


def _dev(job, rank, arr):
    dev = job.contexts[rank].tl_contexts["xla"].obj.device
    return jax.device_put(jnp.asarray(arr), dev)


def _layout(m, rng, gapped, width):
    """Per rank: send and receive counts/displacements in elements for a
    counts matrix ``m`` in rows of ``width`` (m[p][q]: rows p sends q)."""
    n = len(m)
    sc = [[int(m[r][p]) * width for p in range(n)] for r in range(n)]
    rc = [[int(m[p][r]) * width for p in range(n)] for r in range(n)]

    def displs(counts):
        gaps = rng.integers(0, 3, len(counts)) * width if gapped else \
            np.zeros(len(counts), int)
        return [int(v) for v in np.cumsum(gaps + counts) - counts]
    return sc, [displs(c) for c in sc], rc, [displs(c) for c in rc]


def _numpy_a2av(srcs, sc, sd, rc, rd, dsts):
    """The alltoallv every rank should see: each block at its
    displacement in a copy of the destination."""
    n = len(srcs)
    out = [d.copy() for d in dsts]
    for r in range(n):
        for p in range(n):
            out[r][rd[r][p]:rd[r][p] + rc[r][p]] = \
                srcs[p][sd[p][r]:sd[p][r] + sc[p][r]]
    return out


def _post(job, teams, srcs, sc, sd, rc, rd, dsts, dtype):
    """One alltoallv on every rank; dsts[r] None means no dst buffer."""
    argses = [CollArgs(
        coll_type=CollType.ALLTOALLV,
        src=BufferInfoV(_dev(job, r, srcs[r]), sc[r], sd[r], dtype,
                        mem_type=MemoryType.TPU),
        dst=BufferInfoV(None if dsts[r] is None else _dev(job, r, dsts[r]),
                        rc[r], rd[r], dtype, mem_type=MemoryType.TPU))
        for r in range(len(teams))]
    for rq in job.run_coll(teams, lambda r: argses[r]):
        rq.finalize()
    return [np.asarray(a.dst.buffer) for a in argses]


def _spans_on(monkeypatch):
    monkeypatch.setattr(profiling, "_spans_on", lambda: True)
    profiling.reset()


def _builds():
    return profiling.totals().get("ucc.xla.build", (0, 0.0))[0]


class TestPlanning:
    @pytest.mark.parametrize("values,w", [
        ([4096, 2048 * 3, 0], 2048), ([8, 24, 40], 8), ([7, 14], 1),
        ([0, 0], 2048), ([1 << 20], 2048), ([6, 12, 18], 2)])
    def test_row_width(self, values, w):
        assert ops.a2av_row_width(values) == w

    @pytest.mark.parametrize("span,cap", [
        (0, 1), (5, 5), (8, 8), (9, 10), (16, 16), (17, 20), (100, 112),
        (24576, 24576), (24577, 28672)])
    def test_ladder(self, span, cap):
        assert tl_xla._ladder(span) == cap
        assert span <= cap <= max(1, span * 5 // 4)

    def test_plan_rejects(self):
        m = np.array([[1, 2], [3, 4]]) * 4
        sd = np.cumsum(m, axis=1) - m
        rd = np.cumsum(m, axis=0) - m
        ops.a2av_plan(m, sd, m.T, rd.T, 4, 28, 24)   # rows 12, 28; cols 16, 24
        with pytest.raises(ValueError, match="do not match"):
            ops.a2av_plan(m, sd, m, rd.T, 4, 28, 24)
        with pytest.raises(ValueError, match="rows of 8"):
            ops.a2av_plan(m, sd, m.T, rd.T, 8, 28, 24)
        with pytest.raises(ValueError, match="outside"):
            ops.a2av_plan(m, sd, m.T, rd.T, 4, 24, 24)

    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_plan_drives_ragged_semantics(self, seed):
        """The runtime arrays, fed to a numpy emulation of
        ``lax.ragged_all_to_all`` as documented (what the TPU body
        consumes), give the numpy alltoallv."""
        rng = np.random.default_rng(seed)
        w = 4
        m = rng.integers(0, 5, size=(N, N))
        sc, sd, rc, rd = _layout(m, rng, True, w)
        src_cap = max(d[-1] + c[-1] for c, d in zip(sc, sd)) + 2 * w
        dst_cap = max(d[-1] + c[-1] for c, d in zip(rc, rd)) + w
        plan = ops.a2av_plan(sc, sd, rc, rd, w, src_cap, dst_cap)
        srcs = [rng.standard_normal(src_cap).astype(np.float32)
                for _ in range(N)]
        dsts = [rng.standard_normal(dst_cap).astype(np.float32)
                for _ in range(N)]
        rows = [s.reshape(-1, w) for s in srcs]
        got = [d.reshape(-1, w).copy() for d in dsts]
        for r in range(N):
            in_off, send, out_off, _ = plan[r]
            for i in range(N):        # SEND(operand[slice], to i)
                blk = rows[r][in_off[i]:in_off[i] + send[i]]
                # the receiver writes at output_offsets as all_to_all'd
                got[i][out_off[i]:out_off[i] + plan[i][3][r]] = blk
        want = _numpy_a2av(srcs, sc, sd, rc, rd, dsts)
        for r in range(N):
            np.testing.assert_array_equal(got[r].reshape(-1), want[r])


class TestXlaRows:
    @pytest.mark.parametrize("gapped", [False, True])
    @pytest.mark.parametrize("given", [False, True])
    @pytest.mark.parametrize("width,dtype", [
        (2048, DataType.BFLOAT16), (16, DataType.FLOAT32),
        (1, DataType.INT32)])
    def test_matches_numpy(self, job4, gapped, given, width, dtype):
        """Dense or gapped displacements, a dst buffer or none, rows of
        2048 and element counts that only rows of one divide."""
        job, teams = job4
        rng = np.random.default_rng(width + 2 * gapped + given)
        nd = {DataType.BFLOAT16: jnp.bfloat16, DataType.FLOAT32: np.float32,
              DataType.INT32: np.int32}[dtype]
        m = rng.integers(0, 4, size=(N, N))
        if width == 1:
            m = m * 2 + 1              # odd element counts
        sc, sd, rc, rd = _layout(m, rng, gapped, width)
        src_len = max(d[-1] + c[-1] for c, d in zip(sc, sd)) + width
        srcs = [(rng.integers(-50, 50, src_len)).astype(nd)
                for _ in range(N)]
        span = [d[-1] + c[-1] for c, d in zip(rc, rd)]
        dsts = [np.full(max(span) + 3 * width, -7, nd) for _ in range(N)]
        got = _post(job, teams, srcs, sc, sd, rc, rd,
                    dsts if given else [None] * N, dtype)
        want = _numpy_a2av(srcs, sc, sd, rc, rd, dsts)
        for r in range(N):
            if given:
                np.testing.assert_array_equal(got[r], want[r])
            else:
                assert got[r].shape == (span[r],)
                for p in range(N):
                    s = slice(rd[r][p], rd[r][p] + rc[r][p])
                    np.testing.assert_array_equal(got[r][s], want[r][s])

    @pytest.mark.parametrize("given", [True, False])
    def test_fifty_routings_one_program(self, job4, monkeypatch, given):
        """50 posts, each with another counts matrix, at fixed
        capacities build one program: the counts are runtime inputs."""
        job, teams = job4
        width, rows_cap = 8, 61             # 61 rows pins rows of 8
        rng = np.random.default_rng(50 + given)
        srcs = [rng.integers(-99, 99, width * rows_cap).astype(np.float32)
                for _ in range(N)]
        dst_len = width * (N * rows_cap + 3)
        caps = set()
        _spans_on(monkeypatch)
        for k in range(50):
            m = rng.integers(0, rows_cap // N + 1, size=(N, N))
            sc, sd, rc, rd = _layout(m, rng, k % 2 == 1, width)
            if given:
                dsts = [np.full(dst_len, k, np.float32) for _ in range(N)]
                got = _post(job, teams, srcs, sc, sd, rc, rd, dsts,
                            DataType.FLOAT32)
            else:
                dsts = [np.zeros(d[-1] + c[-1], np.float32)
                        for c, d in zip(rc, rd)]
                span = max(len(d) for d in dsts)
                caps.add(-(-tl_xla._ladder(span) // width) * width)
                got = _post(job, teams, srcs, sc, sd, rc, rd, [None] * N,
                            DataType.FLOAT32)
            want = _numpy_a2av(srcs, sc, sd, rc, rd, dsts)
            for r in range(N):
                for p in range(N):
                    s = slice(rd[r][p], rd[r][p] + rc[r][p])
                    np.testing.assert_array_equal(got[r][s], want[r][s])
                if given:
                    np.testing.assert_array_equal(got[r], want[r])
        # no dst buffer: one program per rung of the ladder that the
        # largest span reached
        assert _builds() == (1 if given else len(caps))

    def test_fixed_capacity_builds_once(self, job4, monkeypatch):
        """At a given dst capacity the build count is exactly one, and
        a plan span wraps every launch."""
        job, teams = job4
        width, rows_cap = 4, 37
        rng = np.random.default_rng(5)
        srcs = [np.arange(width * rows_cap, dtype=np.float32) + 1000 * r
                for r in range(N)]
        dst_len = width * N * rows_cap
        _spans_on(monkeypatch)
        for _ in range(10):
            m = rng.integers(0, rows_cap // N + 1, size=(N, N))
            sc, sd, rc, rd = _layout(m, rng, False, width)
            _post(job, teams, srcs, sc, sd, rc, rd,
                  [np.zeros(dst_len, np.float32)] * N, DataType.FLOAT32)
        t = profiling.totals()
        assert t["ucc.xla.build"][0] == 1
        assert t["ucc.xla.a2av.plan"][0] == 10

    @pytest.mark.parametrize("fault", ["counts", "short_dst"])
    def test_bad_layout_fails(self, job4, fault):
        """Receive counts that do not match the senders', or a block past
        the end of the caller's dst buffer: the launch fails on every
        rank (ERR_INVALID_PARAM from the planning), nothing is clamped."""
        job, teams = job4
        sc = [[4] * N for _ in range(N)]
        rc = [list(c) for c in sc]
        if fault == "counts":
            rc[0][1] = 8
        dst = (lambda r: None) if fault == "counts" else \
            (lambda r: _dev(job, r, np.zeros(8, np.float32)))
        argses = [CollArgs(
            coll_type=CollType.ALLTOALLV,
            src=BufferInfoV(_dev(job, r, np.zeros(4 * N, np.float32)),
                            sc[r], None, DataType.FLOAT32,
                            mem_type=MemoryType.TPU),
            dst=BufferInfoV(dst(r), rc[r], None, DataType.FLOAT32,
                            mem_type=MemoryType.TPU)) for r in range(N)]
        reqs = [t.collective_init(argses[r]) for r, t in enumerate(teams)]
        for rq in reqs:
            rq.post()
        job.progress_until(lambda: all(
            rq.test() != Status.IN_PROGRESS for rq in reqs))
        assert all(rq.test().is_error for rq in reqs)
        for rq in reqs:
            rq.finalize()


class TestSelfPlacement:
    @pytest.mark.parametrize("mem", [MemoryType.TPU, MemoryType.HOST])
    def test_alltoallv_at_displacement(self, job1, mem):
        """A nonzero displacement in a destination larger than the
        count: the block lands there, the rest of the buffer is kept."""
        job, teams = job1
        src = np.arange(40, dtype=np.float32)
        dst = np.full(64, -1.0, np.float32)
        put = (lambda a: jnp.asarray(a)) if mem == MemoryType.TPU else \
            (lambda a: a)
        args = CollArgs(
            coll_type=CollType.ALLTOALLV,
            src=BufferInfoV(put(src), [12], [8], DataType.FLOAT32,
                            mem_type=mem),
            dst=BufferInfoV(put(dst), [12], [20], DataType.FLOAT32,
                            mem_type=mem))
        job.run_coll(teams, lambda r: args)[0].finalize()
        want = dst.copy()
        want[20:32] = src[8:20]
        np.testing.assert_array_equal(np.asarray(args.dst.buffer), want)

    def test_allgatherv_without_buffer(self, job1):
        job, teams = job1
        src = jnp.arange(6, dtype=jnp.int32) + 1
        args = CollArgs(
            coll_type=CollType.ALLGATHERV,
            src=BufferInfo(src, 6, DataType.INT32, mem_type=MemoryType.TPU),
            dst=BufferInfoV(None, [6], [3], DataType.INT32,
                            mem_type=MemoryType.TPU))
        job.run_coll(teams, lambda r: args)[0].finalize()
        np.testing.assert_array_equal(np.asarray(args.dst.buffer),
                                      [0, 0, 0, 1, 2, 3, 4, 5, 6])

    @pytest.mark.parametrize("dst_rows", [64, 96])
    def test_counts_move_one_program(self, job1, dst_rows):
        """Counts and displacements that move every post, in place or
        shifted, reuse one compiled placement per buffer shape."""
        from ucc_tpu.tl import self as tl_self
        job, teams = job1
        rng = np.random.default_rng(3)
        src = jnp.asarray(rng.standard_normal(64 * 8).astype(np.float32))
        dst = jnp.zeros(dst_rows * 8, jnp.float32)
        fn = tl_self._place_fn(8)
        fn.clear_cache()
        for k in range(12):
            c, s, d = (int(v) * 8 for v in rng.integers(1, 30, 3))
            if k % 2:
                d = s                  # in place: the select alone
            args = CollArgs(
                coll_type=CollType.ALLTOALLV,
                src=BufferInfoV(src, [c], [s], DataType.FLOAT32,
                                mem_type=MemoryType.TPU),
                dst=BufferInfoV(dst, [c], [d], DataType.FLOAT32,
                                mem_type=MemoryType.TPU))
            job.run_coll(teams, lambda r: args)[0].finalize()
            want = np.zeros(dst_rows * 8, np.float32)
            want[d:d + c] = np.asarray(src)[s:s + c]
            np.testing.assert_array_equal(np.asarray(args.dst.buffer), want)
        assert fn._cache_size() == 1

    def test_whole_source_rebinds(self, job1):
        """No displacement, no buffer, the whole source: dst is the
        source array itself, as before."""
        job, teams = job1
        src = jnp.arange(10, dtype=jnp.float32)
        args = CollArgs(
            coll_type=CollType.ALLTOALLV,
            src=BufferInfoV(src, [10], None, DataType.FLOAT32,
                            mem_type=MemoryType.TPU),
            dst=BufferInfoV(None, [10], None, DataType.FLOAT32,
                            mem_type=MemoryType.TPU))
        job.run_coll(teams, lambda r: args)[0].finalize()
        assert args.dst.buffer is src
