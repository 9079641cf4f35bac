"""Layer spans of the post path (utils/profiling.py ``begin``/``end``):
off without a profiler session, and under one nested as
``ucc.post`` ⊃ ``ucc.xla.launch`` ⊃ ``ucc.xla.dispatch`` on the host
thread line of the captured profile, counted once per request/launch."""
import glob

import numpy as np
import pytest

from ucc_tpu import (BufferInfo, CollArgs, CollArgsFlags, CollType,
                     DataType, MemoryType, ReductionOp, Status)
from ucc_tpu.utils import profiling

from harness import UccJob

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

#: float32 elements: above TL/XLA's short-message range on the CPU mesh,
#: so the 4-rank allreduce runs the compiled program
COUNT = 1 << 16


@pytest.fixture(scope="module")
def job4():
    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 virtual devices")
    j = UccJob(4)
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


def _avg_inplace(job, teams):
    """One in-place AVG allreduce of device buffers on every rank."""
    n = len(teams)
    argses = [CollArgs(
        coll_type=CollType.ALLREDUCE,
        src=BufferInfo(None, COUNT, DataType.FLOAT32,
                       mem_type=MemoryType.TPU),
        dst=BufferInfo(_dev(job, r, np.full(COUNT, r + 1.0, np.float32)),
                       COUNT, DataType.FLOAT32, mem_type=MemoryType.TPU),
        op=ReductionOp.AVG, flags=CollArgsFlags.IN_PLACE)
        for r in range(n)]
    reqs = job.run_coll(teams, lambda r: argses[r])
    want = (n + 1) / 2
    for rq, a in zip(reqs, argses):
        assert rq.test() == Status.OK
        np.testing.assert_allclose(np.asarray(a.dst.buffer), want)
        rq.finalize()


class _Capture:
    """A JAX profiler session around the block; ``path`` is its
    .xplane.pb afterwards."""

    def __init__(self, tmp_path):
        self.dir = str(tmp_path)
        self.path = None

    def __enter__(self):
        profiling.reset()
        jax.profiler.start_trace(self.dir)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        self.path = glob.glob(f"{self.dir}/**/*.xplane.pb",
                              recursive=True)[0]


def _host_events(path):
    """name -> [(start_ns, end_ns, line)] of the ucc.* host events."""
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("ucc."):
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         line.name))
    return out


def _inside(inner, outers):
    s, e, line = inner
    return any(os <= s and e <= oe and ol == line for os, oe, ol in outers)


def test_off_without_a_profiler_session(job1, monkeypatch):
    assert not profiling.ENABLED
    made = []

    class Counting(profiling._Span):
        __slots__ = ()

        def __init__(self, *a, **kw):
            made.append(a)
            super().__init__(*a, **kw)

    monkeypatch.setattr(profiling, "_Span", Counting)
    profiling.reset()
    _avg_inplace(*job1)
    assert profiling.totals() == {}
    assert made == []


def test_spans_count_and_nest_under_a_profile(job1, job4, tmp_path):
    with _Capture(tmp_path) as cap:
        _avg_inplace(*job1)
        _avg_inplace(*job4)
    tot = profiling.totals()
    requests = 1 + 4
    for name in ("ucc.init", "ucc.select", "ucc.tl_init", "ucc.post"):
        assert tot[name][0] == requests, (name, tot)
    assert tot["ucc.xla.launch"][0] == 1
    assert tot["ucc.xla.stage"][0] == tot["ucc.xla.dispatch"][0] == 1
    init_s = tot["ucc.init"][1]
    assert tot["ucc.select"][1] + tot["ucc.tl_init"][1] <= init_s
    assert tot["ucc.xla.launch"][1] <= tot["ucc.post"][1]

    ev = _host_events(cap.path)
    assert len(ev["ucc.post"]) == requests
    (dispatch,) = ev["ucc.xla.dispatch"]
    (launch,) = ev["ucc.xla.launch"]
    assert _inside(dispatch, [launch])
    assert _inside(launch, ev["ucc.post"])
    for child in ev["ucc.select"] + ev["ucc.tl_init"]:
        assert _inside(child, ev["ucc.init"])


def test_persistent_repost_stages_in_place(job4, tmp_path):
    """A persistent re-post runs the one launch path: it stages the
    shards (already on their devices, so none is placed) and reuses the
    compiled program (no build)."""
    job, teams = job4
    n = len(teams)
    argses = [CollArgs(
        coll_type=CollType.ALLREDUCE,
        src=BufferInfo(_dev(job, r, np.full(COUNT, r + 1.0, np.float32)),
                       COUNT, DataType.FLOAT32, mem_type=MemoryType.TPU),
        dst=BufferInfo(None, COUNT, DataType.FLOAT32,
                       mem_type=MemoryType.TPU),
        op=ReductionOp.SUM, flags=CollArgsFlags.PERSISTENT)
        for r in range(n)]
    reqs = [teams[r].collective_init(argses[r]) for r in range(n)]

    def round_():
        for rq in reqs:
            rq.post()
        job.progress_until(lambda: all(
            rq.test() != Status.IN_PROGRESS for rq in reqs))
        for rq, a in zip(reqs, argses):
            assert rq.test() == Status.OK
            np.testing.assert_allclose(np.asarray(a.dst.buffer), 10.0)

    round_()                       # first post builds the program
    with _Capture(tmp_path):
        round_()
    tot = profiling.totals()
    assert tot["ucc.post"][0] == n
    assert tot["ucc.xla.launch"][0] == tot["ucc.xla.dispatch"][0] == \
        tot["ucc.xla.stage"][0] == 1
    assert "ucc.xla.place" not in tot
    assert "ucc.xla.build" not in tot
    for rq in reqs:
        rq.finalize()


def _placement_args(job, r, where):
    """Rank ``r``'s AVG allreduce of ``r + 1`` as placement ``where``
    gives it: device-resident in place, a host numpy source, or (rank 0
    alone) a source committed to rank 1's device."""
    x = np.full(COUNT, r + 1.0, np.float32)
    dst = BufferInfo(None, COUNT, DataType.FLOAT32, mem_type=MemoryType.TPU)
    if where == "device":
        dst.buffer = _dev(job, r, x)
        return CollArgs(coll_type=CollType.ALLREDUCE,
                        src=BufferInfo(None, COUNT, DataType.FLOAT32,
                                       mem_type=MemoryType.TPU),
                        dst=dst, op=ReductionOp.AVG,
                        flags=CollArgsFlags.IN_PLACE)
    if where == "host":
        src = BufferInfo(x, COUNT, DataType.FLOAT32,
                         mem_type=MemoryType.HOST)
    else:
        src = BufferInfo(_dev(job, 1 if r == 0 else r, x), COUNT,
                         DataType.FLOAT32, mem_type=MemoryType.TPU)
    return CollArgs(coll_type=CollType.ALLREDUCE, src=src, dst=dst,
                    op=ReductionOp.AVG)


@pytest.mark.parametrize("where,placed", [
    ("device", 0), ("host", 4), ("wrong_device", 1)])
def test_staging_places_only_shards_not_in_place(job4, tmp_path, where,
                                                  placed):
    """A shard already on its rank's device goes into the global array
    as it is; each other shard costs one ``ucc.xla.place``."""
    job, teams = job4
    n = len(teams)
    argses = [_placement_args(job, r, where) for r in range(n)]
    with _Capture(tmp_path) as cap:
        reqs = job.run_coll(teams, lambda r: argses[r])
    for rq, a in zip(reqs, argses):
        assert rq.test() == Status.OK
        np.testing.assert_allclose(np.asarray(a.dst.buffer), (n + 1) / 2)
        rq.finalize()
    tot = profiling.totals()
    assert tot["ucc.xla.launch"][0] == tot["ucc.xla.stage"][0] == 1
    assert tot.get("ucc.xla.place", (0,))[0] == placed
    ev = _host_events(cap.path)
    for child in ev.get("ucc.xla.place", []):
        assert _inside(child, ev["ucc.xla.stage"])


def test_alltoallv_device_shards_are_not_placed(job4, tmp_path):
    """Device-resident alltoallv source and destination go into the
    launch as they are, and the destination keeps its contents outside
    the blocks it receives."""
    from ucc_tpu import BufferInfoV
    job, teams = job4
    n = len(teams)
    m = np.array([[1, 2, 0, 3], [2, 1, 4, 0], [0, 3, 1, 2], [1, 0, 2, 1]])
    gap, fill = 2, -7.0
    argses = []
    for r in range(n):
        sc = [int(c) for c in m[r]]
        rc = [int(m[p][r]) for p in range(n)]
        rd = [int(sum(rc[:p])) + gap * p for p in range(n)]
        src = np.arange(sum(sc), dtype=np.float32) + 100 * r
        dst = np.full(rd[-1] + rc[-1] + gap, fill, np.float32)
        argses.append(CollArgs(
            coll_type=CollType.ALLTOALLV,
            src=BufferInfoV(_dev(job, r, src), sc, None, DataType.FLOAT32,
                            mem_type=MemoryType.TPU),
            dst=BufferInfoV(_dev(job, r, dst), rc, rd, DataType.FLOAT32,
                            mem_type=MemoryType.TPU)))
    with _Capture(tmp_path):
        reqs = job.run_coll(teams, lambda r: argses[r])
    tot = profiling.totals()
    assert tot["ucc.xla.launch"][0] == tot["ucc.xla.stage"][0] == 1
    assert "ucc.xla.place" not in tot
    for r, (rq, a) in enumerate(zip(reqs, argses)):
        assert rq.test() == Status.OK
        out = np.asarray(a.dst.buffer)
        want = np.full(out.shape, fill, np.float32)
        for p in range(n):
            c, d = int(m[p][r]), a.dst.displacements[p]
            sd = int(m[p][:r].sum())
            want[d:d + c] = np.arange(sd, sd + c, dtype=np.float32) + 100 * p
        np.testing.assert_array_equal(out, want)
        rq.finalize()


def test_threads_lose_no_span(tmp_path):
    """Spans closed on many threads at once all reach ``totals()``."""
    import sys
    import threading
    n_threads, per = 12, 2000

    def work():
        for _ in range(per):
            profiling.end(profiling.begin("ucc.select"))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with _Capture(tmp_path):
            ths = [threading.Thread(target=work) for _ in range(n_threads)]
            for t in ths:
                t.start()
            for t in ths:
                t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in ths)
    assert profiling.totals()["ucc.select"][0] == n_threads * per


def test_accum_dump_writes_span_table(tmp_path, monkeypatch):
    import importlib
    import json
    out = tmp_path / "accum.json"
    monkeypatch.setenv("UCC_PROFILE_MODE", "accum")
    monkeypatch.setenv("UCC_PROFILE_FILE", str(out))
    importlib.reload(profiling)
    try:
        tok = profiling.begin("ucc.select")
        assert tok is not None
        profiling.end(tok)
        profiling._dump_accum()
    finally:
        monkeypatch.delenv("UCC_PROFILE_MODE")
        importlib.reload(profiling)
    rows = {r["name"]: r for r in map(json.loads,
                                      out.read_text().splitlines())}
    assert rows["ucc.select"]["count"] == 1
    assert set(rows["ucc.select"]) == {"name", "count", "total_us",
                                       "avg_us"}


def test_allreduce_program_is_named():
    from jax.sharding import Mesh

    from ucc_tpu.tl.xla import _build_xla_program
    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 virtual devices")
    mesh = Mesh(np.array(jax.devices()[:4]), ("r",))
    args = CollArgs(coll_type=CollType.ALLREDUCE, op=ReductionOp.SUM)
    program, padded = _build_xla_program(
        mesh, 4, CollType.ALLREDUCE, args, np.dtype(np.float32), 8, "xla")
    text = program.lower(
        jax.ShapeDtypeStruct((4 * padded,), jnp.float32)).as_text()
    assert "@jit_ucc_allreduce_xla" in text


def test_alltoallv_plan_and_build_nest_in_the_launch(job4, tmp_path):
    """TL/XLA's alltoallv launch holds ``ucc.xla.a2av.plan`` every time
    and ``ucc.xla.build`` on a program-cache miss only: a second routing
    at the same buffer shapes builds nothing."""
    from ucc_tpu import BufferInfoV
    job, teams = job4
    n = len(teams)
    w, cap = 8, 53                       # rows of 8, 53 rows per source

    def post(m):
        argses = [CollArgs(
            coll_type=CollType.ALLTOALLV,
            src=BufferInfoV(_dev(job, r, np.arange(w * cap,
                                                   dtype=np.float32)),
                            [w * int(c) for c in m[r]], None,
                            DataType.FLOAT32, mem_type=MemoryType.TPU),
            dst=BufferInfoV(_dev(job, r, np.zeros(n * w * cap, np.float32)),
                            [w * int(m[p][r]) for p in range(n)], None,
                            DataType.FLOAT32, mem_type=MemoryType.TPU))
            for r in range(n)]
        for rq in job.run_coll(teams, lambda r: argses[r]):
            rq.finalize()

    rng = np.random.default_rng(0)
    with _Capture(tmp_path) as cap_:
        for _ in range(2):
            post(rng.integers(0, cap // n + 1, size=(n, n)))
    tot = profiling.totals()
    assert tot["ucc.xla.launch"][0] == tot["ucc.xla.a2av.plan"][0] == 2
    assert tot["ucc.xla.build"][0] == 1
    ev = _host_events(cap_.path)
    for child in ev["ucc.xla.a2av.plan"] + ev["ucc.xla.build"]:
        assert _inside(child, ev["ucc.xla.launch"])
