"""Device-side compiler backend (ucc_tpu/dsl/lower_device, ISSUE 15):
verified DSL programs lowered to generated device collectives on the
xla TL — the in-jit XLA layer schedule on the virtual CPU mesh and the
Pallas remote-DMA kernels in interpret mode, cross-rank correctness vs
numpy for every registered variant (inplace, AVG, bf16, quantized
edges, every bcast root), registration/provenance, fallback behavior,
the launch-cache bound fix, and the device flight-recorder events."""
import os

import numpy as np
import pytest

import ucc_tpu
from ucc_tpu import (BufferInfo, CollArgs, CollArgsFlags, CollType,
                     DataType, MemoryType, ReductionOp, Status)

from harness import UccJob

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

N = 4


@pytest.fixture(scope="module")
def job():
    if len(jax.devices()) < 8:
        pytest.skip("needs >= 8 virtual devices")
    os.environ["UCC_GEN_DEVICE"] = "y"
    os.environ["UCC_QUANT"] = "int8"
    j = UccJob(N)
    yield j
    j.cleanup()
    os.environ.pop("UCC_GEN_DEVICE", None)
    os.environ.pop("UCC_QUANT", None)


@pytest.fixture(scope="module")
def teams(job):
    return job.create_team()


@pytest.fixture(scope="module")
def pallas_job():
    if len(jax.devices()) < N:
        pytest.skip("needs >= 4 virtual devices")
    os.environ["UCC_GEN_DEVICE"] = "y"
    os.environ["UCC_GEN_DEVICE_BACKEND"] = "pallas"
    os.environ["UCC_QUANT"] = "int8"
    j = UccJob(N)
    teams = j.create_team()
    yield j, teams
    j.cleanup()
    for k in ("UCC_GEN_DEVICE", "UCC_GEN_DEVICE_BACKEND", "UCC_QUANT"):
        os.environ.pop(k, None)


def dev_buf(job, rank, np_arr, dt):
    dev = job.contexts[rank].tl_contexts["xla"].obj.device
    arr = jax.device_put(jnp.asarray(np_arr), dev)
    return BufferInfo(arr, int(np.prod(np_arr.shape)), dt,
                      mem_type=MemoryType.TPU)


def run_forced(job, teams, alg, make_args, timeout=60.0):
    """Init pinned to candidate *alg* by name on every rank, run to
    completion, return the per-rank requests."""
    from ucc_tpu.api.types import coll_args_msgsize
    from ucc_tpu.core.coll import CollRequest, InitArgs

    n = len(teams)
    argses = [make_args(r) for r in range(n)]
    msgsize = coll_args_msgsize(argses[0], n, 0)
    coll = argses[0].coll_type
    reqs = []
    for r in range(n):
        cands = teams[r].score_map.lookup(coll, MemoryType.TPU, msgsize)
        cand = next(c for c in cands if c.alg_name == alg)
        ia = InitArgs(args=argses[r], team=teams[r],
                      mem_type=MemoryType.TPU, msgsize=msgsize)
        task = cand.init(ia, cand.team)
        task.alg_name = alg
        reqs.append(CollRequest(task, teams[r], argses[r]))
    for rq in reqs:
        rq.post()
    job.progress_until(lambda: all(
        rq.test() != Status.IN_PROGRESS for rq in reqs), timeout=timeout)
    for rq in reqs:
        assert rq.test() == Status.OK, (alg, rq.test())
    return reqs, argses


def registered_dev_algs(teams, coll, msgsize=1 << 12):
    return sorted({c.alg_name
                   for c in teams[0].score_map.lookup(
                       coll, MemoryType.TPU, msgsize)
                   if c.origin == "generated-device"})


# ---------------------------------------------------------------------------
# lowering plan units
# ---------------------------------------------------------------------------

class TestLoweringPlan:
    def test_ring_detected(self):
        from ucc_tpu.dsl import families as fam
        from ucc_tpu.dsl.lower_device import plan_rounds, ring_schedule
        p = fam.gen_ring(4, chunks=2)
        plans = plan_rounds(p, 4)
        sched = ring_schedule(plans, 4)
        assert sched is not None and len(sched) == 2 * 3
        assert all(length == 2 for length, _ in sched)

    def test_direct_exchange_not_ring(self):
        from ucc_tpu.dsl import families as fam
        from ucc_tpu.dsl.lower_device import plan_rounds, ring_schedule
        p = fam.gen_rhd(4, radix=4)
        plans = plan_rounds(p, 4)
        assert ring_schedule(plans, 4) is None
        # direct exchange reduce round: every rank receives its chunk
        # from all 3 peers, scheduled over >= 3 layers with the
        # receiver's op-stream order preserved
        assert len(plans[0].layers) >= 3

    def test_receiver_order_preserved(self):
        """Layer order must replay each receiver's op-stream order —
        the accumulate-order contract that makes device results
        bitwise-identical to the host interpreter."""
        from ucc_tpu.dsl import families as fam
        from ucc_tpu.dsl.lower_device import plan_rounds
        from ucc_tpu.dsl.ir import OpKind
        n = 8
        p = fam.gen_rhd(n, radix=n)
        plans = plan_rounds(p, n)
        for k, plan in enumerate(plans):
            seen = {q: [] for q in range(n)}
            for lay in plan.layers:
                for run in lay.runs:
                    seen[run.q].append(run.p)
            for q in range(n):
                stream = [(op.peer, op.chunk)
                          for op in p.ranks[q].rounds[k]
                          if op.kind in (OpKind.RECV, OpKind.REDUCE)]
                assert seen[q] == [pr for pr, _ in stream]

    def test_cross_round_match_refused(self):
        from ucc_tpu.dsl import families as fam
        from ucc_tpu.dsl.ir import ProgramBuilder
        from ucc_tpu.dsl.lower_device import plan_rounds
        b = ProgramBuilder("x", CollType.ALLREDUCE, 2, 1)
        b.next_round()
        b.send(0, 0, to=1, slot=99)
        b.next_round()
        b.reduce(1, 0, frm=0, slot=99)   # cross-round rendezvous
        prog = b.build("x")
        with pytest.raises(fam.Inapplicable):
            plan_rounds(prog, 2)

    def test_device_program_sweep(self):
        from ucc_tpu.dsl.lower_device import device_programs
        progs = device_programs(4, quant_mode="int8")
        names = {p.name for p in progs}
        assert {"gen_ring_c1", "gen_ring_c2", "gen_rhd_r2",
                "gen_bc_kn_r2", "gen_bc_chain_c2",
                "gen_qint8_direct"} <= names

    def test_bad_families_knob_rejected(self):
        from ucc_tpu.dsl.lower_device import parse_device_families
        with pytest.raises(ValueError):
            parse_device_families("ag_ring(1)")   # not device-lowerable
        with pytest.raises(ValueError):
            parse_device_families("nosuch(2)")


# ---------------------------------------------------------------------------
# registration & provenance
# ---------------------------------------------------------------------------

class TestRegistration:
    def test_candidates_registered(self, teams):
        algs = registered_dev_algs(teams, CollType.ALLREDUCE)
        assert "gen_dev_ring_c1" in algs
        assert "gen_dev_rhd_r2" in algs
        assert "gen_dev_qint8_direct" in algs
        assert "gen_dev_bc_kn_r2" in registered_dev_algs(
            teams, CollType.BCAST)

    def test_provenance_in_print_info(self, teams):
        info = teams[0].score_map.print_info("t")
        assert "generated-device gen:ring(chunks=1)" in info
        assert "gen_dev_ring_c1" in info
        # the quantized variant carries its precision tag
        assert "generated-device,int8" in info

    def test_off_means_absent(self):
        j = UccJob(2, lib_overrides={"GEN_DEVICE": "n"})
        try:
            tms = j.create_team()
            cands = tms[0].score_map.lookup(CollType.ALLREDUCE,
                                            MemoryType.TPU, 1 << 12)
            assert not any(c.origin == "generated-device"
                           for c in cands)
            assert not any((c.alg_name or "").startswith("gen_dev_")
                           for c in cands)
        finally:
            j.cleanup()

    def test_never_static_default(self, teams):
        cands = teams[0].score_map.lookup(CollType.ALLREDUCE,
                                          MemoryType.TPU, 1 << 12)
        assert not (cands[0].alg_name or "").startswith("gen_dev_")


# ---------------------------------------------------------------------------
# correctness: every registered variant vs numpy (XLA backend)
# ---------------------------------------------------------------------------

COUNT = 96          # divisible by every registered nchunks at n=2/4/8
RNG = np.random.default_rng(11)


def _allreduce_case(job, teams, alg, op=ReductionOp.SUM,
                    dt=DataType.FLOAT32, nd=np.float32, inplace=False,
                    count=COUNT):
    n = len(teams)
    srcs = [(RNG.standard_normal(count) * 3).astype(nd)
            for _ in range(n)]

    def mk(r):
        if inplace:
            buf = dev_buf(job, r, srcs[r], dt)
            return CollArgs(coll_type=CollType.ALLREDUCE, src=buf,
                            dst=buf, op=op, flags=CollArgsFlags.IN_PLACE)
        return CollArgs(coll_type=CollType.ALLREDUCE,
                        src=dev_buf(job, r, srcs[r], dt),
                        dst=BufferInfo(None, count, dt,
                                       mem_type=MemoryType.TPU), op=op)
    reqs, argses = run_forced(job, teams, alg, mk)
    outs = [np.asarray(a.dst.buffer) for a in argses]
    stack = np.stack([s.astype(np.float32) for s in srcs])
    ref = {ReductionOp.SUM: stack.sum(0),
           ReductionOp.AVG: stack.sum(0) / n,
           ReductionOp.MAX: stack.max(0),
           ReductionOp.MIN: stack.min(0),
           ReductionOp.PROD: stack.prod(0)}[op]
    for rq in reqs:
        rq.finalize()
    return outs, ref


class TestAllreduceXla:
    @pytest.mark.parametrize("alg", [
        "gen_dev_ring_c1", "gen_dev_ring_c2", "gen_dev_ring_c4",
        "gen_dev_rhd_r2", "gen_dev_rhd_r4"])
    def test_sum_f32(self, job, teams, alg):
        algs = registered_dev_algs(teams, CollType.ALLREDUCE)
        if alg not in algs:
            pytest.skip(f"{alg} not registered at n={N}")
        outs, ref = _allreduce_case(job, teams, alg)
        for out in outs:
            np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)
        # cross-rank bitwise agreement (every rank ran the same
        # generated schedule)
        for out in outs[1:]:
            assert (out.view(np.int32) == outs[0].view(np.int32)).all()

    @pytest.mark.parametrize("op", [ReductionOp.AVG, ReductionOp.MAX,
                                    ReductionOp.PROD])
    def test_ops(self, job, teams, op):
        outs, ref = _allreduce_case(job, teams, "gen_dev_ring_c1",
                                    op=op)
        for out in outs:
            np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)

    def test_inplace(self, job, teams):
        outs, ref = _allreduce_case(job, teams, "gen_dev_rhd_r2",
                                    inplace=True)
        for out in outs:
            np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)

    def test_bf16(self, job, teams):
        ml_dtypes = pytest.importorskip("ml_dtypes")
        outs, ref = _allreduce_case(job, teams, "gen_dev_ring_c2",
                                    dt=DataType.BFLOAT16,
                                    nd=ml_dtypes.bfloat16)
        for out in outs:
            np.testing.assert_allclose(out.astype(np.float32), ref,
                                       rtol=0.05, atol=0.2)

    def test_quantized_budget_and_agreement(self, job, teams):
        count = 256
        srcs = [(RNG.standard_normal(count) * 2).astype(np.float32)
                for _ in range(N)]

        def mk(r):
            return CollArgs(coll_type=CollType.ALLREDUCE,
                            src=dev_buf(job, r, srcs[r],
                                        DataType.FLOAT32),
                            dst=BufferInfo(None, count, DataType.FLOAT32,
                                           mem_type=MemoryType.TPU),
                            op=ReductionOp.SUM)
        reqs, argses = run_forced(job, teams, "gen_dev_qint8_direct", mk)
        outs = [np.asarray(a.dst.buffer) for a in argses]
        for rq in reqs:
            rq.finalize()
        exact = np.stack(srcs).sum(0)
        scale = np.abs(exact).max() or 1.0
        assert np.abs(outs[0] - exact).max() / scale < 0.1
        # sender-side re-decode keeps every rank bitwise identical
        for out in outs[1:]:
            assert (out.view(np.int32) == outs[0].view(np.int32)).all()


class TestBcastXla:
    @pytest.mark.parametrize("alg", ["gen_dev_bc_kn_r2",
                                     "gen_dev_bc_linear",
                                     "gen_dev_bc_chain_c2"])
    @pytest.mark.parametrize("root", list(range(N)))
    def test_all_roots(self, job, teams, alg, root):
        data = (np.arange(COUNT) * 1.5 + 7).astype(np.float32)

        def mk(r):
            src = data if r == root else np.zeros(COUNT, np.float32)
            return CollArgs(coll_type=CollType.BCAST, root=root,
                            src=dev_buf(job, r, src, DataType.FLOAT32))
        reqs, argses = run_forced(job, teams, alg, mk)
        for r in range(N):
            np.testing.assert_array_equal(
                np.asarray(argses[r].src.buffer), data)
        for rq in reqs:
            rq.finalize()


# ---------------------------------------------------------------------------
# pallas interpret backend (same variants, remote-DMA kernels)
# ---------------------------------------------------------------------------

class TestPallasInterpret:
    @pytest.mark.parametrize("alg", [
        "gen_dev_ring_c1",            # _make_step_dma ring fast path
        "gen_dev_rhd_r4",             # generic full-perm layer path
        "gen_dev_qint8_direct"])      # in-kernel quantize/dequantize
    def test_allreduce(self, pallas_job, alg):
        job, teams = pallas_job
        count = 64
        srcs = [(RNG.standard_normal(count) * 2).astype(np.float32)
                for _ in range(N)]

        def mk(r):
            return CollArgs(coll_type=CollType.ALLREDUCE,
                            src=dev_buf(job, r, srcs[r],
                                        DataType.FLOAT32),
                            dst=BufferInfo(None, count, DataType.FLOAT32,
                                           mem_type=MemoryType.TPU),
                            op=ReductionOp.SUM)
        reqs, argses = run_forced(job, teams, alg, mk, timeout=180)
        outs = [np.asarray(a.dst.buffer) for a in argses]
        for rq in reqs:
            rq.finalize()
        exact = np.stack(srcs).sum(0)
        scale = np.abs(exact).max() or 1.0
        tol = 0.1 if "qint8" in alg else 1e-5
        assert np.abs(outs[0] - exact).max() / scale < tol
        for out in outs[1:]:
            assert (out.view(np.int32) == outs[0].view(np.int32)).all()

    def test_bcast_nonzero_root(self, pallas_job):
        job, teams = pallas_job
        count = 64
        data = np.arange(count, dtype=np.float32) + 5

        def mk(r):
            src = data if r == 2 else np.zeros(count, np.float32)
            return CollArgs(coll_type=CollType.BCAST, root=2,
                            src=dev_buf(job, r, src, DataType.FLOAT32))
        reqs, argses = run_forced(job, teams, "gen_dev_bc_chain_c2",
                                  mk, timeout=180)
        for r in range(N):
            np.testing.assert_array_equal(
                np.asarray(argses[r].src.buffer), data)
        for rq in reqs:
            rq.finalize()

    def test_matches_xla_backend_bitwise(self, job, teams, pallas_job):
        """Both backends execute the identical layer plan: same inputs
        -> bitwise-identical outputs."""
        pj, pteams = pallas_job
        count = 64
        srcs = [(RNG.standard_normal(count) * 2).astype(np.float32)
                for _ in range(N)]

        def run(j, tms):
            def mk(r):
                return CollArgs(
                    coll_type=CollType.ALLREDUCE,
                    src=dev_buf(j, r, srcs[r], DataType.FLOAT32),
                    dst=BufferInfo(None, count, DataType.FLOAT32,
                                   mem_type=MemoryType.TPU),
                    op=ReductionOp.SUM)
            reqs, argses = run_forced(j, tms, "gen_dev_rhd_r2", mk,
                                      timeout=180)
            outs = [np.asarray(a.dst.buffer).copy() for a in argses]
            for rq in reqs:
                rq.finalize()
            return outs
        a = run(job, teams)
        b = run(pj, pteams)
        for x, y in zip(a, b):
            assert (x.view(np.int32) == y.view(np.int32)).all()


# ---------------------------------------------------------------------------
# 2- and 8-rank meshes
# ---------------------------------------------------------------------------

class TestOtherTeamSizes:
    @pytest.mark.parametrize("n", [2, 8])
    def test_matrix(self, n):
        if len(jax.devices()) < n:
            pytest.skip(f"needs >= {n} virtual devices")
        os.environ["UCC_GEN_DEVICE"] = "y"
        os.environ["UCC_QUANT"] = "int8"
        j = UccJob(n)
        try:
            tms = j.create_team()
            algs = registered_dev_algs(tms, CollType.ALLREDUCE)
            assert "gen_dev_ring_c1" in algs
            srcs = [(RNG.standard_normal(COUNT) * 2).astype(np.float32)
                    for _ in range(n)]
            ref = np.stack(srcs).sum(0)
            for alg in algs:
                def mk(r):
                    return CollArgs(
                        coll_type=CollType.ALLREDUCE,
                        src=dev_buf(j, r, srcs[r], DataType.FLOAT32),
                        dst=BufferInfo(None, COUNT, DataType.FLOAT32,
                                       mem_type=MemoryType.TPU),
                        op=ReductionOp.SUM)
                reqs, argses = run_forced(j, tms, alg, mk)
                outs = [np.asarray(a.dst.buffer) for a in argses]
                for rq in reqs:
                    rq.finalize()
                tol = 0.1 * (np.abs(ref).max() or 1.0) \
                    if "qint8" in alg else 1e-4
                assert np.abs(outs[0] - ref).max() < tol, alg
                for out in outs[1:]:
                    assert (out.view(np.int32)
                            == outs[0].view(np.int32)).all(), alg
            for alg in registered_dev_algs(tms, CollType.BCAST):
                data = np.arange(COUNT, dtype=np.float32)
                root = n - 1

                def mkb(r):
                    src = data if r == root else np.zeros(COUNT,
                                                          np.float32)
                    return CollArgs(coll_type=CollType.BCAST, root=root,
                                    src=dev_buf(j, r, src,
                                                DataType.FLOAT32))
                reqs, argses = run_forced(j, tms, alg, mkb)
                for r in range(n):
                    np.testing.assert_array_equal(
                        np.asarray(argses[r].src.buffer), data, alg)
                for rq in reqs:
                    rq.finalize()
        finally:
            j.cleanup()
            os.environ.pop("UCC_GEN_DEVICE", None)
            os.environ.pop("UCC_QUANT", None)


# ---------------------------------------------------------------------------
# fallback behavior
# ---------------------------------------------------------------------------

class TestFallback:
    def test_nondivisible_count_falls_back(self, job, teams):
        """A TUNE-pinned generated-device candidate refusing a count
        (chunk divisibility) walks the fallback chain to the monolithic
        program instead of failing the collective."""
        count = 67                     # not divisible by 4 chunks
        srcs = [np.ones(count, np.float32) * (r + 1) for r in range(N)]
        argses = [CollArgs(coll_type=CollType.ALLREDUCE,
                           src=dev_buf(job, r, srcs[r],
                                       DataType.FLOAT32),
                           dst=BufferInfo(None, count, DataType.FLOAT32,
                                          mem_type=MemoryType.TPU),
                           op=ReductionOp.SUM) for r in range(N)]
        from ucc_tpu.api.types import coll_args_msgsize
        from ucc_tpu.core.coll import InitArgs
        msgsize = coll_args_msgsize(argses[0], N, 0)
        cands = teams[0].score_map.lookup(CollType.ALLREDUCE,
                                          MemoryType.TPU, msgsize)
        gen = [c for c in cands if c.alg_name == "gen_dev_rhd_r2"]
        assert gen
        ia = InitArgs(args=argses[0], team=teams[0],
                      mem_type=MemoryType.TPU, msgsize=msgsize)
        task, chosen = teams[0].score_map.init_coll(
            CollType.ALLREDUCE, MemoryType.TPU, msgsize, ia,
            gen + [c for c in cands if c.alg_name != "gen_dev_rhd_r2"])
        assert chosen.alg_name != "gen_dev_rhd_r2"

    def test_wrong_team_size_not_registered(self):
        """Programs are built per team size at registration; a 3-rank
        team registers 3-rank programs only (rhd pow-of-radix grid
        entries drop out, ring stays)."""
        if len(jax.devices()) < 3:
            pytest.skip("needs >= 3 devices")
        os.environ["UCC_GEN_DEVICE"] = "y"
        j = UccJob(3)
        try:
            tms = j.create_team()
            algs = registered_dev_algs(tms, CollType.ALLREDUCE)
            assert "gen_dev_ring_c1" in algs
            assert "gen_dev_rhd_r2" not in algs   # 3 != 2^k
        finally:
            j.cleanup()
            os.environ.pop("UCC_GEN_DEVICE", None)


# ---------------------------------------------------------------------------
# per-team shared state is dropped at team destroy
# ---------------------------------------------------------------------------

class TestTeamSharedClear:
    def test_destroy_clears_programs_and_pending_unit(self):
        """A refcount-0 put() on a bare XlaTeamShared drops the compiled
        programs and the pending rendezvous slots."""
        from ucc_tpu.tl.xla import XlaTeamShared
        s = XlaTeamShared(object(), None, [], 1)
        s.programs["p"] = "x"
        s.pending[1] = {0: ("shard", None)}
        s.refcount = 1
        s.put()
        assert not s.programs and not s.pending


# ---------------------------------------------------------------------------
# flight-recorder device lifecycle events (ISSUE 15 satellite)
# ---------------------------------------------------------------------------

class TestDeviceFlightEvents:
    def test_dev_launch_and_ready_events(self):
        from ucc_tpu.obs import flight
        if not flight.ENABLED:
            pytest.skip("flight recorder disabled")
        j = UccJob(2)
        try:
            tms = j.create_team()
            count = 64
            argses = [CollArgs(
                coll_type=CollType.ALLREDUCE,
                src=dev_buf(j, r, np.ones(count, np.float32),
                            DataType.FLOAT32),
                dst=BufferInfo(None, count, DataType.FLOAT32,
                               mem_type=MemoryType.TPU),
                op=ReductionOp.SUM) for r in range(2)]
            j.run_coll(tms, lambda r: argses[r])
            kinds = set()
            for rec in flight.recorders():
                for ev in rec.wire.events():
                    kinds.add(ev["kind"])
            assert "dev_launch" in kinds
            assert "dev_ready" in kinds
        finally:
            j.cleanup()


# ---------------------------------------------------------------------------
# device search / cost-model ICI class (ISSUE 15 satellite)
# ---------------------------------------------------------------------------

class TestDeviceSearch:
    def test_ici_link_class(self):
        from ucc_tpu.score import cost
        assert "ici" in cost.SEED_LINKS
        assert cost.link_of_device()(0, 1) == "ici"
        m = cost.CostModel()
        from ucc_tpu.dsl import families as fam
        ring = fam.gen_ring(4, chunks=1)
        direct = fam.gen_rhd(4, radix=4)
        # ICI pricing is latency-light: at tiny sizes the one-round
        # direct exchange must price below the 6-round ring
        small_r = m.predict_us(ring, 256, cost.link_of_device())
        small_d = m.predict_us(direct, 256, cost.link_of_device())
        assert small_d < small_r

    def test_propose_device_space(self):
        from ucc_tpu.dsl.search import propose, shortlist
        from ucc_tpu.score import cost
        cands = propose(CollType.ALLREDUCE, 4, quant_mode="int8",
                        target="device")
        names = {c.name for c in cands}
        assert "gen_ring_c1" in names
        assert "gen_rhd_r4" in names or "gen_rhd_r2" in names
        assert any(n.startswith("gen_qint8") for n in names)
        # nothing non-lowerable leaks in
        assert not any(c.family in ("sra", "sra_pipe", "hier")
                       for c in cands)
        sl = shortlist(cands, cost.CostModel(), 1 << 16, 4,
                       cost.link_of_device())
        assert len(sl) == 4
        assert all(c.predicted_us is not None for c in sl)
        # non-device colls refuse the device target
        assert propose(CollType.ALLGATHER, 4, target="device") == []


# ---------------------------------------------------------------------------
# device-side stragglers feed the continuous scorer (ISSUE 16)
# ---------------------------------------------------------------------------

class TestDeviceStragglerScoring:
    """dev_launch/dev_ready wire events share a (team, tag, slot) key
    across ranks, so the wire-lag straggler signal — and therefore the
    continuous collector's incremental StragglerScorer — attributes a
    slow device rank even though XLA collectives post no host wire
    rounds at all."""

    @staticmethod
    def _dev_window(n=4, lag_rank=1, lag_s=0.08, n_colls=3):
        """One synthetic merged window: every rank launches the same
        device collectives; *lag_rank*'s launches trail by *lag_s*."""
        ranks = {}
        for r in range(n):
            off = lag_s if r == lag_rank else 0.0
            wire = []
            for c in range(n_colls):
                t0 = 1.0 + 0.5 * c + off
                wire.append({"t": t0, "ev": "snd", "kind": "dev_launch",
                             "tkey": "xteam", "epoch": 0, "tag": 100 + c,
                             "slot": 0, "nbytes": 4096})
                wire.append({"t": t0 + 0.01, "ev": "snd",
                             "kind": "dev_ready", "tkey": "xteam",
                             "epoch": 0, "tag": 100 + c, "slot": 1,
                             "nbytes": 4096})
            ranks[r] = {"events": [], "wire": wire}
        return {"ranks": {str(r): v for r, v in ranks.items()},
                "absent_ranks": []}

    def test_wire_lag_names_slow_device_rank(self):
        from ucc_tpu.obs import diagnose
        findings = diagnose.detect_stragglers(self._dev_window())
        lag_f = [f for f in findings if f["signal"] == "wire_lag"]
        assert lag_f and lag_f[0]["rank"] == 1
        assert lag_f[0]["lag_s"] == pytest.approx(0.08, abs=0.02)

    def test_scorer_flags_persistently_slow_device_rank(self):
        from ucc_tpu.obs import diagnose
        sc = diagnose.StragglerScorer(decay=0.5, flag_on=0.7,
                                      flag_off=0.2, windows=2)
        flagged = frozenset()
        for _ in range(4):
            flagged = sc.step(self._dev_window())
        assert flagged == frozenset({1})
        # symmetric launches never flag
        sc2 = diagnose.StragglerScorer(windows=2)
        for _ in range(4):
            assert sc2.step(self._dev_window(lag_s=0.0)) == frozenset()

    def test_live_dev_events_flow_into_scorer(self):
        """A real generated-device allreduce leaves dev_launch/dev_ready
        wire events that survive cross-rank merge and feed the scorer
        without tripping it on a healthy run. Own job: the shared module
        teams carry abandoned-init tag skew from the fallback tests."""
        from ucc_tpu.obs import diagnose, flight
        if not flight.ENABLED:
            pytest.skip("flight recorder disabled")
        if len(jax.devices()) < N:
            pytest.skip("needs >= 4 virtual devices")
        had = os.environ.get("UCC_GEN_DEVICE")
        os.environ["UCC_GEN_DEVICE"] = "y"
        j = UccJob(N)
        try:
            tms = j.create_team()
            count = 96
            srcs = [np.ones(count, np.float32) * (r + 1)
                    for r in range(N)]

            def mk(r):
                return CollArgs(coll_type=CollType.ALLREDUCE,
                                src=dev_buf(j, r, srcs[r],
                                            DataType.FLOAT32),
                                dst=BufferInfo(None, count,
                                               DataType.FLOAT32,
                                               mem_type=MemoryType.TPU),
                                op=ReductionOp.SUM)
            reqs, _ = run_forced(j, tms, "gen_dev_ring_c1", mk)
            for rq in reqs:
                rq.finalize()
            merged = flight.collect_process(j.contexts[0], "test")
        finally:
            j.cleanup()
            if had is None:
                os.environ.pop("UCC_GEN_DEVICE", None)
            else:
                os.environ["UCC_GEN_DEVICE"] = had
        kinds = {w.get("kind")
                 for snap in merged["ranks"].values()
                 for w in snap.get("wire", ())}
        assert "dev_launch" in kinds and "dev_ready" in kinds
        sc = diagnose.StragglerScorer(windows=2)
        # first window can never flag (streak < windows); the call must
        # digest device-kind wire events without raising
        assert sc.step(merged) == frozenset()
        assert sc.windows_seen == 1
