"""Logarithmic bootstrap (ISSUE 8): tree-structured OOB store exchange —
layout construction, thread/TCP tree allgather correctness, O(log n)
round/fan-in scaling, subset-capable SubsetOob rounds, and the k-ary
TransportOob exchange surviving its rewrite."""
import socket
import threading

import pytest

from ucc_tpu.core.oob import (SubsetOob, TcpTreeOob, ThreadOobWorld,
                              ThreadTreeOobWorld, tree_layout)


class TestTreeLayout:
    def test_symmetric(self):
        lay = tree_layout(64, ppn=8, radix=4)
        assert [len(groups) for groups in lay] == [8, 2, 1]
        assert lay[0][0] == list(range(8))
        assert lay[1][0] == [0, 8, 16, 24]          # node leaders
        assert lay[2][0] == [0, 32]                 # chunk leaders

    def test_asymmetric_cyclic(self):
        lay = tree_layout(5, ppn="2,1", radix=2)
        assert lay[0] == [[0, 1], [2], [3, 4]]
        assert lay[1] == [[0, 2], [3]]
        assert lay[2] == [[0, 3]]

    def test_single_rank(self):
        assert tree_layout(1) == [[[0]]]

    def test_single_node(self):
        assert tree_layout(4, ppn=8) == [[[0, 1, 2, 3]]]

    def test_no_ppn_uses_radix_blocks(self):
        lay = tree_layout(16, radix=4)
        assert [len(g) for g in lay[0]] == [4, 4, 4, 4]
        assert len(lay) == 2

    def test_every_level_partitions_leaders(self):
        lay = tree_layout(100, ppn="3,1,5", radix=3)
        # level 0 partitions ALL ranks
        flat = sorted(r for g in lay[0] for r in g)
        assert flat == list(range(100))
        # each level's members are exactly the previous level's leaders
        for lvl in range(1, len(lay)):
            members = sorted(r for g in lay[lvl] for r in g)
            leaders = sorted(g[0] for g in lay[lvl - 1])
            assert members == leaders
        assert len(lay[-1]) == 1


def _run_threads(n, fn):
    errs = []

    def wrap(r):
        try:
            fn(r)
        except Exception as e:  # noqa: BLE001 - surfaced below
            errs.append((r, e))

    ths = [threading.Thread(target=wrap, args=(r,)) for r in range(n)]
    for t in ths:
        t.start()
    for t in ths:
        t.join(60)
    assert not errs, errs


class TestThreadTreeOob:
    def test_allgather_matches_world(self):
        n = 24
        w = ThreadTreeOobWorld(n, ppn=3, radix=2)
        eps = w.endpoints()
        out = [None] * n

        def run(r):
            out[r] = eps[r].allgather(f"blob-{r}".encode()).result

        _run_threads(n, run)
        expect = [f"blob-{r}".encode() for r in range(n)]
        assert all(o == expect for o in out)

    def test_pipelined_rounds_stay_ordered(self):
        n = 12
        w = ThreadTreeOobWorld(n, ppn=4, radix=2)
        eps = w.endpoints()
        out = [None] * n

        def run(r):
            reqs = [eps[r].allgather(f"{r}.{i}".encode()) for i in range(4)]
            out[r] = [rq.result for rq in reqs]

        _run_threads(n, run)
        for r in range(n):
            for i in range(4):
                assert out[r][i] == [f"{x}.{i}".encode() for x in range(n)]

    def test_empty_and_large_payloads(self):
        n = 9
        w = ThreadTreeOobWorld(n, ppn=3, radix=3)
        eps = w.endpoints()
        payloads = [b"" if r % 2 else bytes([r]) * (10_000 + r)
                    for r in range(n)]
        out = [None] * n

        def run(r):
            out[r] = eps[r].allgather(payloads[r]).result

        _run_threads(n, run)
        assert all(o == payloads for o in out)

    def test_rounds_scale_logarithmically(self):
        """The tentpole claim, at the OOB layer: per-allgather store
        rounds grow with tree DEPTH, per-store fan-in stays bounded by
        max(ppn, radix) — both << n, where the flat store funnels n
        connections into one server."""
        for n in (64, 512):
            w = ThreadTreeOobWorld(n, ppn=8, radix=8)
            eps = w.endpoints()
            out = [None] * n

            def run(r):
                out[r] = eps[r].allgather(str(r).encode()).result

            _run_threads(n, run)
            assert all(o == [str(x).encode() for x in range(n)]
                       for o in out)
            levels = eps[0].stats["levels"]
            assert levels <= 3
            assert max(e.stats["max_fanin"] for e in eps) == 8 < n
            assert max(e.stats["rounds"] for e in eps) <= 2 * levels

    def test_single_rank_world(self):
        w = ThreadTreeOobWorld(1)
        ep = w.endpoint(0)
        assert ep.allgather(b"solo").result == [b"solo"]
        assert ep.stats["rounds"] == 0


class TestTcpTreeOob:
    #: fresh port blocks tried after the first one fails
    RETRIES = 3

    @classmethod
    def _make_ends(cls, n):
        """The n ranks' trees on a freshly probed port block. The probe
        frees its port before the trees bind the ports above it, so
        another process can take one of them in between: construction
        then fails with OSError, and a new block is probed."""
        for _ in range(1 + cls.RETRIES):
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            base = s.getsockname()[1]
            s.close()
            ends = [None] * n
            errs = []

            def mk(r):
                try:
                    ends[r] = TcpTreeOob(r, n, base_port=base + 1, key="t",
                                         ppn=2, radix=2, timeout_s=20)
                except OSError as e:
                    errs.append(e)

            _run_threads(n, mk)
            if not errs:
                return ends
            for e in ends:
                if e is not None:
                    e.close()
        raise errs[0]

    def test_allgather_over_sockets(self):
        n = 8
        assert TcpTreeOob.ports_needed(n, ppn=2, radix=2) == 7
        ends = self._make_ends(n)
        out = [None] * n

        def ag(r):
            out[r] = ends[r].allgather(f"tcp{r}".encode()).result

        _run_threads(n, ag)
        try:
            expect = [f"tcp{r}".encode() for r in range(n)]
            assert all(o == expect for o in out)
            # no store saw more than max(ppn, radix)=2 members
            assert ends[0].stats["max_fanin"] == 2
        finally:
            for e in ends:
                e.close()


class TestSubsetCapability:
    """ISSUE 8 satellite: subset bootstrap over a capable parent runs
    members-only rounds — non-members skip entirely, so a nested
    subgroup create no longer costs a whole-team round per level."""

    def test_members_only_round(self):
        w = ThreadOobWorld(6)
        subs = [SubsetOob(w.endpoint(r), [1, 2, 4]) for r in (1, 2, 4)]
        reqs = [s.allgather(f"m{s.oob_ep}".encode()) for s in subs]
        for rq in reqs:
            assert rq.result == [b"m0", b"m1", b"m2"]
        # the parent's main round space was never touched: ranks 0/3/5
        # did not participate and no main round was consumed
        assert w.next_round == [0] * 6
        assert not w.rounds

    def test_participate_is_noop_on_capable_parent(self):
        w = ThreadOobWorld(4)
        ep = w.endpoint(3)
        from ucc_tpu.status import Status
        rq = SubsetOob.participate(ep)
        assert rq.test() == Status.OK
        assert w.next_round == [0] * 4

    def test_nested_subsets(self):
        w = ThreadOobWorld(8)
        outer_ranks = [1, 3, 5, 7]
        outers = [SubsetOob(w.endpoint(r), outer_ranks)
                  for r in outer_ranks]
        assert all(o.SUBSET_CAPABLE for o in outers)
        # inner subset {3, 7} = outer indices {1, 3}
        inners = [SubsetOob(outers[1], [1, 3]), SubsetOob(outers[3], [1, 3])]
        reqs = [i.allgather(f"n{i.oob_ep}".encode()) for i in inners]
        for rq in reqs:
            assert rq.result == [b"n0", b"n1"]
        assert w.next_round == [0] * 8

    def test_legacy_parent_keeps_full_round_contract(self):
        """A non-capable parent (no subset_allgather) still needs the
        whole-team participate round."""

        class Legacy(ThreadOobWorld):
            pass

        w = Legacy(3)
        eps = w.endpoints()
        for ep in eps:
            ep.SUBSET_CAPABLE = False      # simulate a flat TCP store
            ep.subset_allgather = None
        sub = SubsetOob(eps[1], [1, 2])
        sub2 = SubsetOob(eps[2], [1, 2])
        assert not sub.SUBSET_CAPABLE
        r1 = sub.allgather(b"a")
        r2 = sub2.allgather(b"b")
        SubsetOob.participate(eps[0])      # rank 0 must ride along
        assert r1.result == [b"a", b"b"] == r2.result

    def test_create_from_parent_nonmember_skips(self):
        """Team.create_from_parent over a capable OOB: non-members
        return immediately without consuming any parent round."""
        import sys
        sys.path.insert(0, __file__.rsplit("/", 1)[0])
        from harness import UccJob
        job = UccJob(4)
        try:
            teams = job.create_team()
            world = job.teams and None
            from ucc_tpu.core.team import Team
            subs = {}

            def split(i):
                subs[i] = Team.create_from_parent(teams[i], [0, 2])

            # cooperative: members' create must not need non-members
            for i in (1, 3):
                split(i)
                assert subs[i] is None
            for i in (0, 2):
                split(i)
            import time
            from ucc_tpu import Status
            deadline = time.monotonic() + 30
            while True:
                sts = [subs[i].create_test() for i in (0, 2)]
                if all(s == Status.OK for s in sts):
                    break
                assert not any(s.is_error for s in sts), sts
                for c in job.contexts:
                    c.progress()
                assert time.monotonic() < deadline
            assert subs[0].size == 2 and subs[2].rank == 1
            subs[0].destroy()
            subs[2].destroy()
        finally:
            job.cleanup()


class TestTransportOobTree:
    """The k-ary rewrite of the fault-tolerant transport OOB: correctness
    over a live service-team transport, batched tree fan-in."""

    def _mk_oob(self, job, teams, r, epoch=7):
        from ucc_tpu.core.oob import TransportOob
        svc = teams[r].service_team
        members = [int(teams[r].ctx_map.eval(i))
                   for i in range(teams[r].size)]
        return TransportOob(svc.comp_context, svc.transport, members,
                            teams[r].context.rank,
                            ("test", teams[r].team_key), epoch)

    @pytest.mark.parametrize("n", [2, 5, 9])
    def test_allgather(self, n):
        import sys
        sys.path.insert(0, __file__.rsplit("/", 1)[0])
        from harness import UccJob
        from ucc_tpu import Status
        job = UccJob(n)
        try:
            teams = job.create_team()
            oobs = [self._mk_oob(job, teams, r) for r in range(n)]
            payloads = [b"" if r == 1 else f"tp-{r}".encode() * (r + 1)
                        for r in range(n)]
            reqs = [oobs[r].allgather(payloads[r]) for r in range(n)]
            # list comprehension, NOT a short-circuiting generator:
            # interior tree members forward inside test(), so every
            # member must be polled (the shrink drivers' contract)
            job.progress_until(lambda: all(
                [rq.test() != Status.IN_PROGRESS for rq in reqs]))
            for rq in reqs:
                assert rq.result == payloads
            # second round on the same oob instances (round_idx keying)
            reqs = [oobs[r].allgather(f"r2-{r}".encode())
                    for r in range(n)]
            job.progress_until(lambda: all(
                [rq.test() != Status.IN_PROGRESS for rq in reqs]))
            for rq in reqs:
                assert rq.result == [f"r2-{x}".encode() for x in range(n)]
        finally:
            job.cleanup()
