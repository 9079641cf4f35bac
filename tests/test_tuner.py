"""Autotuner tests (ISSUE 5): online exploration + rank-0 freeze,
topology-keyed cache round trip, offline compilation, and the
zero-cost-when-off contract."""
import json
import os

import numpy as np
import pytest

import ucc_tpu
from ucc_tpu import (BufferInfo, CollArgs, CollArgsFlags, CollType,
                     ReductionOp, Status)
from ucc_tpu.constants import DataType, MemoryType
from ucc_tpu.score.tuner import (bucket_range, cand_label,
                                 cache_entries, compile_measurements,
                                 load_cache, size_bucket, store_entries,
                                 topo_signature)
from ucc_tpu.utils.config import SIZE_INF

from harness import UccJob

COUNT = 8192                       # 32 KiB f32: the bandwidth-alg regime
NBYTES = COUNT * 4


@pytest.fixture(autouse=True)
def _fresh_session_cache():
    # decisions frozen by one test must not warm-start the next — each
    # test owns its tmp_path file cache, so the in-process session cache
    # (membership-change warm-start, PR 17) is cleared around each test
    from ucc_tpu.score import tuner
    tuner.session_reset()
    yield
    tuner.session_reset()


def _persistent_allreduce(teams, srcs, dsts):
    argses = [CollArgs(coll_type=CollType.ALLREDUCE, op=ReductionOp.SUM,
                       src=BufferInfo(srcs[r], COUNT, DataType.FLOAT32),
                       dst=BufferInfo(dsts[r], COUNT, DataType.FLOAT32),
                       flags=CollArgsFlags.PERSISTENT)
              for r in range(len(teams))]
    return [teams[r].collective_init(argses[r]) for r in range(len(teams))]


def _drive(job, reqs, rounds, dsts, n):
    for _ in range(rounds):
        for rq in reqs:
            rq.post()
        job.progress_until(lambda: all(
            rq.test() != Status.IN_PROGRESS for rq in reqs))
        for rq in reqs:
            assert rq.test() == Status.OK, rq.test()
        # exploration must never trade correctness: every round is a
        # real allreduce of ones over n ranks
        for d in dsts:
            assert abs(float(d[0]) - n) < 1e-6


# ---------------------------------------------------------------------------
# unit level
# ---------------------------------------------------------------------------

class TestUnits:
    def test_size_buckets(self):
        assert size_bucket(0) == 0
        assert bucket_range(0) == (0, 1)
        for msg in (1, 7, 4096, 32768, (1 << 20) + 3):
            lo, hi = bucket_range(size_bucket(msg))
            assert lo <= msg < hi

    def test_compile_measurements_merges_adjacent_winners(self):
        recs = []
        for size, winner in ((1024, "a"), (2048, "a"), (4096, "b")):
            for alg in ("a", "b"):
                recs.append({"coll": "allreduce", "mem": "host",
                             "alg": alg, "comp": "shm", "size_bytes": size,
                             "p50_us": 1.0 if alg == winner else 9.0})
        entries = compile_measurements(recs)
        assert entries == [
            {"coll": "allreduce", "mem": "host", "start": 0, "end": 4096,
             "alg": "a", "comp": "shm"},
            {"coll": "allreduce", "mem": "host", "start": 4096,
             "end": SIZE_INF, "alg": "b", "comp": "shm"},
        ]

    def test_compile_skips_malformed_records(self):
        entries = compile_measurements([
            {"coll": "allreduce"},                      # no size/latency
            {"size_bytes": 8, "alg": "x", "p50_us": 1}, # no coll
            {"coll": "bcast", "mem": "host", "alg": "kn",
             "size_bytes": 64, "avg_us": 2.0},          # avg fallback
        ])
        assert len(entries) == 1 and entries[0]["coll"] == "bcast"

    def test_cache_roundtrip_and_merge(self, tmp_path):
        path = str(tmp_path / "tune.json")
        e1 = {"coll": "allreduce", "mem": "host", "start": 0, "end": 4096,
              "alg": "a"}
        store_entries(path, "sigA", [e1])
        # same window replaces, new window appends, other sig untouched
        e2 = dict(e1, alg="b")
        e3 = {"coll": "allreduce", "mem": "host", "start": 4096,
              "end": 8192, "alg": "c"}
        store_entries(path, "sigA", [e2, e3], source="online")
        store_entries(path, "sigB", [e1])
        cache = load_cache(path)
        got = cache_entries(cache, "sigA")
        assert [e["alg"] for e in got] == ["b", "c"]
        assert cache_entries(cache, "sigB")[0]["alg"] == "a"
        assert cache_entries(cache, "nope") == []

    def test_load_cache_tolerates_garbage(self, tmp_path):
        p = tmp_path / "bad.json"
        p.write_text("{not json")
        assert load_cache(str(p)) == {}
        assert load_cache(str(tmp_path / "missing.json")) == {}


# ---------------------------------------------------------------------------
# online mode: 4-rank convergence, agreement, cache persistence
# ---------------------------------------------------------------------------

SAMPLES = 8
# freeze point: SAMPLES exploration posts, then the decision is posted
# on the FIRST hold post (so the last exploration sample is recorded),
# then the deterministic hold window (service-bcast tree depth + 2 = 3
# for a 4-rank team), then the switch post — see the OnlineTuner
# divergence-safety docstring
FREEZE_ROUNDS = SAMPLES + 1 + 3 + 1


class TestOnline:
    def test_converges_freezes_and_agrees(self, tmp_path):
        cache = str(tmp_path / "tune.json")
        job = UccJob(4, lib_overrides={"TUNER": "online",
                                       "TUNER_SAMPLES": str(SAMPLES),
                                       "TUNER_CACHE": cache})
        try:
            teams = job.create_team()
            assert all(t.tuner is not None for t in teams)
            sigs = {topo_signature(t) for t in teams}
            assert len(sigs) == 1            # signature is rank-invariant
            srcs = [np.ones(COUNT, np.float32) for _ in range(4)]
            dsts = [np.zeros(COUNT, np.float32) for _ in range(4)]
            reqs = _persistent_allreduce(teams, srcs, dsts)
            # probe lane bound while exploring: _post is an instance attr
            assert all("_post" in rq.__dict__ for rq in reqs)
            _drive(job, reqs, FREEZE_ROUNDS + 1, dsts, 4)
            # converged: exploration bounded by the sample budget, then
            # the deterministic hold window, then frozen + unbound
            assert all("_post" not in rq.__dict__ for rq in reqs)
            assert all(not t.tuner.exploring(
                t.tuner.key_for(CollType.ALLREDUCE, MemoryType.HOST,
                                NBYTES)) for t in teams)
            # every rank runs the SAME winner (the rank-0 decision)
            algs = {rq.task.alg_name for rq in reqs}
            assert len(algs) == 1, algs
            tops = {(t.score_map.lookup(CollType.ALLREDUCE,
                                        MemoryType.HOST, NBYTES)[0].alg_name,
                     t.score_map.lookup(CollType.ALLREDUCE,
                                        MemoryType.HOST, NBYTES)[0].origin)
                    for t in teams}
            assert len(tops) == 1
            assert next(iter(tops))[1] == "learned"
            # later rounds stay on the frozen winner
            _drive(job, reqs, 3, dsts, 4)
            assert {rq.task.alg_name for rq in reqs} == algs
            # rank 0 persisted the decision, keyed by the signature
            data = load_cache(cache)
            entries = cache_entries(data, next(iter(sigs)))
            assert entries, data
            lo, hi = bucket_range(size_bucket(NBYTES))
            assert any(e["coll"] == "allreduce" and e["start"] == lo and
                       e["end"] == hi for e in entries)
            for rq in reqs:
                rq.finalize()
        finally:
            job.cleanup()

    def test_cache_reload_starts_tuned_with_zero_exploration(self,
                                                             tmp_path):
        cache = str(tmp_path / "tune.json")
        overrides = {"TUNER": "online", "TUNER_SAMPLES": str(SAMPLES),
                     "TUNER_CACHE": cache}
        job = UccJob(4, lib_overrides=overrides)
        try:
            teams = job.create_team()
            srcs = [np.ones(COUNT, np.float32) for _ in range(4)]
            dsts = [np.zeros(COUNT, np.float32) for _ in range(4)]
            reqs = _persistent_allreduce(teams, srcs, dsts)
            _drive(job, reqs, FREEZE_ROUNDS + 1, dsts, 4)
            winner = reqs[0].task.alg_name
            for rq in reqs:
                rq.finalize()
        finally:
            job.cleanup()

        # second activation: the learned table loads at team create and
        # the key is covered — no probe lane, no exploration posts
        job2 = UccJob(4, lib_overrides=overrides)
        try:
            teams2 = job2.create_team()
            top = teams2[0].score_map.lookup(CollType.ALLREDUCE,
                                             MemoryType.HOST, NBYTES)[0]
            assert top.origin == "learned" and top.alg_name == winner
            srcs = [np.ones(COUNT, np.float32) for _ in range(4)]
            dsts = [np.zeros(COUNT, np.float32) for _ in range(4)]
            reqs = _persistent_allreduce(teams2, srcs, dsts)
            assert all("_post" not in rq.__dict__ for rq in reqs)
            assert all(rq.task.alg_name == winner for rq in reqs)
            _drive(job2, reqs, 2, dsts, 4)
            assert all(not t.tuner._keys for t in teams2)  # zero explored
            for rq in reqs:
                rq.finalize()
        finally:
            job2.cleanup()

    def test_overlapped_posts_freeze_to_static_defaults(self, tmp_path):
        """Streaming apps post a key's collectives back-to-back without
        waiting; post counts then advance without completions, breaking
        the hold window's causality argument. claim() detects the
        overlap by FINALIZE order (program order, rank-invariant) and
        deterministically ends tuning for the key instead."""
        cache = str(tmp_path / "tune.json")
        job = UccJob(2, lib_overrides={"TUNER": "online",
                                       "TUNER_SAMPLES": "4",
                                       "TUNER_CACHE": cache})
        try:
            teams = job.create_team()
            srcs = [np.ones(COUNT, np.float32) for _ in range(2)]
            d1 = [np.zeros(COUNT, np.float32) for _ in range(2)]
            d2 = [np.zeros(COUNT, np.float32) for _ in range(2)]
            r1 = _persistent_allreduce(teams, srcs, d1)
            r2 = _persistent_allreduce(teams, srcs, d2)
            assert all("_post" in rq.__dict__ for rq in r1 + r2)
            # overlap: post BOTH requests on every rank before waiting
            for rq in r1:
                rq.post()
            for rq in r2:
                rq.post()
            job.progress_until(lambda: all(
                rq.test() != Status.IN_PROGRESS for rq in r1 + r2))
            for rq in r1 + r2:
                assert rq.test() == Status.OK
            for d in d1 + d2:
                assert abs(float(d[0]) - 2) < 1e-6
            # the overlapped key froze to static defaults on every rank
            key = teams[0].tuner.key_for(CollType.ALLREDUCE,
                                         MemoryType.HOST, NBYTES)
            for t in teams:
                st = t.tuner._keys[key]
                assert st.frozen and st.winner is None
            top = teams[0].score_map.lookup(CollType.ALLREDUCE,
                                            MemoryType.HOST, NBYTES)[0]
            assert top.origin == "default"
            # later rounds keep working, unbound, on the same algorithm
            for _ in range(2):
                for rq in r1:
                    rq.post()
                job.progress_until(lambda: all(
                    rq.test() != Status.IN_PROGRESS for rq in r1))
            assert all("_post" not in rq.__dict__ for rq in r1 + r2)
            assert len({rq.task.alg_name for rq in r1}) == 1
            for rq in r1 + r2:
                rq.finalize()
        finally:
            job.cleanup()

    def test_single_rank_team_freezes_locally(self, tmp_path):
        # size-1 teams decide through tl/self's trivial service bcast
        cache = str(tmp_path / "tune.json")
        job = UccJob(1, lib_overrides={"TUNER": "online",
                                       "TUNER_SAMPLES": "2",
                                       "TUNER_CACHE": cache})
        try:
            teams = job.create_team()
            # a 1-rank team's score map usually has a single live self
            # candidate per coll -> wants() is False and nothing binds;
            # the team must still activate and run
            srcs = [np.ones(COUNT, np.float32)]
            dsts = [np.zeros(COUNT, np.float32)]
            reqs = _persistent_allreduce(teams, srcs, dsts)
            _drive(job, reqs, 3, dsts, 1)
            for rq in reqs:
                rq.finalize()
        finally:
            job.cleanup()


class TestOffModes:
    def test_off_leaves_dispatch_unbound(self):
        job = UccJob(2)
        try:
            teams = job.create_team()
            assert all(t.tuner is None for t in teams)
            srcs = [np.ones(COUNT, np.float32) for _ in range(2)]
            dsts = [np.zeros(COUNT, np.float32) for _ in range(2)]
            reqs = _persistent_allreduce(teams, srcs, dsts)
            # no probe lane: post stays the plain class method (the
            # UCC_TUNER=off byte-identical dispatch contract)
            assert all("_post" not in rq.__dict__ for rq in reqs)
            assert all(rq._tuner is None for rq in reqs)
            _drive(job, reqs, 2, dsts, 2)
            for rq in reqs:
                rq.finalize()
        finally:
            job.cleanup()

    def test_offline_applies_cache_without_exploring(self, tmp_path):
        cache = str(tmp_path / "tune.json")
        # probe the signature with a throwaway off-mode job first
        probe = UccJob(2)
        try:
            sig = topo_signature(probe.create_team()[0])
        finally:
            probe.cleanup()
        store_entries(cache, sig, [
            {"coll": "allreduce", "mem": "host", "start": 0,
             "end": SIZE_INF, "alg": "ring", "comp": "shm"}])
        job = UccJob(2, lib_overrides={"TUNER": "offline",
                                       "TUNER_CACHE": cache})
        try:
            teams = job.create_team()
            assert all(t.tuner is None for t in teams)  # no explorer
            for t in teams:
                top = t.score_map.lookup(CollType.ALLREDUCE,
                                         MemoryType.HOST, NBYTES)[0]
                assert (top.alg_name, top.origin) == ("ring", "learned")
            srcs = [np.ones(COUNT, np.float32) for _ in range(2)]
            dsts = [np.zeros(COUNT, np.float32) for _ in range(2)]
            reqs = _persistent_allreduce(teams, srcs, dsts)
            assert all(rq.task.alg_name == "ring" for rq in reqs)
            _drive(job, reqs, 2, dsts, 2)
            for rq in reqs:
                rq.finalize()
        finally:
            job.cleanup()

    def test_mismatched_signature_is_ignored(self, tmp_path):
        cache = str(tmp_path / "tune.json")
        store_entries(cache, "v1|n999|some-other-shape", [
            {"coll": "allreduce", "mem": "host", "start": 0,
             "end": SIZE_INF, "alg": "ring", "comp": "shm"}])
        job = UccJob(2, lib_overrides={"TUNER": "offline",
                                       "TUNER_CACHE": cache})
        try:
            teams = job.create_team()
            top = teams[0].score_map.lookup(CollType.ALLREDUCE,
                                            MemoryType.HOST, NBYTES)[0]
            assert top.origin == "default"
        finally:
            job.cleanup()


# ---------------------------------------------------------------------------
# offline CLI (tools/tune.py / ucc_tune)
# ---------------------------------------------------------------------------

class TestOfflineCli:
    def test_sweep_writes_cache_and_from_compiles(self, tmp_path):
        from ucc_tpu.tools.tune import main as tune_main
        cache = str(tmp_path / "cache.json")
        meas = str(tmp_path / "sweep.jsonl")
        rc = tune_main(["-p", "2", "-c", "allreduce", "-b", "1k", "-e",
                        "2k", "-n", "2", "-w", "0", "-o", cache,
                        "--measurements", meas])
        assert rc == 0
        data = load_cache(cache)
        sigs = list((data.get("signatures") or {}))
        assert len(sigs) == 1 and sigs[0].startswith("v1|n2|")
        entries = cache_entries(data, sigs[0])
        assert entries and entries[0]["coll"] == "allreduce"
        assert os.path.exists(meas)
        records = [json.loads(ln) for ln in open(meas)]
        assert all(r["bench"] == "sweep" for r in records)
        assert {r["alg"] for r in records} >= {"knomial", "ring"}
        # --from re-compiles the measurement file into a second cache
        cache2 = str(tmp_path / "cache2.json")
        rc = tune_main(["--from", meas, "--signature", sigs[0], "-o",
                        cache2])
        assert rc == 0
        assert cache_entries(load_cache(cache2), sigs[0]) == entries
