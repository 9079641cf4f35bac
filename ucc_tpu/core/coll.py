"""Collective dispatch — the hot path.

Reference: /root/reference/src/core/ucc_coll.c (``ucc_collective_init``:172):
memtype auto-detect via MC (:25-36, :216), zero-size fast path with a stub
task (:191-208), active-set restriction to bcast (:210-214), score-map
lookup with fallback (:248), timeout stamping (:409), persistent post
status checks (:362), user callback and coll trace (:329-345).
"""
from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np
from typing import TYPE_CHECKING, Any, Optional

from ..api.types import (BufferInfo, BufferInfoV, CollArgs,
                         coll_args_msgsize)
from ..constants import (FLAG_MEM_MAPPED_BUFFERS, FLAG_TIMEOUT, CollType,
                         DataType, EventType, GenericDataType, MemoryType,
                         ReductionOp, coll_type_str)
from .. import integrity
from ..mc.base import detect_mem_type
from ..obs import metrics
from ..schedule.schedule import Schedule
from ..schedule.task import CollTask
from ..status import RankFailedError, Status, UccError
from ..utils import profiling
from ..utils.log import get_logger

if TYPE_CHECKING:
    # team.py imports this module for Team.collective_init
    from .team import Team

logger = get_logger("coll")

#: the collectives that get a dt-validation prefix (``_maybe_wrap_dt_check``)
#: and those whose results are attested, as int masks: an ``&`` on the
#: IntFlag itself runs enum.Flag.__and__ in Python on every request
_DT_CHECKED = int(CollType.GATHER | CollType.GATHERV | CollType.SCATTER
                  | CollType.SCATTERV | CollType.BCAST | CollType.REDUCE)
_ATTESTED = int(integrity.ATTEST_COLLS)


class _DtCheckTask(CollTask):
    """Datatype-consistency validation for rooted collectives
    (ucc_service_coll.c:231+, design comment ucc_schedule.h:68-94): a
    service allreduce(MIN) over [dt, -dt, mem, -mem]; if min(dt) != -min(-dt)
    some rank passed a different datatype and the collective errors out
    instead of corrupting data."""

    def __init__(self, team: Team, dt_id: int, mem_id: int):
        super().__init__(team=team)
        self.core_team = team
        self.vec = np.array([dt_id, -dt_id, mem_id, -mem_id], dtype=np.int64)
        self._svc = None

    def post_fn(self) -> Status:
        self._svc = self.core_team.service_team.service_allreduce(
            self.vec, ReductionOp.MIN)
        self._svc.post()
        return Status.OK

    def progress_fn(self) -> None:
        svc = self._svc
        if svc is None or not svc.is_completed():
            return
        if svc.super_status.is_error:
            self.status = svc.super_status
            return
        r = svc.result
        if int(r[0]) != -int(r[1]) or int(r[2]) != -int(r[3]):
            logger.error("asymmetric datatype/memtype detected across team "
                         "%s ranks", self.core_team.id)
            self.status = Status.ERR_INVALID_PARAM
            return
        self.status = Status.OK


@dataclass
class InitArgs:
    """ucc_base_coll_args_t: resolved args handed to algorithm inits."""

    args: CollArgs
    team: Team
    mem_type: MemoryType
    msgsize: int


class _StubTask(CollTask):
    """Zero-size fast path (ucc_coll.c:191-208): completes at post."""

    def post_fn(self) -> Status:
        self.status = Status.OK
        return Status.OK


#: task failure statuses eligible for runtime score-map fallback: local
#: resource/support failures. Timeouts and cancels are excluded (they
#: imply peers were already engaged), as is INVALID_PARAM (a different
#: algorithm won't fix the caller's arguments).
_FALLBACK_ELIGIBLE = frozenset((Status.ERR_NOT_SUPPORTED,
                                Status.ERR_NO_RESOURCE,
                                Status.ERR_NO_MESSAGE,
                                Status.ERR_NO_MEMORY))


class CollRequest:
    """ucc_coll_req_h: post/test/finalize + persistent re-post."""

    #: autotuner probe lane (score/tuner.py): while a (coll, mem,
    #: size-bucket) key is still exploring, ``_bind_tuner`` shadows the
    #: class ``_post`` with ``_tuner_post`` as an INSTANCE attribute —
    #: the PR-3 ``_instr`` binding pattern, so UCC_TUNER=off adds no
    #: per-post branch to this hot path
    _tuner = None
    #: flight recorder (obs/flight.py): the context's recorder, bound
    #: once at init (same pattern) — None when UCC_FLIGHT=n, so the post
    #: path pays exactly one branch
    _flight = None
    _flight_msgsize = 0
    #: small-collective coalescer (core/coalesce.py): bound at init for
    #: eligible members of a UCC_COALESCE team — post() hands the task
    #: to the batcher instead of the wire. Class-attr None keeps the
    #: off path at one branch (the _flight pattern).
    _coalesce = None
    #: latency-valve hook bound on priority>=2 teams' requests while any
    #: coalescer is attached in the context: posting flushes open
    #: batches so this collective never waits out a bulk gather window
    _coal_flush = None
    #: sampled result attestation (integrity/__init__.py): bound by
    #: collective_init at the deterministic UCC_INTEGRITY_SAMPLE cadence
    #: under UCC_INTEGRITY=verify — test() holds the request IN_PROGRESS
    #: until the cross-rank digest exchange settles. Class-attr None
    #: keeps the off path at one branch (the _flight pattern).
    _attest = None

    def __init__(self, task: CollTask, team: Team, args: CollArgs):
        self.task = task
        self.team = team
        self.args = args
        fr = team.context.flight
        if fr is not None:
            self._flight = fr
        self._posted = False
        self._finalized = False
        #: runtime fallback chain: (init_args, [remaining MsgRange]) set
        #: by collective_init for plain (unwrapped, non-persistent) tasks
        self._fallback = None
        self._fb_used = False
        # fixed after init: read once here, not on every post
        self._persistent = args.is_persistent
        self._trace = team.coll_trace

    @property
    def status(self) -> Status:
        return self.task.super_status

    @property
    def failed_ranks(self):
        """Attribution for an ERR_RANK_FAILED outcome: the failed ranks
        (context ranks) this request's cancellation named, falling back
        to the context health registry's view. None when no failure has
        been attributed."""
        fr = getattr(self.task, "failed_ranks", None)
        if fr:
            return sorted(int(r) for r in fr)
        # registry fallback ONLY for a rank-failure outcome: a healthy
        # request on an unaffected team must report None even when some
        # other team's rank is known dead
        if self.task.super_status == Status.ERR_RANK_FAILED:
            reg = getattr(self.team.context, "health", None)
            if reg is not None and reg.dead:
                return sorted(reg.dead_set())
        return None

    def post(self) -> Status:
        """ucc_collective_post (ucc_coll.c:375), inside the ``ucc.post``
        layer span on every lane (plain, tuner, coalesce)."""
        tok = profiling.begin("ucc.post")
        if tok is None:
            return self._post()
        try:
            return self._post()
        finally:
            tok.set_metadata(seq=self.task.seq_num)
            profiling.end(tok)

    def _post(self) -> Status:
        st = self.task.super_status
        if self._posted:
            if st == Status.IN_PROGRESS:
                # COLL_POST_STATUS_CHECK (ucc_coll.c:362)
                raise UccError(Status.ERR_INVALID_PARAM,
                               "collective re-posted while in progress")
            if not self._persistent:
                raise UccError(Status.ERR_INVALID_PARAM,
                               "re-post of non-persistent collective")
            self.task.reset()
        self._posted = True
        self.task.progress_queue = self.team.context.progress_queue
        if metrics.ENABLED:
            metrics.inc("coll_posted", component="core",
                        coll=self.task.coll_name or "",
                        alg=self.task.alg_name or "")
        if self._flight is not None:
            self._flight_post(self.task)
        if self._trace:
            logger.info("coll post: %s team %s seq %d",
                        coll_type_str(self.args.coll_type), self.team.id,
                        self.task.seq_num)
        if self._coalesce is not None:
            # hand the fully-accounted post (metrics/flight/trace above
            # keep per-request attribution) to the team's batcher
            return self._coalesce.add(self)
        if self._coal_flush is not None:
            self._coal_flush()
        return self.task.post()

    def _flight_post(self, task: CollTask) -> None:
        """Flight-ring post event. The per-team ``flight_seq`` advances
        in program order — identical on every member by the UCC
        ordered-issue contract — and is the cross-rank join key the
        desync/straggler diagnosis correlates on (obs/diagnose.py)."""
        team = self.team
        fs = team.flight_seq + 1
        team.flight_seq = fs
        self._flight.post(team.id, team.epoch, fs, task.seq_num,
                          task.coll_name or "", task.alg_name or "",
                          self._flight_msgsize)

    # ------------------------------------------------------------------
    # autotuner probe lane (UCC_TUNER=online; score/tuner.py)
    def _bind_tuner(self, tuner, key, init_args, candidates,
                    chosen) -> None:
        self._tuner = tuner
        self._tuner_key = key
        self._tuner_ia = init_args
        self._tuner_cands = candidates
        self._tuner_cur = chosen
        self._tuner_user_cb = self.task.cb   # restore target on unbind
        self._tuner_wrapped_cb = None
        self._post = self._tuner_post        # shadow the class method

    def _tuner_unbind(self) -> None:
        if self._tuner_wrapped_cb is not None and \
                self.task.cb is self._tuner_wrapped_cb:
            self.task.cb = self._tuner_user_cb
        self._tuner_wrapped_cb = None
        self._tuner = None
        self.__dict__.pop("_post", None)     # back to the class post

    def _tuner_swap_task(self, cand, new_task) -> None:
        old = self.task
        try:
            old.finalize()
        except Exception:  # noqa: BLE001 - probe teardown is best-effort
            pass
        new_task.coll_name = old.coll_name
        new_task.alg_name = str(cand.alg_name or cand.team)
        new_task.timeout = old.timeout
        _attach_user_opts(new_task, self.args)
        if profiling.ENABLED:
            _attach_profiling(new_task, self.args.coll_type)
        self.task = new_task
        self._tuner_cur = cand
        self._tuner_user_cb = new_task.cb
        self._tuner_wrapped_cb = None

    def _tuner_swap_to_winner(self, winner) -> None:
        """Re-init the frozen winner under a persistent request so later
        re-posts run it without another collective_init. An init failure
        propagates: every peer switches to the team-agreed winner at
        this same post, so a rank that cannot run it must fail loudly —
        silently keeping a different algorithm would deadlock the team.
        """
        from ..score.tuner import cand_label
        if cand_label(self._tuner_cur) == winner:
            return
        for cand in self._tuner_cands:
            if cand.init is None or cand_label(cand) != winner:
                continue
            new_task = cand.init(self._tuner_ia, cand.team)
            self._tuner_swap_task(cand, new_task)
            return

    def _tuner_post(self) -> Status:
        """Exploration-round post: deterministic candidate rotation with
        post->completion timing, until the rank-0 decision freezes the
        key and the request drops back to the plain post path."""
        from ..score.tuner import cand_label
        task = self.task
        st = task.super_status
        if self._posted and st == Status.IN_PROGRESS:
            raise UccError(Status.ERR_INVALID_PARAM,
                           "collective re-posted while in progress")
        if self._posted and not self._persistent:
            # same user-error contract as the class post(); silently
            # re-running would also consume an exploration slot on this
            # rank only and desync the lockstep per-key counters
            raise UccError(Status.ERR_INVALID_PARAM,
                           "re-post of non-persistent collective")
        if task.triggered_task is not None:
            # EE-dispatched request: the EE installed observers on THIS
            # task, so keep the plain lifecycle (EE use is symmetric
            # across ranks, so leaving without consuming a rotation
            # slot cannot desynchronize the counters)
            self._tuner_unbind()
            return self._post()
        tuner = self._tuner
        key = self._tuner_key
        frozen, winner = tuner.poll(key)
        if frozen:
            if winner is not None:
                self._tuner_swap_to_winner(winner)
            self._tuner_unbind()
            return self._post()
        if not tuner.claim(key, self):
            # another un-finalized request drives this key (overlapped
            # posts): the key just froze to static defaults — leave the
            # probe lane without consuming a rotation slot
            self._tuner_unbind()
            return self._post()
        new_task = None
        chosen = None
        for cand in tuner.explore_order(key, self._tuner_cands):
            if cand is self._tuner_cur:
                new_task, chosen = task, cand
                break
            try:
                new_task = cand.init(self._tuner_ia, cand.team)
            except UccError as e:
                if e.status != Status.ERR_NOT_SUPPORTED:
                    # only NOT_SUPPORTED is symmetric across ranks (a
                    # pure function of the args, like init_coll's
                    # fallback walk). A rank-local transient failure
                    # must surface, not silently shift this rank's
                    # deterministic rotation off its peers'
                    raise
                tuner.record_unsupported(key, cand)
                continue
            chosen = cand
            break
        if new_task is None:
            # nothing explorable survived init: leave the probe lane
            self._tuner_unbind()
            return self._post()
        if new_task is not task:
            self._tuner_swap_task(chosen, new_task)
        elif self._posted:
            new_task.reset()
        self._posted = True
        new_task.progress_queue = self.team.context.progress_queue
        if metrics.ENABLED:
            metrics.inc("coll_posted", component="core",
                        coll=new_task.coll_name or "",
                        alg=new_task.alg_name or "")
        if self._flight is not None:
            self._flight_post(new_task)
        if self._trace:
            logger.info("coll post (tuner explore): %s alg %s team %s "
                        "seq %d", new_task.coll_name, new_task.alg_name,
                        self.team.id, new_task.seq_num)
        label = cand_label(chosen)
        t0 = time.perf_counter()
        user_cb = self._tuner_user_cb

        def cb(t, s, _t0=t0):
            tuner.record(key, label, time.perf_counter() - _t0, s)
            if user_cb is not None:
                user_cb(t, s)
        new_task.cb = cb
        self._tuner_wrapped_cb = cb
        return new_task.post()

    def test(self) -> Status:
        st = self.task.super_status
        if st == Status.OPERATION_INITIALIZED:
            return Status.OPERATION_INITIALIZED
        if st.is_error and self._try_runtime_fallback():
            return Status.IN_PROGRESS
        if st == Status.OK and self._attest is not None:
            # sampled result attestation: the collective itself is done,
            # but this request stays IN_PROGRESS until every live rank's
            # result digest has been exchanged and compared (raises
            # DataCorruptedError on a digest minority)
            return integrity.attest_test(self)
        return st

    def _try_runtime_fallback(self) -> bool:
        """Runtime extension of the score-map fallback walk (score_map.c
        walks candidates on ERR_NOT_SUPPORTED at INIT only): a posted
        task that failed with a local resource error BEFORE committing
        any data to the wire is re-initialized once on the next
        candidate in the chain and re-posted, invisibly to the caller
        (test() keeps returning IN_PROGRESS across the swap). Tasks that
        already sent/received anything are NOT retried — peers may have
        consumed fragments of the first attempt, and only a team-wide
        restart can reconcile that."""
        fb = self._fallback
        task = self.task
        if fb is None or self._fb_used or not self._posted or \
                self._persistent or getattr(task, "data_committed", True) or \
                task.super_status not in _FALLBACK_ELIGIBLE:
            return False
        if task.cb is not None or any(task.em.listeners) or \
                task.triggered_task is not None:
            # observers (user callback, EVENT subscribers, EE triggered
            # proxies) already saw the first attempt's error completion —
            # swapping in a fallback now would double-signal one
            # collective (error then success).
            return False
        init_args, remaining = fb
        for cand in remaining:
            if cand.init is None:
                continue
            try:
                new_task = cand.init(init_args, cand.team)
            except UccError:
                continue
            self._fb_used = True
            new_task.coll_name = task.coll_name
            new_task.alg_name = str(cand.alg_name or cand.team)
            new_task.timeout = task.timeout
            new_task.progress_queue = self.team.context.progress_queue
            logger.warning(
                "runtime fallback: %s alg %s failed (%s) before data "
                "commit; retrying once on %s", task.coll_name,
                task.alg_name, task.super_status.name, new_task.alg_name)
            if metrics.ENABLED:
                metrics.inc("coll_fallback_runtime", component="core",
                            coll=new_task.coll_name or "",
                            alg=new_task.alg_name or "")
            try:
                task.finalize()
            except Exception:  # noqa: BLE001 - old task teardown is
                # best-effort; the replacement is already wired in
                pass
            self.task = new_task
            new_task.post()
            return True
        return False

    def wait(self, timeout: float = 60.0) -> Status:
        deadline = time.monotonic() + timeout
        while self.test() == Status.IN_PROGRESS:
            self.team.context.progress()
            if time.monotonic() > deadline:
                # cancel, don't just raise: leaving the task IN_PROGRESS
                # would orphan its posted ops in the progress queue and
                # make the request un-finalizable (finalize raises on
                # in-progress) — satellite fix, ISSUE 2
                self.task.cancel(Status.ERR_TIMED_OUT)
                raise UccError(Status.ERR_TIMED_OUT,
                               "CollRequest.wait timed out")
        return self.test()

    def finalize(self) -> Status:
        """ucc_collective_finalize (ucc_coll.c:460-508). Releases the
        task's resources — for host TL tasks that includes returning
        pool-leased scratch to the mc mpool (tl/host/task.py
        finalize_fn), which is why persistent requests should be
        finalized rather than dropped: a dropped task's lease is
        reclaimed only by GC and its buffers never re-enter the pool."""
        if self.task.super_status == Status.IN_PROGRESS:
            raise UccError(Status.ERR_INVALID_PARAM,
                           "finalize of in-progress collective")
        # program-order marker the autotuner's per-key claim() reads: a
        # finalized request can no longer post, so a successor request on
        # the same key is sequential, not overlapped
        self._finalized = True
        return self.task.finalize()


def _resolve_mem_type(args: CollArgs) -> MemoryType:
    """Memtype auto-detect (ucc_coll.c:25-36). Every buffer gets its
    mem_type resolved (TLs branch on it per-buffer); the collective's
    selection memtype prefers dst, else src."""
    chosen: Optional[MemoryType] = None
    for bi in (args.dst, args.src):
        if bi is None:
            continue
        if bi.mem_type is None:
            mt = detect_mem_type(bi.buffer)
            if mt != MemoryType.UNKNOWN:
                bi.mem_type = mt
        if chosen is None and bi.mem_type is not None:
            chosen = bi.mem_type
    return chosen if chosen is not None else MemoryType.HOST


def _is_zero_size(args: CollArgs) -> bool:
    ct = args.coll_type
    if ct in (CollType.BARRIER, CollType.FANIN, CollType.FANOUT):
        return False
    for bi in (args.src, args.dst):
        if bi is None:
            continue
        if isinstance(bi, BufferInfoV):
            if bi.counts and any(int(c) > 0 for c in bi.counts):
                return False
        elif isinstance(bi, BufferInfo):
            if bi.count > 0:
                return False
    return True


def collective_init(args: CollArgs, team: Team) -> CollRequest:
    """ucc_collective_init (ucc_coll.c:172), inside the ``ucc.init``
    layer span (its ``ucc.select`` and ``ucc.tl_init`` children split
    it)."""
    tok = profiling.begin("ucc.init")
    if tok is None:
        return _init_request(args, team)
    req = None
    try:
        req = _init_request(args, team)
        return req
    finally:
        if req is not None:
            t = req.task
            tok.set_metadata(seq=t.seq_num, coll=t.coll_name or "",
                             alg=t.alg_name or "")
        profiling.end(tok)


def _init_request(args: CollArgs, team: Team) -> CollRequest:
    if team._shrunk:
        # the old epoch's tag space is fenced; collectives must move to
        # the successor team the Shrink/Grow request returned
        how = team._retired_by or "shrunk"
        raise RankFailedError(
            f"team {team.id} was retired by a membership {how}; post on "
            "the successor team")
    if team.score_map is None:
        raise UccError(Status.ERR_INVALID_PARAM, "team is not active")
    ct = args.coll_type
    if args.active_set is not None and ct != CollType.BCAST:
        # reference restriction (ucc_coll.c:210-214)
        raise UccError(Status.ERR_NOT_SUPPORTED,
                       "active sets supported for bcast only")
    mem_type = _resolve_mem_type(args)
    onesided_args = (args.global_work_buffer is not None
                     or args.src_memh is not None
                     or args.dst_memh is not None
                     or bool(int(args.flags) & FLAG_MEM_MAPPED_BUFFERS))
    if onesided_args and mem_type == MemoryType.TPU:
        # one-sided args on HOST memory are served by the socket/shm
        # RDMA-emulation path (tl/host/onesided.py, TUNE-selected like the
        # reference's onesided algorithms); on DEVICE memory they are
        # honestly rejected: TPU DCN NICs expose no user RDMA window over
        # HBM, and the device-initiated role is served on ICI by
        # tl/ring_dma (see PARITY.md "one-sided capabilities")
        raise UccError(Status.ERR_NOT_SUPPORTED,
                       "one-sided (global_work_buffer / mem-mapped) "
                       "collectives are host-memory only on the TPU DCN "
                       "path; see PARITY.md")
    if _is_zero_size(args) and mem_type != MemoryType.TPU and \
            not onesided_args:
        # (one-sided colls are excluded from the stub: peers count THIS
        # rank's put notifies, so an all-zero-count rank must still post
        # its zero-byte puts or the team's arrival counters never fill)
        # zero-size fast path (ucc_coll.c:191-208) — HOST memory only.
        # Device-memory colls are served by the rendezvous TL (tl/xla),
        # where a rank that stubs out desyncs the team's deposit count
        # (e.g. the zero-count rank of an uneven scatterv); the device
        # path runs them for real, with typed zero padding.
        task: CollTask = _StubTask()
        task.coll_name = coll_type_str(ct)
        task.alg_name = "zero_size_stub"
        req = CollRequest(task, team, args)
        _attach_user_opts(task, args)
        return req

    msgsize = coll_args_msgsize(args, team.size, team.rank)
    init_args = InitArgs(args=args, team=team, mem_type=mem_type,
                         msgsize=msgsize)
    assert team.score_map is not None
    tok = profiling.begin("ucc.select")
    bias = team.rank_bias
    if bias is not None:
        # promote any staged straggler-feedback table at its
        # deterministic switch index: every rank ticks here in program
        # order with an identical flight_seq sequence, so the flagged
        # set (and the reordered candidate list below) changes on the
        # same post everywhere — the tuner-switch divergence argument
        bias.tick(team.flight_seq)
    candidates = team.score_map.lookup(ct, mem_type, msgsize, bias=bias)
    if tok is not None:
        profiling.end(tok)
    tok = profiling.begin("ucc.tl_init")
    try:
        task, chosen = team.score_map.init_coll(ct, mem_type, msgsize,
                                                init_args, candidates)
    finally:
        if tok is not None:
            profiling.end(tok)
    # observability labels: metrics key the (collective, algorithm) pair
    # and the watchdog dump names both; stamped once at init, read only
    # on cold paths
    task.coll_name = coll_type_str(ct)
    task.alg_name = str(chosen.alg_name or chosen.team)
    if team.coll_trace:
        logger.info("coll init: %s/%s msgsize %d -> %s (score %d) team %s",
                    coll_type_str(ct), mem_type.name.lower(), msgsize,
                    chosen.alg_name or chosen.team, chosen.score, team.id)
    inner = task
    task = _maybe_wrap_dt_check(task, args, team, mem_type)
    if task is not inner:
        task.coll_name = inner.coll_name
        task.alg_name = inner.alg_name
    _attach_user_opts(task, args)
    if profiling.ENABLED:
        _attach_profiling(task, ct)
    req = CollRequest(task, team, args)
    req._flight_msgsize = msgsize
    tuner = team.tuner
    coal = team.coalescer
    if coal is None and team.priority >= 2 and \
            getattr(team.context, "_open_coalescers", None):
        # latency-class tenant while bulk teams batch: posting this
        # request seals their open windows (core/coalesce.py valve)
        from .coalesce import flush_open
        req._coal_flush = (lambda ctx=team.context:
                           flush_open(ctx, "priority-post"))
    if tuner is not None and task is inner and args.active_set is None \
            and tuner.wants(ct, mem_type, msgsize, candidates):
        # autotuner probe lane (UCC_TUNER=online, score/tuner.py): the
        # first UCC_TUNER_SAMPLES posts of this (coll, mem, size-bucket)
        # rotate through the candidates, then freeze the rank-0 winner.
        # Bound only for plain (unwrapped) tasks — like the fallback
        # retention below, a dt-check schedule's identity is not the
        # algorithm's. Mutually exclusive with runtime fallback: the
        # probe lane owns task identity while bound.
        req._bind_tuner(tuner, tuner.key_for(ct, mem_type, msgsize),
                        init_args, candidates, chosen)
    elif coal is not None and task is inner and \
            coal.eligible(args, mem_type, msgsize):
        # small-collective coalescing (UCC_COALESCE, core/coalesce.py):
        # post() hands this member to the team batcher. Bound AFTER the
        # candidate walk so candidate lists and the chosen algorithm are
        # byte-identical with the knob off, and mutually exclusive with
        # the tuner/runtime-fallback lanes (both re-post task identity
        # at rank-local times, which would skew wire-tag parity for a
        # held member).
        req._coalesce = coal
    elif task is inner and not req._persistent:
        # retain the fallback-chain tail for RUNTIME fallback (see
        # CollRequest._try_runtime_fallback). Wrapped (dt-check) and
        # persistent tasks are excluded: the former's failure status is
        # the schedule's, the latter's re-post lanes cache task identity.
        try:
            rest = candidates[candidates.index(chosen) + 1:]
        except ValueError:
            rest = []
        if rest:
            req._fallback = (init_args, rest)
    if coal is not None and req._coalesce is None and coal.pending:
        # a same-team post that cannot join the open batch is a
        # program-order closure point — seal it (every rank inits this
        # collective at the same point by the ordered-issue contract)
        coal.flush("ineligible")
    if integrity.VERIFY and task is inner and team.size > 1 and \
            args.active_set is None and mem_type == MemoryType.HOST and \
            (int(ct) & _ATTESTED) and req._coalesce is None and \
            req._tuner is None:
        # sampled cross-rank result attestation (UCC_INTEGRITY=verify):
        # binds _attest at the deterministic UCC_INTEGRITY_SAMPLE cadence.
        # Every predicate above is rank-invariant (coll type, active set,
        # team size, mem type, wrap status; tuner/coalesce binding by the
        # ordered-issue and tag-parity contracts), so all ranks tick the
        # per-team attestation counter in lockstep — the checked subset
        # is identical everywhere without any extra agreement round.
        integrity.bind(req, team)
    return req


def _maybe_wrap_dt_check(task: CollTask, args: CollArgs, team: Team,
                         mem_type: MemoryType) -> CollTask:
    """Rooted colls optionally get a dt-validation schedule prefix
    (ucc_coll.c:274-289)."""
    # the reference scopes this to the gather/scatter family
    # (ucc_coll.c:274-277); we additionally wrap bcast/reduce — the same
    # root-vs-leaf dt asymmetry can corrupt them. Note the zero-size fast
    # path means a rank posting all-zero counts skips the check (same
    # property as ucc_coll.c:191 vs :274). Active-set colls are excluded:
    # only the subset posts, but the validation allreduce is team-wide.
    if not (int(args.coll_type) & _DT_CHECKED) or team.size <= 1 or \
            args.active_set is not None:
        return task
    if not team.check_asymmetric_dt:
        return task
    if team.service_team is None or \
            not hasattr(team.service_team, "service_allreduce"):
        return task
    bi = args.src if args.src is not None else args.dst
    if bi is None or isinstance(bi.datatype, GenericDataType):
        return task
    sched = Schedule(team=team, args=args)
    chk = _DtCheckTask(team, int(DataType(bi.datatype)) + 1,
                       int(mem_type) + 1)
    sched.add_task(chk)
    sched.add_dep_on_schedule_start(chk)
    sched.add_task(task)
    task.subscribe_dep(chk, EventType.EVENT_COMPLETED)
    return sched


def _attach_profiling(task: CollTask, ct: CollType) -> None:
    name = coll_type_str(ct)
    # the request span id IS the task seq num; every nested task/TL event
    # carries the same id (or a parent link to it), so one collective's
    # full dispatch -> schedule -> TL lifetime reassembles offline
    profiling.request_new(name, task.seq_num, alg=task.alg_name or "")
    prev = task.cb

    def cb(t, st):
        profiling.request_complete(name, t.seq_num, status=st.name)
        if prev is not None:
            prev(t, st)
    task.cb = cb


def _attach_user_opts(task: CollTask, args: CollArgs) -> None:
    if int(args.flags) & FLAG_TIMEOUT and args.timeout > 0:
        task.timeout = args.timeout
    if args.cb is not None:
        task.cb = args.cb
