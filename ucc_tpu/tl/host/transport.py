"""Tagged point-to-point transport for host-side TLs.

This is the stand-in for UCX tagged send/recv that TL/UCP builds on
(/root/reference/src/components/tl/ucp/tl_ucp_sendrecv.h:83-110: 64-bit
tags packed from team id / scope / rank / user tag). UCX is absent on TPU
pods, so the framework owns its transports (SURVEY §7.6):

  - InProcTransport ("shm"): ranks are contexts inside one process
    (threads); matching is a lock-protected mailbox keyed by
    (team_key, scope, coll_tag, slot, src). Eager sends under a threshold
    copy-and-complete; larger sends hand a zero-copy view to the receiver
    (rendezvous), completing when the receiver lands it.
  - SocketTransport ("socket", tl/host/socket_transport.py): same mailbox
    semantics over TCP for multi-process / DCN.

Both present identical nonblocking requests, so every collective algorithm
runs unchanged on either.
"""
from __future__ import annotations

import threading
import uuid
import zlib
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ... import integrity as _integrity
from ... import native as _native   # registers UCC_NATIVE (ucc_info -cf)
from ...status import Status

del _native

#: matching key: (team_key, epoch, coll_tag, slot, src_uid). The epoch
#: field is the team's recovery epoch (0 for every team that never
#: shrank): after a rank-failure shrink the survivors fence the old
#: (team_key, epoch) space so a stale pre-shrink send can never match a
#: post-shrink recv — without it, a late message from the dead team
#: could scribble into a pool-reissued lease buffer (see Mailbox.fence).
TagKey = Tuple[Any, int, int, int, int]


class SendReq:
    __slots__ = ("done", "cancelled")

    def __init__(self, done: bool = False):
        self.done = done
        self.cancelled = False

    def test(self) -> bool:
        return self.done

    def cancel(self) -> None:
        """Give up on completion. The message itself cannot be unsent
        (it may already sit in the peer's unexpected queue); the caller
        just stops waiting on it."""
        self.cancelled = True
        self.done = True


class RecvReq:
    __slots__ = ("done", "dst", "nbytes", "error", "cancelled", "_mb",
                 "corrupt_src")

    def __init__(self, dst: np.ndarray):
        self.done = False
        self.dst = dst
        self.nbytes = 0
        self.error = None   # str reason when the matched send misbehaved
        self.cancelled = False
        self._mb = None     # owning Mailbox (set at post; cancel sync)
        self.corrupt_src = None  # sender ctx rank on a wire crc mismatch

    def test(self) -> bool:
        return self.done

    def cancel(self) -> None:
        """Withdraw a posted recv: the mailbox skips cancelled entries
        at match time, so a LATE send can no longer scribble into a
        buffer the cancelled collective's caller may have reclaimed.
        Taken under the owning mailbox's lock — delivery happens inside
        that lock too (``push``), so cancel-vs-match cannot interleave:
        whichever wins the lock decides, and a req that was already
        delivered stays delivered (the data landed before the caller
        could reclaim anything)."""
        mb = self._mb
        if mb is None:
            if not self.done:
                self.error = self.error or "canceled"
            self.cancelled = True
            self.done = True
            return
        with mb.lock:
            if not self.done:
                self.error = self.error or "canceled"
                self.done = True
            self.cancelled = True


class _PendingSend:
    __slots__ = ("data", "req", "copied", "crc")

    def __init__(self, data: np.ndarray, req: SendReq, copied: bool,
                 crc: Optional[int] = None):
        self.data = data
        self.req = req
        self.copied = copied
        #: send-side crc32 (UCC_INTEGRITY wire mode) carried in the
        #: match metadata; None = unchecked delivery (integrity off)
        self.crc = crc


class Mailbox:
    """Per-context receive side with unexpected-message queues."""

    def __init__(self):
        self.lock = threading.Lock()
        #: key -> deque of _PendingSend (unexpected messages)
        self.unexpected: Dict[TagKey, deque] = {}
        #: key -> deque of RecvReq (posted receives)
        self.posted: Dict[TagKey, deque] = {}
        #: epoch fences: team_key -> minimum accepted epoch. Empty (the
        #: default, and always under UCC_FT=none) costs one falsy dict
        #: test per message; once a team shrinks, messages keyed to an
        #: older epoch of a fenced team_key are DISCARDED at the matching
        #: boundary instead of parked or delivered.
        self.fences: Dict[Any, int] = {}

    def _is_fenced(self, key: TagKey) -> bool:
        """Caller holds self.lock and has checked ``self.fences`` truthy.
        Non-team keys (one-sided replies etc.) never collide with a
        team_key, so the epoch comparison only runs for fenced teams."""
        f = self.fences.get(key[0])
        return f is not None and key[1] < f

    def fence(self, team_key, min_epoch: int) -> int:
        """Fence every epoch of *team_key* below *min_epoch*: record the
        floor for future arrivals and purge already-parked state — posted
        recvs error out as "fenced" (their buffers may be reclaimed by
        the caller), unexpected sends are dropped and their send reqs
        completed (the sender must stop waiting; the data is gone with
        the old epoch). Returns the number of purged entries."""
        purged = 0
        with self.lock:
            cur = self.fences.get(team_key)
            if cur is None or min_epoch > cur:
                self.fences[team_key] = min_epoch
            for key in [k for k in self.posted
                        if k[0] == team_key and k[1] < min_epoch]:
                for req in self.posted.pop(key):
                    if not req.done:
                        req.error = req.error or "fenced: stale team epoch"
                        req.done = True
                    req.cancelled = True
                    purged += 1
            for key in [k for k in self.unexpected
                        if k[0] == team_key and k[1] < min_epoch]:
                for ps in self.unexpected.pop(key):
                    ps.req.done = True
                    purged += 1
        return purged

    def _match_posted_locked(self, key: TagKey) -> Optional[RecvReq]:
        """Pop the first live (non-cancelled) posted recv for *key*.
        Caller holds self.lock."""
        rq = self.posted.get(key)
        while rq:
            cand = rq.popleft()
            if not rq:
                del self.posted[key]
            if not cand.cancelled:
                return cand
        return None

    def push(self, key: TagKey, ps: _PendingSend) -> None:
        # delivery happens INSIDE the lock: RecvReq.cancel synchronizes
        # on the same lock, so a recv cannot be cancelled (and its
        # buffer reclaimed) between being matched and being written
        with self.lock:
            if self.fences and self._is_fenced(key):
                ps.req.done = True   # discarded: stale-epoch delivery
                return
            req = self._match_posted_locked(key)
            if req is None:
                self.unexpected.setdefault(key, deque()).append(ps)
                return
            _deliver(req, ps, key)

    def send(self, key: TagKey, data_u8: np.ndarray, eager_limit: int,
             crc: Optional[int] = None) -> Tuple[SendReq, str]:
        """Copy-free matching fast path (sender side of ``push``): when a
        matching recv is already posted, deliver STRAIGHT from the
        sender's buffer into the posted dst — no eager staging copy at
        any size, and the send completes immediately (the data has
        landed, so the sender may reuse its buffer). Only an UNEXPECTED
        message pays the classic eager copy (<= *eager_limit*) or parks
        a zero-copy rendezvous view (larger). Returns the send request
        plus how the message traveled: ``direct`` / ``eager`` /
        ``rndv``. Same lock discipline as ``push`` — cancel-vs-match
        cannot interleave. The eager staging copy runs under the lock
        (the match outcome decides whether a copy is needed at all);
        it is bounded by *eager_limit* (8K default), so the lock-held
        window stays small — always-eager mode (limit=inf) trades that
        for sender-buffer freedom, by explicit configuration.

        *crc* is the UCC_INTEGRITY wire checksum: computed here when the
        mode is armed and the caller did not supply one (the fault
        injector supplies the CLEAN payload's crc alongside a corrupted
        payload — modeling in-flight corruption); verified at delivery."""
        if crc is None and _integrity.WIRE:
            crc = zlib.crc32(data_u8) & 0xFFFFFFFF
        with self.lock:
            if self.fences and self._is_fenced(key):
                # stale-epoch send: complete-and-discard so the sender
                # proceeds (its team is gone; nothing will ever recv this)
                return SendReq(done=True), "fenced"
            req = self._match_posted_locked(key)
            if req is not None:
                ps = _PendingSend(data_u8, SendReq(), copied=False, crc=crc)
                _deliver(req, ps, key)
                return ps.req, "direct"
            if data_u8.nbytes <= eager_limit:
                ps = _PendingSend(data_u8.copy(), SendReq(done=True),
                                  copied=True, crc=crc)
                kind = "eager"
            else:
                ps = _PendingSend(data_u8, SendReq(), copied=False, crc=crc)
                kind = "rndv"
            self.unexpected.setdefault(key, deque()).append(ps)
            return ps.req, kind

    def occupancy(self) -> Tuple[int, int]:
        """(parked unexpected messages, live posted recvs) — the backlog
        gauges the interval/watchdog dumps sample (a growing unexpected
        queue is the first visible symptom of a receiver falling
        behind). Cold path: takes the lock."""
        with self.lock:
            unexp = sum(len(q) for q in self.unexpected.values())
            posted = sum(len(q) for q in self.posted.values())
        return unexp, posted

    def post_recv(self, key: TagKey, req: RecvReq) -> None:
        with self.lock:
            req._mb = self
            if self.fences and self._is_fenced(key):
                # posting into a fenced epoch is a stale-team bug on the
                # LOCAL side; fail the recv rather than park it forever
                req.error = "fenced: stale team epoch"
                req.cancelled = True
                req.done = True
                return
            uq = self.unexpected.get(key)
            if uq:
                ps = uq.popleft()
                if not uq:
                    del self.unexpected[key]
            else:
                self.posted.setdefault(key, deque()).append(req)
                return
            _deliver(req, ps, key)


def _deliver(req: RecvReq, ps: _PendingSend, key: Optional[TagKey] = None
             ) -> None:
    n = min(req.dst.size, ps.data.size)
    if ps.data.size > req.dst.size:
        # truncation = algorithm geometry bug (inconsistent per-rank
        # counts); surface it so the task can fail instead of completing
        # with silently partial data (cf. UCS_ERR_MESSAGE_TRUNCATED)
        req.error = (f"message truncated: sent {ps.data.size} elements "
                     f"into a {req.dst.size}-element recv buffer")
    req.dst[:n] = ps.data[:n]
    if ps.crc is not None and req.error is None and \
            (zlib.crc32(req.dst[:n]) & 0xFFFFFFFF) != ps.crc:
        # verified over the LANDED bytes: catches corruption anywhere
        # between the sender's checksum and this buffer. The sender ctx
        # rank rides the matching key (key[4]) — the attribution the
        # task layer feeds to integrity.note_wire_mismatch.
        src = key[4] if key is not None and len(key) == 5 else -1
        req.corrupt_src = src
        req.error = f"data corrupted: crc32 mismatch (from ctx rank {src})"
    req.nbytes = n
    req.done = True
    ps.req.done = True


# ---------------------------------------------------------------------------
# in-process transport
# ---------------------------------------------------------------------------

#: process-global endpoint registry: uid -> InProcTransport (the "shared
#: memory segment"; cf. reference tl_cuda SysV shm control segment
#: tl_cuda_team.c:141-181 — same role, in-process)
_SHM_WORLD: Dict[str, "InProcTransport"] = {}
_SHM_LOCK = threading.Lock()

_DEFAULT_EAGER_LIMIT = 8192


def _register_eager_knob():
    """UCC_HOST_EAGER_LIMIT replaces the hardcoded eager threshold for
    every host transport endpoint; registered so ucc_info -cf lists it.
    Per-TL EAGER_THRESH (UCC_TL_SHM_EAGER_THRESH) still overrides when
    set to a concrete size."""
    from ...utils.config import (ConfigField, ConfigTable, parse_memunits,
                                 register_table)
    return register_table(ConfigTable(
        prefix="HOST_", name="tl/host-transport", fields=[
            ConfigField("EAGER_LIMIT", str(_DEFAULT_EAGER_LIMIT),
                        "eager copy limit for host transports: unexpected "
                        "sends at or under it are copied-and-completed, "
                        "larger ones park a zero-copy rendezvous view; "
                        "sends matching an already-posted recv are always "
                        "delivered copy-free regardless of size",
                        parse_memunits),
        ]))


_HOST_TRANSPORT_CONFIG = _register_eager_knob()


def eager_limit_from_env() -> int:
    """Resolve the process eager limit: UCC_HOST_EAGER_LIMIT (memunits,
    env or UCC_CONFIG_FILE — standard precedence via the config table),
    else the historical 8K default. ``inf`` means always-eager
    (unbounded copy threshold, same meaning as the per-TL EAGER_THRESH);
    only ``auto`` defers to the default."""
    from ...utils.config import Config, SIZE_AUTO
    try:
        v = Config(_HOST_TRANSPORT_CONFIG).eager_limit
        if v != SIZE_AUTO:
            return int(v)          # SIZE_INF passes through: always-eager
    except ValueError:
        pass
    return _DEFAULT_EAGER_LIMIT


class InProcTransport:
    """One endpoint per core context. Uses the native C++ tag matcher
    (ucc_tpu.native) when built; pure-Python mailbox otherwise."""

    EAGER_THRESHOLD = _DEFAULT_EAGER_LIMIT

    def __init__(self, use_native: Optional[bool] = None,
                 default_native: bool = True):
        self.uid = uuid.uuid4().hex
        self.mailbox = Mailbox()
        self.EAGER_THRESHOLD = eager_limit_from_env()
        # data-path accounting (plain ints — cheap enough to keep on
        # unconditionally; tests and bench read them directly)
        self.n_direct = 0        # copy-free deliveries into posted recvs
        self.n_eager = 0         # unexpected sends staged via eager copy
        self.n_rndv = 0         # unexpected zero-copy rendezvous views
        self.n_fenced = 0        # stale-epoch sends discarded at the fence
        # flight recorder wire ring (obs/flight.py): bound ONCE by the
        # owning TL context — the endpoint-level analog of the PR-3
        # `_instr` per-post binding, so the send path pays one branch
        # when off and one ring append when on. Covers native sends too:
        # they route back through _count_send with their kind.
        self._flight = None
        self.native = None
        forced = False
        if use_native is None:
            import os
            # the v2 core (native/ucc_tpu_core.cc) reaches contract
            # parity with the python Mailbox — copy-free delivery,
            # eager/rndv split, cancel-skip, epoch fences — and polls
            # completions through a mapped publication window (no ffi on
            # the poll path), so it is the default in BOTH thread modes,
            # including under UCC_FT=shrink. GIL-released matching still
            # wins big when many OS threads drive progress
            # concurrently. UCC_TL_SHM_NATIVE overrides in
            # either direction.
            env = os.environ.get("UCC_TL_SHM_NATIVE", "").strip().lower()
            if env and env != "auto":   # auto = same as unset
                from ...utils.config import parse_bool
                try:
                    use_native = parse_bool(env)
                    forced = use_native
                except ValueError:      # unrecognized: behave as auto
                    use_native = default_native
            else:
                use_native = default_native
        else:
            forced = bool(use_native)
        if use_native:
            try:
                from ...native import NativeMailbox, available
                if available():
                    self.native = NativeMailbox()
            except Exception:  # noqa: BLE001 - fall back to python matcher
                self.native = None
            if self.native is None and forced:
                # only an EXPLICIT request warns: the default-on path must
                # stay silent on toolchain-less machines (debug-logged by
                # ucc_tpu.native instead)
                from ...utils.log import get_logger
                get_logger("tl_shm").warning(
                    "native matcher requested but unavailable (no source "
                    "checkout / build failed, see native/build.log) — "
                    "falling back to the python matcher")
        with _SHM_LOCK:
            _SHM_WORLD[self.uid] = self

    # -- address plumbing ---------------------------------------------
    def pack_address(self) -> bytes:
        return self.uid.encode()

    @staticmethod
    def resolve(addr: bytes) -> Optional["InProcTransport"]:
        with _SHM_LOCK:
            return _SHM_WORLD.get(addr.decode())

    # -- data path -----------------------------------------------------
    def _count_send(self, kind: str) -> None:
        if kind == "direct":
            self.n_direct += 1
        elif kind == "eager":
            self.n_eager += 1
        elif kind == "rndv":
            self.n_rndv += 1
        else:
            self.n_fenced += 1

    def occupancy(self) -> Dict[str, int]:
        """Mailbox backlog gauges: python unexpected/posted queue
        lengths plus (when the native matcher is attached) the C core's
        unexpected/posted/live-slot counts. Cold path."""
        unexp, posted = self.mailbox.occupancy()
        d = {"unexpected": unexp, "posted": posted}
        if self.native is not None:
            try:
                n = self.native.occupancy()
            except Exception:  # noqa: BLE001 - diagnostics only
                n = None
            if n is not None:
                d["unexpected"] += int(n[0])
                d["posted"] += int(n[1])
                d["native_slots_in_use"] = int(n[2])
        return d

    def send_nb(self, peer: "InProcTransport", key: TagKey,
                data: np.ndarray, crc: Optional[int] = None) -> SendReq:
        if peer.native is not None:
            # matching lives in the RECEIVER's mailbox: route by the peer's
            # matcher only (a mixed pair must not split send/recv across
            # python and native matchers). The native push applies the
            # same copy-free / eager / rndv / fenced protocol as the
            # python Mailbox.send below, with the delivery memcpy done
            # GIL-released in C++ — including the UCC_INTEGRITY wire
            # checksum (computed/verified C-side; *crc* only overrides
            # for the fault injector's in-flight-corruption model).
            req, kind = peer.native.push_native(key, data,
                                                self.EAGER_THRESHOLD,
                                                crc=crc)
        else:
            # copy-free fast path: a send whose recv is already posted
            # lands directly in the destination buffer — the eager
            # staging copy is paid only for genuinely unexpected small
            # messages
            req, kind = peer.mailbox.send(
                key, data.reshape(-1).view(np.uint8),
                self.EAGER_THRESHOLD, crc=crc)
        self._count_send(kind)
        fr = self._flight
        if fr is not None:
            # flight-recorder round event: how this message traveled
            # (direct/eager/rndv/fenced) plus its round identity — one
            # allocation-free ring append (obs/flight.py WireRing)
            fr.append(kind, key, data.nbytes)
        return req

    def recv_nb(self, key: TagKey, dst: np.ndarray) -> RecvReq:
        if self.native is not None:
            return self.native.post_recv_native(key, dst)
        # (peers route sends by OUR matcher, so python recv is consistent)
        req = RecvReq(dst.reshape(-1).view(np.uint8))
        self.mailbox.post_recv(key, req)
        return req

    def fence(self, team_key, min_epoch: int) -> int:
        """Epoch-fence *team_key* on this endpoint's receive side (see
        Mailbox.fence). Routed to the native matcher's fence when this
        endpoint matches natively — the v2 core purges parked stale
        entries and discards late stale arrivals at the match boundary,
        so UCC_FT=shrink no longer forces the python matcher (the PR-4
        capability fork is closed). The python mailbox is fenced too:
        it is unused while a native matcher is attached, but keeping both
        floors consistent is free."""
        purged = self.mailbox.fence(team_key, min_epoch)
        if self.native is not None:
            purged += self.native.fence(team_key, min_epoch)
        return purged

    def progress(self) -> None:
        pass  # delivery happens inline at send/recv

    def close(self) -> None:
        with _SHM_LOCK:
            _SHM_WORLD.pop(self.uid, None)
        if self.native is not None:
            self.native.destroy()
            self.native = None


# ---------------------------------------------------------------------------
# backlog observability (cold: watchdog dumps + UCC_STATS snapshots)
# ---------------------------------------------------------------------------

def occupancy_snapshot(limit: int = 64) -> List[Dict[str, int]]:
    """Per-endpoint mailbox backlog for diagnostic dumps: unexpected
    queue length, posted recvs, native slot-table in-use. A backlog is
    otherwise invisible until it becomes a stall."""
    with _SHM_LOCK:
        eps = list(_SHM_WORLD.values())[:limit]
    out = []
    for ep in eps:
        try:
            d = ep.occupancy()
        except Exception:  # noqa: BLE001 - diagnostics only
            continue
        if any(d.values()):
            d["uid"] = ep.uid[:8]
            out.append(d)
    return out


def _occupancy_sampler() -> None:
    """Aggregate backlog gauges, sampled into every UCC_STATS snapshot
    (interval/exit/SIGUSR2 dumps) via the metrics sampler hook."""
    from ...obs import metrics
    unexp = posted = nslots = 0
    with _SHM_LOCK:
        eps = list(_SHM_WORLD.values())
    for ep in eps[:256]:
        try:
            d = ep.occupancy()
        except Exception:  # noqa: BLE001
            continue
        unexp += d.get("unexpected", 0)
        posted += d.get("posted", 0)
        nslots += d.get("native_slots_in_use", 0)
    metrics.gauge("mailbox_unexpected", unexp, component="tl/host")
    metrics.gauge("mailbox_posted_recvs", posted, component="tl/host")
    metrics.gauge("native_slots_in_use", nslots, component="tl/host")


from ...obs import metrics as _obs_metrics  # noqa: E402 - sampler wiring

_obs_metrics.register_sampler(_occupancy_sampler)
del _obs_metrics
