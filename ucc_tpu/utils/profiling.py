"""Profiling — request-lifetime event tracing.

Reference: UCS-based binary profiler (SURVEY §5: ``UCC_PROFILE_MODE``
{log,accum}, ``UCC_PROFILE_FILE``, zero-cost when off via compile-time
on/off headers, profile/ucc_profile.h:28, request events sprinkled in hot
paths e.g. allreduce_knomial.c:181,201).

TPU build: JSON-lines trace (chrome://tracing-compatible events) written to
``UCC_PROFILE_FILE`` (default ucc_profile.json). "Zero-cost when off" is a
module-level boolean checked before any formatting — the Python analog of
the compiled-out macros. ``accum`` mode aggregates per-(event,coll) counts
and total times, dumped at exit.

Layer spans (``begin``/``end``) time the boundaries of the post path —
init, selection, TL task creation, post, the TL/XLA launch — and are on
whenever a JAX profiler session is capturing or ``UCC_PROFILE_MODE`` is
set. Each one opens a ``TraceMe("ucc.<layer>")`` on the calling thread, so
it lands in the captured profile on the device trace's clock, nested in
whatever span the caller holds, and adds its duration to an in-memory
table that ``totals()`` reads. Off, ``begin`` is one gate check.
"""
from __future__ import annotations

import atexit
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation as _TraceMe

_mode = os.environ.get("UCC_PROFILE_MODE", "").strip().lower()
ENABLED = _mode in ("log", "accum")
_file = os.environ.get("UCC_PROFILE_FILE", "ucc_profile.json")
_lock = threading.Lock()
_fh = None
_accum: Dict[str, Dict[str, float]] = {}
_t0 = time.perf_counter()
#: layer-span gate: True while a profiler session captures (one native
#: call), or always under UCC_PROFILE_MODE
_spans_on = (lambda: True) if ENABLED else _TraceMe.is_enabled
#: layer-span tables, one per thread that closed a span (each written
#: only by its thread): name -> [count, seconds]
_tls = threading.local()
_tables: List[Dict[str, list]] = []


def _ensure_fh():
    global _fh
    if _fh is None:
        _fh = open(_file, "a", buffering=1)
    return _fh


def event(name: str, phase: str = "i", **fields: Any) -> None:
    """Record one event. phase: 'B' begin / 'E' end / 'i' instant."""
    if not ENABLED:
        return
    ts = (time.perf_counter() - _t0) * 1e6
    if _mode == "accum":
        with _lock:
            slot = _accum.setdefault(name, {"count": 0, "last_B": 0.0,
                                            "total_us": 0.0})
            if phase == "B":
                slot["last_B"] = ts
            elif phase == "E":
                # count completed B/E pairs only; clear last_B so a
                # persistent re-post's extra E doesn't accumulate the
                # whole elapsed-since-init
                if slot["last_B"]:
                    slot["count"] += 1
                    slot["total_us"] += ts - slot["last_B"]
                    slot["last_B"] = 0.0
            else:
                slot["count"] += 1
        return
    rec = {"name": name, "ph": phase, "ts": ts, "pid": os.getpid(),
           "tid": threading.get_ident() % 100000}
    rec.update(fields)
    with _lock:
        _ensure_fh().write(json.dumps(rec) + "\n")


def request_new(coll: str, seq: int, **fields) -> None:
    """Collective-request begin. ``seq`` doubles as the span id (task seq
    nums are process-unique); pass ``parent=<span>`` to link nested
    requests (schedule -> child task -> TL round)."""
    event(f"coll_{coll}", "B", seq=seq, span=seq, **fields)


def request_complete(coll: str, seq: int, **fields) -> None:
    event(f"coll_{coll}", "E", seq=seq, span=seq, **fields)


# ---------------------------------------------------------------------------
# span API — the generalized request_new/complete used by the schedule and
# TL layers. A span is a named B/E pair carrying a process-unique id (task
# seq_num) and an optional parent span id, so a chrome://tracing load shows
# the full dispatch -> schedule -> TL lifetime of one collective and the
# parent links survive in accum-free JSON for offline tools.
# ---------------------------------------------------------------------------

def span_begin(name: str, span: int, parent: Optional[int] = None,
               **fields: Any) -> None:
    if not ENABLED:
        return
    if parent is not None:
        fields["parent"] = parent
    event(name, "B", span=span, **fields)


def span_end(name: str, span: int, **fields: Any) -> None:
    if not ENABLED:
        return
    event(name, "E", span=span, **fields)


# ---------------------------------------------------------------------------
# layer spans — scoped, on the profiler's clock, summed in memory
# ---------------------------------------------------------------------------

class _Span(_TraceMe):
    """An open layer span: the profiler event itself, plus its name and
    host start time for the totals table."""
    __slots__ = ("name", "t0")


def begin(name: str) -> Optional[_Span]:
    """Open layer span ``name`` (``ucc.<layer>``); returns the token
    ``end`` takes, or None when spans are off (nothing else is done
    then). ``token.set_metadata(seq=...)`` adds stats to the event."""
    if not _spans_on():
        return None
    sp = _Span(name)
    sp.name = name
    sp.__enter__()
    sp.t0 = time.perf_counter()
    return sp


def end(sp: _Span) -> None:
    """Close a span ``begin`` opened (callers skip a None token)."""
    dt = time.perf_counter() - sp.t0
    sp.__exit__(None, None, None)
    try:
        table = _tls.table
    except AttributeError:
        table = _tls.table = {}
        with _lock:
            _tables.append(table)
    slot = table.get(sp.name)
    if slot is None:
        table[sp.name] = [1, dt]
    else:
        slot[0] += 1
        slot[1] += dt


def totals() -> Dict[str, Tuple[int, float]]:
    """Layer spans closed since the last ``reset``, over all threads:
    name -> (count, seconds)."""
    out: Dict[str, Tuple[int, float]] = {}
    with _lock:
        tables = [dict(t) for t in _tables]
    for t in tables:
        for name, (c, secs) in t.items():
            c0, s0 = out.get(name, (0, 0.0))
            out[name] = (c0 + c, s0 + secs)
    return out


def reset() -> None:
    with _lock:
        for t in _tables:
            t.clear()


@atexit.register
def _dump_accum() -> None:
    spans = totals()
    if ENABLED and _mode == "accum" and (_accum or spans):
        rows = [(name, slot["count"], slot["total_us"])
                for name, slot in _accum.items()]
        rows += [(name, c, secs * 1e6) for name, (c, secs) in spans.items()]
        with open(_file, "a") as fh:
            for name, count, total_us in sorted(rows):
                fh.write(json.dumps({
                    "name": name, "count": int(count),
                    "total_us": round(total_us, 1),
                    "avg_us": round(total_us / max(1, count), 2)}) + "\n")
