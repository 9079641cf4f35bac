"""The library's own layer spans (``ucc.*``), as the per-layer metrics
read them.

ucc_tpu times its post-path boundaries while a profiler session captures
(``ucc_tpu.utils.profiling.totals()``: span name -> (count, seconds)), so
in a traced run the table holds the measured window and nothing else. A
library without that table gives nothing to read, and every reader then
returns None.
"""
from __future__ import annotations

LAUNCH = "ucc.xla.launch"


def totals() -> dict:
    from ucc_tpu.utils import profiling
    read = getattr(profiling, "totals", None)
    return read() if read is not None else {}


def _secs(t: dict, name: str) -> float:
    return t[name][1] if name in t else 0.0


def per_request(run, name: str, minus=()) -> float | None:
    """Microseconds per request of span ``name`` less its child spans
    ``minus`` (an absent child counts as 0); None without ``name``."""
    t = totals()
    if not t.get(name, (0,))[0] or not run.requests:
        return None
    secs = _secs(t, name) - sum(_secs(t, m) for m in minus)
    return secs / run.requests * 1e6


def per_launch(run, name: str) -> float | None:
    """Microseconds of span ``name`` per TL/XLA launch; None without
    ``name`` or without a launch."""
    t = totals()
    launches = t.get(LAUNCH, (0,))[0]
    if not t.get(name, (0,))[0] or not launches:
        return None
    return _secs(t, name) / launches * 1e6
