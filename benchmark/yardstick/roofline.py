"""One kind of collective of a step against the chip's roofline, for the
per-layer readers of streams whose ``coll_bytes`` carries ``kinds``."""
from __future__ import annotations


def share(run, kind: str, program: str, least) -> float | None:
    """Percent of the least time of the step's collectives of ``kind``
    (``least(S, n, peaks)`` seconds for S bytes per chip on n chips) in
    the device time of the library's programs named ``program*``, mean
    over the chips. None without a trace, on one chip, for a stream
    whose ``coll_bytes`` has no ``kinds``, or where no such program
    ran."""
    t = run.trace
    kinds = getattr(run.coll_bytes, "kinds", None)
    if t is None or run.n < 2 or kinds is None:
        return None
    secs = sum(s for m, s in t.modules.items() if m.startswith(program))
    if secs <= 0:
        return None
    total = sum(least(s, run.n, run.peaks)
                for s, k in zip(run.coll_bytes, kinds) if k == kind)
    return 100.0 * run.steps * total / secs


def gather_scatter_least(s: float, n: int, peaks: dict) -> float:
    """Least seconds of an allgather of S output bytes per chip, or a
    reduce-scatter of S input bytes per chip, on n chips."""
    return max((n - 1) / n * s / peaks["ici_bytes_per_s"],
               (1 + 1 / n) * s / peaks["hbm_bytes_per_s"])
