"""The library's alltoallv programs against the chip's roofline.

Least time of one alltoallv is the larger of the most bytes any chip sends
or receives over the interconnect, S, over the interconnect peak, and 2 *
S (read the rows, write them) over the HBM peak. S comes from the
generator's list of a step's collectives (``coll_bytes``: each
collective's busiest-chip bytes, meaned over the window's steps), so the
work counted is the same whatever implements the exchange. The time is the
device time of the library's ``jit_ucc_alltoallv*`` programs in the
traced window, mean over the chips. Nothing to read on one chip."""

PROGRAM = "jit_ucc_alltoallv"


def read(run):
    t = run.trace
    if t is None or run.n < 2:
        return None
    secs = sum(s for m, s in t.modules.items() if m.startswith(PROGRAM))
    if secs <= 0:
        return None
    ici = run.peaks["ici_bytes_per_s"]
    hbm = run.peaks["hbm_bytes_per_s"]
    least = sum(max(s / ici, 2 * s / hbm) for s in run.coll_bytes)
    return 100.0 * run.steps * least / secs
