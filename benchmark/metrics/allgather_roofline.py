"""The library's allgather programs against the chip's roofline.

Least time of one allgather of S output bytes per chip over n chips is the
larger of the bytes each chip receives, (n-1)/n * S, over the interconnect
peak and its memory bytes, (1 + 1/n) * S (read the shard, write the
result), over the HBM peak. S comes from the generator's list of a step's
collectives (``coll_bytes``, the allgathers by its ``kinds``), so the work
counted is the same whatever implements the allgather. The time is the
device time of the library's ``jit_ucc_allgather_*`` programs in the traced
window, mean over the chips. Nothing to read on one chip."""
from yardstick import roofline


def read(run):
    return roofline.share(run, "allgather", "jit_ucc_allgather_",
                          roofline.gather_scatter_least)
