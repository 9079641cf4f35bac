"""The library's reduce-scatter programs against the chip's roofline.

Least time of one reduce-scatter of S input bytes per chip over n chips is
the larger of the bytes each chip receives, (n-1)/n * S, over the
interconnect peak and its memory bytes, (1 + 1/n) * S (read the input,
write the block), over the HBM peak. S comes from the generator's list of
a step's collectives (``coll_bytes``, the reduce-scatters by its
``kinds``), so the work counted is the same whatever implements the
reduce-scatter. The time is the device time of the library's
``jit_ucc_reduce_scatter_*`` programs in the traced window, mean over the
chips. Nothing to read on one chip."""
from yardstick import roofline


def read(run):
    return roofline.share(run, "reduce_scatter", "jit_ucc_reduce_scatter_",
                          roofline.gather_scatter_least)
