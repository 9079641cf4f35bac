"""Host time per TL/XLA alltoallv launch in its planning: the library's
``ucc.xla.a2av.plan`` span (counts table -> runtime arrays -> device_put),
divided by its own count. Nothing to read from a library without that
span."""
from yardstick import lib_spans

SPAN = "ucc.xla.a2av.plan"


def read(run):
    count, secs = lib_spans.totals().get(SPAN, (0, 0.0))
    return secs / count * 1e6 if count else None
