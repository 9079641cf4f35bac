"""Host time per TL/XLA launch in staging: the library's ``ucc.xla.stage``
span (each rank's shard made a single-device array on its device, then the
global array built from them), divided by the ``ucc.xla.launch`` count."""
from yardstick import lib_spans


def read(run):
    return lib_spans.per_launch(run, "ucc.xla.stage")
