"""Host time per TL/XLA launch in jit dispatch: the library's
``ucc.xla.dispatch`` span (the ``program(garr)`` call), divided by the
``ucc.xla.launch`` count."""
from yardstick import lib_spans


def read(run):
    return lib_spans.per_launch(run, "ucc.xla.dispatch")
