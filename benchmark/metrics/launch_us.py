"""Host time per TL/XLA launch: the library's ``ucc.xla.launch``
span (staging, program lookup, dispatch, result binding), divided by its
count, one launch per bucket per step."""
from yardstick import lib_spans


def read(run):
    return lib_spans.per_launch(run, lib_spans.LAUNCH)
