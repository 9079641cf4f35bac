"""Host time per request in selection: the library's ``ucc.select``
span (the score-map lookup and the straggler-bias tick before it) over the
traced window, divided by the requests. Also read as ``select_us.host``
in the host-bound cell."""
from yardstick import lib_spans


def read(run):
    return lib_spans.per_request(run, "ucc.select")
