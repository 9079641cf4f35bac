"""Host time per request in TL task creation: the library's
``ucc.tl_init`` span (the chosen TL's task constructor, plus any
NOT_SUPPORTED fallback inits) over the traced window, divided by the
requests. Also read as ``tl_init_us.host`` in the host-bound cell."""
from yardstick import lib_spans


def read(run):
    return lib_spans.per_request(run, "ucc.tl_init")
