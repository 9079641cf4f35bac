"""Host time per request in the core's own share of
``collective_init``: the library's ``ucc.init`` span less its
``ucc.select`` and ``ucc.tl_init`` children, divided by the requests.
Also read as ``init_self_us.host`` in the host-bound cell."""
from yardstick import lib_spans


def read(run):
    return lib_spans.per_request(run, "ucc.init",
                                 minus=("ucc.select", "ucc.tl_init"))
