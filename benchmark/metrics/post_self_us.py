"""Host time per request in the core's own share of ``post``:
the library's ``ucc.post`` span less the ``ucc.xla.launch`` it holds on
the depositing rank (absent on one rank), divided by the requests. Also
read as ``post_self_us.host`` in the host-bound cell."""
from yardstick import lib_spans


def read(run):
    return lib_spans.per_request(run, "ucc.post", minus=(lib_spans.LAUNCH,))
