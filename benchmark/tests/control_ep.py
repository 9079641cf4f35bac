"""The control of ``correct`` in the expert-parallel cells: every bf16
alltoallv result rounded to float8_e4m3, the precision below the
configuration's bfloat16, planted into a run so that the harness's own
comparison reads it (tests/test_ep.py on the CPU, tests/chip_control_ep.py
on the chip). On several ranks the rounding follows TL/XLA's result
binding, on one rank TL/self's placement; the int32 counts all-to-all is
left exact."""
from collections import Counter

import jax.numpy as jnp

import ucc_tpu
from control import low
from ucc_tpu.tl import self as tl_self
from ucc_tpu.tl import xla as tl_xla


def _round(dst) -> None:
    if dst.buffer is not None and \
            jnp.issubdtype(dst.buffer.dtype, jnp.floating):
        dst.buffer = low(dst.buffer)


def plant(setattr, setenv) -> Counter:
    """Returns a count of the algorithms that served the run's
    requests, by team size and collective."""
    copy_out = tl_xla.XlaCollTask._a2av_copy_out

    def copy_out_low(self):
        copy_out(self)
        _round(self.args.dst)

    setattr(tl_xla.XlaCollTask, "_a2av_copy_out", copy_out_low)
    post = tl_self.TlSelfTask.post_fn

    def post_low(self):
        st = post(self)
        _round(self.args.dst)
        return st

    setattr(tl_self.TlSelfTask, "post_fn", post_low)
    algs = Counter()
    init = ucc_tpu.Team.collective_init

    def counted(self, args):
        req = init(self, args)
        algs[f"{self.size}:{req.task.alg_name}:"
             f"{ucc_tpu.constants.coll_type_str(args.coll_type)}"] += 1
        return req

    setattr(ucc_tpu.Team, "collective_init", counted)
    return algs
