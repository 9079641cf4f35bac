"""The control of ``correct`` in the FSDP2 cells: the reduce-scatter
computed in bfloat16, the precision below the configuration's float32
``reduce_dtype``, planted into a run so that the harness's own comparison
reads it (tests/test_fsdp.py on the CPU, tests/chip_control_fsdp.py on the
chip). On several ranks TL/XLA's reduce-scatter program sums and averages
bfloat16 and casts back; on one rank TL/self's float32 result is rounded
to bfloat16. The bfloat16 allgathers are left exact."""
from collections import Counter

import jax.numpy as jnp
from jax import lax

import ucc_tpu
from ucc_tpu import ops
from ucc_tpu.tl import self as tl_self


def low_reduce_scatter(x, op, axis_name="r"):
    """The float32 reduce-scatter summed and averaged in bfloat16."""
    y = lax.psum_scatter(x.astype(jnp.bfloat16), axis_name,
                         scatter_dimension=x.ndim - 1, tiled=True)
    if op == ucc_tpu.ReductionOp.AVG:
        y = y / jnp.bfloat16(lax.axis_size(axis_name))
    return y.astype(x.dtype)


def plant(setattr, setenv) -> Counter:
    """Returns a count of the algorithms that served the run's
    requests, by team size and collective."""
    setattr(ops, "reduce_scatter", low_reduce_scatter)
    post = tl_self.TlSelfTask.post_fn

    def post_low(self):
        st = post(self)
        dst = self.args.dst
        if self.args.coll_type == ucc_tpu.CollType.REDUCE_SCATTER:
            dst.buffer = dst.buffer.astype(jnp.bfloat16).astype(
                dst.buffer.dtype)
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
