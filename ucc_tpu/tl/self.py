"""TL/SELF — loopback transport for single-rank teams.

Reference: /root/reference/src/components/tl/self (662 LoC): supports all 16
coll types for team_size == 1 (tl_self.h:78-85), keeping full collective
semantics (buffer movement via MC) so 1-rank teams behave identically to
N-rank ones. Also serves as the service team for 1-rank teams.
"""
from __future__ import annotations

import functools
from typing import Any, List, Optional

import numpy as np

from ..api.types import BufferInfo, BufferInfoV
from ..constants import COLL_TYPE_ALL, CollType, MemoryType, ReductionOp
from ..core.components import (BaseContext, BaseLib, TransportLayer,
                               register_tl)
from ..schedule.task import CollTask
from ..score.score import CollScore
from ..status import Status, UccError
from .base import TlTeamBase, binfo_typed, binfo_u8

SUPPORTED = COLL_TYPE_ALL  # tl_self.h:78-85


class TlSelfTask(CollTask):
    """Local copy task: dst <- src (or no-op for in-place/sync colls)."""

    def __init__(self, init_args, team):
        super().__init__(team=team, args=init_args.args)
        self.init_args = init_args

    def post_fn(self) -> Status:
        args = self.args
        if not args.is_inplace and args.src is not None and \
                args.dst is not None and args.src.buffer is not None:
            if isinstance(args.src, BufferInfoV) or \
                    isinstance(args.dst, BufferInfoV):
                _place_v(args.src, args.dst)
            elif args.dst.mem_type == MemoryType.TPU:
                # TPU buffer convention: jax.Arrays are immutable, the
                # result is delivered by rebinding dst.buffer (see tl/xla).
                # 1-rank semantics: result == src.
                buf = args.src.buffer
                if args.src.mem_type != MemoryType.TPU:
                    import jax
                    buf = jax.device_put(np.asarray(buf))
                args.dst.buffer = buf
            elif args.dst.buffer is not None:
                src_u8 = binfo_u8(args.src)
                dst_u8 = binfo_u8(args.dst)
                n = min(src_u8.size, dst_u8.size)
                dst_u8[:n] = src_u8[:n]
        self.status = Status.OK
        return Status.OK


def _block(bi):
    """(displacement, count) of rank 0's block of a buffer info."""
    if isinstance(bi, BufferInfoV):
        return (int(bi.displacements[0]) if bi.displacements is not None
                else 0), int(bi.counts[0])
    return 0, int(bi.count)


def _place_v(src, dst) -> None:
    """A v-collective on one rank: the source block lands at the
    destination's displacement, inside the caller's ``dst.buffer`` when
    one is given (the rest of it kept), else in zeros up to the block's
    end. On the device the offsets and count are one runtime int32 input
    (a single transfer) of one program per buffer shape
    (``ops.place_rows``)."""
    s_off, s_cnt = _block(src)
    d_off, d_cnt = _block(dst)
    count = min(s_cnt, d_cnt)
    if dst.mem_type != MemoryType.TPU:
        if dst.buffer is not None:
            out = binfo_typed(dst, count=d_off + count)
            out[d_off:] = binfo_typed(src, count=count, elem_offset=s_off)
        return
    import jax
    import jax.numpy as jnp

    from ..ops import a2av_row_width
    buf = src.buffer
    if src.mem_type != MemoryType.TPU:
        buf = jax.device_put(np.asarray(buf))
    x = jnp.ravel(buf) if buf.ndim != 1 else buf
    if dst.buffer is None and s_off == d_off == 0 and count == x.size:
        dst.buffer = x         # the whole source, where it stands
        return
    out = dst.buffer if dst.buffer is not None else \
        jnp.zeros(d_off + count, x.dtype, device=x.device)
    flat = jnp.ravel(out) if out.ndim != 1 else out
    w = a2av_row_width([s_off, d_off, count, x.size, flat.size])
    placed = _place_fn(w)(x, flat, np.array(
        [s_off // w, count // w, d_off // w], np.int32))
    dst.buffer = placed.reshape(out.shape) if out.ndim != 1 else placed


@functools.lru_cache(maxsize=None)
def _place_fn(w: int):
    import jax

    from .. import ops

    def self_place(x, out, rows):     # rows: source row, count, dst row
        return ops.place_rows(ops.a2av_rows(x, w), ops.a2av_rows(out, w),
                              rows[0], rows[1], rows[2]).reshape(-1)
    return jax.jit(self_place)


class _SelfServiceTask(CollTask):
    def __init__(self, result):
        super().__init__()
        self.result = result

    def post_fn(self) -> Status:
        self.status = Status.OK
        return Status.OK


class TlSelfTeam(TlTeamBase):
    NAME = "self"

    def __init__(self, comp_context, core_team, scope: str = "cl"):
        if core_team.size != 1:
            raise UccError(Status.ERR_NOT_SUPPORTED,
                           "tl/self requires team size 1")
        super().__init__(comp_context, core_team, scope)

    def get_scores(self) -> CollScore:
        return CollScore.build_default(
            self, TlSelf.DEFAULT_SCORE, list(CollType),
            TlSelf.SUPPORTED_MEM_TYPES, self.coll_init, "self")

    def coll_init(self, init_args, team=None) -> CollTask:
        return TlSelfTask(init_args, self)

    # ---- service collectives (1-rank trivial) -------------------------
    def service_allreduce(self, arr: np.ndarray, op: ReductionOp) -> CollTask:
        return _SelfServiceTask(arr.copy())

    def service_allgather(self, data: bytes) -> CollTask:
        return _SelfServiceTask([bytes(data)])

    def service_bcast(self, data: Optional[bytes], root: int = 0) -> CollTask:
        return _SelfServiceTask(bytes(data or b""))


@register_tl
class TlSelf(TransportLayer):
    NAME = "self"
    DEFAULT_SCORE = 50
    SUPPORTED_COLLS = SUPPORTED
    SUPPORTED_MEM_TYPES = (MemoryType.HOST, MemoryType.TPU)
    SERVICE_CAPABLE = True
    lib_cls = BaseLib
    context_cls = BaseContext
    team_cls = TlSelfTeam
