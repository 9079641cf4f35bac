"""TL/SHM — in-process shared-memory transport layer.

The fast intra-node host transport: ranks whose contexts live in one
process (threads — the productized form of the reference's in-process gtest
job, test_ucc.h:123-151) exchange messages through lock-protected mailboxes
with zero-copy rendezvous for large payloads. Role-wise this mirrors the
reference's intra-node fast path (tl/cuda over IPC; tl/ucp shm transports)
while TL/SOCKET covers multi-process/DCN with the same algorithm suite.
"""
from __future__ import annotations

import pickle
from typing import Dict

import numpy as np

from ..constants import COLL_TYPE_ALL, MemoryType
from ..core.components import BaseContext, BaseLib, TransportLayer, register_tl
from ..ec.cpu import EcCpu
from ..status import Status, UccError
from ..utils.config import (ConfigField, ConfigTable, parse_memunits,
                            register_table)
from .host.config_fields import HOST_ALG_FIELDS
from .host.team import HostTlTeam
from .host.transport import InProcTransport

from ..utils.config import parse_bool, parse_string

TL_SHM_CONFIG = register_table(ConfigTable(
    prefix="TL_SHM_", name="tl/shm", fields=HOST_ALG_FIELDS + [
        ConfigField("EAGER_THRESH", "auto", "eager copy threshold for "
                    "UNEXPECTED sends; larger sends are zero-copy "
                    "rendezvous (sends matching a posted recv are always "
                    "copy-free). auto = defer to UCC_HOST_EAGER_LIMIT "
                    "(default 8k)", parse_memunits),
        ConfigField("NATIVE", "auto", "use the native C++ tag matcher "
                    "(v2: copy-free delivery, eager/rndv split at the "
                    "eager limit, cancel-skip, epoch fences — FT-safe) "
                    "for this endpoint. auto = on when the core is "
                    "built, in both thread modes; y/n forces. The "
                    "process-wide kill switch is UCC_NATIVE",
                    parse_string),
    ]))


class TlShmContext(BaseContext):
    def __init__(self, comp_lib, core_context, config):
        super().__init__(comp_lib, core_context, config)
        # the v2 native core (copy-free matching, epoch fences, mapped
        # completion window instead of per-poll ffi) is the default in
        # BOTH thread modes — single-threaded it holds parity with the
        # in-GIL python matcher and GIL-released matching wins big under
        # concurrent progress threads. The UCC_TL_SHM_NATIVE knob (env or
        # config file) overrides.
        use_native = None
        if config is not None:
            try:
                nv = str(config.get("native")).strip().lower()
                if nv and nv != "auto":
                    use_native = parse_bool(nv)
            except (KeyError, ValueError):  # unrecognized: behave as auto
                pass
        self.transport = InProcTransport(use_native=use_native)
        # flight-recorder wire ring: bound once per endpoint (the PR-3
        # bind-at-post pattern applied at endpoint scope) — None keeps
        # the send path branch-false
        rec = getattr(core_context, "flight", None)
        if rec is not None:
            self.transport._flight = rec.wire
        if config is not None:
            from ..utils.config import SIZE_AUTO
            if config.eager_thresh != SIZE_AUTO:
                self.transport.EAGER_THRESHOLD = config.eager_thresh
        self.executor = EcCpu()
        self.peer_info: Dict[int, tuple] = {}
        self._mailboxes: Dict[int, object] = {}

    def pack_address(self) -> bytes:
        import os
        return pickle.dumps((os.getpid(), self.transport.uid))

    def unpack_addresses(self, addrs: Dict[int, bytes]) -> None:
        for rank, blob in addrs.items():
            if blob:
                self.peer_info[rank] = pickle.loads(blob)

    def same_process(self, ctx_rank: int) -> bool:
        import os
        info = self.peer_info.get(ctx_rank)
        return bool(info) and info[0] == os.getpid()

    def _peer(self, ctx_rank: int):
        peer = self._mailboxes.get(ctx_rank)
        if peer is None:
            info = self.peer_info.get(ctx_rank)
            if info is None:
                raise UccError(Status.ERR_NOT_FOUND,
                               f"no shm address for ctx rank {ctx_rank}")
            peer = InProcTransport.resolve(info[1].encode()
                                           if isinstance(info[1], str)
                                           else info[1])
            if peer is None:
                raise UccError(Status.ERR_NOT_FOUND,
                               f"shm peer {ctx_rank} endpoint gone")
            self._mailboxes[ctx_rank] = peer
        return peer

    def send_to(self, peer_ctx_rank: int, key, data: np.ndarray, crc=None):
        return self.transport.send_nb(self._peer(peer_ctx_rank), key, data,
                                      crc=crc)

    # -- one-sided (tl/host/onesided.py): every peer is in-process, so
    # put/get/atomic apply directly under the registry lock; flush is a
    # no-op fence (in-order, synchronous application)
    def os_put(self, peer_ctx_rank: int, desc: dict, offset: int,
               data: np.ndarray, notify=None) -> None:
        from .host.onesided import local_os_put
        local_os_put(desc, offset, data, notify)

    def os_get(self, peer_ctx_rank: int, desc: dict, offset: int,
               dst: np.ndarray):
        from .host.onesided import local_os_get
        return local_os_get(desc, offset, dst)

    def os_flush(self, peer_ctx_rank: int):
        from .host.transport import SendReq
        return SendReq(done=True)

    def global_work_buffer_size(self) -> int:
        from .host.onesided import sw_max_work_buffer
        return sw_max_work_buffer(self.config)

    def destroy(self) -> None:
        self.transport.close()


class TlShmTeam(HostTlTeam):
    NAME = "shm"

    def __init__(self, comp_context, core_team, scope: str = "cl"):
        super().__init__(comp_context, core_team, scope)
        ctx_map = self.ctx_map
        my_ctx = core_team.context.rank
        for gr in range(self.size):
            cr = ctx_map.eval(gr)
            if cr != my_ctx and not comp_context.same_process(cr):
                raise UccError(Status.ERR_NOT_SUPPORTED,
                               "tl/shm requires all team ranks in-process")


TlShmTeam.TL_CLS = None  # set below


@register_tl
class TlShm(TransportLayer):
    NAME = "shm"
    DEFAULT_SCORE = 40            # intra-node prior (tl_cuda.h:28 = 40)
    SUPPORTED_COLLS = COLL_TYPE_ALL
    SUPPORTED_MEM_TYPES = (MemoryType.HOST,)
    SERVICE_CAPABLE = True
    CONTEXT_CONFIG = TL_SHM_CONFIG
    lib_cls = BaseLib
    context_cls = TlShmContext
    team_cls = TlShmTeam


TlShmTeam.TL_CLS = TlShm
