"""Core enums and flags of the ucc_tpu public API.

Feature-parity targets (reference /root/reference/src/ucc/api/ucc.h):
  - 16 collective types        (ucc.h:147-165)
  - 18 predefined datatypes    (ucc.h:203-221) + generic user datatypes
  - 13 reduction operations    (ucc.h:454-469) incl. AVG / MINLOC / MAXLOC
  - thread modes               (ucc.h:493-497)
  - coll-args flags            (ucc.h:1669-1727)
  - memory types               (ucc/api mem types; TPU HBM replaces CUDA)

The TPU build swaps the CUDA memory world for JAX/TPU: MemoryType.TPU means
"a jax.Array resident in device HBM"; HOST means numpy/CPU memory.
"""
from __future__ import annotations

import enum

import numpy as np
import ml_dtypes


class CollType(enum.IntFlag):
    """Collective operation types (bitflags, like ucc_coll_type_t ucc.h:147)."""

    BARRIER = 1 << 0
    BCAST = 1 << 1
    ALLREDUCE = 1 << 2
    REDUCE = 1 << 3
    ALLTOALL = 1 << 4
    ALLTOALLV = 1 << 5
    ALLGATHER = 1 << 6
    ALLGATHERV = 1 << 7
    GATHER = 1 << 8
    GATHERV = 1 << 9
    SCATTER = 1 << 10
    SCATTERV = 1 << 11
    REDUCE_SCATTER = 1 << 12
    REDUCE_SCATTERV = 1 << 13
    FANIN = 1 << 14
    FANOUT = 1 << 15


COLL_TYPE_ALL = CollType((1 << 16) - 1)
COLL_TYPE_LIST = list(CollType)
COLL_TYPE_NUM = 16

#: Rooted collectives — have a root rank whose buffers differ from non-roots
#: (cf. reference ucc_coll_utils.h root handling, ucc_coll.c:236 asymmetric path)
ROOTED_COLLS = (
    CollType.BCAST
    | CollType.REDUCE
    | CollType.GATHER
    | CollType.GATHERV
    | CollType.SCATTER
    | CollType.SCATTERV
    | CollType.FANIN
    | CollType.FANOUT
)


#: int mask of ROOTED_COLLS, for hot-path tests as ``int(ct) & ROOTED_MASK``
#: (an ``&`` on the IntFlag runs enum.Flag.__and__ in Python)
ROOTED_MASK = int(ROOTED_COLLS)

#: lower-case name of each single collective type, keyed by its value
_COLL_TYPE_NAMES = {int(m): m.name.lower() for m in CollType}


def coll_type_str(ct: CollType) -> str:
    """Pretty name like the reference's ucc_coll_type_str (ucc_coll_utils.h:263).
    A single type is a table lookup; a combination names its members, and a
    value that names none falls back to ``coll_type_0x<hex>``."""
    try:
        return _COLL_TYPE_NAMES[ct]
    except (KeyError, TypeError):
        pass
    try:
        name = CollType(ct).name
    except ValueError:
        name = None
    return name.lower() if name else f"coll_type_0x{int(ct):x}"


class MemoryType(enum.IntEnum):
    """Where a buffer lives. TPU replaces the reference's CUDA/ROCM axis."""

    HOST = 0          # numpy / host DRAM
    TPU = 1           # jax.Array in device HBM
    TPU_PINNED = 2    # host-pinned staging (device_put'able committed host array)
    UNKNOWN = 3

    # aliases keeping reference spellings meaningful in configs
    @classmethod
    def parse(cls, s: str) -> "MemoryType":
        s = s.strip().lower()
        aliases = {
            "host": cls.HOST, "cpu": cls.HOST,
            "tpu": cls.TPU, "cuda": cls.TPU, "device": cls.TPU, "hbm": cls.TPU,
            "tpu_pinned": cls.TPU_PINNED, "pinned": cls.TPU_PINNED,
        }
        if s not in aliases:
            raise ValueError(f"unknown memory type '{s}'")
        return aliases[s]


MEM_TYPE_NUM = 3  # HOST, TPU, TPU_PINNED participate in score maps


class ReductionOp(enum.IntEnum):
    """13 predefined reduction ops (ucc_reduction_op_t ucc.h:454-469)."""

    SUM = 0
    PROD = 1
    MAX = 2
    MIN = 3
    LAND = 4
    LOR = 5
    LXOR = 6
    BAND = 7
    BOR = 8
    BXOR = 9
    MINLOC = 10
    MAXLOC = 11
    AVG = 12


class DataType(enum.IntEnum):
    """18 predefined datatypes (ucc_datatype_t ucc.h:203-221).

    INT128/UINT128/FLOAT128/FLOAT128_COMPLEX exist for API parity; they have
    sizes (so copy-style colls work on raw bytes) but no numpy compute dtype,
    matching the reference where EC backends reject them (ec_cpu lacks them
    too on most builds).
    """

    INT8 = 0
    UINT8 = 1
    INT16 = 2
    UINT16 = 3
    INT32 = 4
    UINT32 = 5
    INT64 = 6
    UINT64 = 7
    INT128 = 8
    UINT128 = 9
    FLOAT16 = 10
    FLOAT32 = 11
    FLOAT64 = 12
    FLOAT128 = 13
    BFLOAT16 = 14
    FLOAT32_COMPLEX = 15
    FLOAT64_COMPLEX = 16
    FLOAT128_COMPLEX = 17


_DT_INFO = {
    DataType.INT8: (1, np.dtype(np.int8)),
    DataType.UINT8: (1, np.dtype(np.uint8)),
    DataType.INT16: (2, np.dtype(np.int16)),
    DataType.UINT16: (2, np.dtype(np.uint16)),
    DataType.INT32: (4, np.dtype(np.int32)),
    DataType.UINT32: (4, np.dtype(np.uint32)),
    DataType.INT64: (8, np.dtype(np.int64)),
    DataType.UINT64: (8, np.dtype(np.uint64)),
    DataType.INT128: (16, None),
    DataType.UINT128: (16, None),
    DataType.FLOAT16: (2, np.dtype(np.float16)),
    DataType.FLOAT32: (4, np.dtype(np.float32)),
    DataType.FLOAT64: (8, np.dtype(np.float64)),
    DataType.FLOAT128: (16, None),
    DataType.BFLOAT16: (2, np.dtype(ml_dtypes.bfloat16)),
    DataType.FLOAT32_COMPLEX: (8, np.dtype(np.complex64)),
    DataType.FLOAT64_COMPLEX: (16, np.dtype(np.complex128)),
    DataType.FLOAT128_COMPLEX: (32, None),
}

#: numpy dtype -> DataType (for memtype/dtype auto-detection)
_NP_TO_DT = {info[1]: dt for dt, info in _DT_INFO.items() if info[1] is not None}


def dt_size(dt: "DataType | GenericDataType") -> int:
    """Element size in bytes (ucc_dt_size analog)."""
    if isinstance(dt, GenericDataType):
        return dt.size
    try:
        # a DataType and its int hash and compare alike: one lookup
        return _DT_INFO[dt][0]
    except (KeyError, TypeError):
        return _DT_INFO[DataType(dt)][0]    # ValueError names the value


def dt_numpy(dt: DataType) -> np.dtype:
    """numpy dtype for a predefined DataType; raises for 128-bit types."""
    try:
        nd = _DT_INFO[dt][1]
    except (KeyError, TypeError):
        nd = _DT_INFO[DataType(dt)][1]
    if nd is None:
        raise TypeError(f"{DataType(dt).name} has no host compute representation")
    return nd


def dt_from_numpy(nd) -> DataType:
    nd = np.dtype(nd)
    if nd not in _NP_TO_DT:
        raise TypeError(f"no predefined DataType for numpy dtype {nd}")
    return _NP_TO_DT[nd]


def dt_has_compute(dt: "DataType | GenericDataType") -> bool:
    if isinstance(dt, GenericDataType):
        return dt.reduce_cb is not None
    return _DT_INFO[DataType(dt)][1] is not None


#: dtypes representable in JAX on TPU (FLOAT64/complex run on CPU backend only)
def dt_jax(dt: DataType):
    import jax.numpy as jnp

    m = {
        DataType.INT8: jnp.int8, DataType.UINT8: jnp.uint8,
        DataType.INT16: jnp.int16, DataType.UINT16: jnp.uint16,
        DataType.INT32: jnp.int32, DataType.UINT32: jnp.uint32,
        DataType.INT64: jnp.int64, DataType.UINT64: jnp.uint64,
        DataType.FLOAT16: jnp.float16, DataType.FLOAT32: jnp.float32,
        DataType.FLOAT64: jnp.float64, DataType.BFLOAT16: jnp.bfloat16,
        DataType.FLOAT32_COMPLEX: jnp.complex64,
        DataType.FLOAT64_COMPLEX: jnp.complex128,
    }
    if DataType(dt) not in m:
        raise TypeError(f"{DataType(dt).name} not representable in jax")
    return m[DataType(dt)]


class GenericDataType:
    """User-defined datatype (ucc_dt_create_generic, ucc.h:289-433).

    pack/unpack/reduce callbacks operate on contiguous byte views. A generic
    dtype with no reduce_cb can be used only in non-reducing collectives,
    matching the reference contract.
    """

    __slots__ = ("size", "pack_cb", "unpack_cb", "reduce_cb", "name")

    def __init__(self, size: int, pack_cb=None, unpack_cb=None, reduce_cb=None,
                 name: str = "generic"):
        if size <= 0:
            raise ValueError("generic datatype size must be positive")
        self.size = int(size)
        self.pack_cb = pack_cb
        self.unpack_cb = unpack_cb
        self.reduce_cb = reduce_cb
        self.name = name

    def __repr__(self):
        return f"GenericDataType({self.name}, size={self.size})"


class ThreadMode(enum.IntEnum):
    """ucc_thread_mode_t (ucc.h:493-497)."""

    SINGLE = 0
    FUNNELED = 1
    MULTIPLE = 2


class CollSyncType(enum.IntEnum):
    """Synchronous vs non-synchronous collective model (ucc.h:521-524)."""

    NON_SYNC_COLLECTIVES = 0
    SYNC_COLLECTIVES = 1


class CollArgsFlags(enum.IntFlag):
    """ucc_coll_args_flags_t (ucc.h:1669-1727)."""

    IN_PLACE = 1 << 0
    PERSISTENT = 1 << 1
    COUNT_64BIT = 1 << 2
    DISPLACEMENTS_64BIT = 1 << 3
    CONTIG_SRC_BUFFER = 1 << 4
    CONTIG_DST_BUFFER = 1 << 5
    TIMEOUT = 1 << 6
    MEM_MAPPED_BUFFERS = 1 << 7
    MEM_MAP_SRC_MEMH = 1 << 8
    MEM_MAP_DST_MEMH = 1 << 9


#: int bits of the flags the request path tests, as ``int(flags) & FLAG_*``
#: (works the same on a CollArgsFlags and a plain int)
FLAG_IN_PLACE = int(CollArgsFlags.IN_PLACE)
FLAG_PERSISTENT = int(CollArgsFlags.PERSISTENT)
FLAG_TIMEOUT = int(CollArgsFlags.TIMEOUT)
FLAG_MEM_MAPPED_BUFFERS = int(CollArgsFlags.MEM_MAPPED_BUFFERS)


class CollArgsHints(enum.IntFlag):
    """Optimization hints (ucc.h:1732-1766)."""

    OPTIMIZE_LATENCY = 1 << 0
    OPTIMIZE_BANDWIDTH = 1 << 1
    NO_MEMORY_REUSE = 1 << 2


class EventType(enum.IntEnum):
    """Task/schedule events (ucc_event_t, schedule/ucc_schedule.h:22-30)."""

    EVENT_COMPLETED = 0
    EVENT_SCHEDULE_STARTED = 1
    EVENT_TASK_STARTED = 2
    EVENT_COMPLETED_SCHEDULE = 3
    EVENT_ERROR = 4
    EVENT_LAST = 5


class EeType(enum.IntEnum):
    """Execution-engine types (ucc_ee_type_t). TPU replaces CUDA streams."""

    TPU_STREAM = 0     # triggered execution inside a jitted program
    CPU_THREAD = 1
    LAST = 2


class ErrorType(enum.IntEnum):
    """ucc_error_type_t (ucc.h:1803-1806)."""

    LOCAL = 0
    GLOBAL = 1
