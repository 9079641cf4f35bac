"""The request-init path does no per-request enum arithmetic and no import.

``CollType`` and ``CollArgsFlags`` are ``enum.IntFlag``: every ``&`` or
``|`` on them runs ``enum.Flag.__and__``/``__or__`` in Python and builds
a member through ``EnumType.__call__``. The path from
``Team.collective_init`` to the returned request, and ``finalize``, tests
flags as plain-int masks and looks fixed names up in tables built at
import, so one request makes none of those calls and runs no ``import``.
The masks and tables must answer as the enum expressions they replace.
"""
import builtins
import collections
import enum
import itertools
import sys
import threading

import numpy as np
import pytest

from ucc_tpu import (BufferInfo, CollArgs, CollArgsFlags, CollType,
                     DataType, MemoryType, ReductionOp, Status)
from ucc_tpu.constants import (_DT_INFO, FLAG_IN_PLACE,
                               FLAG_MEM_MAPPED_BUFFERS, FLAG_PERSISTENT,
                               FLAG_TIMEOUT, ROOTED_COLLS, coll_type_str,
                               dt_numpy, dt_size)
from ucc_tpu.core import coll as core_coll
from ucc_tpu import integrity

from harness import UccJob

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

#: bf16 elements: above TL/XLA's short-message range on the CPU mesh, so
#: the 4-rank allreduce takes the compiled-program path
COUNT = 1 << 16

#: enum.py entry points that flag arithmetic runs (``EnumType.__call__``
#: also builds each result member of ``&`` and ``|``)
_ENUM_CODES = {
    enum.Flag.__and__.__code__: "Flag.__and__",
    enum.Flag.__or__.__code__: "Flag.__or__",
    enum.Flag.__xor__.__code__: "Flag.__xor__",
    enum.Flag.__invert__.__code__: "Flag.__invert__",
    enum.EnumType.__call__.__code__: "EnumType.__call__",
}

#: (ranks, the TL the request must select)
_CASES = {"xla4": (4, "xla"), "self1": (1, "self")}


@pytest.fixture(scope="module", params=sorted(_CASES))
def job(request):
    n, tl = _CASES[request.param]
    if len(jax.devices()) < n:
        pytest.skip(f"needs >= {n} devices")
    j = UccJob(n)
    teams = j.create_team()
    yield j, teams, tl
    j.cleanup()


def _argses(j, n):
    """One in-place AVG bf16 allreduce of device buffers per rank."""
    out = []
    for r in range(n):
        dev = j.contexts[r].tl_contexts["xla"].obj.device
        buf = jax.device_put(jnp.full(COUNT, r + 1.0, jnp.bfloat16), dev)
        out.append(CollArgs(
            coll_type=CollType.ALLREDUCE,
            src=BufferInfo(None, COUNT, DataType.BFLOAT16,
                           mem_type=MemoryType.TPU),
            dst=BufferInfo(buf, COUNT, DataType.BFLOAT16,
                           mem_type=MemoryType.TPU),
            op=ReductionOp.AVG, flags=CollArgsFlags.IN_PLACE))
    return out


class _Watch:
    """Counts, on this thread only, calls into ``_ENUM_CODES`` (through
    ``sys.setprofile``) and ``import`` statements (through
    ``builtins.__import__``, which the interpreter calls for every
    ``import`` once it is not the default)."""

    def __init__(self):
        self.enum_calls = collections.Counter()
        self.imports = collections.Counter()
        self._tid = threading.get_ident()

    def _prof(self, frame, event, arg):
        if event == "call":
            name = _ENUM_CODES.get(frame.f_code)
            if name is not None:
                caller = frame.f_back
                self.enum_calls[(name, caller.f_code.co_filename,
                                 caller.f_lineno)] += 1

    def __enter__(self):
        real = self._real = builtins.__import__

        def counted(name, *a, **k):
            if threading.get_ident() == self._tid:
                f = sys._getframe(1)
                self.imports[(name, f.f_code.co_filename, f.f_lineno)] += 1
            return real(name, *a, **k)
        builtins.__import__ = counted
        sys.setprofile(self._prof)
        return self

    def __exit__(self, *exc):
        sys.setprofile(None)
        builtins.__import__ = self._real


def _one_request(j, teams, watch=None):
    """Init, post, complete and finalize one allreduce on every rank;
    ``watch`` (if given) covers the inits and the finalizes."""
    argses = _argses(j, len(teams))
    if watch is None:
        reqs = [t.collective_init(a) for t, a in zip(teams, argses)]
    else:
        with watch:
            reqs = [t.collective_init(a) for t, a in zip(teams, argses)]
    for rq in reqs:
        rq.post()
    j.progress_until(lambda: all(rq.test() != Status.IN_PROGRESS
                                 for rq in reqs))
    assert [rq.test() for rq in reqs] == [Status.OK] * len(reqs)
    np.testing.assert_allclose(
        np.asarray(argses[0].dst.buffer, np.float32), (len(teams) + 1) / 2)
    algs = [rq.task.alg_name for rq in reqs]
    if watch is None:
        for rq in reqs:
            rq.finalize()
    else:
        with watch:
            for rq in reqs:
                rq.finalize()
    return algs


@pytest.mark.parametrize("what", ["enum_flag_calls", "imports"])
def test_request_init_runs_no_enum_arithmetic_or_import(job, what):
    j, teams, tl = job
    _one_request(j, teams)          # warm-up: first-use builds and caches
    watch = _Watch()
    algs = _one_request(j, teams, watch)
    assert algs == [tl] * len(teams)
    found = watch.enum_calls if what == "enum_flag_calls" else watch.imports
    assert dict(found) == {}


# ---------------------------------------------------------------------------
# the int masks and tables answer as the enum expressions they replace
# ---------------------------------------------------------------------------

_DT_CHECKED_ENUM = (CollType.GATHER | CollType.GATHERV | CollType.SCATTER
                    | CollType.SCATTERV | CollType.BCAST | CollType.REDUCE)
_TESTED_FLAGS = (CollArgsFlags.IN_PLACE, CollArgsFlags.PERSISTENT,
                 CollArgsFlags.TIMEOUT, CollArgsFlags.MEM_MAPPED_BUFFERS)


def _same_coll_type_sets():
    for ct in CollType:
        assert CollArgs(coll_type=ct).is_rooted == bool(ct & ROOTED_COLLS)
        assert bool(int(ct) & core_coll._DT_CHECKED) == \
            bool(ct & _DT_CHECKED_ENUM)
        assert bool(int(ct) & core_coll._ATTESTED) == \
            bool(ct & integrity.ATTEST_COLLS)


def _same_flag_tests():
    for k in range(len(_TESTED_FLAGS) + 1):
        for subset in itertools.combinations(_TESTED_FLAGS, k):
            f = CollArgsFlags(0)
            for bit in subset:
                f |= bit
            for flags in (f, int(f)):
                a = CollArgs(flags=flags)
                assert a.is_inplace == bool(f & CollArgsFlags.IN_PLACE)
                assert a.is_persistent == bool(f & CollArgsFlags.PERSISTENT)
                for mask, bit in ((FLAG_IN_PLACE, CollArgsFlags.IN_PLACE),
                                  (FLAG_PERSISTENT, CollArgsFlags.PERSISTENT),
                                  (FLAG_TIMEOUT, CollArgsFlags.TIMEOUT),
                                  (FLAG_MEM_MAPPED_BUFFERS,
                                   CollArgsFlags.MEM_MAPPED_BUFFERS)):
                    assert bool(int(flags) & mask) == bool(f & bit)


def _same_coll_type_names():
    for ct in CollType:
        assert coll_type_str(ct) == CollType(ct).name.lower()
        assert coll_type_str(int(ct)) == CollType(ct).name.lower()
    # a combination names its members, as CollType's own name does
    both = CollType.BCAST | CollType.ALLREDUCE
    assert coll_type_str(both) == both.name.lower()
    assert coll_type_str(int(both)) == both.name.lower()
    # a value that is no CollType falls back to its hex
    assert coll_type_str(4.5) == "coll_type_0x4"
    # as does one that names no collective
    assert coll_type_str(1 << 20) == "coll_type_0x100000"
    assert coll_type_str(0) == "coll_type_0x0"


def _same_dt_lookups():
    for dt in DataType:
        size, nd = _DT_INFO[DataType(dt)]     # keyed by the enum member
        for v in (dt, int(dt)):
            assert dt_size(v) == size
            if nd is None:
                with pytest.raises(TypeError, match=f"^{dt.name} has no "
                                   "host compute representation$"):
                    dt_numpy(v)
            else:
                assert dt_numpy(v) == nd
    for bad in (99, -1, "x", [1]):
        for f in (dt_size, dt_numpy):
            with pytest.raises(ValueError,
                               match=r"is not a valid DataType$"):
                f(bad)


_EQUIVALENCES = {
    "coll_type_sets": _same_coll_type_sets,
    "flag_tests": _same_flag_tests,
    "coll_type_names": _same_coll_type_names,
    "dt_lookups": _same_dt_lookups,
}


@pytest.mark.parametrize("what", sorted(_EQUIVALENCES))
def test_int_masks_and_tables_match_enum_expressions(what):
    _EQUIVALENCES[what]()
