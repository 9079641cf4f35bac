"""TL/RING_DMA — device-initiated ICI transport: ring collectives as
Pallas kernels driving `make_async_remote_copy` (inter-chip RDMA).

This TL owns the transport schedule at the DMA level — the role tl/mlx5
(12.9 kLoC of device-initiated InfiniBand) and the sliding-window one-sided
allreduce (/root/reference/src/components/tl/ucp/allreduce/
allreduce_sliding_window.h:30-50) play in the reference. Where TL/XLA asks
the compiler for a collective (lax.psum lowers to whatever schedule XLA
picks), TL/RING_DMA *is* the schedule: each chip copies its block to its
ring neighbor with an explicit async remote DMA, overlap and slotting are
written in the kernel, and semaphores are the completion protocol (the
QP/doorbell analog).

Algorithms: ring allreduce (reduce-scatter phase + allgather phase,
2*(n-1) block steps), ring allgather, ring reduce_scatter, pairwise
alltoall, and pipelined ring bcast (the tl/mlx5 mcast role). ALL five
have NO element cap beyond HBM on n>1 teams: vectors larger than one
VMEM pass run HBM-resident grid kernels with double-buffered HBM<->VMEM
staging overlapping the ring DMAs inside the kernel schedule (the
sliding-window role; bcast/alltoall joined in round 4 — the reference's
tl_mlx5/mcast streams arbitrary sizes too). Selectable via ``UCC_TL_RING_DMA_TUNE``
or by boosting the TL score; default score sits below TL/XLA so
compiler-scheduled collectives stay the default.

Compiled kernels open with a ring-neighbor barrier-semaphore handshake
(collective_id'd) so a remote DMA cannot land before the peer kernel owns
its comm slots, and every ring-schedule kernel runs the CONSUMER-ACK
THROTTLE (ported from ``fused_attention.py``): before each step's DMA the
sender waits one consumption ack from its right neighbor, closing the
2-slot protocol's skew hole (a rank running 2+ steps ahead can no longer
overwrite an unread slot; acks flow left while data flows right, so no
wait cycle). The pairwise alltoall needs neither (single-use slots).
Interpret mode skips both (no semaphore model there). Every family
compiles as Mosaic for a described v5e:2x2 at 1 and 4 chips
(tests/test_tpu_compile.py); none has run on a chip yet. Mosaic
addresses 1-D VMEM/HBM refs in whole tiles, so blocks are padded to
TILE_BYTES and 2-slot buffers are flat (``_slots``).

Kernels run compiled on real TPU meshes and in Pallas interpret mode on
the virtual CPU mesh (tests); the rendezvous/dispatch machinery is shared
with TL/XLA (same team model: rank == chip, deposits launch a shard_map
program over the team mesh).

This module's primitive set is also the substrate of the DEVICE-SIDE
COMPILER BACKEND (``dsl/lower_device.py``, ISSUE 15): generated
collectives lowered from verified DSL programs reuse
``_make_step_dma`` (the 2-slot parity protocol + consumer-ack
throttle), ``_neighbor_barrier``/``_all_rank_barrier``, ``_guarded``,
``_accum`` and ``_compiler_params`` — treat
their signatures/semantics as shared API (collective_id 10 belongs to
the generated kernels; see the id registry note at
build_hbm_alltoall_program).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List

from ..constants import CollType, MemoryType, ReductionOp
from ..core.components import BaseLib, TransportLayer, register_tl
from ..score.score import CollScore
from ..status import Status, UccError
from ..utils.config import (ConfigField, ConfigTable, parse_string,
                            register_table)
from ..utils.backend import is_tpu
from .base import AlgSpec, build_scores
from .xla import TlXlaContext, TlXlaTeam, XlaCollTask

TL_RING_DMA_CONFIG = register_table(ConfigTable(
    prefix="TL_RING_DMA_", name="tl/ring_dma", fields=[
        ConfigField("DEVICE_KIND", "", "restrict to a device platform "
                    "(tpu/cpu); empty = default backend", parse_string),
    ]))

#: per-kernel VMEM working-set bound (~16 MiB/core). Vectors larger than
#: this are CHUNKED: small overflows slice at the program level (XLA
#: schedules the passes); large allreduces run the HBM-RESIDENT grid
#: kernel, which keeps the full vector in HBM and double-buffers
#: HBM<->VMEM staging against the ring DMAs inside the kernel schedule
#: (the sliding-window role, allreduce_sliding_window.h:30-50 — no
#: whole-vector working set, no element cap beyond HBM capacity).
CHUNK_ELEMS = 1 << 18


def _accum(op: ReductionOp):
    import jax.numpy as jnp
    return {ReductionOp.SUM: jnp.add, ReductionOp.AVG: jnp.add,
            ReductionOp.MAX: jnp.maximum, ReductionOp.MIN: jnp.minimum,
            ReductionOp.PROD: jnp.multiply}[op]


#: bytes of one 1-D tile (8 sublanes x 128 lanes x 4 bytes): Mosaic
#: addresses a 1-D VMEM or HBM ref only in whole tiles, so every block a
#: kernel slices — and every dynamic offset — is a multiple of it
TILE_BYTES = 4096


def _tile(nd) -> int:
    """Elements per 1-D tile of dtype ``nd``."""
    return max(1, TILE_BYTES // nd.itemsize)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _vmem_pass_elems(n: int, t: int) -> int:
    """Per-rank elements one VMEM-resident ring pass covers (a multiple
    of n tiles of t elements). Single source of truth: the HBM-routing
    predicate and both builders must agree or counts in the gap
    mis-route."""
    return max(n * t, (CHUNK_ELEMS // (n * t)) * n * t)


def _slots(ref, size: int):
    """Accessor over a flat ref of equal ``size``-element slots:
    ``slot(i)`` is the view of slot i, for a static or traced i. Slots
    stay flat and tile-aligned — a (2, size) array would need 1-row
    slices of a 2-row tile, which Mosaic refuses."""
    from jax.experimental import pallas as pl

    def slot(i):
        off = i * size
        if not isinstance(off, int):
            off = pl.multiple_of(off, size)
        return ref.at[pl.ds(off, size)]
    return slot


def _guarded(pred, fn):
    """Run fn under pl.when(pred); static True runs unguarded, static
    False elides. Shared by the slot protocol's ack predicates and the
    semaphore helpers below."""
    from jax.experimental import pallas as pl

    if pred is True:
        fn()
    elif pred is not False:
        pl.when(pred)(fn)


def _compiler_params(collective_id: int, n: int):
    """Mosaic compiler params. ``collective_id`` keys the global barrier
    semaphore; only kernels with peers (n > 1) open with that barrier,
    and Mosaic refuses an id on a kernel that uses none."""
    from jax.experimental.pallas import tpu as pltpu
    if n > 1:
        return pltpu.CompilerParams(collective_id=collective_id,
                                    has_side_effects=True)
    return pltpu.CompilerParams(has_side_effects=True)


def _neighbor_barrier(n: int, axis: str, multi_axis: bool = False):
    """Initial ring-neighbor handshake (the standard Pallas distributed
    entry barrier): a remote DMA must not land in a peer's comm slots
    before that peer's kernel instance owns them, and the one-step-skew
    argument that makes 2-slot double buffering safe assumes neighbors
    start within one step of each other. Skipped in interpret mode
    (no barrier-semaphore model there; the compiled path is what needs
    it — not yet run on a chip, see module docstring).

    ``multi_axis``: the ring runs along ``axis`` of a multi-axis mesh
    (e.g. the sp axis of a dp x sp training mesh) — neighbors are
    addressed with dict MESH device ids (unnamed axes default to the
    caller's own coordinate), which Mosaic lowers via mesh strides."""
    import jax
    from jax.experimental.pallas import tpu as pltpu

    if n == 1:
        return                      # no neighbors; self-signal is noise
    me = jax.lax.axis_index(axis)
    left = jax.lax.rem(me - 1 + n, n)
    right = jax.lax.rem(me + 1, n)
    barrier = pltpu.get_barrier_semaphore()
    for nb in (left, right):
        if multi_axis:
            pltpu.semaphore_signal(barrier, inc=1, device_id={axis: nb},
                                   device_id_type=pltpu.DeviceIdType.MESH)
        else:
            pltpu.semaphore_signal(barrier, inc=1, device_id=nb,
                                   device_id_type=pltpu.DeviceIdType.LOGICAL)
    pltpu.semaphore_wait(barrier, 2)


def _make_step_dma(comm, send_sem, recv_sem, right, *, ack=None):
    """The correctness-critical slot protocol, shared by every ring
    kernel (``comm`` is the ``_slots`` accessor of the 2-slot comm
    buffer; the returned recv slot index is read as ``comm(rs)``):
    copy the outgoing block into the send slot, start the remote
    DMA into the right neighbor's recv slot, wait both semaphores (send
    drained + left neighbor's block arrived). Slots alternate by global
    step parity, so the slot being overwritten at step t is exactly the
    one whose send completed at t-1.

    ``ack`` (compiled path only) closes the protocol's skew hole: the
    2-slot parity argument tolerates ONE step of neighbor skew but is
    not self-enforcing — a rank running 2+ steps ahead (preemption, grid
    skew) would overwrite a slot its right neighbor has not consumed.
    ack = (ack_sem, left, wait_pred, signal_pred): before step t's DMA
    the sender waits one consumption ack from its RIGHT neighbor
    (certifying right finished step t-1: send drained + recv consumed),
    and after step t's rdma.wait it acks its LEFT neighbor. Acks flow
    left while data flows right, so there is no wait cycle within a
    step; wait_pred/signal_pred(t) -> bool | traced bool make the first
    step wait-free and the last step signal-free so the REGULAR
    semaphore drains to zero at kernel exit (grid kernels pass traced
    predicates spanning chunk boundaries). Ported from the fused ring
    attention kernel's consumer-ack throttle (fused_attention.py)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def step_dma(t: int, send_block_getter=None):
        send_slot = t % 2
        recv_slot = (t + 1) % 2
        if ack is not None:
            ack_sem, _left, wait_pred, _sig = ack
            _guarded(wait_pred(t),
                     lambda: pltpu.semaphore_wait(ack_sem, 1))
        if send_block_getter is not None:
            comm(send_slot)[...] = send_block_getter()
        rdma = pltpu.make_async_remote_copy(
            src_ref=comm(send_slot),
            dst_ref=comm(recv_slot),
            send_sem=send_sem.at[send_slot],
            recv_sem=recv_sem.at[recv_slot],
            device_id=right,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        rdma.start()
        rdma.wait()
        if ack is not None:
            ack_sem, left, _wait, sig_pred = ack
            _guarded(sig_pred(t), lambda: pltpu.semaphore_signal(
                ack_sem, inc=1, device_id=left,
                device_id_type=pltpu.DeviceIdType.LOGICAL))
        return recv_slot

    return step_dma


def _ack_boundary_signal(ack_sem, left, pred):
    """Cross-chunk consumer ack for the HBM grid kernels: emitted AFTER
    the chunk's final recv slot is consumed (the in-step signal fires
    inside step_dma before the caller's consumption, which would let the
    left neighbor's next-chunk step-0 DMA race the final staging copy —
    for odd steps-per-chunk the boundary write targets exactly that
    slot). sig_pred therefore statically suppresses the last in-step
    signal and this helper supplies the balancing one."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    pl.when(pred)(lambda: pltpu.semaphore_signal(
        ack_sem, inc=1, device_id=left,
        device_id_type=pltpu.DeviceIdType.LOGICAL))


def _ring_reduce_steps(work, comm, step_dma, *, n, me, acc, mode, t0=0):
    """The 2(n-1)-step reduce ring, shared by the VMEM and HBM kernels.

    reduce-scatter phase: with ring shift c, after n-1 steps rank me
    owns the fully-reduced block (me + 1 - c) % n. allreduce uses c=0
    (its allgather phase redistributes everything); reduce_scatter uses
    c=1 so each rank ends up owning ITS OWN block. Returns the next
    global step counter (slot parity continues across calls). ``work``
    is the ``_slots`` accessor of the n rank-blocks."""
    import jax

    shift = 1 if mode == "reduce_scatter" else 0
    t = t0
    for step in range(n - 1):
        send_i = jax.lax.rem(me - step - shift + n + n, n)
        recv_i = jax.lax.rem(me - step - 1 - shift + n + n, n)
        rs = step_dma(t, lambda i=send_i: work(i)[...])
        work(recv_i)[...] = acc(work(recv_i)[...], comm(rs)[...])
        t += 1
    if mode == "reduce_scatter":
        return t
    # allgather phase: circulate the reduced blocks
    for step in range(n - 1):
        send_i = jax.lax.rem(me + 1 - step + n + n, n)
        recv_i = jax.lax.rem(me - step + n + n, n)
        rs = step_dma(t, lambda i=send_i: work(i)[...])
        work(recv_i)[...] = comm(rs)[...]
        t += 1
    return t


def _ring_kernel(local_ref, out_ref, work_ref, comm_ref, send_sem,
                 recv_sem, ack_sem, *, n: int, blk: int, op, mode: str,
                 axis: str = "r", barrier: bool = False):
    """One kernel body for the three VMEM-resident ring collectives.

    mode:
      - "allreduce":      out (n*blk,) = reduced full vector
      - "reduce_scatter": out (blk,)   = my reduced block
      - "allgather":      out (n*blk,) = concatenated blocks
    """
    import jax

    me = jax.lax.axis_index(axis)
    right = jax.lax.rem(me + 1, n)
    left = jax.lax.rem(me - 1 + n, n)
    acc = _accum(op) if op is not None else None
    if barrier:
        _neighbor_barrier(n, axis)
    n_steps = 2 * (n - 1) if mode == "allreduce" else n - 1
    ack = (ack_sem, left, lambda t: t >= 1,
           lambda t: t <= n_steps - 2) if barrier else None
    comm = _slots(comm_ref, blk)
    step_dma = _make_step_dma(comm, send_sem, recv_sem, right, ack=ack)

    if mode == "allgather":
        out = _slots(out_ref, blk)
        out(me)[...] = local_ref[:]
        comm(0)[...] = local_ref[:]
        for t in range(n - 1):
            src_dev = jax.lax.rem(me - t - 1 + n + n, n)
            # the block to forward already sits in the send slot (it is
            # last step's recv slot) — no copy needed
            rs = step_dma(t)
            out(src_dev)[...] = comm(rs)[...]
        return

    # input refs are read-only: allreduce reduces in out_ref;
    # reduce_scatter in scratch
    work_ref = out_ref if mode == "allreduce" else work_ref
    work_ref[:] = local_ref[:]
    work = _slots(work_ref, blk)
    _ring_reduce_steps(work, comm, step_dma, n=n, me=me, acc=acc,
                       mode=mode)
    if mode == "reduce_scatter":
        out_ref[:] = work(me)[...]


def _all_rank_barrier(n: int, axis: str):
    """Entry barrier against EVERY rank (not just ring neighbors): the
    pairwise-exchange kernel DMAs to arbitrary partners, so any rank's
    remote write must not land before the target kernel instance owns
    its comm slots."""
    import jax
    from jax.experimental.pallas import tpu as pltpu

    if n == 1:
        return                      # no peers; self-signal is noise
    me = jax.lax.axis_index(axis)
    barrier = pltpu.get_barrier_semaphore()
    for d in range(1, n):
        peer = jax.lax.rem(me + d, n)
        pltpu.semaphore_signal(barrier, inc=1, device_id=peer,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
    pltpu.semaphore_wait(barrier, n - 1)


def _alltoall_kernel(local_ref, out_ref, comm_ref, send_sem, recv_sem, *,
                     n: int, blk: int, axis: str = "r",
                     barrier: bool = False):
    """Pairwise-exchange alltoall — the tl_mlx5 hardware-alltoall role
    (/root/reference/src/components/tl/mlx5/alltoall/): at step s every
    rank DMAs its block for rank (me+s) DIRECTLY to that rank (remote
    DMA takes any device_id, not just a ring neighbor) and receives the
    matching block from (me-s).

    Unlike the ring kernels, partners are arbitrary, so NO slot-parity
    skew argument applies. Safety comes from single-use resources
    instead: comm slot s and recv_sem s are written/signaled by exactly
    ONE sender (the step-s partner) and consumed exactly once — a peer
    running arbitrarily ahead writes its own unique slot, never one
    still in use. The entry barrier is against ALL ranks for the same
    reason."""
    import jax
    from jax.experimental.pallas import tpu as pltpu

    me = jax.lax.axis_index(axis)
    if barrier:
        _all_rank_barrier(n, axis)

    out, local, comm = (_slots(r, blk) for r in (out_ref, local_ref,
                                                 comm_ref))
    # my own block moves locally
    out(me)[...] = local(me)[...]
    for s in range(1, n):
        to = jax.lax.rem(me + s, n)
        frm = jax.lax.rem(me - s + n + n, n)
        comm(s - 1)[...] = local(to)[...]
        rdma = pltpu.make_async_remote_copy(
            src_ref=comm(s - 1),
            dst_ref=comm(n - 1 + s - 1),
            send_sem=send_sem.at[s - 1],
            recv_sem=recv_sem.at[s - 1],
            device_id=to,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        rdma.start()
        rdma.wait()
        out(frm)[...] = comm(n - 1 + s - 1)[...]


def _blocks_padded(x, nb: int, b0: int, b: int):
    """(nb * b0,) -> (nb * b,): pad each of nb blocks from b0 to b."""
    import jax.numpy as jnp
    if b == b0:
        return x
    return jnp.pad(x.reshape(nb, b0), ((0, 0), (0, b - b0))).reshape(-1)


def _blocks_unpadded(x, nb: int, b0: int, b: int):
    """Inverse of ``_blocks_padded``."""
    if b == b0:
        return x
    return x.reshape(nb, b)[:, :b0].reshape(-1)


def _build_vmem_kernel_program(mesh, kernel_fn, padded: int,
                               scratch_fn, collective_id: int, out_spec,
                               blocks=None):
    """Shared scaffold for the whole-vector VMEM kernels (bcast,
    alltoall): interpret probe, pad-to-padded, compiler params with the
    barrier gate, pallas_call, shard_map wrap. kernel_fn(barrier=...)
    returns the kernel partial; scratch_fn(dtype) the scratch list.
    ``blocks`` = (nb, b0, b): the launch shard is nb blocks of b0 and
    the kernel sees them padded to b (tile-aligned), in and out."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.sharding import PartitionSpec as P

    interpret = not is_tpu(mesh)
    cp = _compiler_params(collective_id, mesh.devices.size)
    kernel = kernel_fn(barrier=not interpret)

    def body(x):
        if x.size != padded:
            x = jnp.pad(x, (0, padded - x.size))
        if blocks is not None:
            x = _blocks_padded(x, *blocks)
        kw = {} if interpret else {"compiler_params": cp}
        out = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            scratch_shapes=scratch_fn(x.dtype),
            interpret=interpret,
            **kw,
        )(x)
        return out if blocks is None else _blocks_unpadded(out, *blocks)

    program = jax.jit(jax.shard_map(body, mesh=mesh,
                                    in_specs=P("r"),
                                    out_specs=out_spec, check_vma=False))
    return program, padded


def build_alltoall_program(mesh, n: int, nd, count: int):
    """shard_map-wrapped pairwise alltoall. count = per-rank total
    (n blocks). Returns (program, padded)."""
    from jax.experimental.pallas import tpu as pltpu
    from jax.sharding import PartitionSpec as P

    padded = _round_up(max(count, n), n)
    blk0 = padded // n
    blk = _round_up(blk0, _tile(nd))

    def scratch(dtype):
        # n==1 degenerates to the local block move; zero-sized VMEM /
        # semaphore arrays do not lower on real hardware, so keep the
        # (unused) scratch at minimum size 1
        return [
            # single-use slots: n-1 send + n-1 recv blocks, flat
            pltpu.VMEM((max(1, 2 * (n - 1)) * blk,), dtype),
            pltpu.SemaphoreType.DMA((max(1, n - 1),)),
            pltpu.SemaphoreType.DMA((max(1, n - 1),)),
        ]

    return _build_vmem_kernel_program(
        mesh,
        lambda barrier: functools.partial(_alltoall_kernel, n=n, blk=blk,
                                          barrier=barrier),
        padded, scratch, collective_id=3, out_spec=P("r"),
        blocks=(n, blk0, blk))


def _bcast_kernel(local_ref, out_ref, comm_ref, send_sem, recv_sem,
                  ack_sem, *, n: int, blk: int, nsub: int, root: int,
                  axis: str = "r", barrier: bool = False):
    """Ring-pipelined bcast — the tl/mlx5 mcast role
    (/root/reference/src/components/tl/mlx5/mcast/): the root streams
    ``nsub`` sub-blocks around the ring; every hop forwards sub-block s
    while receiving s+1, so the pipe is full after ``dist`` steps and the
    whole bcast takes nsub + n - 2 block-steps instead of nsub * (n-1).

    The step schedule is fully SYMMETRIC (every rank DMAs to its right
    neighbor every step, the wrap-around into the root carries ignored
    data) so each rdma.start/wait pairs exactly with the neighbors' —
    no asymmetric semaphore accounting. Rank at ring distance d from the
    root consumes sub-block s = t - (d - 1) at step t.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    me = jax.lax.axis_index(axis)
    right = jax.lax.rem(me + 1, n)
    left = jax.lax.rem(me - 1 + n, n)
    dist = jax.lax.rem(me - root + n, n)
    is_root = dist == 0
    if barrier:
        _neighbor_barrier(n, axis)

    @pl.when(is_root)
    def _():
        out_ref[:] = local_ref[:]

    comm, local, out = (_slots(r, blk) for r in (comm_ref, local_ref,
                                                 out_ref))
    n_steps = nsub + n - 2
    for t in range(n_steps):
        send_slot = t % 2
        recv_slot = (t + 1) % 2
        if barrier and t >= 1:
            # consumer-ack throttle (see _make_step_dma): my step-t DMA
            # overwrites the slot my right neighbor consumed at t-1
            pltpu.semaphore_wait(ack_sem, 1)

        @pl.when(is_root)
        def _(t=t, s=send_slot):
            sub = min(t, nsub - 1)     # static: clamp past-end sends
            comm(s)[...] = local(sub)[...]

        rdma = pltpu.make_async_remote_copy(
            src_ref=comm(send_slot),
            dst_ref=comm(recv_slot),
            send_sem=send_sem.at[send_slot],
            recv_sem=recv_sem.at[recv_slot],
            device_id=right,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        rdma.start()
        rdma.wait()
        if barrier and t <= n_steps - 2:
            # signals balance the waits; drains to zero at kernel exit
            pltpu.semaphore_signal(
                ack_sem, inc=1, device_id=left,
                device_id_type=pltpu.DeviceIdType.LOGICAL)

        s_idx = t - (dist - 1)         # traced: per-rank arrival index
        valid = jnp.logical_and(dist > 0,
                                jnp.logical_and(s_idx >= 0,
                                                s_idx < nsub))
        s_clamped = jnp.clip(s_idx, 0, nsub - 1)

        @pl.when(valid)
        def _(rs=recv_slot, s=s_clamped):
            out(s)[...] = comm(rs)[...]


def _hbm_bcast_kernel(local_ref, out_ref, comm_ref, stage_ref, fetch_sem,
                      self_sem, flush_sem, send_sem, recv_sem, ack_sem, *,
                      n: int, blk: int, nsub: int, axis: str = "r",
                      root: int = 0, barrier: bool = False):
    """HBM-resident ring-pipelined bcast (lifts the VMEM cap of
    ``_bcast_kernel`` — round-3 verdict missing #4; the tl/mlx5 mcast
    role streams arbitrary sizes, /root/reference/src/components/tl/
    mlx5/mcast/): local/out live in HBM (``pl.ANY``); the root stages
    each sub-block HBM->VMEM into the send slot, every hop forwards
    sub-block s while receiving s+1, and consumers drain arriving
    blocks through a double-buffered VMEM staging pair with async
    VMEM->HBM flushes overlapping the ring.

    Grid = one program instance per TWO ring steps: slot parity is
    (global step % 2), so pairing steps keeps every comm-slot,
    semaphore and stage index STATIC (traced semaphore indices do not
    lower); the builder pads ``nsub`` so the step count is even. The
    step schedule is the same symmetric one as the VMEM kernel (every
    rank DMAs every step; wrap-around into the root carries ignored
    data), and the consumer-ack throttle spans grid steps unchanged —
    grid instances run sequentially on the core, so the one-step-skew
    argument is identical to the single-call kernel's."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    g = pl.program_id(0)
    n_steps = nsub + n - 2                 # even by construction
    me = jax.lax.axis_index(axis)
    right = jax.lax.rem(me + 1, n)
    left = jax.lax.rem(me - 1 + n, n)
    dist = jax.lax.rem(me - root + n, n)
    is_root = dist == 0

    if barrier:
        @pl.when(g == 0)
        def _():
            _neighbor_barrier(n, axis)

    # the root's own output: one whole-vector HBM->HBM copy spanning the
    # grid (started at step 0, drained in the epilogue)
    self_copy = pltpu.make_async_copy(local_ref, out_ref, self_sem)
    comm, stage, local, out = (_slots(r, blk) for r in (
        comm_ref, stage_ref, local_ref, out_ref))

    @pl.when(jnp.logical_and(is_root, g == 0))
    def _():
        self_copy.start()

    def valid_at(t):
        s_idx = t - (dist - 1)
        return jnp.logical_and(
            dist > 0, jnp.logical_and(s_idx >= 0, s_idx < nsub))

    def flush_at(t, slot):
        s = jnp.clip(t - (dist - 1), 0, nsub - 1)
        return pltpu.make_async_copy(
            stage(slot), out(s),
            flush_sem.at[slot])

    # the consumer-ack throttle rides _make_step_dma unchanged (the
    # protocol's single home): grid steps pair ring steps, so the t the
    # helper sees is the STATIC sub-step index (slot parity source) and
    # the predicates close over g for the traced cross-grid conditions.
    # Ack waits cover global steps 1..n_steps-1 (sub_i==0 waits iff
    # g>0), signals cover 0..n_steps-2 (sub_i==1 signals iff another
    # grid step follows) — identical accounting to the VMEM kernel's.
    ack = (ack_sem, left,
           lambda si: True if si == 1 else (g > 0),
           lambda si: True if si == 0 else (g + 1 < n_steps // 2)) \
        if barrier and n > 1 else None
    step_dma = _make_step_dma(comm, send_sem, recv_sem, right,
                              ack=ack)

    for sub_i in (0, 1):
        t = 2 * g + sub_i                  # traced global ring step

        # the root stages sub-block min(t, nsub-1) into the send slot
        # (clamped past-end sends keep the schedule symmetric) BEFORE
        # the step: the slot held step t-1's wrap-around data, drained
        # by that step's rdma.wait, and the staging is local — it does
        # not need the ack gate (which orders only the remote DMA)
        sub = jnp.clip(t, 0, nsub - 1)
        fetch = pltpu.make_async_copy(
            local(sub), comm(sub_i), fetch_sem)

        @pl.when(is_root)
        def _(fetch=fetch):
            fetch.start()
            fetch.wait()

        rs = step_dma(sub_i)

        # consumer: drain the flush issued 2 steps ago from this stage
        # slot, then sync-consume the recv slot and flush it onward
        @pl.when(valid_at(t - 2))
        def _(t=t, slot=sub_i):
            flush_at(t - 2, slot).wait()

        @pl.when(valid_at(t))
        def _(t=t, slot=sub_i, rs=rs):
            stage(slot)[...] = comm(rs)[...]
            flush_at(t, slot).start()

    # epilogue: drain the last two flushes + the root's self copy
    @pl.when(g + 1 >= n_steps // 2)
    def _():
        t_last = n_steps - 1

        @pl.when(valid_at(t_last - 1))
        def _():
            flush_at(t_last - 1, 0).wait()

        @pl.when(valid_at(t_last))
        def _():
            flush_at(t_last, 1).wait()

        @pl.when(is_root)
        def _():
            self_copy.wait()


def _sem_wait_when(pred, sem, count: int = 1):
    """_guarded semaphore wait (accepts static True/False preds)."""
    from jax.experimental.pallas import tpu as pltpu

    _guarded(pred, lambda: pltpu.semaphore_wait(sem, count))


def _sem_signal_when(pred, sem, device):
    """_guarded remote semaphore signal (accepts static preds)."""
    from jax.experimental.pallas import tpu as pltpu

    _guarded(pred, lambda: pltpu.semaphore_signal(
        sem, inc=1, device_id=device,
        device_id_type=pltpu.DeviceIdType.LOGICAL))


def build_hbm_bcast_program(mesh, n: int, root: int, nd, count: int):
    """shard_map-wrapped HBM-resident pipelined ring bcast (no element
    cap beyond HBM). Returns (jitted program, padded per-rank count)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from jax.sharding import PartitionSpec as P

    interpret = not is_tpu(mesh)

    t = _tile(nd)
    blk = min(_round_up(max(count, 1), t), max(t, CHUNK_ELEMS // 2 // t * t))
    padded = _round_up(max(count, 1), blk)
    nsub = padded // blk
    if (nsub + n - 2) % 2:
        # the grid pairs ring steps (static slot parity): pad one extra
        # sub-block so the step count is even; the surplus block carries
        # padding and lands in the out padding region
        nsub += 1
        padded = nsub * blk
    n_steps = nsub + n - 2

    cp = _compiler_params(6, n)
    kernel = functools.partial(
        _hbm_bcast_kernel, n=n, blk=blk, nsub=nsub, root=root,
        barrier=not interpret)

    def body(x):
        if x.size != padded:
            x = jnp.pad(x, (0, padded - x.size))
        kw = {} if interpret else {"compiler_params": cp}
        return pl.pallas_call(
            kernel,
            grid=(n_steps // 2,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            out_shape=jax.ShapeDtypeStruct((padded,), x.dtype),
            scratch_shapes=[
                pltpu.VMEM((2 * blk,), x.dtype),      # ring comm slots
                pltpu.VMEM((2 * blk,), x.dtype),      # flush staging
                pltpu.SemaphoreType.DMA,              # root fetch
                pltpu.SemaphoreType.DMA,              # root self copy
                pltpu.SemaphoreType.DMA((2,)),        # flush (per slot)
                pltpu.SemaphoreType.DMA((2,)),        # ring send
                pltpu.SemaphoreType.DMA((2,)),        # ring recv
                pltpu.SemaphoreType.REGULAR,          # consumption acks
            ],
            interpret=interpret,
            **kw,
        )(x)

    program = jax.jit(jax.shard_map(body, mesh=mesh,
                                    in_specs=P("r"),
                                    out_specs=P(None), check_vma=False))
    return program, padded


def _hbm_alltoall_kernel(local_ref, out_ref, comm_ref, fetch_sem,
                         self_sem, flush_sem, send_sem, recv_sem,
                         ack_sem, *, n: int, cblk: int, n_chunks: int,
                         axis: str = "r", barrier: bool = False):
    """HBM-resident pairwise-exchange alltoall (lifts the VMEM cap of
    ``_alltoall_kernel`` — round-3 verdict missing #4): per-partner
    blocks of ``blk_tot`` live in HBM; grid step g exchanges the SAME
    ``cblk``-sized sub-range of every block through single-use VMEM
    slots, staging each outgoing piece HBM->VMEM and draining each
    arriving piece VMEM->HBM before reuse.

    Within a chunk the safety story is the VMEM kernel's: slot s and
    its semaphores have exactly ONE writer. ACROSS chunks the slots are
    reused, so chunk g > 0 opens by waiting n-1 consumption acks — one
    from every partner, each sent only after that partner drained my
    chunk g-1 block from its recv slot to HBM. A partner racing ahead
    can therefore never overwrite an undrained slot; its early
    recv_sem signals are just counts my next rdma.wait consumes.

    The staging PIPELINES around the ICI transfers: step s+1's
    HBM->VMEM fetch is started before step s's remote DMA (it rides
    behind the ICI), and step s's VMEM->HBM flush drains one step later
    (behind step s+1's work) — fetch/flush semaphores alternate 2-slot
    parity, and the ack to step s's writer is emitted only after that
    flush's completion is observed at s+1 (the ack licenses the slot's
    next-chunk reuse, so it must trail the drain)."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    g = pl.program_id(0)
    me = jax.lax.axis_index(axis)

    if barrier:
        @pl.when(g == 0)
        def _():
            _all_rank_barrier(n, axis)

    # chunk g of partner p's block, in HBM; single-use comm slots
    local, out, comm = (_slots(r, cblk) for r in (local_ref, out_ref,
                                                  comm_ref))

    def piece(p):
        return p * n_chunks + g

    # my own block: per-chunk HBM->HBM copy overlapping the exchanges
    self_copy = pltpu.make_async_copy(local(piece(me)), out(piece(me)),
                                      self_sem)
    self_copy.start()

    if barrier and n > 1:
        _sem_wait_when(g > 0, ack_sem, n - 1)

    def fetch(s):
        to = jax.lax.rem(me + s, n)
        return pltpu.make_async_copy(local(piece(to)), comm(s - 1),
                                     fetch_sem.at[(s - 1) % 2])

    def flush(s):
        frm = jax.lax.rem(me - s + n + n, n)
        return pltpu.make_async_copy(comm(n - 1 + s - 1), out(piece(frm)),
                                     flush_sem.at[(s - 1) % 2])

    def ack(s):
        frm = jax.lax.rem(me - s + n + n, n)
        _sem_signal_when(g + 1 < n_chunks, ack_sem, frm)

    if n > 1:
        fetch(1).start()
    for s in range(1, n):
        fetch(s).wait()
        if s + 1 < n:
            fetch(s + 1).start()       # rides behind this step's ICI
        to = jax.lax.rem(me + s, n)
        rdma = pltpu.make_async_remote_copy(
            src_ref=comm(s - 1),
            dst_ref=comm(n - 1 + s - 1),
            send_sem=send_sem.at[s - 1],
            recv_sem=recv_sem.at[s - 1],
            device_id=to,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        rdma.start()
        rdma.wait()
        flush(s).start()
        if s >= 2:
            # drain the PREVIOUS step's flush behind this one, then ack
            # its writer (single-use slots: nothing in chunk g rereads
            # the slot, the ack only licenses next-chunk reuse)
            flush(s - 1).wait()
            if barrier and n > 1:
                ack(s - 1)
    if n > 1:
        flush(n - 1).wait()
        if barrier and n > 1:
            ack(n - 1)

    self_copy.wait()


def build_hbm_alltoall_program(mesh, n: int, nd, count: int):
    """shard_map-wrapped HBM-resident chunked pairwise alltoall.
    count = per-rank total (n blocks). Returns (jitted program, padded
    per-rank launch count)."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from jax.sharding import PartitionSpec as P

    interpret = not is_tpu(mesh)

    padded0 = max(count, n)
    if padded0 % n:
        padded0 += n - padded0 % n
    blk0 = padded0 // n
    t = _tile(nd)
    # comm slots hold 2(n-1) sub-blocks: bound the total by CHUNK_ELEMS
    cblk = min(_round_up(blk0, t),
               max(t, CHUNK_ELEMS // max(1, 2 * (n - 1)) // t * t))
    blk_tot = _round_up(blk0, cblk)
    n_chunks = blk_tot // cblk

    # collective_id 9: 7/8 belong to the fused attention kernels
    # (fused_attention._build) — a shared id would key one global
    # barrier semaphore across overlapping dispatches of DIFFERENT
    # kernels, letting one kernel's barrier signals satisfy the other's
    cp = _compiler_params(9, n)
    kernel = functools.partial(
        _hbm_alltoall_kernel, n=n, cblk=cblk, n_chunks=n_chunks,
        barrier=not interpret)

    def body(x):
        # the launch path END-pads the flat shard to padded0; the kernel
        # wants n partner-blocks of blk_tot — re-pad PER BLOCK so block
        # boundaries stay aligned, and slice the same layout back out
        x = _blocks_padded(x[:padded0], n, blk0, blk_tot)
        kw = {} if interpret else {"compiler_params": cp}
        out = pl.pallas_call(
            kernel,
            grid=(n_chunks,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            out_shape=jax.ShapeDtypeStruct((n * blk_tot,), x.dtype),
            scratch_shapes=[
                pltpu.VMEM((max(1, 2 * (n - 1)) * cblk,), x.dtype),
                pltpu.SemaphoreType.DMA((2,)),        # fetch (pipelined)
                pltpu.SemaphoreType.DMA,              # my-block copy
                pltpu.SemaphoreType.DMA((2,)),        # flush (pipelined)
                pltpu.SemaphoreType.DMA((max(1, n - 1),)),   # send
                pltpu.SemaphoreType.DMA((max(1, n - 1),)),   # recv
                pltpu.SemaphoreType.REGULAR,          # consumption acks
            ],
            interpret=interpret,
            **kw,
        )(x)
        return _blocks_unpadded(out, n, blk0, blk_tot)

    program = jax.jit(jax.shard_map(body, mesh=mesh,
                                    in_specs=P("r"),
                                    out_specs=P("r"), check_vma=False))
    return program, padded0


def _hbm_chunk_schedule(g, n_chunks, fetch_copies, flush_copy, ring_pass):
    """The shared double-buffer schedule of the HBM-resident grid
    kernels (allreduce, reduce_scatter): stage chunk g into a VMEM work
    slot, run the ring pass, flush the result back — with chunk g+1's
    HBM->VMEM fetch started BEFORE g's ring pass so the local DMA
    overlaps the remote ones (double buffering written into the kernel
    schedule, not left to XLA).

    ``fetch_copies(chunk, slot)`` / ``flush_copy(chunk, slot)`` return
    the (lists of) async-copy objects for staging chunk->work[slot] and
    work[slot]->out; reconstructing the same copy is how a start is
    waited later. ``ring_pass(slot)`` runs the ring steps in-place on
    work[slot]. Drain invariants owned here: a work slot is never
    prefetch-overwritten while its flush is in flight, and at most one
    write-back is outstanding (the two flush slots never alias)."""
    import jax
    from jax.experimental import pallas as pl

    buf = jax.lax.rem(g, 2)
    nxt = jax.lax.rem(g + 1, 2)

    @pl.when(g == 0)
    def _():
        # prologue: blocking fetch of chunk 0
        for c in fetch_copies(0, 0):
            c.start()
        for c in fetch_copies(0, 0):
            c.wait()

    @pl.when(jax.numpy.logical_and(g > 0, g + 1 < n_chunks))
    def _():
        # work[nxt] is about to be prefetch-overwritten, but chunk g-1's
        # FLUSH still reads from it — drain that flush first (the race
        # is invisible in interpret mode, where DMAs are synchronous)
        flush_copy(g - 1, nxt).wait()

    @pl.when(g + 1 < n_chunks)
    def _():
        # prefetch chunk g+1 while this chunk's ring runs
        for c in fetch_copies(g + 1, nxt):
            c.start()

    ring_pass(buf)

    # drain the previous flush when no prefetch did it (final chunk)
    @pl.when(jax.numpy.logical_and(g > 0, g + 1 >= n_chunks))
    def _():
        flush_copy(g - 1, nxt).wait()

    flush = flush_copy(g, buf)
    flush.start()

    @pl.when(g + 1 >= n_chunks)
    def _():
        flush.wait()                   # epilogue: drain the last flush

    @pl.when(g + 1 < n_chunks)
    def _():
        # the next grid step reads work[nxt]: its fetch must land
        for c in fetch_copies(g + 1, nxt):
            c.wait()


def _hbm_allreduce_kernel(local_ref, out_ref, work_ref, comm_ref,
                          fetch_sem, flush_sem, send_sem, recv_sem,
                          ack_sem, *, n: int, blk: int, n_chunks: int,
                          op, axis: str = "r", barrier: bool = False):
    """HBM-resident ring allreduce, one grid step per chunk (the
    sliding-window role, allreduce_sliding_window.h:30-50): the full
    vector never leaves HBM; the _hbm_chunk_schedule double buffering
    stages each chunk through VMEM around the 2(n-1)-step ring pass.

    Slot safety across chunks: each chunk runs exactly 2(n-1) ring steps
    (even), so the 2-slot parity restarts aligned at every chunk boundary
    and the one-step-skew argument holds across the whole grid.
    """
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    g = pl.program_id(0)
    csize = n * blk                    # chunk elements (rank-blocked)

    if barrier:
        @pl.when(g == 0)
        def _():
            _neighbor_barrier(n, axis)

    local, out, work = (_slots(r, csize) for r in (local_ref, out_ref,
                                                   work_ref))

    def fetch_copies(chunk, slot):
        return [pltpu.make_async_copy(local(chunk), work(slot),
                                      fetch_sem.at[slot])]

    def flush_copy(chunk, slot):
        return pltpu.make_async_copy(work(slot), out(chunk),
                                     flush_sem.at[slot])

    acc = _accum(op)
    me = jax.lax.axis_index(axis)
    right = jax.lax.rem(me + 1, n)
    left = jax.lax.rem(me - 1 + n, n)
    # the ack throttle spans CHUNK boundaries (a rank racing into chunk
    # g+1 step 0 overwrites a slot its right neighbor is still on in
    # chunk g): chunk step 0 waits only past the first chunk, the last
    # in-step signal is statically suppressed, and the balancing
    # cross-chunk signal is emitted after the final recv consumption
    # (_ack_boundary_signal) — counts balance, semaphore drains to zero
    n_steps = 2 * (n - 1)
    ack = (ack_sem, left,
           lambda t: True if t >= 1 else (g > 0),
           lambda t: t <= n_steps - 2) if barrier else None
    comm = _slots(comm_ref, blk)
    step_dma = _make_step_dma(comm, send_sem, recv_sem, right, ack=ack)

    def ring_pass(slot):
        _ring_reduce_steps(_slots(work(slot), blk), comm, step_dma, n=n,
                           me=me, acc=acc, mode="allreduce")
        if ack is not None and n > 1:
            _ack_boundary_signal(ack_sem, left, g + 1 < n_chunks)

    _hbm_chunk_schedule(g, n_chunks, fetch_copies, flush_copy, ring_pass)


def build_hbm_allreduce_program(mesh, n: int, op, nd, count: int):
    """shard_map-wrapped HBM-resident chunked ring allreduce.
    Returns (jitted program, padded per-rank count)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from jax.sharding import PartitionSpec as P

    interpret = not is_tpu(mesh)

    csize = _vmem_pass_elems(n, _tile(nd))     # chunk elems, n tiles
    padded = _round_up(max(count, 1), csize)
    n_chunks = padded // csize
    blk = csize // n

    cp = _compiler_params(1, n)
    kernel = functools.partial(
        _hbm_allreduce_kernel, n=n, blk=blk, n_chunks=n_chunks, op=op,
        barrier=not interpret)

    def body(x):
        if x.size != padded:
            x = jnp.pad(x, (0, padded - x.size))
        kw = {} if interpret else {"compiler_params": cp}
        out = pl.pallas_call(
            kernel,
            grid=(n_chunks,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            out_shape=jax.ShapeDtypeStruct((padded,), x.dtype),
            scratch_shapes=[
                pltpu.VMEM((2 * csize,), x.dtype),    # work (dbl-buffered)
                pltpu.VMEM((2 * blk,), x.dtype),      # ring comm slots
                pltpu.SemaphoreType.DMA((2,)),        # fetch
                pltpu.SemaphoreType.DMA((2,)),        # flush
                pltpu.SemaphoreType.DMA((2,)),        # ring send
                pltpu.SemaphoreType.DMA((2,)),        # ring recv
                pltpu.SemaphoreType.REGULAR,          # consumption acks
            ],
            interpret=interpret,
            **kw,
        )(x)
        if op == ReductionOp.AVG:
            out = (out / n).astype(out.dtype)
        return out

    program = jax.jit(jax.shard_map(body, mesh=mesh,
                                    in_specs=P("r"),
                                    out_specs=P("r"), check_vma=False))
    return program, padded


def _hbm_allgather_kernel(local_ref, out_ref, comm_ref, stage_ref,
                          fetch_sem, myout_sem, flush_sem, send_sem,
                          recv_sem, ack_sem, *, n: int, csize: int,
                          n_chunks: int, axis: str = "r",
                          barrier: bool = False):
    """HBM-resident ring allgather, one grid step per chunk of the LOCAL
    block (no element cap beyond HBM): chunk g of every rank's block
    circulates the ring in n-1 remote-DMA steps; each arriving block is
    consumed with a SYNCHRONOUS copy into a dedicated staging buffer
    (the same consumption semantics the VMEM ring kernel's out_ref store
    has — an async read of the comm slot would race the upstream
    neighbor's next write into it, which no local drain can order), then
    flushed staging->HBM while the ring keeps moving.

    Slot parity restarts at 0 every chunk on EVERY rank — neighbors only
    need to AGREE on the slot schedule, so a uniform restart is safe for
    any n (no even-step requirement like the allreduce kernel). The
    staging buffer is purely local (no remote writes land in it): its
    reuse drain below is complete protection for the async flushes.
    """
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    g = pl.program_id(0)
    me = jax.lax.axis_index(axis)
    right = jax.lax.rem(me + 1, n)

    if barrier:
        @pl.when(g == 0)
        def _():
            _neighbor_barrier(n, axis)

    local, out, comm, stage = (_slots(r, csize) for r in (
        local_ref, out_ref, comm_ref, stage_ref))

    def src_dev(s):
        return jax.lax.rem(me - s - 1 + n + n, n)

    def flush_copy(slot, s):
        # chunk g of src_dev(s)'s block (blocks are padded = n_chunks
        # chunks long)
        return pltpu.make_async_copy(
            stage(slot), out(src_dev(s) * n_chunks + g), flush_sem.at[slot])

    # stage my chunk into this chunk's first send slot, and start my own
    # block's HBM->HBM copy into the output (overlaps the whole ring)
    fetch = pltpu.make_async_copy(local(g), comm(0), fetch_sem)
    fetch.start()
    myout = pltpu.make_async_copy(local(g), out(me * n_chunks + g),
                                  myout_sem)
    myout.start()
    fetch.wait()

    left = jax.lax.rem(me - 1 + n, n)
    ack = (ack_sem, left,
           lambda t: True if t >= 1 else (g > 0),
           lambda t: t <= n - 3) if barrier else None
    step_dma = _make_step_dma(comm, send_sem, recv_sem, right, ack=ack)
    for s in range(n - 1):
        # the block to forward already sits in the send slot (it is last
        # step's recv slot); s == 0 sends the fetched slot 0
        rs = step_dma(s)
        f = s % 2
        if s >= 2:
            # staging slot f is still the source of the flush issued at
            # s-2 — drain it before the synchronous overwrite below
            flush_copy(f, s - 2).wait()
        stage(f)[...] = comm(rs)[...]      # sync consume of the recv slot
        if ack is not None and s == n - 2:
            # cross-chunk ack only AFTER the final recv is staged (see
            # _ack_boundary_signal: the in-step signal would race the
            # left neighbor's next-chunk step-0 write into this slot)
            _ack_boundary_signal(ack_sem, left, g + 1 < n_chunks)
        flush_copy(f, s).start()

    # chunk boundary: drain every outstanding flush (issued at the last
    # one or two steps) + my own block's copy, so the next chunk starts
    # with the staging and output regions quiescent
    for s in range(max(0, n - 3), n - 1):
        flush_copy(s % 2, s).wait()
    myout.wait()


def build_hbm_allgather_program(mesh, n: int, nd, count: int):
    """shard_map-wrapped HBM-resident chunked ring allgather. count =
    per-rank block elements. Returns (jitted program, padded per-rank
    count); global out is (n * padded,), replicated."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from jax.sharding import PartitionSpec as P

    interpret = not is_tpu(mesh)

    count0 = max(count, 1)
    t = _tile(nd)
    csize = min(_round_up(count0, t), max(t, CHUNK_ELEMS // t * t))
    padded = _round_up(count0, csize)
    n_chunks = padded // csize

    cp = _compiler_params(4, n)
    kernel = functools.partial(
        _hbm_allgather_kernel, n=n, csize=csize, n_chunks=n_chunks,
        barrier=not interpret)

    def body(x):
        # the launch path END-pads the per-rank shard to `padded`; the
        # kernel circulates whole padded blocks, so the gathered output
        # has padding interleaved per block — sliced off below
        if x.size != padded:
            x = jnp.pad(x, (0, padded - x.size))
        kw = {} if interpret else {"compiler_params": cp}
        out = pl.pallas_call(
            kernel,
            grid=(n_chunks,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            out_shape=jax.ShapeDtypeStruct((n * padded,), x.dtype),
            scratch_shapes=[
                pltpu.VMEM((2 * csize,), x.dtype),    # ring comm slots
                pltpu.VMEM((2 * csize,), x.dtype),    # flush staging
                pltpu.SemaphoreType.DMA,              # fetch
                pltpu.SemaphoreType.DMA,              # my-block copy
                pltpu.SemaphoreType.DMA((2,)),        # flush (per slot)
                pltpu.SemaphoreType.DMA((2,)),        # ring send
                pltpu.SemaphoreType.DMA((2,)),        # ring recv
                pltpu.SemaphoreType.REGULAR,          # consumption acks
            ],
            interpret=interpret,
            **kw,
        )(x)
        if padded != count0:
            out = out.reshape(n, padded)[:, :count0].reshape(-1)
        return out

    program = jax.jit(jax.shard_map(body, mesh=mesh,
                                    in_specs=P("r"),
                                    out_specs=P(None), check_vma=False))
    return program, padded


def _hbm_reduce_scatter_kernel(local_ref, out_ref, work_ref, comm_ref,
                               fetch_sem, flush_sem, send_sem, recv_sem,
                               ack_sem, *, n: int, cblk: int,
                               n_chunks: int, op,
                               axis: str = "r", barrier: bool = False):
    """HBM-resident ring reduce_scatter (no element cap beyond HBM):
    the per-rank input is n rank-blocks of ``blk_tot``; grid step g
    covers the SAME ``cblk``-sized sub-range of every rank-block (a
    valid smaller reduce_scatter), staged into VMEM with n strided
    fetches, reduced around the ring in n-1 steps, and the owned block
    flushed back — with chunk g+1's fetches started before g's ring
    pass (double buffering, mirroring the HBM allreduce kernel).

    Slot parity restarts per chunk uniformly (see the allgather kernel's
    note: neighbors only need to agree on the schedule)."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    g = pl.program_id(0)
    me = jax.lax.axis_index(axis)
    right = jax.lax.rem(me + 1, n)

    if barrier:
        @pl.when(g == 0)
        def _():
            _neighbor_barrier(n, axis)

    local, out = _slots(local_ref, cblk), _slots(out_ref, cblk)
    work = _slots(work_ref, n * cblk)

    def fetch_copies(chunk, slot):
        # strided: the same cblk sub-range of each of the n rank-blocks
        # (blocks are blk_tot = n_chunks chunks long)
        return [pltpu.make_async_copy(
            local(i * n_chunks + chunk), _slots(work(slot), cblk)(i),
            fetch_sem.at[slot]) for i in range(n)]

    def flush_copy(chunk, slot):
        # only my owned block of the chunk flushes back
        return pltpu.make_async_copy(
            _slots(work(slot), cblk)(me), out(chunk), flush_sem.at[slot])

    acc = _accum(op)
    left = jax.lax.rem(me - 1 + n, n)
    ack = (ack_sem, left,
           lambda t: True if t >= 1 else (g > 0),
           lambda t: t <= n - 3) if barrier else None
    comm = _slots(comm_ref, cblk)
    step_dma = _make_step_dma(comm, send_sem, recv_sem, right, ack=ack)

    def ring_pass(slot):
        _ring_reduce_steps(_slots(work(slot), cblk), comm, step_dma, n=n,
                           me=me, acc=acc, mode="reduce_scatter")
        if ack is not None and n > 1:
            # cross-chunk ack AFTER the final recv's accumulate inside
            # _ring_reduce_steps (see _ack_boundary_signal)
            _ack_boundary_signal(ack_sem, left, g + 1 < n_chunks)

    _hbm_chunk_schedule(g, n_chunks, fetch_copies, flush_copy, ring_pass)


def build_hbm_reduce_scatter_program(mesh, n: int, op, nd, count: int):
    """shard_map-wrapped HBM-resident chunked ring reduce_scatter.
    count = per-rank TOTAL input elements (n rank-blocks). Returns
    (jitted program, padded per-rank count)."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from jax.sharding import PartitionSpec as P

    interpret = not is_tpu(mesh)

    count0 = max(count, 1)
    blk0 = max(count0 // n, 1)         # caller enforces count % n == 0
    t = _tile(nd)
    cblk = min(_round_up(blk0, t), max(t, CHUNK_ELEMS // n // t * t))
    blk_tot = _round_up(blk0, cblk)
    n_chunks = blk_tot // cblk
    padded = n * blk_tot

    cp = _compiler_params(5, n)
    kernel = functools.partial(
        _hbm_reduce_scatter_kernel, n=n, cblk=cblk, n_chunks=n_chunks,
        op=op, barrier=not interpret)

    def body(x):
        # the launch path END-pads the flat (n * blk0) shard; the kernel
        # wants n rank-blocks of blk_tot — re-pad PER BLOCK so block
        # boundaries stay aligned
        x = _blocks_padded(x[:n * blk0], n, blk0, blk_tot)
        kw = {} if interpret else {"compiler_params": cp}
        out = pl.pallas_call(
            kernel,
            grid=(n_chunks,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            out_shape=jax.ShapeDtypeStruct((blk_tot,), x.dtype),
            scratch_shapes=[
                pltpu.VMEM((2 * n * cblk,), x.dtype),  # work (dbl-buffered)
                pltpu.VMEM((2 * cblk,), x.dtype),     # ring comm slots
                pltpu.SemaphoreType.DMA((2,)),        # fetch
                pltpu.SemaphoreType.DMA((2,)),        # flush
                pltpu.SemaphoreType.DMA((2,)),        # ring send
                pltpu.SemaphoreType.DMA((2,)),        # ring recv
                pltpu.SemaphoreType.REGULAR,          # consumption acks
            ],
            interpret=interpret,
            **kw,
        )(x)
        if op == ReductionOp.AVG:
            out = (out / n).astype(out.dtype)
        return out

    program = jax.jit(jax.shard_map(body, mesh=mesh,
                                    in_specs=P("r"),
                                    out_specs=P("r"), check_vma=False))
    return program, padded


def build_bcast_program(mesh, n: int, root: int, nd, count: int):
    """shard_map-wrapped pipelined ring bcast. Returns (program, padded)."""
    from jax.experimental.pallas import tpu as pltpu
    from jax.sharding import PartitionSpec as P

    t = _tile(nd)
    # sub-block size: small messages go whole (1 sub-block); large ones
    # pipeline in VMEM-bounded pieces
    blk = min(_round_up(max(count, 1), t), max(t, CHUNK_ELEMS // 2 // t * t))
    padded = _round_up(max(count, 1), blk)
    nsub = padded // blk

    def scratch(dtype):
        return [
            pltpu.VMEM((2 * blk,), dtype),     # 2 comm slots, flat
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR,       # consumption acks
        ]

    return _build_vmem_kernel_program(
        mesh,
        lambda barrier: functools.partial(_bcast_kernel, n=n, blk=blk,
                                          nsub=nsub, root=root,
                                          barrier=barrier),
        padded, scratch, collective_id=2, out_spec=P(None))


def build_ring_program(mesh, n: int, coll: CollType, op, nd, count: int):
    """shard_map-wrapped pallas_call for one (coll, count) instance.
    Returns (jitted program, padded per-rank launch count). Blocks are
    padded to whole tiles: at the tail for allreduce (elementwise), per
    rank-block for reduce_scatter and allgather (sliced off again)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from jax.sharding import PartitionSpec as P

    interpret = not is_tpu(mesh)
    t = _tile(nd)
    count0 = max(count, 1)
    if coll == CollType.ALLGATHER:
        mode, out_specs = "allgather", P(None)
        padded, blk0 = count0, count0
        blk = _round_up(blk0, t)
        out_elems = n * blk
    elif coll == CollType.ALLREDUCE:
        mode, out_specs = "allreduce", P("r")
        padded = _round_up(count0, n * t)
        blk0 = blk = padded // n
        out_elems = padded
    else:                       # caller enforces count % n == 0
        mode, out_specs = "reduce_scatter", P("r")
        padded, blk0 = count0, count0 // n
        blk = _round_up(blk0, t)
        out_elems = blk
    cp = _compiler_params(0, n)
    kernel = functools.partial(_ring_kernel, n=n, blk=blk, op=op,
                               mode=mode, barrier=not interpret)
    work_elems = n * blk if mode == "reduce_scatter" else t

    # counts beyond one VMEM pass never reach this builder: the task
    # routes them to the HBM-resident grid kernels
    # (build_hbm_{allreduce,allgather,reduce_scatter}_program), which
    # keep the vector in HBM and double-buffer the staging inside the
    # kernel schedule instead of unrolling pallas_calls
    def body(x):
        if mode == "allgather":
            x = jnp.pad(x, (0, blk - x.size))
        elif mode == "allreduce":
            x = jnp.pad(x, (0, padded - x.size))
        elif blk != blk0:
            x = jnp.pad(x.reshape(n, blk0),
                        ((0, 0), (0, blk - blk0))).reshape(-1)
        kw = {} if interpret else {"compiler_params": cp}
        out = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((out_elems,), x.dtype),
            scratch_shapes=[
                pltpu.VMEM((work_elems,), x.dtype),
                pltpu.VMEM((2 * blk,), x.dtype),   # 2 comm slots, flat
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.REGULAR,       # consumption acks
            ],
            interpret=interpret,
            **kw,
        )(x)
        if mode == "allgather" and blk != blk0:
            out = out.reshape(n, blk)[:, :blk0].reshape(-1)
        if op == ReductionOp.AVG and mode != "allgather":
            out = (out / n).astype(out.dtype)
        return out

    program = jax.jit(jax.shard_map(body, mesh=mesh,
                                    in_specs=P("r"),
                                    out_specs=out_specs, check_vma=False))
    return program, padded


class RingDmaCollTask(XlaCollTask):
    """Rendezvous/dispatch shared with TL/XLA; the launched program is the
    Pallas ring kernel instead of a lax collective."""

    def __init__(self, init_args, team, alg: str = "ring_dma"):
        super().__init__(init_args, team, alg=alg)
        args = init_args.args
        if self.coll not in _COLL_NAME:
            raise UccError(Status.ERR_NOT_SUPPORTED,
                           f"tl/ring_dma does not implement {self.coll}")
        op = args.op if args.op is not None else ReductionOp.SUM
        if self.coll not in (CollType.ALLGATHER, CollType.BCAST) and \
                op not in (
                ReductionOp.SUM, ReductionOp.AVG, ReductionOp.MAX,
                ReductionOp.MIN, ReductionOp.PROD):
            raise UccError(Status.ERR_NOT_SUPPORTED,
                           f"tl/ring_dma does not implement op {op}")
        total = int((args.dst or args.src).count)
        if self.coll in (CollType.BCAST, CollType.ALLTOALL) and \
                total > CHUNK_ELEMS and team.size == 1:
            # the n>1 paths route to the HBM-resident grid kernels
            # (build_hbm_{bcast,alltoall}_program — no cap beyond HBM);
            # a 1-rank team has no ring to pipeline over, so the VMEM
            # whole-vector kernel is the only shape — fall back to
            # TL/XLA (or tl/self) rather than fail at Mosaic allocation
            raise UccError(Status.ERR_NOT_SUPPORTED,
                           f"tl/ring_dma {self.coll} count {total} "
                           f"exceeds the VMEM bound {CHUNK_ELEMS} on a "
                           "1-rank team")
        if self.coll == CollType.REDUCE_SCATTER:
            # the ring delivers per-rank shards; a non-divisible total
            # would need the near-equal remainder convention — defer to
            # TL/XLA's replicated-slice path via selection fallback
            src_bi = args.dst if args.is_inplace or args.src is None \
                else args.src
            if int(src_bi.count) % team.size != 0:
                raise UccError(Status.ERR_NOT_SUPPORTED,
                               "tl/ring_dma reduce_scatter requires "
                               "count % team_size == 0")

    def build_program(self, shared, slot=None):
        args = self.args
        n = len(shared.devices)
        count = self.src_count()
        op = args.op if args.op is not None else ReductionOp.SUM
        root = int(args.root) if self.coll == CollType.BCAST else 0
        key = ("ring_dma", self.coll, op, self.np_dtype.str, count, root)
        cached = shared.programs.get(key)
        if cached is not None:
            return cached
        fam = kernel_family(self.coll, count, n, self.np_dtype)
        if fam in ("bcast", "hbm_bcast"):
            built = _BUILDERS[fam](shared.mesh, n, root, self.np_dtype,
                                   count)
        elif fam in ("alltoall", "hbm_alltoall", "hbm_allgather"):
            built = _BUILDERS[fam](shared.mesh, n, self.np_dtype, count)
        elif fam.startswith("ring_"):
            built = build_ring_program(shared.mesh, n, self.coll, op,
                                       self.np_dtype, count)
        else:
            built = _BUILDERS[fam](shared.mesh, n, op, self.np_dtype,
                                   count)
        shared.programs[key] = built
        return built


_COLL_NAME = {CollType.ALLREDUCE: "allreduce", CollType.ALLGATHER: "allgather",
              CollType.REDUCE_SCATTER: "reduce_scatter",
              CollType.BCAST: "bcast", CollType.ALLTOALL: "alltoall"}


def kernel_family(coll: CollType, count: int, n: int, nd) -> str:
    """The kernel family serving ``coll`` at per-rank ``count`` of dtype
    ``nd`` on an n-rank team: counts beyond one VMEM pass take the
    HBM-resident grid kernels (no element cap beyond HBM)."""
    name = _COLL_NAME[coll]
    if coll in (CollType.BCAST, CollType.ALLTOALL):
        return f"hbm_{name}" if count > CHUNK_ELEMS and n > 1 else name
    vmem_max = _vmem_pass_elems(n, _tile(nd))
    if coll == CollType.ALLGATHER:
        vmem_max //= n
    return f"hbm_{name}" if count > vmem_max else f"ring_{name}"


_BUILDERS = {
    "bcast": build_bcast_program,
    "hbm_bcast": build_hbm_bcast_program,
    "alltoall": build_alltoall_program,
    "hbm_alltoall": build_hbm_alltoall_program,
    "hbm_allreduce": build_hbm_allreduce_program,
    "hbm_allgather": build_hbm_allgather_program,
    "hbm_reduce_scatter": build_hbm_reduce_scatter_program,
}

class TlRingDmaTeam(TlXlaTeam):
    NAME = "ring_dma"
    TL_CLS: Any = None

    def alg_table(self) -> Dict[CollType, List[AlgSpec]]:
        def spec(i, name):
            def init(ia, team):
                return RingDmaCollTask(ia, self, alg=name)
            return AlgSpec(i, name, init)

        return {ct: [spec(0, "ring_dma")] for ct in _COLL_NAME}

    def get_scores(self) -> CollScore:
        return build_scores(self, TlRingDma.DEFAULT_SCORE, self.alg_table(),
                            TlRingDma.SUPPORTED_MEM_TYPES,
                            tune_env="UCC_TL_RING_DMA_TUNE")


@register_tl
class TlRingDma(TransportLayer):
    """Device-initiated ring transport (the tl/mlx5 / sliding-window
    role): Pallas kernels own the ICI schedule at the DMA level."""

    NAME = "ring_dma"
    DEFAULT_SCORE = 20        # below TL/XLA: opt-in via TUNE/score boost
    SUPPORTED_COLLS = (CollType.ALLREDUCE | CollType.ALLGATHER
                       | CollType.REDUCE_SCATTER | CollType.BCAST
                       | CollType.ALLTOALL)
    SUPPORTED_MEM_TYPES = (MemoryType.TPU,)
    SERVICE_CAPABLE = False
    CONTEXT_CONFIG = TL_RING_DMA_CONFIG
    lib_cls = BaseLib
    context_cls = TlXlaContext
    team_cls = TlRingDmaTeam


TlRingDmaTeam.TL_CLS = TlRingDma
