"""Traceable collective operations — the compiled/ICI compute path.

These are the XLA-native bodies of every UCC collective, usable in two
ways:

1. Inside any user ``shard_map``/``jit`` program (the TPU-native analog of
   the reference's triggered-post/EE execution model, ucc.h:2050-2260: a
   collective embedded in the device stream — here, embedded in the
   compiled program, which is where TPUs want it).
2. By TL/XLA (tl/xla.py), which wraps them in cached shard_map programs to
   serve the eager init/post/test API over a team Mesh.

All functions operate on a named mesh axis (default ``"r"`` = team ranks)
on shard-local arrays whose LAST axis is the data (``(..., count)``).
TL/XLA feeds them flat 1-D shards (global layout ``(n_ranks*count,)`` with
``PartitionSpec('r')`` — used as-is, no per-shard eager ops) through a
``x[None, :]`` view inside its jitted body.

Op mapping (the TL/NCCL dt/op tables analog, tl_nccl_coll.c:21-75):
SUM/AVG/MAX/MIN ride the native psum/pmax/pmin collectives (ICI-optimized
by XLA); PROD/logical/bitwise/MINLOC/MAXLOC gather and reduce locally —
semantically exact, one extra HBM pass, only used by exotic ops.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from .constants import ReductionOp

_NATIVE = {ReductionOp.SUM, ReductionOp.AVG, ReductionOp.MAX,
           ReductionOp.MIN}


def axis_size(axis_name: str) -> int:
    return lax.axis_size(axis_name) if hasattr(lax, "axis_size") else \
        lax.psum(1, axis_name)


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

def _gather_reduce(x, op: ReductionOp, axis_name: str):
    """Exact fallback for ops without a native XLA collective."""
    g = lax.all_gather(x, axis_name)            # (n, *x.shape)
    if op == ReductionOp.PROD:
        return jnp.prod(g, axis=0)
    if op == ReductionOp.LAND:
        return jnp.all(g != 0, axis=0).astype(x.dtype)
    if op == ReductionOp.LOR:
        return jnp.any(g != 0, axis=0).astype(x.dtype)
    if op == ReductionOp.LXOR:
        return (jnp.sum(g != 0, axis=0) % 2).astype(x.dtype)
    if op == ReductionOp.BAND:
        return _bitwise_fold(g, jnp.bitwise_and)
    if op == ReductionOp.BOR:
        return _bitwise_fold(g, jnp.bitwise_or)
    if op == ReductionOp.BXOR:
        return _bitwise_fold(g, jnp.bitwise_xor)
    if op in (ReductionOp.MINLOC, ReductionOp.MAXLOC):
        vals = g[..., 0::2]
        idxs = g[..., 1::2]
        pick = jnp.argmin(vals, axis=0) if op == ReductionOp.MINLOC \
            else jnp.argmax(vals, axis=0)
        sel_val = jnp.take_along_axis(vals, pick[None], axis=0)[0]
        # ties -> lowest index (MPI loc semantics)
        ties = vals == sel_val[None]
        big = jnp.asarray(jnp.inf, dtype=vals.dtype) if \
            jnp.issubdtype(vals.dtype, jnp.floating) else \
            jnp.iinfo(vals.dtype).max
        sel_idx = jnp.min(jnp.where(ties, idxs, big), axis=0)
        out = jnp.empty_like(x)
        out = out.at[..., 0::2].set(sel_val)
        out = out.at[..., 1::2].set(sel_idx)
        return out
    raise NotImplementedError(f"op {op}")


def _bitwise_fold(g, fn):
    acc = g[0]
    for i in range(1, g.shape[0]):
        acc = fn(acc, g[i])
    return acc


def allreduce(x, op: ReductionOp = ReductionOp.SUM, axis_name: str = "r"):
    """lax.psum-family allreduce (BASELINE north star: allreduce -> psum)."""
    if op == ReductionOp.SUM:
        return lax.psum(x, axis_name)
    if op == ReductionOp.AVG:
        return lax.pmean(x, axis_name)
    if op == ReductionOp.MAX:
        return lax.pmax(x, axis_name)
    if op == ReductionOp.MIN:
        return lax.pmin(x, axis_name)
    return _gather_reduce(x, op, axis_name)


def allreduce_ring(x, op: ReductionOp = ReductionOp.SUM, axis_name: str = "r"):
    """Explicit ring allreduce via ppermute (reduce-scatter + allgather) —
    the manual-schedule alternative the score DSL can select (@ring) when
    XLA's own lowering is not wanted. Requires count % n == 0 (the TL pads)."""
    n = axis_size(axis_name)
    if op not in (ReductionOp.SUM, ReductionOp.AVG):
        return allreduce(x, op, axis_name)
    me = lax.axis_index(axis_name)
    count = x.shape[-1]
    blk = count // n
    perm = [(i, (i + 1) % n) for i in range(n)]

    # reduce-scatter phase: carry a partial block around the ring.
    # Invariant: rank r starts with block (r-1); after permute at step s it
    # holds the partial of block (r-2-s) and adds its local chunk of that
    # block; after n-1 steps rank r holds fully-reduced block r.
    def body(s, carry):
        acc = lax.ppermute(carry, axis_name, perm)
        idx = (me - 2 - s) % n
        mine = lax.dynamic_slice_in_dim(x, idx * blk, blk, axis=-1)
        return acc + mine

    start_idx = (me - 1) % n
    start = lax.dynamic_slice_in_dim(x, start_idx * blk, blk, axis=-1)
    reduced = lax.fori_loop(0, n - 1, body, start)
    if op == ReductionOp.AVG:
        reduced = reduced / n
    # allgather phase: row j of the gather is rank j's block == block j
    gathered = lax.all_gather(reduced, axis_name, axis=0, tiled=False)
    # gathered: (n, ..., blk) -> (..., n*blk)
    out = jnp.moveaxis(gathered, 0, -2)
    return out.reshape(x.shape[:-1] + (n * blk,))


def reduce_scatter(x, op: ReductionOp = ReductionOp.SUM, axis_name: str = "r"):
    """x: (..., total) -> (..., total/n), rank r gets block r
    (lax.psum_scatter, tiled)."""
    if op in (ReductionOp.SUM, ReductionOp.AVG):
        out = lax.psum_scatter(x, axis_name, scatter_dimension=x.ndim - 1,
                               tiled=True)
        if op == ReductionOp.AVG:
            out = out / axis_size(axis_name)
        return out
    full = _gather_reduce(x, op, axis_name)
    n = axis_size(axis_name)
    me = lax.axis_index(axis_name)
    blk = x.shape[-1] // n
    return lax.dynamic_slice_in_dim(full, me * blk, blk, axis=-1)


def allgather(x, axis_name: str = "r"):
    """x: (..., count) -> (..., n*count)."""
    return lax.all_gather(x, axis_name, axis=x.ndim - 1, tiled=True)


def alltoall(x, axis_name: str = "r"):
    """x: (1, n*blk) -> (1, n*blk) with block exchange."""
    n = axis_size(axis_name)
    blk = x.shape[-1] // n
    y = x.reshape(x.shape[:-1] + (n, blk))
    y = lax.all_to_all(y, axis_name, split_axis=y.ndim - 2,
                       concat_axis=y.ndim - 2, tiled=False)
    return y.reshape(x.shape)


def allgatherv(x, counts, axis_name: str = "r"):
    """In-jit allgatherv with STATIC per-rank counts: each rank
    contributes ``counts[i]`` elements (x padded to max(counts)); returns
    the packed concatenation (sum(counts) elements, same on every rank).
    Implemented as a padded all_gather + a static gather-index unpack."""
    import numpy as np
    c = [int(v) for v in counts]
    n = len(c)
    maxc = max(1, max(c) if c else 1)
    flat = jnp.ravel(x)
    if flat.size < maxc:
        flat = jnp.pad(flat, (0, maxc - flat.size))
    g = lax.all_gather(flat[:maxc], axis_name, axis=0, tiled=False)  # (n, maxc)
    rows = g.reshape(n * maxc)
    idx = np.concatenate([i * maxc + np.arange(c[i]) for i in range(n)]) \
        if sum(c) else np.empty(0, np.int64)
    return rows[jnp.asarray(idx, dtype=jnp.int32)]   # (sum(counts),)


#: widest row, in elements, that alltoallv planning groups elements into
A2AV_MAX_ROW = 2048


def a2av_row_width(values, cap: int = A2AV_MAX_ROW) -> int:
    """The largest power of two, up to ``cap``, that divides every value:
    the row width an alltoallv moves its blocks in. Any MoE routing at
    hidden size 2048 lands on rows of 2048; arbitrary element counts on
    narrower rows, down to single elements."""
    g = 0
    for v in values:
        g = math.gcd(g, int(v))
    return cap if g == 0 else min(g & -g, cap)


def a2av_plan(scounts, sdispls, rcounts, rdispls, w: int, src_cap: int,
              dst_cap: int):
    """The runtime arrays of one alltoallv, in rows of ``w`` elements.

    ``scounts[r][p]`` elements go from rank r to rank p, starting at
    ``sdispls[r][p]`` of r's source; ``rcounts[r][p]`` elements arrive at
    rank r from rank p, at ``rdispls[r][p]`` of r's destination. Returns
    int32 ``(n, 4, n)``: per rank, ``lax.ragged_all_to_all``'s
    input_offsets, send_sizes, output_offsets (where the block lands in
    the receiver's buffer) and recv_sizes. Raises ValueError for counts
    that do not match across ranks, that ``w`` does not divide, or that
    reach outside a buffer of ``src_cap``/``dst_cap`` elements."""
    import numpy as np
    sc = np.asarray(scounts, np.int64)
    sd = np.asarray(sdispls, np.int64)
    rc = np.asarray(rcounts, np.int64)
    rd = np.asarray(rdispls, np.int64)
    n = sc.shape[0]
    if any(a.shape != (n, n) for a in (sd, rc, rd)):
        raise ValueError("alltoallv: every rank needs n counts and "
                         "displacements")
    if (rc != sc.T).any():
        raise ValueError("alltoallv: receive counts do not match the "
                         "senders' counts")
    if ((sc | sd | rd) % w).any():
        raise ValueError(f"alltoallv: rows of {w} do not divide the "
                         "counts and displacements")
    if (sc < 0).any() or (sd < 0).any() or (rd < 0).any() or \
            (sd + sc > src_cap).any() or (rd + rc > dst_cap).any():
        raise ValueError("alltoallv: a block lies outside its buffer")
    plan = np.empty((n, 4, n), np.int32)
    plan[:, 0] = sd // w
    plan[:, 1] = sc // w
    plan[:, 2] = rd.T // w
    plan[:, 3] = rc // w
    return plan


def a2av_rows(flat, w: int):
    """A flat buffer as rows of ``w`` elements. A multiple of 128 is
    viewed as ``(rows, w // 128, 128)``: on a TPU that is a bitcast of
    the 1-D layout for 16-bit rows of 2048 (and 32-bit rows of 1024),
    where ``(rows, w)`` is a relayout copy in and out."""
    tail = (w // 128, 128) if w % 128 == 0 else (w,)
    return flat.reshape((flat.shape[0] // w,) + tail)


def _take_rows(x, idx, keep, fill):
    """``x[idx]`` where ``keep``, else ``fill`` (rows of x)."""
    keep = keep.reshape(keep.shape + (1,) * (x.ndim - 1))
    return jnp.where(keep, x[idx], fill)


def ragged_all_to_all_portable(x, out, input_offsets, send_sizes,
                               output_offsets, recv_sizes, *,
                               axis_name: str = "r"):
    """``lax.ragged_all_to_all``'s semantics from gathers and one
    ``lax.all_to_all``, for platforms without the op (XLA:CPU). The row
    indices are computed in the program from the same runtime arrays, so
    the program is the same for every routing; every block is padded to
    the source's length, so the exchange moves n times the source."""
    n = input_offsets.shape[0]
    rows = x.shape[0]
    j = lax.iota(jnp.int32, rows)
    src = input_offsets[:, None] + j
    packed = _take_rows(x, jnp.clip(src, 0, rows - 1),
                        j < send_sizes[:, None], 0)
    got = lax.all_to_all(packed, axis_name, 0, 0, tiled=False)
    at = lax.all_to_all(output_offsets, axis_name, 0, 0, tiled=True)
    rel = lax.iota(jnp.int32, out.shape[0]) - at[:, None]
    hit = (rel >= 0) & (rel < recv_sizes[:, None])
    # the highest sender wins an overlap, as ragged_all_to_all's
    # in-order writes do
    flat = jnp.max(jnp.where(hit, lax.iota(jnp.int32, n)[:, None] * rows
                             + rel, -1), axis=0)
    return _take_rows(got.reshape((n * rows,) + x.shape[1:]),
                      jnp.maximum(flat, 0), flat >= 0, out)


def ragged_all_to_all_rows(x, out, plan, axis_name: str = "r"):
    """Rows of ``x`` into ``out`` across ``axis_name`` as ``plan`` (this
    rank's ``(4, n)`` slice of ``a2av_plan``) says, leaving the rest of
    ``out`` as it is: ``lax.ragged_all_to_all`` on a TPU, which moves only
    the real rows, the portable body elsewhere."""
    args = (x, out, plan[0], plan[1], plan[2], plan[3])
    return lax.platform_dependent(
        *args,
        tpu=functools.partial(lax.ragged_all_to_all, axis_name=axis_name),
        default=functools.partial(ragged_all_to_all_portable,
                                  axis_name=axis_name))


def place_rows(x, out, src_off, size, dst_off):
    """One rank's alltoallv: rows ``[src_off, src_off + size)`` of ``x``
    written at row ``dst_off`` of ``out``, the rest of ``out`` kept. The
    offsets and size may be traced, so one program serves every count.
    Where the rows stay in place (equal shapes and offsets) it is one
    select over the buffer; a shift gathers the rows first."""
    def mask(a):
        i = lax.broadcasted_iota(jnp.int32, a.shape, 0)
        return (i >= dst_off) & (i < dst_off + size)

    def shifted(x, out):
        rel = lax.iota(jnp.int32, out.shape[0]) - dst_off
        moved = x[jnp.clip(rel + src_off, 0, x.shape[0] - 1)]
        return jnp.where(mask(moved), moved, out)

    if x.shape != out.shape:
        return shifted(x, out)
    return lax.cond(src_off == dst_off,
                    lambda x, out: jnp.where(mask(out), x, out), shifted,
                    x, out)


def alltoallv(x, counts, axis_name: str = "r"):
    """In-jit alltoallv with a STATIC per-pair counts matrix
    (``counts[i][j]`` = elements rank i sends rank j) — the uneven-routing
    primitive MoE-style workloads need without capacity padding.

    Layout contract (packed): rank i's send buffer ``x`` holds its blocks
    for ranks 0..n-1 back to back (cumsum displacements), padded to
    ``max_i sum_j counts[i][j]`` elements; the return value is the recv
    buffer in the same packed layout (blocks from ranks 0..n-1), padded
    with zeros to ``max_j sum_i counts[i][j]``. The counts become a
    constant ``(n, 4, n)`` plan (``a2av_plan``) that ``axis_index``
    selects from, and ``ragged_all_to_all_rows`` moves the rows."""
    import numpy as np
    m = np.asarray(counts, dtype=np.int64)
    n = m.shape[0]
    sd = np.cumsum(m, axis=1) - m          # sender's displacements
    rd = np.cumsum(m, axis=0) - m          # rd[p, i]: p's block at i
    max_src = max(1, int(m.sum(axis=1).max()))
    max_dst = max(1, int(m.sum(axis=0).max()))
    w = a2av_row_width(np.concatenate(
        [m.ravel(), sd.ravel(), rd.ravel(), [max_src, max_dst]]))
    plan = a2av_plan(m, sd, m.T, rd.T, w, max_src, max_dst)
    flat = jnp.ravel(x)[:max_src]
    if flat.size < max_src:
        flat = jnp.pad(flat, (0, max_src - flat.size))
    out = jnp.zeros((max_dst,), flat.dtype)
    mine = jnp.asarray(plan)[lax.axis_index(axis_name)]
    return ragged_all_to_all_rows(a2av_rows(flat, w), a2av_rows(out, w),
                                  mine, axis_name).reshape(-1)


def bcast(x, root: int, axis_name: str = "r"):
    """Root's shard to everyone (masked psum — the ICI-friendly form)."""
    me = lax.axis_index(axis_name)
    masked = jnp.where(me == root, x, jnp.zeros_like(x))
    if jnp.issubdtype(x.dtype, jnp.integer) or x.dtype == jnp.bool_:
        return lax.psum(masked, axis_name).astype(x.dtype)
    return lax.psum(masked, axis_name)


def reduce(x, root: int, op: ReductionOp = ReductionOp.SUM,
           axis_name: str = "r"):
    """Allreduce whose result is consumed at root (XLA has no rooted
    reduce; the all-form is what the hardware does anyway on ICI rings)."""
    return allreduce(x, op, axis_name)


def gather(x, root: int, axis_name: str = "r"):
    return allgather(x, axis_name)


def scatter(x_full, root: int, axis_name: str = "r"):
    """Root holds (..., total); every rank gets its block."""
    n = axis_size(axis_name)
    me = lax.axis_index(axis_name)
    blk = x_full.shape[-1] // n
    full = bcast(x_full, root, axis_name)
    return lax.dynamic_slice_in_dim(full, me * blk, blk, axis=-1)


def barrier(axis_name: str = "r"):
    return lax.psum(jnp.ones((1, 1), jnp.int32), axis_name)


def ring_shift(x, axis_name: str = "r", shift: int = 1):
    """Rotate shards around the ring: rank r's block goes to r+shift.
    The building block of ring/sequence-parallel pipelines (the ppermute
    pattern of the pallas guide's ring collectives)."""
    n = axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)
