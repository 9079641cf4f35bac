"""The plain reference of the expert-parallel exchange, and the comparison
that decides ``correct`` for it.

Everything is made again from the seed: each rank's routing of the layer
(``routing.layer_counts``) and each rank's send rows as they were posted
(``data.source``), and the rows every rank should hold are built with plain
jax.numpy, one rank at a time, on the device that holds its answer. It
imports nothing of ucc_tpu and takes nothing ucc_tpu made.

Per answer (one rank's result of one collective of one layer at one step):

- counts all-to-all: the counts that rank receives, from each rank in
  turn, for the experts it holds;
- dispatch: the rows every rank routed to the experts this rank holds,
  from rank 0 up, packed from row 0 of the destination; rows past the
  received count are not compared;
- combine: the rows this rank dispatched, back in their places.

The number compared is ``max|answer - ref| / max|ref|`` over the compared
rows, as in ``check.py``. An exchange is a copy: a sound run reads 0.0;
the float8_e4m3 control (tests/control_ep.py) reads ~2^-5..2^-4; a wrong
row reads ~1.
"""
from __future__ import annotations

import functools

import numpy as np

from . import data, routing

COUNTS, DISPATCH, COMBINE = range(3)


@functools.lru_cache(maxsize=None)
def _ref_fn(kind: int, n: int, rows: int, hidden: int, cap: int,
            tokens: int, topk: int, local: int, fresh: int):
    """jitted (key, step, layer, me, weights, answer) -> error of one
    rank's answer."""
    import jax
    import jax.numpy as jnp

    def bench_ref(key, step, layer, me, weights, answer):
        counts = jnp.stack([
            routing.layer_counts(key, step, layer, jnp.uint32(p), tokens,
                                 weights, topk)[:n * local]
            for p in range(n)]).reshape(n, n, local)   # [src, dst, expert]
        if kind == COUNTS:
            want = counts[:, me].reshape(-1).astype(jnp.float32)
            got = answer.astype(jnp.float32)
            return jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))
        blocks = counts.sum(axis=2)                  # rows src -> dst
        sends = jnp.stack([
            data.source(key, p, layer, rows * hidden, step, fresh)
            .reshape(rows, hidden) for p in range(n)])
        i = jax.lax.iota(jnp.int32, cap)
        if kind == DISPATCH:                         # from each src p
            size = blocks[:, me]
            at = jnp.cumsum(size) - size             # where p's rows land
            src_at = jnp.cumsum(blocks, axis=1)[:, me] - size
            p = jnp.clip(jnp.searchsorted(at + size, i, side="right"),
                         0, n - 1)
            row = p * rows + src_at[p] + i - at[p]
        else:                                        # own rows, in place
            size = blocks[me]
            row = me * rows + i
        valid = i < jnp.sum(size)
        want = sends.reshape(n * rows, hidden)[
            jnp.clip(row, 0, n * rows - 1)].astype(jnp.float32)
        got = answer.reshape(cap, hidden).astype(jnp.float32)
        err = jnp.max(jnp.where(valid[:, None], jnp.abs(got - want), 0))
        scale = jnp.max(jnp.where(valid[:, None], jnp.abs(want), 0))
        return jnp.where(scale > 0, err / scale, err)   # none received

    return jax.jit(bench_ref)


def answer_errors(key, answers, shape: dict, weights):
    """answers: [(step, unit, [array per rank or None])], unit = 3 *
    layer + kind. ``shape``: n, rows, hidden, tokens, topk, local, fresh.
    Returns (max_err over answers, number compared, number missing)."""
    import jax
    n, rows, hidden = shape["n"], shape["rows"], shape["hidden"]
    worst, compared, missing = 0.0, 0, 0
    for step, unit, arrs in answers:
        layer, kind = divmod(unit, 3)
        for me, a in enumerate(arrs):
            want = n * shape["local"] if kind == COUNTS else \
                (n * rows if kind == DISPATCH else rows) * hidden
            if a is None or a.shape != (want,):
                missing += 1
                continue
            dev = next(iter(a.devices()))
            fn = _ref_fn(kind, n, rows, hidden,
                         n * rows if kind == DISPATCH else rows,
                         shape["tokens"], shape["topk"], shape["local"],
                         shape["fresh"])
            e = fn(jax.device_put(key, dev), np.uint32(step),
                   np.uint32(layer), np.int32(me),
                   jax.device_put(weights, dev), a)
            worst = max(worst, float(e))
            compared += 1
    return worst, compared, missing
