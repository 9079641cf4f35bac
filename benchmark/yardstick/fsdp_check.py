"""The plain reference of the FSDP2 unit stream, and the comparison that
decides ``correct`` for it.

Everything is made again from the seed, in float32 with plain jax.numpy,
one answer at a time, on the device that holds it; it imports nothing of
ucc_tpu and takes nothing ucc_tpu made.

- allgather of unit u: the ranks' bf16 shards as they were posted at that
  step (``data.source``), concatenated from rank 0 up. An allgather is a
  copy: an answer that differs in any bit reads 1.0, else 0.0.
- AVG reduce-scatter of unit u: the mean of the ranks' full-unit float32
  gradients (``grad``), cut to the rank's block. The number compared is
  ``max|answer - ref| / max|ref|`` over the block, as in ``check.py``. The
  library's four-term sum rounds in an order the reference does not fix:
  a few ulp, ~1e-7 of the largest value; a bfloat16 reduce reads ~4e-3
  (the control, tests/control_fsdp.py); a wrong block reads ~1.

Gradient elements are hashes of (seed, rank, unit, step, index), as the
shards' are (``data.py``), with random sign, exponent in [2^-3, 2^5) and
23 random mantissa bits, so any block of them can be made alone.
"""
from __future__ import annotations

import functools

import numpy as np

from . import data

AG, RS = range(2)
#: offset of the gradient streams' unit index from the shards'
GRAD = 1 << 16


def grad(key, rank, unit, step, count: int, start=0):
    """Elements ``start .. start + count`` of one rank's float32 gradient
    of ``unit`` at ``step`` (traceable in every argument but ``count``)."""
    import jax
    import jax.numpy as jnp
    skey = data.stream_key(key, rank, GRAD + unit, step)
    i = jax.lax.iota(jnp.uint32, count) + jnp.asarray(start, jnp.uint32)
    bits = data._mix(i * jnp.uint32(0x9E3779B1) ^ skey)
    exp = (((bits >> 23) & 0x7) + 124) << 23
    word = (bits & jnp.uint32(0x807FFFFF)) | exp
    return jax.lax.bitcast_convert_type(word, jnp.float32)


@functools.lru_cache(maxsize=None)
def _ref_fn(kind: int, n: int, count: int, fresh: int):
    """jitted (key, unit, step, me, answer) -> error of one rank's answer;
    ``count`` is the per-rank count."""
    import jax
    import jax.numpy as jnp

    def bench_fsdp_ref(key, unit, step, me, answer):
        if kind == AG:
            want = jnp.concatenate([data.source(key, p, unit, count, step,
                                                fresh) for p in range(n)])
            same = jnp.all(jax.lax.bitcast_convert_type(answer, jnp.uint16)
                           == jax.lax.bitcast_convert_type(want, jnp.uint16))
            return jnp.where(same, 0.0, 1.0).astype(jnp.float32)
        want = sum(grad(key, p, unit, step, count, me * count)
                   for p in range(n)) / n
        return jnp.max(jnp.abs(answer - want)) / jnp.max(jnp.abs(want))

    return jax.jit(bench_fsdp_ref)


def answer_errors(key, answers, n: int, counts, fresh: int):
    """answers: [(step, b, [array per rank or None])], b < len(counts) the
    allgather of unit b, else the reduce-scatter of unit b - len(counts);
    ``counts`` per rank per unit. Returns (max_err over answers, number
    compared, number missing)."""
    import jax
    units = len(counts)
    worst, compared, missing = 0.0, 0, 0
    for step, b, arrs in answers:
        kind, unit = divmod(b, units)
        count = counts[unit]
        want = (n * count if kind == AG else count,)
        for me, a in enumerate(arrs):
            if a is None or a.shape != want:
                missing += 1
                continue
            dev = next(iter(a.devices()))
            e = _ref_fn(kind, n, count, fresh)(
                jax.device_put(key, dev), np.uint32(unit), np.uint32(step),
                np.uint32(me), a)
            worst = max(worst, float(e))
            compared += 1
    return worst, compared, missing
