"""Generator ``ep_alltoallv``: the token exchange of an expert-parallel
MoE job, as Megatron's all-to-all dispatcher drives ProcessGroupUCC in the
forward half of a training step.

One step: the stand-in layers write ``FRESH`` new elements at the head of
every layer's send rows, in place (donated), so every dispatch gets a new
jax.Array, and each rank routes its tokens for every MoE layer
(``yardstick/routing.py``). Then, layer by layer, each rank posts through
the eager API (``collective_init`` -> ``post`` -> ``test`` until complete
-> ``finalize``):

1. an ALLTOALL of int32 per-expert counts, ``local`` per peer; the host
   reads what it received (the one sync per layer torch's dispatcher
   makes too);
2. the dispatch: an ALLTOALLV of bf16 rows of ``hidden_size``, already
   grouped by destination, into a destination of n * tokens * topk rows,
   the bound no routing exceeds, so no row is dropped and the shape never
   changes;
3. the combine: an identity expert sends back what it received, an
   ALLTOALLV with the transposed counts into a tokens * topk row buffer.

The step ends when every result is ready on every chip. The ranks present
are the cell's chips: rank r holds experts ``local * r`` up to
``local * (r + 1)``, the router routes over all of the deployment's
experts, and on fewer chips than the deployment has the rows routed to
the absent ranks' experts are left out.

Before warm-up, ``ready()`` checks the one contract the cell rests on: an
alltoallv lands each block at its displacement inside the caller's dst
buffer, whose shape and other elements it keeps. A library that does not
keep it cannot run the deployment, and the run ends there, non-zero, with
the reason on one line; so does a warm-up that has not ended
``WARMUP_LIMIT_S`` after ``ready()``.
"""
from __future__ import annotations

import os
import sys
import threading
import time

from yardstick import data, ep_check, routing

ROW_DTYPES = {"bfloat16": "BFLOAT16"}
#: elements the stand-in layer rewrites per send buffer and step (a row)
FRESH = 2048
TEST_TIMEOUT_S = 120.0
#: seconds from ready() to the end of warm-up before the run is given up
WARMUP_LIMIT_S = 180.0
COUNTS, DISPATCH, COMBINE = ep_check.COUNTS, ep_check.DISPATCH, \
    ep_check.COMBINE


class Stream:
    def __init__(self, job, devices, config, traffic, key, spans):
        import jax.numpy as jnp

        import ucc_tpu
        dep = config["deployment"]
        n, ep = job.n, int(dep["expert_parallel"])
        self.local = int(config["n_routed_experts"])
        experts = int(dep["routed_experts"])
        if n > ep or self.local * ep != experts:
            raise ValueError(f"{n} rank(s) cannot hold {self.local} of "
                             f"{experts} experts each over EP={ep}")
        self.layers = int(dep["moe_layers"])
        self.tokens = int(dep["tokens_per_chip"])
        self.topk = int(config["num_experts_per_tok"])
        self.hidden = int(config["hidden_size"])
        self.rows = self.tokens * self.topk
        self.weights = routing.zipf_weights(experts, float(traffic["zipf_s"]))
        self.warm = int(traffic["warmup_steps"])
        self.units = 3 * self.layers
        self.u = ucc_tpu
        self.job = job
        self.n = n
        self.spans = spans
        self.dtype = getattr(ucc_tpu.DataType, ROW_DTYPES[dep["row_dtype"]])
        elems = [self.rows * self.hidden] * self.layers
        self.sends = [data.make_rank(key, r, elems, devices[r])
                      for r in range(n)]
        self.stampers = [data.Stamper(key, r, elems, FRESH, devices[r])
                         for r in range(n)]
        self.routers = [routing.Router(key, r, self.layers, self.tokens,
                                       self.weights, self.topk,
                                       n * self.local, devices[r])
                        for r in range(n)]
        # capacities no routing exceeds; immutable, so shared by every post
        self.dispatch_dst = [jnp.zeros(n * self.rows * self.hidden,
                                       jnp.bfloat16, device=d)
                             for d in devices[:n]]
        self.combine_dst = [jnp.zeros(self.rows * self.hidden, jnp.bfloat16,
                                      device=d) for d in devices[:n]]
        self.results = [[None] * self.units for _ in range(n)]
        self._ici = [0.0] * self.units
        self._window_steps = 0
        self.describe = (
            f"{n} rank(s) of EP={ep}, {self.local} of {experts} experts "
            f"each, {self.layers} MoE layers, {self.tokens} tokens x top-"
            f"{self.topk}, {self.units} requests per rank per step, "
            f"{2 * self.layers * self.rows * self.hidden * 2 / 1e9:.4f} GB "
            "dispatched and combined per rank per step")

    @property
    def coll_bytes(self):
        """Per collective of a step: the most bytes any chip sends or
        receives over the interconnect, meaned over the window's steps
        (0 on one chip)."""
        return [b / max(1, self._window_steps) for b in self._ici]

    def ready(self) -> None:
        import jax
        jax.block_until_ready([self.sends, self.dispatch_dst,
                               self.combine_dst])
        self._probe()
        self._watchdog = threading.Timer(WARMUP_LIMIT_S, _give_up, (
            f"warm-up did not end within {WARMUP_LIMIT_S:.0f} s of set-up "
            "(compiling per routing?)",))
        self._watchdog.daemon = True
        self._watchdog.start()

    def _probe(self) -> None:
        """One small alltoallv per rank: 8 elements to every peer, landing
        one block past the head of a buffer of sentinels with room to
        spare. Block r -> p holds r * n + p, exact in any float format."""
        import jax
        import numpy as np
        u, n = self.u, self.n
        blk, cap = 8, 8 * (n + 2)
        devs = [s[0].devices().pop() for s in self.sends]
        argses = [u.CollArgs(
            coll_type=u.CollType.ALLTOALLV,
            src=u.BufferInfoV(jax.device_put(np.repeat(
                np.arange(n, dtype=np.float32) + r * n, blk), devs[r]),
                [blk] * n, None, u.DataType.FLOAT32,
                mem_type=u.MemoryType.TPU),
            dst=u.BufferInfoV(jax.device_put(
                np.full(cap, -1, np.float32), devs[r]), [blk] * n,
                [blk * (p + 1) for p in range(n)], u.DataType.FLOAT32,
                mem_type=u.MemoryType.TPU)) for r in range(n)]
        if self._run(-1, 0, argses):
            _give_up("the alltoallv probe failed")
        for r in range(n):
            got = np.asarray(self.results[r][0])
            want = np.full(cap, -1, np.float32)
            want[blk:blk * (n + 1)] = np.repeat(np.arange(n) * n + r, blk)
            if got.shape != want.shape or (got != want).any():
                _give_up(
                    "alltoallv does not land blocks at their displacements "
                    f"inside the caller's dst buffer (rank {r}: shape "
                    f"{got.shape} for {want.shape}); a dropless exchange "
                    "into a capacity buffer cannot run")

    def _run(self, k, unit, argses) -> int:
        """One collective on every rank; returns 1 if it did not complete
        OK on every rank."""
        u = self.u
        sp = self.spans
        tok = sp.begin("init")
        reqs = [self.job.teams[r].collective_init(a)
                for r, a in enumerate(argses)]
        sp.end(tok)
        tok = sp.begin("post")
        for q in reqs:
            q.post()
        sp.end(tok)
        tok = sp.begin("test")
        deadline = None
        while True:
            sts = [q.test() for q in reqs]
            if all(s != u.Status.IN_PROGRESS for s in sts):
                break
            for c in self.job.ctxs:
                c.progress()
            if deadline is None:
                deadline = time.monotonic() + TEST_TIMEOUT_S
            elif time.monotonic() > deadline:
                raise TimeoutError(f"unit {unit} step {k}: {sts}")
        sp.end(tok)
        tok = sp.begin("finalize")
        for q in reqs:
            q.finalize()
        sp.end(tok)
        for r, a in enumerate(argses):
            self.results[r][unit] = a.dst.buffer \
                if sts[r] == u.Status.OK else None
        return int(not all(s == u.Status.OK for s in sts))

    def _v(self, buf, rows):
        """BufferInfoV of per-peer row counts, dense displacements."""
        u = self.u
        return u.BufferInfoV(buf, [c * self.hidden for c in rows], None,
                             self.dtype, mem_type=u.MemoryType.TPU)

    def step(self, k: int) -> int:
        """Run step ``k``; returns the number of collectives that did not
        complete OK on every rank."""
        import jax
        import numpy as np
        u = self.u
        sp = self.spans
        n, local = self.n, self.local
        if k == self.warm:
            self._watchdog.cancel()
        self.results = [[None] * self.units for _ in range(n)]
        tok = sp.begin("stamp")
        self.sends = [st(self.sends[r], k)
                      for r, st in enumerate(self.stampers)]
        counts = [rt(k) for rt in self.routers]
        sp.end(tok)
        tok = sp.begin("wait")
        mine = [np.asarray(c).reshape(self.layers, n, local).sum(axis=2)
                for c in jax.device_get(counts)]         # [layer, peer]
        sp.end(tok)
        failed = 0
        for layer in range(self.layers):
            base = 3 * layer
            cnt = n * local
            failed += self._run(k, base + COUNTS, [u.CollArgs(
                coll_type=u.CollType.ALLTOALL,
                src=u.BufferInfo(counts[r][layer], cnt, u.DataType.INT32,
                                 mem_type=u.MemoryType.TPU),
                dst=u.BufferInfo(None, cnt, u.DataType.INT32,
                                 mem_type=u.MemoryType.TPU))
                for r in range(n)])
            got = self.results
            if any(got[r][base] is None for r in range(n)):
                failed += 2
                continue
            tok = sp.begin("wait")
            recv = [np.asarray(c).reshape(n, local).sum(axis=1)
                    for c in jax.device_get([got[r][base]
                                             for r in range(n)])]
            sp.end(tok)
            send = [m[layer] for m in mine]
            failed += self._run(k, base + DISPATCH, [u.CollArgs(
                coll_type=u.CollType.ALLTOALLV,
                src=self._v(self.sends[r][layer], send[r]),
                dst=self._v(self.dispatch_dst[r], recv[r]))
                for r in range(n)])
            if any(got[r][base + DISPATCH] is None for r in range(n)):
                failed += 1
                continue
            failed += self._run(k, base + COMBINE, [u.CollArgs(
                coll_type=u.CollType.ALLTOALLV,
                src=self._v(got[r][base + DISPATCH], recv[r]),
                dst=self._v(self.combine_dst[r], send[r]))
                for r in range(n)])
            if k >= self.warm:
                self._count(base, send, recv)
        if k >= self.warm:
            self._window_steps += 1
        tok = sp.begin("wait")
        jax.block_until_ready([x for row in self.results for x in row
                               if x is not None])
        sp.end(tok)
        return failed

    def _count(self, base, send, recv) -> None:
        """Interconnect bytes of one layer's collectives, busiest chip."""
        n, item = self.n, 2
        if n < 2:
            return
        self._ici[base + COUNTS] += (n - 1) * self.local * 4
        rows = max(max(sum(s) - s[r], sum(v) - v[r])
                   for r, (s, v) in enumerate(zip(send, recv)))
        for kind in (DISPATCH, COMBINE):
            self._ici[base + kind] += rows * self.hidden * item

    def answer(self, b: int, keep: bool = True):
        """This step's results of collective ``b``, one per rank. With
        ``keep`` a result that is a send buffer is copied, since the next
        step donates it."""
        out = []
        for r in range(self.n):
            x = self.results[r][b]
            if keep and x is not None and \
                    any(x is s for s in self.sends[r]):
                x = data.copy(x)
            out.append(x)
        return out

    def check(self, key, answers, device):
        """answers: [(step, unit, [array per rank or None])]. Returns
        (max_err, compared, missing); each rank's answer is compared on
        the chip that holds it."""
        return ep_check.answer_errors(key, answers, {
            "n": self.n, "rows": self.rows, "hidden": self.hidden,
            "tokens": self.tokens, "topk": self.topk, "local": self.local,
            "fresh": FRESH}, self.weights)

    def close(self) -> None:
        self._watchdog.cancel()
        self.sends = self.stampers = self.routers = self.results = None
        self.dispatch_dst = self.combine_dst = None


def _give_up(reason: str) -> None:
    """End the run here, non-zero, with its reason on one line."""
    print(f"ep_alltoallv: {reason}", file=sys.stderr, flush=True)
    sys.stdout.flush()
    os._exit(3)
