"""Generator ``fsdp2_units``: the parameter and gradient collectives of an
FSDP2 training step, as torch's ``fully_shard`` drives ProcessGroupUCC
with ``MixedPrecisionPolicy(param_dtype=bfloat16, reduce_dtype=float32)``
and ``reshard_after_forward=False`` (torchtitan's default under pipeline
parallelism), one unit per block and the stage's root module holding the
rest (``archs/<architecture>.py`` ``unit_sizes``).

One step, through the eager API (``collective_init`` -> ``post`` ->
``test`` until complete -> ``finalize``), every request non-persistent and
not in place, all ranks posting from this one thread:

1. the stand-in optimizer writes ``FRESH`` new elements at the head of
   each rank's bf16 shard of every unit, in place (donated), so every
   allgather moves new data, as DDP's buckets do after backward;
2. forward, units in order: the ALLGATHER of unit u, the rank's shard as
   ``src`` into a ``dst`` of n shards, posted ``prefetch`` units ahead of
   the one waited on (FSDP2's implicit forward prefetch);
3. backward, units in reverse: the stand-in backward writes unit u's
   float32 gradient whole (n shards, new in every element), then, with
   ``reduce_scatters_in_flight`` already posted, waits for the oldest to
   finish on the device (FSDP2 frees a reduce-scatter's input behind the
   next), then posts the AVG REDUCE_SCATTER of unit u into the rank's
   float32 gradient shard. Unit u's gathered output is then no longer the
   step's (reshard after backward); the stream still holds it until the
   step ends, for the check.

The step ends when every result is ready on every chip. The ranks present
are the cell's chips; on fewer chips than the FSDP degree each rank posts
its own share, the per-rank count, and nothing stands in for the absent
ranks (the configuration's ``cut_to_chips``).
"""
from __future__ import annotations

import functools
import time
from collections import deque

from yardstick import data, fsdp_check, spec

DTYPES = {"bfloat16": ("BFLOAT16", 2), "float32": ("FLOAT32", 4)}
#: elements the stand-in optimizer rewrites per shard and step
FRESH = 1024
TEST_TIMEOUT_S = 120.0
KINDS = ("allgather", "reduce_scatter")


class CollBytes(list):
    """Bytes per chip of each collective of a step (an allgather's output,
    a reduce-scatter's input); ``kinds`` says which collective each is."""

    def __init__(self, values, kinds):
        super().__init__(values)
        self.kinds = list(kinds)


@functools.lru_cache(maxsize=None)
def _grad_fn(count: int):
    import jax

    def bench_grad(key, rank, unit, step):
        return fsdp_check.grad(key, rank, unit, step, count)
    return jax.jit(bench_grad)


class Grads:
    """The stand-in backward of one rank: unit u's float32 gradient at the
    step set last, made whole on the rank's device."""

    def __init__(self, key, rank: int, counts, device):
        import jax
        import numpy as np
        self.counts = counts
        self.device = device
        self.key = jax.device_put(key, device)
        self.rank = jax.device_put(np.uint32(rank), device)
        self.units = [jax.device_put(np.uint32(u), device)
                      for u in range(len(counts))]
        self.step = None

    def set_step(self, step: int) -> None:
        import jax
        import numpy as np
        self.step = jax.device_put(np.uint32(step), self.device)

    def __call__(self, u: int):
        return _grad_fn(self.counts[u])(self.key, self.rank, self.units[u],
                                        self.step)


class Stream:
    def __init__(self, job, devices, config, traffic, key, spans):
        import ucc_tpu
        dep = config["deployment"]
        n, degree = job.n, int(dep["fsdp_parallel"])
        if degree % n or dep["reduce_op"] != "avg":
            raise ValueError(f"deployment {dep} is not an AVG FSDP group "
                             f"that {n} rank(s) can hold")
        arch = spec.load_module(
            spec.BENCH_DIR / "archs" / f"{config['architecture']}.py",
            "arch_" + config["architecture"])
        #: per rank, per unit in forward order
        self.counts = [size // degree for _, size in arch.unit_sizes(config)]
        self.prefetch = int(traffic["prefetch"])
        self.in_flight = int(traffic["reduce_scatters_in_flight"])
        ag_dt, ag_item = DTYPES[dep["param_dtype"]]
        rs_dt, rs_item = DTYPES[dep["reduce_dtype"]]
        self.nu = len(self.counts)
        self.units = 2 * self.nu
        self.coll_bytes = CollBytes(
            [n * c * ag_item for c in self.counts] +
            [n * c * rs_item for c in self.counts],
            [k for k in KINDS for _ in self.counts])
        self.describe = (
            f"{n} rank(s) of FSDP={degree}, {self.nu} units, "
            f"{self.units} requests per rank per step: "
            f"{sum(self.coll_bytes[:self.nu]) / 1e9:.4f} GB {dep['param_dtype']}"
            f" allgathered out, {sum(self.coll_bytes[self.nu:]) / 1e9:.4f} GB "
            f"{dep['reduce_dtype']} reduce-scattered in, per rank per step")
        self.u = ucc_tpu
        self.job = job
        self.n = n
        self.spans = spans
        self.ag_dt = getattr(ucc_tpu.DataType, ag_dt)
        self.rs_dt = getattr(ucc_tpu.DataType, rs_dt)
        self.shards = [data.make_rank(key, r, self.counts, devices[r])
                       for r in range(n)]
        self.stampers = [data.Stamper(key, r, self.counts, FRESH,
                                      devices[r]) for r in range(n)]
        self.grads = [Grads(key, r, [n * c for c in self.counts],
                            devices[r]) for r in range(n)]
        self.results = [[None] * self.units for _ in range(n)]

    def ready(self) -> None:
        import jax
        jax.block_until_ready(self.shards)

    def _post(self, b: int, argses):
        """Every rank's request of collective ``b``, initialised and
        posted."""
        sp = self.spans
        tok = sp.begin("init")
        reqs = [self.job.teams[r].collective_init(a)
                for r, a in enumerate(argses)]
        sp.end(tok)
        tok = sp.begin("post")
        for q in reqs:
            q.post()
        sp.end(tok)
        return b, reqs, argses

    def _wait(self, k: int, posted, device: bool = False) -> int:
        """Test until every rank's request of ``posted`` is complete,
        finalize, and keep the results; with ``device`` also wait until
        they are ready on the chips. Returns 1 if any did not complete
        OK."""
        import jax
        u = self.u
        sp = self.spans
        b, reqs, argses = posted
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
                raise TimeoutError(f"collective {b} step {k}: {sts}")
        sp.end(tok)
        tok = sp.begin("finalize")
        for q in reqs:
            q.finalize()
        sp.end(tok)
        out = [a.dst.buffer if s == u.Status.OK else None
               for a, s in zip(argses, sts)]
        for r, x in enumerate(out):
            self.results[r][b] = x
        if device:
            tok = sp.begin("wait")
            jax.block_until_ready([x for x in out if x is not None])
            sp.end(tok)
        return int(not all(s == u.Status.OK for s in sts))

    def _allgather(self, unit: int):
        u = self.u
        c = self.counts[unit]
        return self._post(unit, [u.CollArgs(
            coll_type=u.CollType.ALLGATHER,
            src=u.BufferInfo(self.shards[r][unit], c, self.ag_dt,
                             mem_type=u.MemoryType.TPU),
            dst=u.BufferInfo(None, self.n * c, self.ag_dt,
                             mem_type=u.MemoryType.TPU))
            for r in range(self.n)])

    def _reduce_scatter(self, unit: int, grads):
        u = self.u
        c = self.counts[unit]
        return self._post(self.nu + unit, [u.CollArgs(
            coll_type=u.CollType.REDUCE_SCATTER,
            src=u.BufferInfo(grads[r], self.n * c, self.rs_dt,
                             mem_type=u.MemoryType.TPU),
            dst=u.BufferInfo(None, c, self.rs_dt, mem_type=u.MemoryType.TPU),
            op=u.ReductionOp.AVG) for r in range(self.n)])

    def step(self, k: int) -> int:
        """Run step ``k``; returns the number of collectives that did not
        complete OK on every rank."""
        import jax
        sp = self.spans
        self.results = [[None] * self.units for _ in range(self.n)]
        tok = sp.begin("stamp")
        self.shards = [st(self.shards[r], k)
                       for r, st in enumerate(self.stampers)]
        for g in self.grads:
            g.set_step(k)
        sp.end(tok)
        failed = 0
        ahead = deque(self._allgather(unit)
                      for unit in range(min(self.prefetch, self.nu)))
        for unit in range(self.nu):
            if unit + self.prefetch < self.nu:
                ahead.append(self._allgather(unit + self.prefetch))
            failed += self._wait(k, ahead.popleft())
        flying = deque()
        for unit in reversed(range(self.nu)):
            tok = sp.begin("grad")
            grads = [g(unit) for g in self.grads]
            sp.end(tok)
            if len(flying) == self.in_flight:
                failed += self._wait(k, flying.popleft(), device=True)
            flying.append(self._reduce_scatter(unit, grads))
            del grads
        while flying:
            failed += self._wait(k, flying.popleft(), device=True)
        tok = sp.begin("wait")
        jax.block_until_ready([x for row in self.results for x in row
                               if x is not None])
        sp.end(tok)
        return failed

    def answer(self, b: int, keep: bool = True):
        """This step's results of collective ``b``, one per rank. With
        ``keep`` a result that is a shard itself (an allgather on one
        rank) is copied, since the next step's optimizer donates it."""
        out = []
        for r in range(self.n):
            x = self.results[r][b]
            if keep and x is not None and \
                    any(x is s for s in self.shards[r]):
                x = data.copy(x)
            out.append(x)
        return out

    def check(self, key, answers, device):
        """answers: [(step, b, [array per rank or None])]. Returns
        (max_err, compared, missing); each rank's answer is compared on
        the chip that holds it."""
        return fsdp_check.answer_errors(key, answers, self.n, self.counts,
                                        FRESH)

    def close(self) -> None:
        self.shards = self.stampers = self.grads = self.results = None
