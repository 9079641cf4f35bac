"""Multi-host bootstrap sugar — the launcher-integration layer.

Reference users bootstrap UCC through MPI (`test/mpi`), torch.distributed
stores (torch-ucc), or a custom OOB. This module is the TPU build's
canonical recipe: one call wires the TCP store OOB, (optionally)
jax.distributed for a multi-controller device mesh, a context per local
chip, and a world team — the complete pod bring-up
(SURVEY §3.1-3.3 call stacks, executed for you).

Environment-driven (the torchrun/mpirun shape)::

    # per host:  UCC_BOOTSTRAP=host0:29500 UCC_RANK=<proc> UCC_NPROCS=<n>
    world = ucc_tpu.bootstrap.World.from_env()
    team  = world.team          # spans every rank of every process
    world.finalize()

Explicit::

    world = World(rank=proc_id, nprocs=2, coordinator="host0:29500",
                  ranks_per_proc=4, jax_distributed=True)
"""
from __future__ import annotations

import os
import threading
from typing import List, Optional

from .status import Status, UccError


class World:
    """All ranks of THIS process plus the world team over every process.

    ``ranks_per_proc`` contexts are created (rank == chip model: context
    i claims local device i); ``self.teams[i]`` / ``self.contexts[i]``
    are this process's members, ``self.team`` is members' team 0 for the
    common one-rank-per-process case.
    """

    def __init__(self, rank: int, nprocs: int,
                 coordinator: str = "127.0.0.1:29500",
                 ranks_per_proc: int = 1,
                 jax_distributed: bool = False,
                 lib_params=None, timeout: float = 120.0):
        import ucc_tpu
        from ucc_tpu import ContextParams, TcpStoreOob, TeamParams
        from ucc_tpu.core.oob import (TcpTreeOob, parse_node_sizes,
                                      tree_mode_enabled)

        host, port_s = coordinator.rsplit(":", 1)
        base_port = int(port_s)
        self.proc_rank = rank
        self.nprocs = nprocs
        n = nprocs * ranks_per_proc
        self.world_size = n

        # bootstrap topology (ISSUE 8): UCC_OOB_TREE=y|n|auto selects the
        # tree-structured store exchange (per-node leader stores + radix-
        # bounded parent stores, O(log n) rounds) over the single flat
        # store every rank funnels through. auto = tree from
        # UCC_OOB_TREE_THRESH ranks up, LOOPBACK coordinators only (all
        # group stores bind on the coordinator host, so auto must never
        # break a multi-host flat bootstrap; explicit y asserts
        # single-host). Node shape from UCC_OOB_TREE_PPN (int or cyclic
        # comma list), defaulting to ranks_per_proc so each process's
        # ranks share one leader store. All knobs honor UCC_CONFIG_FILE.
        from ucc_tpu.core.oob import _knob as _oob_knob
        tree_ppn = parse_node_sizes(_oob_knob("UCC_OOB_TREE_PPN", "")) \
            or ([ranks_per_proc] if ranks_per_proc > 1 else None)
        use_tree = tree_mode_enabled(n, host=host)
        if use_tree:
            # port block: [base+3, ...) — base+0/+1 stay the legacy flat
            # stores' ports, base+2 stays jax.distributed's
            tree_ports = TcpTreeOob.ports_needed(n, ppn=tree_ppn)

            def ctx_oob(r):
                return TcpTreeOob(r, n, host=host, base_port=base_port + 3,
                                  key="ucc-ctx", ppn=tree_ppn,
                                  timeout_s=timeout)

            def team_oob(r):
                return TcpTreeOob(r, n, host=host,
                                  base_port=base_port + 3 + tree_ports,
                                  key="ucc-team", ppn=tree_ppn,
                                  timeout_s=timeout)
        else:
            def ctx_oob(r):
                return TcpStoreOob(r, n, host=host, port=base_port)

            def team_oob(r):
                return TcpStoreOob(r, n, host=host, port=base_port + 1)

        if jax_distributed:
            import jax
            jax.distributed.initialize(coordinator_address=f"{host}:"
                                       f"{base_port + 2}",
                                       num_processes=nprocs,
                                       process_id=rank)
        # initialize the jax backend ONCE on this thread before context
        # threads race into device discovery: cold multi-thread backend
        # init can deadlock (TL/XLA context create probes devices)
        from .utils.backend import setup_backend
        setup_backend(virtual_cpu_devices=max(2, ranks_per_proc))

        my_ranks = [rank * ranks_per_proc + i for i in range(ranks_per_proc)]
        self.libs = [ucc_tpu.init(lib_params) if lib_params is not None
                     else ucc_tpu.init() for _ in my_ranks]
        self.contexts: List = [None] * ranks_per_proc
        self.teams: List = [None] * ranks_per_proc
        # per-phase error lists: a context thread that outlives its join
        # timeout must not have its late exception misattributed to the
        # team phase — and a still-alive thread after join IS the error
        # (it keeps running as a daemon against half-torn-down state)
        ctx_errs: List = []

        def mk(i, r):
            try:
                self.contexts[i] = ucc_tpu.Context(
                    self.libs[i], ContextParams(oob=ctx_oob(r)))
            except Exception as e:  # noqa: BLE001
                ctx_errs.append(e)

        ths = [threading.Thread(target=mk, args=(i, r), daemon=True)
               for i, r in enumerate(my_ranks)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=timeout)
        if any(t.is_alive() for t in ths):
            self._teardown_partial()
            raise UccError(Status.ERR_TIMED_OUT,
                           "bootstrap: context create timed out (thread "
                           "still running)")
        if ctx_errs:
            self._teardown_partial()
            raise ctx_errs[0]
        if any(c is None for c in self.contexts):
            self._teardown_partial()
            raise UccError(Status.ERR_TIMED_OUT,
                           "bootstrap: context create timed out")

        team_errs: List = []

        def mkteam(i, r):
            try:
                self.teams[i] = self.contexts[i].create_team_post(
                    TeamParams(oob=team_oob(r)))
            except Exception as e:  # noqa: BLE001
                team_errs.append(e)

        ths = [threading.Thread(target=mkteam, args=(i, r), daemon=True)
               for i, r in enumerate(my_ranks)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=timeout)
        try:
            if any(t.is_alive() for t in ths):
                raise UccError(Status.ERR_TIMED_OUT,
                               "bootstrap: team create timed out (thread "
                               "still running)")
            if team_errs:
                raise team_errs[0]
            if any(t is None for t in self.teams):
                raise UccError(Status.ERR_TIMED_OUT,
                               "bootstrap: team create timed out")
            import time as _time
            deadline = _time.monotonic() + timeout
            while True:
                sts = [t.create_test() for t in self.teams]
                for c in self.contexts:
                    c.progress()
                if all(s == Status.OK for s in sts):
                    break
                bad = [s for s in sts if s.is_error]
                if bad:
                    raise UccError(bad[0], "bootstrap: team create failed")
                if _time.monotonic() > deadline:
                    raise UccError(Status.ERR_TIMED_OUT,
                                   "bootstrap: team create timed out")
        except BaseException:
            self._teardown_partial()
            raise

    def _teardown_partial(self) -> None:
        """Best-effort destruction of whatever the failed bootstrap
        created, so the caller does not leak listeners/threads."""
        for t in getattr(self, "teams", []) or []:
            if t is not None:
                try:
                    t.destroy()
                except Exception:  # noqa: BLE001
                    pass
        self.teams = []
        for c in getattr(self, "contexts", []) or []:
            if c is not None:
                try:
                    c.destroy()
                except Exception:  # noqa: BLE001
                    pass
        self.contexts = []

    # ------------------------------------------------------------------
    @property
    def team(self):
        return self.teams[0]

    @property
    def context(self):
        return self.contexts[0]

    def progress(self) -> None:
        for c in self.contexts:
            c.progress()

    def finalize(self) -> None:
        for t in self.teams:
            if t is not None:
                t.destroy()
        for c in self.contexts:
            if c is not None:
                c.destroy()

    # ------------------------------------------------------------------
    @classmethod
    def from_env(cls, **kw) -> "World":
        """torchrun-style: UCC_BOOTSTRAP=host:port UCC_RANK UCC_NPROCS
        [UCC_RANKS_PER_PROC] [UCC_JAX_DISTRIBUTED=y]."""
        coord = os.environ.get("UCC_BOOTSTRAP", "127.0.0.1:29500")
        rank = int(os.environ.get("UCC_RANK", "0"))
        nprocs = int(os.environ.get("UCC_NPROCS", "1"))
        rpp = int(os.environ.get("UCC_RANKS_PER_PROC", "1"))
        jd = os.environ.get("UCC_JAX_DISTRIBUTED", "n").lower() in (
            "y", "yes", "1", "on")
        kw.setdefault("ranks_per_proc", rpp)
        kw.setdefault("jax_distributed", jd)
        return cls(rank=rank, nprocs=nprocs, coordinator=coord, **kw)
