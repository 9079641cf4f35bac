"""ucc_info — introspection CLI.

Mirrors /root/reference/tools/info/ucc_info.c (:19-36): ``-v`` version and
build info, ``-cf`` every config variable with defaults and docs, ``-s``
the default score map of a probe team, ``-A`` per-TL algorithm lists,
``-c`` coll/memory/datatype capability matrix.
"""
from __future__ import annotations

import argparse
import sys

import ucc_tpu
from ucc_tpu.constants import (COLL_TYPE_LIST, CollType, DataType,
                               MemoryType, ReductionOp, coll_type_str)
from ucc_tpu.core.components import (available_cls, available_tls,
                                     discover_components, get_tl)
from ucc_tpu.utils.config import registered_tables


def print_version() -> None:
    print(f"# UCC-TPU version {ucc_tpu.__version__}")
    print("#  collective communication framework for TPU systems")
    print(f"#  CLs: {', '.join(available_cls())}")
    print(f"#  TLs: {', '.join(available_tls())}")
    import jax
    print(f"#  jax {jax.__version__}, default backend: "
          f"{jax.default_backend()}")


def print_config() -> None:
    discover_components()
    from ucc_tpu.core import lib as _lib  # ensure global table registered
    for name, table in sorted(registered_tables().items()):
        print(f"#\n# {name or 'global'}\n#")
        for f in table.fields:
            env = table.field_env_name(f)
            print(f"{env}={f.default}")
            if f.doc:
                print(f"#   {f.doc}")


def print_algorithms() -> None:
    discover_components()
    print("# per-TL algorithm lists (@id or @name usable in UCC_TL_X_TUNE)")
    for tl_name in available_tls():
        tl = get_tl(tl_name)
        print(f"\ncl/basic tl/{tl_name}:")
        team_cls = tl.team_cls
        if not hasattr(team_cls, "alg_table") or tl_name == "self":
            for c in COLL_TYPE_LIST:
                if c & tl.SUPPORTED_COLLS:
                    print(f"  {coll_type_str(c)}: 0: direct")
            continue
        # instantiate nothing: read the table via a stub where possible
        try:
            import types
            stub = object.__new__(team_cls)
            stub.TL_CLS = tl
            table = team_cls.alg_table(stub)
            for coll, specs in sorted(table.items()):
                algs = " ".join(f"{s.id}:{s.name}" for s in specs)
                print(f"  {coll_type_str(coll)}: {algs}")
        except Exception:  # noqa: BLE001 - table needs a live team
            for c in COLL_TYPE_LIST:
                if c & tl.SUPPORTED_COLLS:
                    print(f"  {coll_type_str(c)}: (runtime)")


def print_scores(team_size: int = 1) -> None:
    """Default score map of a probe team (the reference prints the score
    map at team create; -s does it standalone). ``team_size > 1`` builds
    an in-process multi-rank job (thread OOB, the gtest UccJob shape) so
    multi-rank-only rows show — e.g. the CL/HIER rows, which need a
    NODE/NET decomposition: ``UCC_TOPO_FAKE_PPN=2 ucc_info -s 4``."""
    if team_size <= 1:
        lib = ucc_tpu.init()
        ctx = ucc_tpu.Context(lib)
        team = ctx.create_team(ucc_tpu.TeamParams())
        print(team.score_map.print_info("probe team (size 1)"))
        team.destroy()
        ctx.destroy()
        return

    import threading
    import time

    from ucc_tpu import ContextParams, Status, TeamParams, ThreadOobWorld
    n = team_size
    world = ThreadOobWorld(n)
    libs = [ucc_tpu.init() for _ in range(n)]
    ctxs: list = [None] * n
    errs: list = []

    def mk(r):
        try:
            ctxs[r] = ucc_tpu.Context(libs[r],
                                      ContextParams(oob=world.endpoint(r)))
        except Exception as e:  # noqa: BLE001 - reported below
            errs.append((r, e))

    ths = [threading.Thread(target=mk, args=(r,)) for r in range(n)]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    if errs:
        raise RuntimeError(f"probe context create failed: {errs}")
    tw = ThreadOobWorld(n)
    teams = [c.create_team_post(TeamParams(oob=tw.endpoint(i)))
             for i, c in enumerate(ctxs)]
    deadline = time.monotonic() + 60
    while True:
        sts = [t.create_test() for t in teams]
        for c in ctxs:
            c.progress()
        if all(s == Status.OK for s in sts):
            break
        bad = [s for s in sts if s.is_error]
        if bad:
            raise RuntimeError(f"probe team create failed: {bad}")
        if time.monotonic() > deadline:
            raise RuntimeError("probe team create timed out (60s)")
    print(teams[0].score_map.print_info(f"probe team (size {n})"))
    # resolved hierarchy next to the score rows (ISSUE 8 satellite): the
    # tree cl/hier derived from the (possibly faked) topology, so a
    # mis-detected layout shows here instead of silently running flat —
    # e.g. `UCC_TOPO_FAKE_PPN=2 UCC_TOPO_FAKE_NODES_PER_POD=2 ucc_info -s 8`
    for cl in teams[0].cl_teams:
        describe = getattr(cl, "describe_topology", None)
        if describe is not None:
            print(f"# resolved {cl.name} hierarchy:")
            print(describe())
    for t in teams:
        t.destroy()
    for c in ctxs:
        c.destroy()


def print_caps() -> None:
    print("# collective types:", ", ".join(coll_type_str(c)
                                           for c in COLL_TYPE_LIST))
    print("# memory types:", ", ".join(m.name.lower()
                                       for m in (MemoryType.HOST,
                                                 MemoryType.TPU)))
    print("# datatypes:", ", ".join(d.name.lower() for d in DataType))
    print("# reduction ops:", ", ".join(o.name.lower()
                                        for o in ReductionOp))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="ucc_info")
    p.add_argument("-v", "--version", action="store_true")
    p.add_argument("-cf", "--config", action="store_true",
                   help="print all config variables")
    p.add_argument("-s", "--scores", nargs="?", const=1, type=int,
                   default=None, metavar="N",
                   help="print default score map (optional N = probe "
                        "team size; N>1 shows multi-rank-only rows, "
                        "e.g. CL/HIER under UCC_TOPO_FAKE_PPN)")
    p.add_argument("-A", "--algorithms", action="store_true",
                   help="print per-TL algorithm lists")
    p.add_argument("-c", "--caps", action="store_true",
                   help="print capability matrix")
    args = p.parse_args(argv)
    if args.scores is not None and args.scores < 1:
        p.error("-s team size must be >= 1")
    if not any(v not in (None, False) for v in vars(args).values()):
        args.version = True
    if args.scores is not None or args.caps:
        # these create contexts, whose device TLs enumerate the backend
        from ..utils.backend import setup_backend
        setup_backend(virtual_cpu_devices=4)
    if args.version:
        print_version()
    if args.caps:
        print_caps()
    if args.config:
        print_config()
    if args.algorithms:
        print_algorithms()
    if args.scores is not None:
        print_scores(args.scores)
    return 0


if __name__ == "__main__":
    sys.exit(main())
