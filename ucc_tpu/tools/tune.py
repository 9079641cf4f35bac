"""ucc_tune — offline autotuner sweep CLI.

Sweeps every registered score-map candidate over a message-size grid per
(coll, mem) on a live in-process team, picks the measured winner per
grid point, and compiles the winners into the topology-keyed tuning
cache that ``UCC_TUNER=offline|online`` loads at team activation
(score/tuner.py). Later runs on a same-shaped machine then start tuned
with zero warmup.

Examples::

    # measure + write ~/.cache/ucc_tpu/tune.json for a 4-rank host team
    python -m ucc_tpu.tools.tune -p 4 -c allreduce -b 8 -e 1M

    # keep the raw measurements, write the cache somewhere explicit
    python -m ucc_tpu.tools.tune -p 8 -c allreduce,allgather \\
        --measurements sweep.jsonl -o /tmp/tune.json

    # compile a cache from a perftest sweep instead of measuring here
    python -m ucc_tpu.tools.perftest -c allreduce --sweep > sweep.jsonl
    python -m ucc_tpu.tools.tune --from sweep.jsonl -p 4
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional

import ucc_tpu
from ucc_tpu.api.types import coll_args_msgsize
from ucc_tpu.constants import (CollType, DataType, MemoryType, ReductionOp,
                               dt_size)
from ucc_tpu.score.tuner import (cand_label, compile_measurements,
                                 measure_candidate, measurement_record,
                                 resolve_cache_path, store_entries,
                                 sweep_candidates, topo_signature)
from ucc_tpu.utils.config import memunits_str, parse_memunits

from .perftest import COLLS, InProcJob, lat_stats, make_args


class _Job(InProcJob):
    """perftest's in-process job with lib config overrides — the sweep
    itself always runs with the tuner OFF so measurements see the
    untouched static map."""

    def __init__(self, n: int, overrides: Optional[dict] = None,
                 create_timeout: float = 120.0):
        super().__init__(n, lib_overrides=overrides,
                         create_timeout=create_timeout)


def run_sweep(job: _Job, colls: List[str], sizes: List[int], iters: int,
              warmup: int, mem: MemoryType = MemoryType.HOST,
              dt: DataType = DataType.FLOAT32,
              op: ReductionOp = ReductionOp.SUM,
              verbose: bool = True) -> List[dict]:
    """Measure every candidate at every grid point; one measurement
    record per (coll, size, algorithm) — the same format
    ``ucc_perftest --sweep`` emits."""
    records: List[dict] = []
    n = job.n
    esz = dt_size(dt)
    from ucc_tpu.score import cost as _cost
    cost_model = _cost.load_model()
    for cname in colls:
        ct = COLLS[cname]
        for size in sizes:
            count = max(1, size // esz)
            if ct == CollType.ALLTOALLV:
                from . import perftest as _pt
                _pt._TRAFFIC_MATRIX = _pt.gen_traffic_matrix(
                    "uniform", n, count, 7)
            argses = [make_args(ct, r, n, count, dt, op, mem, False, 0,
                                True, None) for r in range(n)]
            msgsize = coll_args_msgsize(argses[0], n, 0)
            cands = sweep_candidates(job.teams[0], ct, mem, msgsize)
            for idx in range(len(cands)):
                comp, alg = cand_label(cands[idx])
                lats = measure_candidate(job.teams, job.contexts, argses, ct,
                                         mem, msgsize, idx, iters, warmup)
                if lats is None:
                    if verbose:
                        print(f"# ucc_tune: {cname} {memunits_str(size)} "
                              f"{comp}/{alg}: unsupported/failed, skipped",
                              file=sys.stderr, flush=True)
                    continue
                st = lat_stats(lats)
                records.append(measurement_record(
                    cname, mem, n, (comp, alg), size, count, iters, st,
                    precision=cands[idx].precision, gen=cands[idx].gen,
                    predicted_us=_cost.predict_for_record(
                        cost_model, cands[idx].gen, n, size)))
                if verbose:
                    print(f"# {cname:>12} {memunits_str(size):>8} "
                          f"{comp}/{alg:<20} p50 {st['p50_us']:>10.2f}us",
                          flush=True)
    return records


def _summary(job: _Job, records: List[dict], entries: List[dict]) -> None:
    """Measured winner vs what the static map would have picked."""
    by_point = {}
    for r in records:
        key = (r["coll"], r["mem"], r["size_bytes"])
        cur = by_point.get(key)
        if cur is None or r["p50_us"] < cur["p50_us"]:
            by_point[key] = r
    print("# grid winners (measured) vs static defaults:")
    for (coll, mem, size), win in sorted(by_point.items()):
        ct = COLLS[coll]
        mt = MemoryType.parse(mem)
        count = max(1, size // 4)
        if ct == CollType.ALLTOALLV:
            from . import perftest as _pt
            _pt._TRAFFIC_MATRIX = _pt.gen_traffic_matrix(
                "uniform", job.n, count, 7)
        argses = make_args(ct, 0, job.n, count, DataType.FLOAT32,
                           ReductionOp.SUM, mt, False, 0, False, None)
        msgsize = coll_args_msgsize(argses, job.n, 0)
        cands = sweep_candidates(job.teams[0], ct, mt, msgsize)
        static = "/".join(cand_label(cands[0])) if cands else "?"
        mark = "" if static == f"{win['comp']}/{win['alg']}" else "   <- learned"
        print(f"#   {coll:>12} {memunits_str(size):>8}: "
              f"{win['comp']}/{win['alg']} ({win['p50_us']}us) "
              f"vs static {static}{mark}")
    print(f"# compiled {len(entries)} cache entries")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="ucc_tune",
        description="offline autotuner sweep: measure every score-map "
                    "candidate over a msg-size grid and compile the "
                    "winners into the UCC_TUNER tuning cache")
    p.add_argument("-c", "--colls", default="allreduce",
                   help="comma-separated collectives to sweep")
    p.add_argument("-b", "--begin", default="8", help="min size (bytes)")
    p.add_argument("-e", "--end", default="1M", help="max size (bytes)")
    p.add_argument("-n", "--iters", type=int, default=20)
    p.add_argument("-w", "--warmup", type=int, default=3)
    p.add_argument("-p", "--nprocs", type=int, default=4,
                   help="in-process ranks of the live team")
    p.add_argument("-m", "--mem", default="host")
    p.add_argument("-o", "--output", default="",
                   help="cache path (default: UCC_TUNER_CACHE or "
                        "~/.cache/ucc_tpu/tune.json)")
    p.add_argument("--measurements", default="",
                   help="also write the raw measurement records (JSONL)")
    p.add_argument("--from", dest="from_file", default="",
                   help="compile the cache from an existing measurement "
                        "file (e.g. `ucc_perftest --sweep` output) "
                        "instead of measuring here")
    p.add_argument("--signature", default="",
                   help="topology signature for --from (default: probe "
                        "a live -p team for it)")
    p.add_argument("--dry-run", action="store_true",
                   help="print the compiled entries, write nothing")
    p.add_argument("--quant", nargs="?", const="env", default="",
                   choices=["env", "int8", "fp8"],
                   help="include quantized candidates in the sweep: sets "
                        "UCC_QUANT for the probe jobs (bare --quant keeps "
                        "the ambient value, defaulting to int8). With "
                        "UCC_QUANT already exported, quantized candidates "
                        "are swept automatically — this flag just makes "
                        "the opt-in explicit per run")
    p.add_argument("--gen", nargs="?", const="all", default="",
                   metavar="FAMILIES",
                   help="include GENERATED candidates (ucc_tpu/dsl) in "
                        "the sweep: sets UCC_GEN=y for the probe jobs; "
                        "an optional value restricts/parameterizes the "
                        "family grids (UCC_GEN_FAMILIES syntax, e.g. "
                        "'ring(1,2,4),rhd(2,8)'). Winners compile into "
                        "the tuning cache with their family/parameter "
                        "string, so a later UCC_TUNER=offline run with "
                        "UCC_GEN=y starts on the generated winner")
    p.add_argument("--gen-search", action="store_true",
                   help="cost-model-guided program SEARCH instead of "
                        "grid enumeration (ISSUE 14): fit the "
                        "alpha-beta model (from --from records when "
                        "given, else a live probe), propose the joint "
                        "family x radix x chunking x depth x "
                        "quantization (x hierarchy, on multi-node "
                        "topologies) space, prune to the "
                        "UCC_GEN_SEARCH_BUDGET predicted-cheapest per "
                        "grid point, refine by successive halving with "
                        "interleaved measurement, and persist winners "
                        "into the search cache AND the tuning cache "
                        "with origin 'searched' + predicted-vs-measured "
                        "provenance")
    p.add_argument("--search-budget", type=int, default=0,
                   help="override UCC_GEN_SEARCH_BUDGET for --gen-search")
    p.add_argument("--device", action="store_true",
                   help="with --gen-search: search DEVICE programs "
                        "(ucc_tpu/dsl/lower_device) instead of host "
                        "ones — the device-lowerable space priced over "
                        "the ICI link class, the predicted-cheapest "
                        "shortlist registered on a TPU-memtype xla "
                        "team (UCC_GEN_DEVICE_FAMILIES), refined by "
                        "successive halving against the monolithic lax "
                        "candidates; winning generated-device "
                        "selections land in the tuning cache with "
                        "mem 'tpu' and origin 'searched'")
    args = p.parse_args(argv)

    if args.quant:
        if args.quant in ("int8", "fp8"):
            os.environ["UCC_QUANT"] = args.quant
        elif not os.environ.get("UCC_QUANT"):
            os.environ["UCC_QUANT"] = "int8"
    if args.gen:
        os.environ["UCC_GEN"] = "y"
        if args.gen != "all":
            os.environ["UCC_GEN_FAMILIES"] = args.gen

    from ucc_tpu.utils.backend import setup_backend
    setup_backend(virtual_cpu_devices=max(args.nprocs, 4))

    cache_path = resolve_cache_path(
        args.output or os.environ.get("UCC_TUNER_CACHE", ""))
    mem = MemoryType.parse(args.mem)
    colls = [c.strip() for c in args.colls.split(",") if c.strip()]
    for c in colls:
        if c not in COLLS:
            p.error(f"unknown collective '{c}'")

    if args.gen_search:
        import json as _json

        from ucc_tpu.dsl.search import run_device_search, run_search
        from ucc_tpu.score import cost as _cost
        sizes = []
        size = max(parse_memunits(args.begin), 4)
        bmax = parse_memunits(args.end)
        while size <= bmax:
            sizes.append(size)
            size *= 2
        model = None
        if args.from_file:
            with open(args.from_file) as fh:
                records = [_json.loads(ln) for ln in fh
                           if ln.strip().startswith("{")]
            model = _cost.fit_records(
                [r for r in records if r.get("gen")],
                link="ici" if args.device else "shm")
            if model is not None:
                _cost.save_model(model)
                print(f"# cost model fitted from {args.from_file}: "
                      f"{model.source}")
        def print_report(rep, label):
            for res in rep.get("results") or []:
                for f in res.get("finalists") or []:
                    print(f"#   {res['coll']:>10} "
                          f"{memunits_str(res['size_bytes']):>8} "
                          f"{f['alg']:<24} measured "
                          f"{f['measured_us']}us"
                          + (f" predicted {f['predicted_us']}us"
                             if f.get("predicted_us") is not None
                             else ""))
            print(f"# {label} winners: {rep.get('winners')} "
                  f"({rep.get('tuner_entries', 0)} tuning-cache "
                  f"entries -> {cache_path})")

        search_fn = run_device_search if args.device else run_search
        rep = search_fn(
            # iters is the FIRST successive-halving rung; rungs double,
            # so the finalists' confirmation lands near the user's -n
            args.nprocs, colls, sizes, iters=max(3, args.iters // 4),
            budget=args.search_budget or None,
            quant_mode=os.environ.get("UCC_QUANT", "")
            if args.quant else "",
            tuner_cache=cache_path, model=model, verbose=True)
        print_report(rep, "device-search" if args.device else "search")
        return 0

    if args.from_file:
        with open(args.from_file) as fh:
            records = [json.loads(ln) for ln in fh
                       if ln.strip().startswith("{")]
        entries = compile_measurements(records)
        if args.signature:
            sig = args.signature
        else:
            # key the cache to the team shape the measurements came
            # from: a record's `ranks` field wins over -p, otherwise an
            # 8-rank sweep would silently land under a 4-rank signature
            ranks_in = {int(r["ranks"]) for r in records
                        if isinstance(r, dict) and r.get("ranks")}
            if len(ranks_in) > 1:
                p.error("--from file mixes team sizes "
                        f"({sorted(ranks_in)}); pass --signature")
            nprobe = args.nprocs
            if ranks_in and next(iter(ranks_in)) != nprobe:
                nprobe = next(iter(ranks_in))
                print(f"# ucc_tune: measurement file is {nprobe}-rank; "
                      f"probing a {nprobe}-rank team for the signature")
            job = _Job(nprobe, {"TUNER": "off"})
            try:
                sig = topo_signature(job.teams[0])
            finally:
                job.destroy()
    else:
        sizes = []
        size = max(parse_memunits(args.begin), 4)
        bmax = parse_memunits(args.end)
        while size <= bmax:
            sizes.append(size)
            size *= 2
        job = _Job(args.nprocs, {"TUNER": "off"})
        try:
            sig = topo_signature(job.teams[0])
            records = run_sweep(job, colls, sizes, args.iters, args.warmup,
                                mem)
            entries = compile_measurements(records)
            _summary(job, records, entries)
        finally:
            job.destroy()
        if args.measurements:
            with open(args.measurements, "w") as fh:
                for r in records:
                    fh.write(json.dumps(r) + "\n")
            print(f"# measurements -> {args.measurements}")

    if not entries:
        print("# ucc_tune: no usable measurements; nothing written",
              file=sys.stderr)
        return 1
    if args.dry_run:
        print(json.dumps({"signature": sig, "entries": entries}, indent=1))
        return 0
    store_entries(cache_path, sig, entries, source="offline")
    print(f"# tuning cache -> {cache_path} (signature {sig}, "
          f"{len(entries)} entries)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
