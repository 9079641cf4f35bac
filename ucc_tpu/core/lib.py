"""Library object — the root of the framework.

Reference: /root/reference/src/core/ucc_lib.c (``ucc_init_version``:291) and
ucc_constructor.c: parse global ``UCC_*`` config, load CL/TL component
frameworks, init each requested CL lib plus the TLs it needs, compute the
lib attr intersection (thread modes) / union (coll types).
"""
from __future__ import annotations

from typing import Dict, List, Optional

from ..api.types import LibAttr, LibParams
from ..constants import COLL_TYPE_ALL, CollType, ThreadMode
from ..status import Status, UccError
from ..utils.config import (Config, ConfigField, ConfigTable, parse_bool,
                            parse_enum, parse_list, parse_string,
                            parse_uint, register_table)
from ..utils.log import get_logger
from .components import (CL_REGISTRY, TL_REGISTRY, available_cls,
                         available_tls, discover_components, get_cl, get_tl)

logger = get_logger("core")

#: global config table (ucc_global_opts.c:35-121)
GLOBAL_CONFIG = register_table(ConfigTable(prefix="", name="global", fields=[
    ConfigField("CLS", "basic,hier", "comma-separated CL list ('all' for every "
                "available CL)", parse_list),
    ConfigField("TLS", "all", "comma-separated TL allow-list", parse_list),
    ConfigField("LOG_LEVEL", "warn", "ucc log level", parse_string),
    ConfigField("COLL_TRACE", "n", "log every collective init/post/finalize "
                "with the selected CL/TL", parse_bool),
    ConfigField("PROFILE_MODE", "", "profiling mode: log,accum", parse_string),
    ConfigField("PROFILE_FILE", "", "profiling output file", parse_string),
    # the obs knobs are read from the environment at import by
    # ucc_tpu/obs (same zero-cost pattern as PROFILE_MODE above); listed
    # here so `ucc_info -cf` documents them
    ConfigField("STATS", "n", "enable the metrics registry "
                "(counters/gauges/log2 histograms keyed by component/"
                "collective/algorithm); dumped at exit, on SIGUSR2, and "
                "every STATS_INTERVAL; read by the ucc_stats tool",
                parse_bool),
    ConfigField("STATS_FILE", "ucc_stats.json", "metrics dump file "
                "(JSON lines, one snapshot per dump)", parse_string),
    ConfigField("STATS_INTERVAL", "0", "seconds between periodic metric "
                "dumps (0 = exit/SIGUSR2 only)", parse_string),
    ConfigField("WATCHDOG_TIMEOUT", "0", "stall watchdog soft deadline in "
                "seconds: any task IN_PROGRESS longer triggers a one-shot "
                "diagnostic state dump (collective, algorithm, round, "
                "outstanding peers/tags, team state positions); 0 = off",
                parse_string),
    ConfigField("WATCHDOG_FILE", "ucc_watchdog.json", "watchdog state-dump "
                "file (JSON lines)", parse_string),
    ConfigField("WATCHDOG_ACTION", "dump", "escalation ladder: dump = "
                "diagnose only; cancel = also cancel tasks stuck past the "
                "hard deadline with ERR_TIMED_OUT (unwinds posted transport "
                "ops); abort = cancel EVERY in-flight task once one "
                "crosses the hard deadline and fail stalled team creates",
                parse_string),
    ConfigField("WATCHDOG_HARD_TIMEOUT", "0", "hard deadline in seconds "
                "for the cancel/abort watchdog actions (0 = 2x "
                "WATCHDOG_TIMEOUT)", parse_string),
    ConfigField("FAULT", "", "fault-injection spec (deterministic failure "
                "drills): drop=P,delay=P:S,error=P,post_error=P,"
                "kill=R[+R..] — probabilistic send drop/delay, send/recv "
                "post errors, pre-wire task post errors, and simulated "
                "dead ranks at the transport and task boundaries; empty = "
                "off (zero cost)", parse_string),
    ConfigField("FAULT_SEED", "0", "RNG seed for UCC_FAULT decisions: the "
                "same seed + spec replays the same drill", parse_string),
    ConfigField("FT", "none", "rank-failure recovery mode: none = failures "
                "are bounded but terminal (PR-2 behavior; zero cost); "
                "shrink = peer liveness + failure agreement + ULFM-style "
                "Team.shrink — survivors observe ERR_RANK_FAILED naming "
                "the dead ranks, agree on the failed set and recovery "
                "epoch, and rebuild the team without them (old-epoch "
                "traffic is fenced at the transport)", parse_string),
    ConfigField("HEARTBEAT_INTERVAL", "0.05", "seconds between liveness "
                "heartbeats published from each context's progress loop "
                "(UCC_FT=shrink only)", parse_string),
    ConfigField("HEARTBEAT_TIMEOUT", "2.0", "seconds without a peer "
                "heartbeat before the peer is declared failed and "
                "in-flight collectives depending on it are cancelled "
                "with ERR_RANK_FAILED (UCC_FT=shrink only)",
                parse_string),
    ConfigField("FT_GROW_TIMEOUT", "30.0", "seconds a Team.grow waits for "
                "every invited joiner to bootstrap before rolling back "
                "(ERR_TIMED_OUT naming the absent joiner; the pre-grow "
                "team stays fully usable)", parse_string),
    ConfigField("FT_AGREE_GRACE", "3", "bounded deadline extensions a "
                "fault-agreement round grants a pending peer whose "
                "heartbeat is still FRESH — slow-but-alive ranks are not "
                "condemned by the round timer alone (0 restores the "
                "timer-only PR-4 behavior)", parse_string),
    ConfigField("OOB_CONNECT_BACKOFF_BASE", "0.05", "initial TCP-store OOB "
                "connect retry backoff in seconds (exponential, full "
                "jitter)", parse_string),
    ConfigField("OOB_CONNECT_BACKOFF_MAX", "2.0", "TCP-store OOB connect "
                "retry backoff cap in seconds", parse_string),
    ConfigField("OOB_BOOTSTRAP_TIMEOUT", "120", "TCP-store OOB server-side "
                "bootstrap deadline in seconds: after it, registered "
                "ranks are failed with ERR_TIMED_OUT naming the absent "
                "ranks instead of hanging the job (<=0 = wait forever)",
                parse_string),
    ConfigField("OOB_TREE", "auto", "bootstrap store topology: n = one "
                "flat store every rank connects to (O(n) server fan-in); "
                "y = tree-structured exchange (per-node leader stores + "
                "radix-bounded parent stores, O(log n) rounds and "
                "max(ppn, radix) fan-in per server — every store binds "
                "the coordinator host, so y asserts a single-host job); "
                "auto = tree from OOB_TREE_THRESH ranks up, loopback "
                "coordinators only", parse_string),
    ConfigField("OOB_TREE_PPN", "", "ranks-per-node shape of the "
                "bootstrap tree: an int (nodes of N) or a cyclic comma "
                "list of node sizes; empty = ranks_per_proc under "
                "bootstrap.World, else radix-sized blocks", parse_string),
    ConfigField("OOB_TREE_RADIX", "8", "max members per upper-level "
                "bootstrap store (leader-of-leaders group size)",
                parse_string),
    ConfigField("OOB_TREE_THRESH", "32", "team size from which "
                "UCC_OOB_TREE=auto switches the TCP bootstrap onto the "
                "tree exchange", parse_string),
    ConfigField("TOPO_FAKE_PPN", "", "simulated topology: group context "
                "ranks into virtual nodes — an int N (nodes of N) or a "
                "cyclic comma list of node sizes (\"2,1,3\") for "
                "asymmetric layouts; empty = real host detection",
                parse_string),
    ConfigField("TOPO_FAKE_NODES_PER_POD", "", "simulated topology: "
                "group every M consecutive virtual nodes into a DCN pod "
                "(activates the 3-level chip->node->pod hierarchy tree "
                "in CL/HIER); empty = no pod grouping", parse_string),
    ConfigField("TEAM_IDS_POOL_SIZE", "32", "team id pool size per context",
                parse_uint),
    ConfigField("TUNER", "off", "measurement-driven algorithm autotuner: "
                "off = static score map only (zero cost, no new dispatch "
                "branches); offline = load the topology-keyed tuning "
                "cache (written by the ucc_tune CLI / perftest --sweep "
                "compilations / earlier online runs) at team activation; "
                "online = additionally explore live candidates during "
                "the first TUNER_SAMPLES posts per (coll, mem, "
                "size-bucket), freeze the rank-0 winner team-wide over "
                "the service team, and persist it to the cache",
                parse_enum(("off", "offline", "online"))),
    ConfigField("TUNER_SAMPLES", "8", "online exploration budget: tuned "
                "posts per (coll, mem, size-bucket) before every rank "
                "posts the decision bcast and freezes rank 0's measured "
                "winner", parse_uint),
    ConfigField("TUNER_CACHE", "", "tuning-cache file (JSON keyed by the "
                "topology signature: team size, node layout, TL set, "
                "thread mode); empty = ~/.cache/ucc_tpu/tune.json",
                parse_string),
    ConfigField("QUANT", "off", "block-scaled wire precision for eligible "
                "collectives (allreduce/allgather, float32/bfloat16 "
                "payloads): off = exact only (zero cost, candidate lists "
                "unchanged); int8/fp8 = register quantized algorithm "
                "variants in the score maps — 2-4x fewer wire bytes for a "
                "bounded block-relative rounding error; the autotuner "
                "explores them like any other candidate",
                parse_enum(("off", "int8", "fp8"))),
    ConfigField("QUANT_ALLREDUCE", "", "per-collective precision override "
                "for allreduce (off|int8|fp8; empty = inherit UCC_QUANT)",
                parse_string),
    ConfigField("QUANT_ALLGATHER", "", "per-collective precision override "
                "for allgather (off|int8|fp8; empty = inherit UCC_QUANT)",
                parse_string),
    ConfigField("QUANT_BLOCK", "256", "elements per absmax scale block of "
                "the quantized wire format (smaller = tighter error, more "
                "scale overhead: 4B per block)", parse_uint),
    ConfigField("QUANT_ERROR_BUDGET", "auto", "max tolerated relative "
                "error (fraction of the per-block absmax) for quantized "
                "candidates; candidates whose predicted worst-case error "
                "exceeds it fall back to exact algorithms. auto = admit "
                "the selected precision (int8: 0.1, fp8: 1.0); an "
                "explicit float gates strictly", parse_string),
    ConfigField("QUANT_STOCHASTIC", "n", "stochastic rounding in the int8 "
                "encoder (unbiased under repeated accumulation, slightly "
                "higher per-element error)", parse_bool),
    ConfigField("GEN", "n", "collective compiler (ucc_tpu/dsl): y = "
                "generate, statically verify, and register DSL "
                "algorithm families (ring chunking, recursive halving/"
                "doubling radix, SRA pipeline depth, fused "
                "allreduce+quantize) as low-score tuner-explorable "
                "score-map candidates with origin tag 'generated'; n "
                "(default) = zero cost, candidate lists unchanged",
                parse_bool),
    ConfigField("GEN_FAMILIES", "", "generated families and parameter "
                "grids, e.g. 'ring(1,2,4),rhd(2,8),sra_pipe(2),qdirect'"
                " — empty = every built-in family at its default grid; "
                "programs failing the static verifier or inapplicable "
                "at the team size are skipped", parse_string),
    ConfigField("GEN_SEARCH", "y", "register persisted search winners "
                "(ucc_tpu/dsl/search.py, written by `ucc_tune "
                "--gen-search`) from the search cache as score-map "
                "candidates with origin 'searched'; requires UCC_GEN=y; "
                "zero cost when the cache has no entries for this "
                "(team size, topology)", parse_bool),
    ConfigField("GEN_SEARCH_CACHE", "", "search-cache file (JSON: "
                "searched program specs + predicted/measured cost "
                "provenance); empty = ~/.cache/ucc_tpu/search.json "
                "(env-resolved)", parse_string),
    ConfigField("GEN_SEARCH_BUDGET", "10", "cost-model shortlist size "
                "per (collective, message size) grid point: the search "
                "measures at most this many predicted-cheapest "
                "candidates of the joint space through successive "
                "halving", parse_uint),
    ConfigField("GEN_PROG_CACHE", "", "verified-program disk cache "
                "(pickle, keyed by family/params/team size/topology + "
                "DSL_VERSION; a version bump invalidates it): repeated "
                "runs skip O(n^2) program generation + verification; "
                "empty = ~/.cache/ucc_tpu/programs.pkl, 0/n = disable "
                "(env-resolved)", parse_string),
    ConfigField("GEN_COST_CACHE", "", "fitted alpha-beta cost-model "
                "file (JSON, written by `ucc_tune --gen-search`; read "
                "by `ucc_perftest --sweep` for "
                "the predicted_us column); empty = "
                "~/.cache/ucc_tpu/cost.json (env-resolved)",
                parse_string),
    ConfigField("GEN_NATIVE", "auto", "native execution plans: lower a "
                "verified collective program (generated families AND "
                "the hand-written ring/sra allreduce bridges) to a "
                "packed op table retired entirely inside the native "
                "core — one ffi crossing per collective, C-side f32/f64 "
                "reductions, mapped-word completion, native "
                "cancel/fence semantics. auto = on when the native "
                "matcher serves every team endpoint and the dtype/op "
                "runs fully native; y additionally routes assist "
                "rounds (bf16, quantized wire) through plans; n = "
                "always interpret. Plan-executed candidates show "
                "'+plan' in ucc_info -s", parse_string),
    ConfigField("GEN_DEVICE", "n", "device-side compiler backend "
                "(ucc_tpu/dsl/lower_device): y = lower verified DSL "
                "programs to generated DEVICE collectives on the xla "
                "TL — ring/rhd/bcast families plus the fused quantized "
                "direct exchange (under UCC_QUANT) register as "
                "score-map candidates named gen_dev_* with origin "
                "'generated-device' at a low score (tuner-explorable, "
                "TUNE-addressable); n (default) keeps candidate lists "
                "byte-identical", parse_string),
    ConfigField("GEN_DEVICE_FAMILIES", "", "device families and "
                "parameter grids (UCC_GEN_FAMILIES grammar, restricted "
                "to the lowerable set), e.g. 'ring(1,2,4),rhd(2,0),"
                "bc_kn(2,0),bc_chain(2),qdirect'; empty = that default "
                "grid", parse_string),
    ConfigField("GEN_DEVICE_BACKEND", "auto", "lowering backend: auto = "
                "Pallas remote-DMA kernels on real TPU platforms "
                "(VMEM-bounded; larger counts fall back to the XLA "
                "variant), generated in-jit XLA (lax.ppermute layer "
                "schedule) on the virtual CPU mesh; xla / pallas force "
                "one backend (pallas on CPU runs interpret-mode — the "
                "test path)", parse_string),
    ConfigField("POOL_ENABLE", "auto", "pooled (one-sided put+flag "
                "window) variants of the generated families: auto = "
                "whatever UCC_GEN_FAMILIES produced; n drops the pooled "
                "family even if the spec named it (its windows pin "
                "arena heap for the life of the team); y forces it in "
                "at its grid when the spec left it out. Requires "
                "UCC_GEN=y and an arena-backed (ipc) team to retire "
                "through", parse_string),
    ConfigField("POOL_CHUNKS", "", "chunk-count grid for the pooled "
                "variants, e.g. '1,2,4' — replaces the default grid "
                "(1,2) without rewriting UCC_GEN_FAMILIES",
                parse_string),
    # multi-tenant service knobs (ISSUE 18): read from the environment at
    # import by schedule/progress.py, core/team.py, and core/coalesce.py
    # (same zero-cost pattern as the obs knobs); listed here so
    # `ucc_info -cf` documents them
    ConfigField("TEAM_PRIORITY", "1", "default QoS priority class for teams "
                "created without an explicit TeamParams.priority: 0 = bulk "
                "(lowest) .. 3 = latency (highest); selects the "
                "progress-queue lane every task of the team drains from",
                parse_string),
    ConfigField("QOS_WEIGHTS", "1,2,4,8", "per-lane weighted-round-robin "
                "caps (services per progress pass while a higher lane is "
                "non-empty, lane 0 first); the top non-empty lane is never "
                "capped", parse_string),
    ConfigField("QOS_AGE_MS", "10", "anti-starvation bound in milliseconds: "
                "a queued task older than this is serviced regardless of "
                "its lane's WRR cap, and deferrable bulk work (coalesced "
                "dispatch) stops yielding to latency traffic",
                parse_string),
    ConfigField("COALESCE", "n", "small-collective coalescing: same-team "
                "eligible allreduces (contiguous, same op/dtype, <= "
                "COALESCE_LIMIT bytes each) posted within a window are "
                "packed into ONE fused native plan — one ffi crossing for "
                "the whole batch — and unpacked to per-request statuses on "
                "completion; n (default) = zero cost, posts unchanged",
                parse_bool),
    ConfigField("COALESCE_LIMIT", "4096", "per-member payload ceiling in "
                "bytes for coalescing; above it a collective is "
                "bandwidth-bound and batching only adds a copy",
                parse_string),
    ConfigField("COALESCE_WINDOW", "200", "gather window in microseconds "
                "before a non-full batch flushes (any closure trigger — "
                "batch full, ineligible post, test() on a held member — "
                "flushes earlier; this is only the quiescent-rank valve)",
                parse_string),
    ConfigField("COALESCE_MAX_BATCH", "16", "deterministic batch-size cap, "
                "the primary closure trigger: every rank flushes on the "
                "Nth eligible post, keeping fused membership identical "
                "across ranks in program order", parse_string),
    ConfigField("CHECK_ASYMMETRIC_DT", "n", "validate datatype consistency "
                "for gather(v)/scatter(v) via a service allreduce before "
                "the collective (off by default for performance, matching "
                "the reference ucc_global_opts.c:112-119; requires every "
                "rank to post with nonzero counts)", parse_bool),
]))


class TlLib:
    """One loaded TL component within a Lib (ucc_tl_lib_init, ucc_lib.c:237)."""

    def __init__(self, lib: "Lib", tl_cls):
        self.lib = lib
        self.tl_cls = tl_cls
        cfg = Config(tl_cls.LIB_CONFIG) if tl_cls.LIB_CONFIG else None
        self.obj = tl_cls.lib_cls(lib, cfg)

    @property
    def name(self) -> str:
        return self.tl_cls.NAME


class ClLib:
    """One loaded CL component (ucc_cl_lib_init, ucc_lib.c:64)."""

    def __init__(self, lib: "Lib", cl_cls):
        self.lib = lib
        self.cl_cls = cl_cls
        cfg = Config(cl_cls.LIB_CONFIG) if cl_cls.LIB_CONFIG else None
        self.obj = cl_cls.lib_cls(lib, cfg)

    @property
    def name(self) -> str:
        return self.cl_cls.NAME


class Lib:
    """ucc_lib_h."""

    def __init__(self, params: Optional[LibParams] = None,
                 config_overrides: Optional[Dict[str, str]] = None):
        self.params = params or LibParams()
        discover_components()
        self.config = Config(GLOBAL_CONFIG, overrides=config_overrides)

        cls_req: List[str] = self.config.cls
        if cls_req == ["all"]:
            cls_req = available_cls()
        tls_allow: List[str] = self.config.tls
        if tls_allow == ["all"]:
            tls_allow = available_tls()

        self.cl_libs: List[ClLib] = []
        self.tl_libs: Dict[str, TlLib] = {}
        for cl_name in cls_req:
            try:
                cl_cls = get_cl(cl_name)
            except UccError:
                logger.warning("requested CL '%s' not available", cl_name)
                continue
            cl_lib = ClLib(self, cl_cls)
            self.cl_libs.append(cl_lib)
            wanted = cl_cls.REQUIRED_TLS
            if wanted is None:
                wanted = tls_allow
            for tl_name in wanted:
                if tl_name not in tls_allow or tl_name in self.tl_libs:
                    continue
                try:
                    tl_cls = get_tl(tl_name)
                except UccError:
                    logger.warning("TL '%s' not available", tl_name)
                    continue
                self.tl_libs[tl_name] = TlLib(self, tl_cls)
        if not self.cl_libs:
            raise UccError(Status.ERR_NOT_FOUND,
                           f"no usable CL among {cls_req}")

        coll_union = CollType(0)
        for tl in self.tl_libs.values():
            coll_union |= tl.tl_cls.SUPPORTED_COLLS
        self.attr = LibAttr(thread_mode=self.params.thread_mode,
                            coll_types=coll_union or COLL_TYPE_ALL)
        self._finalized = False
        logger.info("ucc_tpu lib init: cls=%s tls=%s",
                    [c.name for c in self.cl_libs], list(self.tl_libs))

    # ------------------------------------------------------------------
    def get_attr(self) -> LibAttr:
        return self.attr

    def finalize(self) -> Status:
        self._finalized = True
        return Status.OK


def init(params: Optional[LibParams] = None, **overrides) -> Lib:
    """ucc_init (ucc.h:779)."""
    return Lib(params, config_overrides=overrides or None)
