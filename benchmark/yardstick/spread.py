"""Run-to-run spread of a cell's end-to-end metrics, as the bounds of
BENCHMARK.json are set from it.

    python3 benchmark/yardstick/spread.py run --workload <cell> \
        --seeds <s1,s2,...> --set <k> --seconds <s> --out <runs.jsonl> \
        [--variant <label>] [--logs <dir>]
    python3 benchmark/yardstick/spread.py summary <runs.jsonl> [...]

``run`` starts ``benchmark/run.py`` once per seed, one process after the
other (this process never touches JAX, so each child has the chips to
itself), and appends one record per run: the result's end-to-end values
and the host counters that the runner logs. The children inherit this
process's environment and CPU affinity, so ``env PYTHONHASHSEED=0 ...`` or
``taskset -c 0-14 ...`` in front of it runs a variant, which ``--variant``
labels. ``summary`` prints, per cell, variant, set and metric, the median,
the trimmed spread and the quartile spread.

Two spreads, each a share of the set's median:
- ``trimmed_spread``: the range of the runs after leaving out the run
  farthest from their median, where that narrows it. A metric whose
  trimmed spread is wider than its bound cannot be told changed or
  unchanged, so a bound is set at twice the widest trimmed spread or more.
- ``quartile_spread``: the distance between the first and third quartile
  as ``statistics.quantiles(values, n=4)`` gives them.
"""
from __future__ import annotations

import argparse
import json
import re
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
#: a run's limit: the runner's own 360 s, and more for a run that compiles
RUN_TIMEOUT_S = 1200

#: the runner's log lines on stdout -> the host counters of a record
PATTERNS = {
    "backend_up_s": r"set-up: backend up at ([\d.]+) s",
    "team_up_s": r"set-up: contexts and team at ([\d.]+) s",
    "data_made_s": r"set-up: data made at ([\d.]+) s",
    "window_steps": r"window: (\d+) steps in",
    "window_s": r"window: \d+ steps in ([\d.]+) s",
    "compiles_in_window": r"compiles in window (\d+)",
    "cpu_user_s": r"main thread cpu ([\d.]+) user",
    "cpu_sys_s": r"user ([\d.]+) sys s",
    "involuntary_switches": r"involuntary switches (\d+)",
    "gc_collections": r"gc collections (\[[\d, ]*\])",
    "speed_ms": r"speed ([\d.]+) ms",
    "load": r"load ([\d.]+)",
    "step_median_ms": r"steps: median ([\d.]+) ms",
    "step_min_ms": r"steps: median [\d.]+ ms, min ([\d.]+)",
    "step_max_ms": r"steps: median [\d.]+ ms, min [\d.]+, max ([\d.]+)",
    "check_s": r"check: .*, ([\d.]+) s$",
}


def trimmed_spread(values) -> float:
    """Range of ``values`` less the one farthest from their median, where
    that narrows it, as a share of the median of all of them."""
    vals = sorted(values)
    med = statistics.median(vals)
    if len(vals) > 2:   # the farthest is the first or the last
        vals.pop(0 if med - vals[0] > vals[-1] - med else -1)
    return (vals[-1] - vals[0]) / med


def quartile_spread(values) -> float:
    """(third quartile - first quartile) / median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def parse_log(text: str) -> dict:
    """Host counters from the runner's log lines (absent ones left out)."""
    out = {}
    for key, pat in PATTERNS.items():
        m = re.search(pat, text, re.MULTILINE)
        if m:
            out[key] = json.loads(m.group(1))
    return out


def run_one(cell: str, seed: int, seconds: float) -> tuple[dict, str]:
    cmd = [sys.executable, "benchmark/run.py", "--workload", cell,
           "--seed", str(seed), "--seconds", str(seconds), "--trace", "0"]
    t = time.perf_counter()
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                       timeout=RUN_TIMEOUT_S)
    rec = {"rc": p.returncode, "wall_s": round(time.perf_counter() - t, 2)}
    lines = p.stdout.strip().splitlines()
    if p.returncode == 0 and lines:
        res = json.loads(lines[-1])
        rec.update(correct=res["correct"],
                   end_to_end={k: v["value"]
                               for k, v in res["metrics"].items()},
                   checks={k: v["value"] for k, v in res["checks"].items()},
                   memory_peak_bytes=res["device"]["memory_peak_bytes"])
    rec["host"] = parse_log(p.stdout)
    return rec, p.stdout + "\n--- stderr ---\n" + p.stderr


def cmd_run(a) -> int:
    logs = Path(a.logs) if a.logs else None
    if logs:
        logs.mkdir(parents=True, exist_ok=True)
    bad = 0
    for seed in (int(s) for s in a.seeds.split(",")):
        rec, text = run_one(a.workload, seed, a.seconds)
        rec = {"cell": a.workload, "variant": a.variant, "set": a.set,
               "seed": seed, **rec}
        with open(a.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
        if logs:
            (logs / f"{a.workload}.{a.variant}.{a.set}.{seed}.log"
             ).write_text(text)
        print(json.dumps(rec), flush=True)
        bad += rec["rc"] != 0 or not rec.get("correct", False)
    return 1 if bad else 0


def load_runs(paths) -> list:
    runs = []
    for path in paths:
        with open(path) as f:
            runs += [json.loads(line) for line in f if line.strip()]
    return runs


def summarise(runs) -> list:
    """One row per (cell, variant, set, metric) of the correct runs."""
    groups = {}
    for r in runs:
        if r.get("rc") == 0 and r.get("correct"):
            key = (r["cell"], r.get("variant", "base"), r["set"])
            groups.setdefault(key, []).append(r)
    rows = []
    for (cell, variant, set_), rs in sorted(groups.items()):
        for m in rs[0]["end_to_end"]:
            vals = [r["end_to_end"][m] for r in rs]
            rows.append({
                "cell": cell, "variant": variant, "set": set_, "metric": m,
                "n": len(vals), "median": statistics.median(vals),
                "trimmed": trimmed_spread(vals),
                "quartile": quartile_spread(vals) if len(vals) > 1 else 0.0,
                "range": (max(vals) - min(vals)) / statistics.median(vals)})
    return rows


def cmd_summary(a) -> int:
    for row in summarise(load_runs(a.files)):
        print(f"{row['cell']:20s} {row['variant']:8s} set {row['set']} "
              f"{row['metric']:18s} n {row['n']:2d} median "
              f"{row['median']:10.4f} trimmed {row['trimmed']:.4%} "
              f"quartile {row['quartile']:.4%} range {row['range']:.4%}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="what", required=True)
    r = sub.add_parser("run")
    r.add_argument("--workload", required=True)
    r.add_argument("--seeds", required=True)
    r.add_argument("--set", type=int, required=True)
    r.add_argument("--seconds", type=float, required=True)
    r.add_argument("--out", required=True)
    r.add_argument("--variant", default="base")
    r.add_argument("--logs")
    s = sub.add_parser("summary")
    s.add_argument("files", nargs="+")
    a = ap.parse_args(argv)
    return cmd_run(a) if a.what == "run" else cmd_summary(a)


if __name__ == "__main__":
    sys.exit(main())
