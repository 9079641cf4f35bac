"""The harness finds every cell's files by name and refuses to run
without the chip or without the program."""
import json
import math
import shutil
import subprocess
import sys

import pytest

from yardstick import spec, trace

SPEC = json.loads((spec.ROOT / "BENCHMARK.json").read_text())


def parallel_degree(deployment: dict) -> int:
    """Chips a deployment spans: the product of its ``*_parallel``
    degrees (``data_parallel``, ``expert_parallel``, ...)."""
    degrees = [int(v) for k, v in deployment.items()
               if k.endswith("_parallel")]
    assert degrees, f"deployment names no parallel degree: {deployment}"
    return math.prod(degrees)


@pytest.mark.parametrize("name", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves(name):
    cell = spec.load_cell(name)
    dep = cell.config["deployment"]
    # a cell runs its deployment's whole degree, or, where the
    # configuration says how (``cut_to_chips``), one chip's share of it
    degree = parallel_degree(dep)
    assert cell.chips == degree or (
        "cut_to_chips" in dep and cell.chips < degree)
    assert {m["name"] for m in cell.per_layer} == set(cell.readers)
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    if "architecture" in cell.config:   # a parameter list, as DDP's buckets
        assert spec.parameters(cell.config)


def test_bare_checkout_exits_nonzero_without_result(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "ddp1.torch-buckets", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_no_chip_exits_nonzero_without_result():
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "ddp1.torch-buckets", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=spec.ROOT, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_interval_arithmetic():
    busy = trace._union([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0)])
    assert busy == [[0.0, 2.0], [3.0, 4.0]]
    gaps = trace._gaps(busy, -1.0, 5.0)
    assert gaps == [(-1.0, 0.0), (2.0, 3.0), (4.0, 5.0)]
    by = trace._attribute(gaps, [(-1.0, -0.5, "bench.post"),
                                 (2.5, 4.5, "bench.wait")])
    assert by == pytest.approx({"bench.post": 0.5, "bench.wait": 1.0,
                                "bench.other": 1.5})
