"""Every end-to-end bound of BENCHMARK.json that a check judges by the
run-to-run spread is at least twice the widest spread of the recorded chip
runs (``data/spread.json``) in each cell it covers, so a check can tell
such a metric changed or unchanged."""
import json
import statistics
from pathlib import Path

import pytest

from yardstick import spec
from yardstick.spread import quartile_spread, summarise, trimmed_spread

SPEC = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
RUNS = json.loads((Path(__file__).parent / "data" / "spread.json")
                  .read_text())["runs"]


def test_trimmed_spread_leaves_out_the_farthest_run():
    runs = [100.0, 101.0, 100.5, 99.5, 100.2, 110.0]
    med = statistics.median(runs)
    assert med == pytest.approx(100.35)
    # 110 lies farthest from the median; the range of the rest is 1.5
    assert trimmed_spread(runs) == pytest.approx(1.5 / med)
    assert trimmed_spread(runs) < (110.0 - 99.5) / med


def test_trimmed_spread_at_the_low_end_and_on_a_tie():
    assert trimmed_spread([90.0, 100.0, 101.0, 100.0, 99.0, 100.5]) \
        == pytest.approx(2.0 / 100.0)
    # both ends equally far: leaving out either gives the same range
    assert trimmed_spread([99.0, 100.0, 101.0]) == pytest.approx(0.01)
    assert trimmed_spread([5.0, 5.0, 5.0, 5.0]) == 0.0


def test_quartile_spread_is_that_of_statistics_quantiles():
    runs = [10.0, 12.0, 11.0, 13.0, 10.5, 11.5]
    q1, _, q3 = statistics.quantiles(runs, n=4)
    assert quartile_spread(runs) == pytest.approx(
        (q3 - q1) / statistics.median(runs))


def _cells(metric: dict) -> list:
    return metric.get("workloads", [w["name"] for w in SPEC["workloads"]])


def _rows(metric: str, cell: str) -> list:
    """The recorded sets of the benchmark as committed (variant base)."""
    return [r for r in summarise(RUNS) if r["cell"] == cell
            and r["metric"] == metric and r["variant"] == "base"
            and r["set"] >= 1]


#: set-up is judged by its median alone, never by its spread, and its bound
#: may not pass 0.25, while the TPU backend alone takes 10-15 s to start
#: on a one-chip v5e host (recorded runs: 22-24 % spread in a set)
SPREAD_JUDGED = [m for m in SPEC["end_to_end"] if m["name"] != "setup_s"]


@pytest.mark.parametrize("metric", SPREAD_JUDGED, ids=lambda m: m["name"])
def test_bound_is_twice_the_widest_trimmed_spread(metric):
    for cell in _cells(metric):
        rows = _rows(metric["name"], cell)
        assert len(rows) >= 2, f"{cell}: fewer than two sets recorded"
        for row in rows:
            assert row["n"] >= 3, row
            assert metric["bound"] >= 2 * row["trimmed"], row
