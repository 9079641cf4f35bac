"""The readers of the library's ``ucc.*`` spans, on a synthetic span table
and run: the right number where the span was recorded, None where not."""
import pytest

from ucc_tpu.utils import profiling
from yardstick.runner import RunView
from yardstick.spec import BENCH_DIR, load_module

#: 2 steps x 3 buckets x 4 ranks = 24 requests, 6 launches
RUN = RunView(n=4, steps=2, coll_bytes=[1, 2, 3], peaks={}, spans={})
TABLE = {"ucc.init": (24, 24e-3), "ucc.select": (24, 2.4e-3),
         "ucc.tl_init": (24, 4.8e-3), "ucc.post": (24, 48e-3),
         "ucc.xla.launch": (6, 30e-3), "ucc.xla.stage": (6, 6e-3),
         "ucc.xla.dispatch": (6, 18e-3)}

CASES = [
    # reader, its value on TABLE (us), the span whose absence gives None
    ("select_us", 100.0, "ucc.select"),
    ("tl_init_us", 200.0, "ucc.tl_init"),
    ("init_self_us", 700.0, "ucc.init"),      # (24 - 2.4 - 4.8) ms / 24
    ("post_self_us", 750.0, "ucc.post"),      # (48 - 30) ms / 24
    ("launch_us", 5000.0, "ucc.xla.launch"),  # per launch
    ("dispatch_us", 3000.0, "ucc.xla.dispatch"),
]


def _reader(name):
    return load_module(BENCH_DIR / "metrics" / f"{name}.py", "m_" + name)


@pytest.mark.parametrize("name,want,span", CASES)
def test_reader_value_and_absence(name, want, span, monkeypatch):
    reader = _reader(name)
    monkeypatch.setattr(profiling, "totals", lambda: dict(TABLE),
                        raising=False)
    assert reader.read(RUN) == pytest.approx(want)
    table = {k: v for k, v in TABLE.items() if k != span}
    monkeypatch.setattr(profiling, "totals", lambda: table, raising=False)
    assert reader.read(RUN) is None


def test_absent_children_count_as_zero(monkeypatch):
    """One rank: no launch inside post, and post_self is all of post."""
    table = {"ucc.init": (24, 24e-3), "ucc.post": (24, 48e-3)}
    monkeypatch.setattr(profiling, "totals", lambda: table, raising=False)
    assert _reader("post_self_us").read(RUN) == pytest.approx(2000.0)
    assert _reader("init_self_us").read(RUN) == pytest.approx(1000.0)
    assert _reader("launch_us").read(RUN) is None
    assert _reader("dispatch_us").read(RUN) is None


@pytest.mark.parametrize("name", [c[0] for c in CASES])
def test_library_without_span_table(name, monkeypatch):
    """A library that records no spans gives nothing, and no error."""
    monkeypatch.delattr(profiling, "totals", raising=False)
    assert _reader(name).read(RUN) is None
