"""The ``stage_us`` reader on a synthetic span table and run: staging time
per TL/XLA launch where the span was recorded, None where not."""
import pytest

from ucc_tpu.utils import profiling
from yardstick.runner import RunView
from yardstick.spec import BENCH_DIR, load_module

#: 2 steps x 3 buckets x 4 ranks = 24 requests, 6 launches
RUN = RunView(n=4, steps=2, coll_bytes=[1, 2, 3], peaks={}, spans={})
TABLE = {"ucc.post": (24, 48e-3), "ucc.xla.launch": (6, 30e-3),
         "ucc.xla.stage": (6, 6e-3), "ucc.xla.place": (24, 3e-3),
         "ucc.xla.dispatch": (6, 18e-3)}


def _reader():
    return load_module(BENCH_DIR / "metrics" / "stage_us.py", "m_stage_us")


@pytest.mark.parametrize("absent,want", [
    (None, 1000.0),                 # 6 ms over 6 launches, place inside
    ("ucc.xla.place", 1000.0),      # no shard placed: the same reading
    ("ucc.xla.stage", None),
    ("ucc.xla.launch", None),
])
def test_stage_per_launch(absent, want, monkeypatch):
    table = {k: v for k, v in TABLE.items() if k != absent}
    monkeypatch.setattr(profiling, "totals", lambda: table, raising=False)
    got = _reader().read(RUN)
    assert got == (None if want is None else pytest.approx(want))


def test_library_without_span_table(monkeypatch):
    monkeypatch.delattr(profiling, "totals", raising=False)
    assert _reader().read(RUN) is None
