"""The FSDP2 cells at a size a CPU test holds: the architecture's counts
are the published model's, the generator and its plain reference agree,
the one-chip share posts rank 0's counts, and the bfloat16-reduce control
fails the comparison. Same configuration and traffic files as the chip's,
with the widths and depth cut."""
import copy
import json

import numpy as np
import pytest

import control_fsdp
import ucc_tpu
from small import cpu_devices
from yardstick import data, fsdp_check, peaks, runner, spec

CELLS = ["fsdp4.fsdp2-units", "fsdp1.fsdp2-units"]
SEEDS = (3, 2**31 + 5, 977)
#: every unit above TL/XLA's 128 KiB short-message cut on the CPU, so the
#: compiled programs are the ones driven, as on the chip
SMALL = {"hidden_size": 256, "intermediate_size": 704,
         "num_attention_heads": 8, "num_key_value_heads": 8,
         "linear_num_key_heads": 8, "linear_num_value_heads": 8,
         "linear_key_head_dim": 16, "linear_value_head_dim": 32,
         "vocab_size": 2048, "num_hidden_layers": 4}


def arch():
    return spec.load_module(spec.BENCH_DIR / "archs" / "olmo_hybrid.py",
                            "arch_olmo_hybrid")


def small_cell(name):
    cell = spec.load_cell(name)
    cfg = copy.deepcopy(cell.config)
    cfg.update(SMALL)
    cfg["layer_types"] = cfg["layer_types"][:4]
    cell.config = cfg
    return cell


def run_small(name, monkeypatch, seed=7, seconds=0.3, trace=False):
    monkeypatch.setattr(peaks, "lookup", lambda kind: {
        "hbm_bytes_per_s": 819e9, "ici_bytes_per_s": 200e9})
    monkeypatch.setattr(runner, "enable_cache", lambda: None)
    return runner.run_cell(small_cell(name), seed, seconds, trace,
                           require=cpu_devices)


def test_counts_are_the_published_models():
    cfg = spec.load_cell(CELLS[0]).config
    a = arch()
    count = (lambda kind: sum(a.elements(s) for _, s in a.block(cfg, kind)))
    assert count("full_attention") == 185_809_920
    assert count("linear_attention") == 215_570_172
    assert a.model_parameters(cfg, cfg["published"]["layer_types"]) == \
        7_430_870_688
    assert cfg["published"]["num_hidden_layers"] == \
        len(cfg["published"]["layer_types"]) == 32
    # the cut keeps two whole periods of the published pattern, and the
    # keys it changes are those the benchmark's entry lists as reduced
    assert cfg["layer_types"] == cfg["published"]["layer_types"][:8]
    entry, = [c for c in json.loads((spec.ROOT / "BENCHMARK.json")
                                    .read_text())["configs"]
              if c["file"].endswith("olmo-hybrid-7b-pp4-fsdp4.json")]
    assert cfg["reduced"] == list(cfg["published"]) == entry["reduced"]
    assert sum(n for _, n in spec.parameters(cfg)) == 96_337_920 * 4 + \
        6 * 215_570_172 + 2 * 185_809_920


def test_per_rank_unit_counts():
    cfg = spec.load_cell(CELLS[0]).config
    sizes = [n for _, n in arch().unit_sizes(cfg)]
    assert [n // 4 for n in sizes] == [96_337_920] + [53_896_384] * 3 + \
        [46_452_480] + [53_896_384] * 3 + [46_452_480]
    assert all(n % 4 == 0 for n in sizes)


def test_cells_run_one_stream():
    """The 1-chip cell's traffic is the 4-chip cell's under its own name:
    every parameter but the description is the same."""
    t4, t1 = (spec.load_cell(name).traffic for name in CELLS)
    assert t1["about"] != t4["about"]
    assert {k: v for k, v in t1.items() if k != "about"} == \
        {k: v for k, v in t4.items() if k != "about"}


def _stream(name, key, chips):
    import jax
    from yardstick.job import Job
    cell = small_cell(name)
    job = Job(chips)
    s = cell.generator.Stream(job, jax.devices()[:chips], cell.config,
                              cell.traffic, key, runner.Spans())
    s.ready()
    return cell, job, s


def test_generator_and_reference_agree_over_two_steps():
    key = data.root_key(2**33 + 9)
    cell, job, s = _stream(CELLS[0], key, 4)
    try:
        answers = []
        for k in range(2):
            assert s.step(k) == 0
            answers += [(k, b, s.answer(b)) for b in range(s.units)]
        err, compared, missing = s.check(key, answers, None)
        assert (compared, missing) == (2 * s.units * 4, 0)
        assert err <= cell.config["max_err_limit"]
        # an answer of the other step is wrong by the reference
        swapped = [(1 - k, b, a) for k, b, a in answers]
        assert s.check(key, swapped, None)[0] > 0.5
    finally:
        s.close()
        job.close()


def test_reduce_scatter_inputs_in_flight_are_bounded(monkeypatch):
    """No more float32 unit gradients than the reduce-scatters in flight
    plus the one being written are alive at once."""
    import jax
    key = data.root_key(5)
    cell, job, s = _stream(CELLS[0], key, 4)
    gen = type(s.grads[0])
    call = gen.__call__
    sizes = {4 * c for c in s.counts}
    seen = []

    def counted(self, u):
        if self.rank is s.grads[0].rank:
            seen.append(sum(1 for a in jax.live_arrays()
                            if a.dtype == np.float32 and a.size in sizes
                            and a.devices() == {self.device}))
        return call(self, u)

    monkeypatch.setattr(gen, "__call__", counted)
    try:
        assert s.step(0) == 0
    finally:
        s.close()
        job.close()
    flying = cell.traffic["reduce_scatters_in_flight"]
    assert seen == [min(u, flying) for u in range(len(s.counts))]


def test_one_chip_share_posts_rank0_counts(monkeypatch):
    posted = []
    init = ucc_tpu.Team.collective_init

    def record(self, args):
        posted.append((self.size, args.coll_type, args.src.count,
                       args.dst.count, args.is_inplace))
        return init(self, args)

    monkeypatch.setattr(ucc_tpu.Team, "collective_init", record)
    cell, job, s = _stream(CELLS[1], data.root_key(11), 1)
    try:
        assert s.step(0) == 0
    finally:
        s.close()
        job.close()
    per_rank = [n // 4 for _, n in arch().unit_sizes(cell.config)]
    ag = ucc_tpu.CollType.ALLGATHER
    rs = ucc_tpu.CollType.REDUCE_SCATTER
    assert posted == [(1, ag, c, c, False) for c in per_rank] + \
        [(1, rs, c, c, False) for c in reversed(per_rank)]


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name, monkeypatch):
    r = run_small(name, monkeypatch, seed=SEEDS[1])
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] % 10 == 0
    cell = spec.load_cell(name)
    assert set(r["metrics"]) == {m["name"] for m in cell.end_to_end}


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", CELLS)
def test_control_fails(seed, name, monkeypatch):
    """The reduce-scatter summed in bfloat16 fails the harness's own
    comparison, by its ``max_err``, on every seed."""
    algs = control_fsdp.plant(monkeypatch.setattr, monkeypatch.setenv)
    r = run_small(name, monkeypatch, seed=seed)
    assert not r["correct"], r["checks"]
    assert r["checks"]["max_err"]["value"] > 1e3 * \
        r["checks"]["max_err"]["limit"]
    assert r["failed"] == 0 and r["checks"]["missing_answers"]["value"] == 0
    n = spec.load_cell(name).chips
    want = {f"{n}:xla:allgather", f"{n}:xla:reduce_scatter"} if n > 1 else \
        {"1:self:allgather", "1:self:reduce_scatter"}
    assert set(algs) == want, algs


def test_fault_makes_fsdp4_incorrect(monkeypatch):
    """A wrong element where the allgather produces it fails the check."""
    from ucc_tpu.tl import xla as tl_xla
    copy_out = tl_xla.XlaCollTask._copy_out

    def altered(self):
        copy_out(self)
        dst = self.args.dst
        if self.coll == ucc_tpu.CollType.ALLGATHER:
            dst.buffer = dst.buffer.at[self.tl_team.rank].add(1)

    monkeypatch.setattr(tl_xla.XlaCollTask, "_copy_out", altered)
    r = run_small(CELLS[0], monkeypatch, seed=SEEDS[0])
    assert not r["correct"] and r["checks"]["max_err"]["value"] == 1.0


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reports_its_per_layer_metrics(name, monkeypatch):
    """The traced path end to end on the CPU; the trace's device numbers
    are stood in for (a CPU trace has no TPU plane)."""
    from yardstick import trace
    fake = trace.Summary(window_s=1.0, chips=4, busy_s=0.25,
                         ops={"all-gather.4": 0.1, "all-reduce": 0.1},
                         modules={"jit_ucc_allgather_xla": 0.1,
                                  "jit_ucc_reduce_scatter_xla": 0.1,
                                  "jit_bench_grad": 0.01},
                         module_runs={"jit_ucc_allgather_xla": 10.0,
                                      "jit_ucc_reduce_scatter_xla": 10.0},
                         idle_by_span={"bench.init": 0.5})
    monkeypatch.setattr(trace, "reduce_trace", lambda path, ids: fake)
    r = run_small(name, monkeypatch, trace=True)
    cell = spec.load_cell(name)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {m["name"] for m in cell.per_layer}
    assert all(v["value"] > 0 for v in r["metrics"].values())


def test_rooflines_split_the_kinds():
    """Each reader counts its own kind's bytes against its own
    programs' time."""
    from yardstick import roofline, trace
    from yardstick.runner import RunView
    gen = spec.load_cell(CELLS[0]).generator
    pk = {"hbm_bytes_per_s": 800e9, "ici_bytes_per_s": 200e9}
    t = trace.Summary(window_s=1.0, chips=4, busy_s=0.5,
                      modules={"jit_ucc_allgather_xla": 0.2,
                               "jit_ucc_reduce_scatter_xla": 0.4,
                               "jit_ucc_allgatherv_xla": 9.0})
    view = RunView(n=4, steps=10, coll_bytes=gen.CollBytes(
        [4e9, 8e9], ["allgather", "reduce_scatter"]), peaks=pk, spans={},
        trace=t)
    ag = spec.load_module(spec.BENCH_DIR / "metrics" /
                          "allgather_roofline.py", "m_ag").read(view)
    rs = spec.load_module(spec.BENCH_DIR / "metrics" /
                          "reduce_scatter_roofline.py", "m_rs").read(view)
    assert ag == pytest.approx(100 * 10 * 0.75 * 4e9 / 200e9 / 0.2)
    assert rs == pytest.approx(100 * 10 * 0.75 * 8e9 / 200e9 / 0.4)
    # HBM binds where the interconnect is faster
    fast = dict(pk, ici_bytes_per_s=2000e9)
    assert roofline.gather_scatter_least(4e9, 4, fast) == \
        pytest.approx(1.25 * 4e9 / 800e9)
    view.coll_bytes = [4e9, 8e9]           # a stream without kinds
    assert spec.load_module(spec.BENCH_DIR / "metrics" /
                            "allgather_roofline.py", "m_ag2").read(view) \
        is None


def test_gradient_blocks_are_made_alone():
    """Any block of a gradient is the same elements as the whole one's,
    and every element is a float32 of the stated range."""
    import jax.numpy as jnp
    key = data.root_key(2**40 + 3)
    whole = np.asarray(fsdp_check.grad(key, 2, 5, 7, 4096))
    part = np.asarray(fsdp_check.grad(key, 2, 5, 7, 1024, jnp.uint32(2048)))
    assert (whole[2048:3072] == part).all()
    mag = np.abs(whole)
    assert mag.min() >= 2.0 ** -3 and mag.max() < 2.0 ** 5
    assert len(np.unique(whole)) == whole.size
    assert (whole != np.asarray(fsdp_check.grad(key, 2, 5, 8, 4096))).all()
