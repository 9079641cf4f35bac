"""The expert-parallel cells at a size a CPU test holds: the generator and
its plain reference agree, the float8 control fails the comparison, and
the routing is a function of the seed. Same configuration and traffic
files as the chip's, with the width, tokens and depth cut."""
import copy

import numpy as np
import pytest

import control_ep
from yardstick import routing, spec
from small import cpu_devices
from yardstick import peaks, runner

CELLS = ["ep4.dispatch-zipf", "ep1.dispatch-zipf"]
SEEDS = (3, 2**31 + 5, 977)


def small_ep_cell(name):
    cell = spec.load_cell(name)
    cfg = copy.deepcopy(cell.config)
    cfg["hidden_size"] = 128
    cfg["deployment"].update(tokens_per_chip=32, moe_layers=2)
    cell.config = cfg
    return cell


def run_small_ep(name, monkeypatch, seed=7, seconds=0.3, trace=False):
    monkeypatch.setattr(peaks, "lookup", lambda kind: {
        "hbm_bytes_per_s": 819e9, "ici_bytes_per_s": 200e9})
    monkeypatch.setattr(runner, "enable_cache", lambda: None)
    return runner.run_cell(small_ep_cell(name), seed, seconds, trace,
                           require=cpu_devices)


def test_cells_run_one_stream():
    """The 1-chip cell's traffic is the 4-chip cell's under its own name
    (a configuration takes a traffic mix once): every parameter but the
    description is the same."""
    ep4, ep1 = (spec.load_cell(name).traffic for name in CELLS)
    assert ep1["about"] != ep4["about"]
    assert {k: v for k, v in ep1.items() if k != "about"} == \
        {k: v for k, v in ep4.items() if k != "about"}


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name, monkeypatch):
    r = run_small_ep(name, monkeypatch, seed=SEEDS[1])
    assert r["correct"], r["checks"]
    assert r["checks"]["max_err"]["value"] == 0.0
    assert r["failed"] == 0 and r["attempted"] % 6 == 0
    cell = spec.load_cell(name)
    assert set(r["metrics"]) == {m["name"] for m in cell.end_to_end}


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", CELLS)
def test_control_fails(seed, name, monkeypatch):
    """Every bf16 exchange rounded to float8_e4m3 fails the harness's
    own comparison, by its ``max_err``, on every seed."""
    algs = control_ep.plant(monkeypatch.setattr, monkeypatch.setenv)
    r = run_small_ep(name, monkeypatch, seed=seed)
    assert not r["correct"], r["checks"]
    assert r["checks"]["max_err"]["value"] > r["checks"]["max_err"]["limit"]
    assert r["failed"] == 0 and r["checks"]["missing_answers"]["value"] == 0
    n = spec.load_cell(name).chips
    want = {f"{n}:xla:alltoallv", f"{n}:short:alltoall"} if n > 1 else \
        {"1:self:alltoallv", "1:self:alltoall"}
    assert set(algs) == want, algs


def test_fault_makes_ep4_incorrect(monkeypatch):
    """A wrong element where the exchange produces it fails the
    check."""
    from ucc_tpu.tl import xla as tl_xla
    copy_out = tl_xla.XlaCollTask._a2av_copy_out

    def altered(self):
        copy_out(self)
        dst = self.args.dst
        if dst.buffer.size > 1024:           # not set-up's probe
            dst.buffer = dst.buffer.at[self.tl_team.rank].add(8)

    monkeypatch.setattr(tl_xla.XlaCollTask, "_a2av_copy_out", altered)
    r = run_small_ep(CELLS[0], monkeypatch, seed=SEEDS[0])
    assert not r["correct"] and r["checks"]["max_err"]["value"] > 0.01


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reports_its_per_layer_metrics(name, monkeypatch):
    """The traced path end to end on the CPU; the trace's device numbers
    are stood in for (a CPU trace has no TPU plane)."""
    from yardstick import trace
    fake = trace.Summary(window_s=1.0, chips=4, busy_s=0.25,
                         ops={"ragged-all-to-all.3": 0.2},
                         modules={"jit_ucc_alltoallv_xla": 0.2,
                                  "jit_bench_stamp": 0.01},
                         module_runs={"jit_ucc_alltoallv_xla": 10.0},
                         idle_by_span={"bench.init": 0.5})
    monkeypatch.setattr(trace, "reduce_trace", lambda path, ids: fake)
    r = run_small_ep(name, monkeypatch, trace=True)
    cell = spec.load_cell(name)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {m["name"] for m in cell.per_layer}
    assert all(v["value"] > 0 for v in r["metrics"].values())
    if "alltoallv_roofline" in r["metrics"]:
        assert r["metrics"]["alltoallv_roofline"]["value"] < 100


def test_parent_contract_gives_up_at_set_up(monkeypatch):
    """A library whose alltoallv result is the span, not the caller's dst
    buffer, ends the run at set-up, non-zero, with the reason."""
    import subprocess
    import sys
    import textwrap
    code = textwrap.dedent("""
        import sys
        sys.path[:0] = %r
        from ucc_tpu.tl import xla
        xla.XlaCollTask._a2av_copy_out_sound = xla.XlaCollTask._a2av_copy_out
        def span_only(self):
            self._a2av_copy_out_sound()
            self.args.dst.buffer = self.args.dst.buffer[:-8]
        xla.XlaCollTask._a2av_copy_out = span_only
        import test_ep
        import pytest
        test_ep.run_small_ep("ep4.dispatch-zipf", pytest.MonkeyPatch())
        print("ran to the end")
    """ % (sys.path,))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 3, p.stderr[-2000:]
    assert "ran to the end" not in p.stdout
    assert p.stderr.strip().splitlines()[-1].startswith(
        "ep_alltoallv: alltoallv does not land blocks")


def _counts(seed, step, layer, rank, tokens=64, experts=64, topk=6):
    import jax
    import jax.numpy as jnp
    from yardstick import data
    w = routing.zipf_weights(experts, 1.0)
    fn = jax.jit(routing.layer_counts, static_argnums=(4, 6))
    return np.asarray(fn(data.root_key(seed), jnp.uint32(step),
                         jnp.uint32(layer), jnp.uint32(rank), tokens,
                         jnp.asarray(w), topk))


def test_routing_is_a_function_of_the_seed():
    a = _counts(2**33 + 1, 5, 3, 2)
    assert (a == _counts(2**33 + 1, 5, 3, 2)).all()
    assert a.sum() == 64 * 6 and a.min() >= 0 and a.max() <= 64
    assert (a != _counts(2**33 + 2, 5, 3, 2)).any()
    assert (a != _counts(2**33 + 1, 6, 3, 2)).any()
    assert (a != _counts(2**33 + 1, 5, 4, 2)).any()


def test_routing_is_skewed_and_moves():
    """Zipf over a permutation drawn per (step, layer): the hottest
    expert changes between layers, and is hot on every rank."""
    hot = [int(np.argmax(_counts(11, 0, layer, 0, tokens=512)))
           for layer in range(6)]
    assert len(set(hot)) > 1
    c0, c1 = _counts(11, 0, 0, 0, 512), _counts(11, 0, 0, 1, 512)
    assert np.argmax(c0) == np.argmax(c1)
    assert c0.max() > 4 * np.median(c0)


def test_generator_and_routing_agree(monkeypatch):
    """The counts the generator posts are the routing's, cut to the
    experts of the ranks present."""
    import jax
    cell = small_ep_cell(CELLS[1])
    from yardstick import data
    key = data.root_key(9)
    dev = jax.devices()[:1]
    from yardstick.job import Job
    job = Job(1)
    s = cell.generator.Stream(job, dev, cell.config, cell.traffic, key,
                              runner.Spans())
    s.ready()
    assert s.step(0) == 0
    got = np.asarray(s.answer(0)[0])
    want = _counts(9, 0, 0, 0, tokens=32)[:16]
    assert (got == want).all()
    s.close()
    job.close()
