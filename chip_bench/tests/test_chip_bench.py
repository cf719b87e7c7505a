"""Tests of the chip benchmark's yardstick, on the CPU at small sizes.

    JAX_PLATFORMS=cpu python -m pytest -q chip_bench/tests

They cover: the reference against the program (tables, rasters, state);
the control (the reference in bfloat16) and planted faults, each of which
the comparison must call not correct; the trace reduction on a trace
recorded on the chip; the kernels' byte counts against their shapes;
discovery of cells, configurations, mixes and metrics by name; the result
line; and the run's refusal to run without a TPU.  No test describes a TPU.
"""
from __future__ import annotations

import contextlib
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chip_bench import compare, kernel_bytes, reference, run  # noqa: E402
from chip_bench import trace_reduce  # noqa: E402

TRACE = os.path.join(BENCH, "testdata", "trace_4x4.xplane.pb")
# two 4x4 steps as 4 shards on 4 chips, halo wire, pipelined schedule
TRACE_H4 = os.path.join(BENCH, "testdata", "trace_4x4_h4.xplane.pb")
WORKLOADS = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]
CELLS = [w["name"] for w in WORKLOADS]
# the whole runs here have one CPU device; test_multichip.py drives four
ONE_CHIP = [w["name"] for w in WORKLOADS if w["chips"] == 1][-1]


def small(cell_name: str, grid: int = 2, steps: int = 8) -> run.Cell:
    """The cell with its grid cut to grid x grid and fewer compared steps;
    every width as the cell has it."""
    cell = run.load_cell(cell_name)
    cell.config["grid"].update(grid_x=grid, grid_y=grid)
    cell.checks["compare_steps"] = steps
    return cell


def cpu():
    import jax
    return jax.devices("cpu")


# ---------------------------------------------------------------------------
# the reference against the program
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def program_4x4():
    """The program's first 20 steps of a 4x4 grid, seed past 2**31."""
    import jax

    cell = small(CELLS[0], grid=4, steps=20)
    seed = 2**31 + 12345
    cfg, sp = run._program(cell, seed)
    state = sp.place(sp.init_state())
    state, raster, _ = jax.block_until_ready(sp.run(state, 0, 20))
    got = run.fetch(sp.plan, state, [raster], cfg.n_neurons, cfg.n_synapses)
    # at one shard the fetch is shard 0's rows, and its first E weights
    old = compare.Outputs(
        raster=np.asarray(raster)[:, 0], v=np.asarray(state.v)[0],
        u=np.asarray(state.u)[0], w=np.asarray(state.w)[0][:cfg.n_synapses])
    for k in ("raster", "v", "u", "w"):
        assert np.array_equal(getattr(got, k), getattr(old, k)), k
        assert getattr(got, k).dtype == getattr(old, k).dtype, k
    return cell, seed, sp, got


def test_reference_regenerates_the_programs_tables(program_4x4):
    cell, seed, sp, _ = program_4x4
    net = reference.make_network(cell.config["grid"], seed)
    order = reference.canonical_order(net)
    n_syn = net.n * net.m
    assert np.array_equal(np.asarray(sp.plan.syn_tgt)[0][:n_syn],
                          net.tgt[order])
    assert np.array_equal(np.asarray(sp.plan.syn_delay)[0][:n_syn],
                          net.delay[order])


@pytest.fixture(scope="module")
def reference_4x4(program_4x4):
    cell, seed, _, got = program_4x4
    return run.reference_outputs(cell, seed, got.raster.shape[0],
                                 ("float64", "bfloat16"))


def test_program_agrees_with_reference(program_4x4, reference_4x4):
    cell, _, _, got = program_4x4
    want, counts = reference_4x4["float64"]
    # the compared steps hold every layer: spikes, arrivals, LTD and LTP
    assert min(counts.values()) > 0, counts
    ok, checks = compare.judge(compare.readings(got, want),
                               cell.checks["limits"])
    assert ok, checks
    assert checks["raster_mismatch"]["value"] == 0


@pytest.mark.parametrize("cell_name", CELLS)
def test_control_is_not_correct(program_4x4, reference_4x4, cell_name):
    """The reference in bfloat16, put in the program's place, fails the
    cell's limits."""
    want, _ = reference_4x4["float64"]
    control, _ = reference_4x4["bfloat16"]
    limits = run.load_cell(cell_name).checks["limits"]
    ok, checks = compare.judge(compare.readings(control, want), limits)
    assert not ok, checks


# ---------------------------------------------------------------------------
# the whole run, with the chip check skipped and faults planted
# ---------------------------------------------------------------------------


def _flip_first_spike(orig):
    def bad(spec, plan, state, t0, n_steps):
        state, raster, tm = orig(spec, plan, state, t0, n_steps)
        return state, raster.at[0, 0, 0].set(~raster[0, 0, 0]), tm
    return bad


def _state_unchanged(orig):
    def bad(spec, plan, state, t0, n_steps):
        _, raster, tm = orig(spec, plan, state, t0, n_steps)
        return state, raster, tm
    return bad


def _no_delivery(orig):
    def bad(spec, plan, state, spiked_src, t):
        return state
    return bad


FAULTS = {
    "state_unchanged": ("run", _state_unchanged),
    "spike_delivery_left_out": ("phase_b", _no_delivery),
    "spike_altered_where_produced": ("run", _flip_first_spike),
}


@contextlib.contextmanager
def planted(fault):
    from repro.core import engine
    if fault is None:
        yield
        return
    attr, make = FAULTS[fault]
    orig = getattr(engine, attr)
    setattr(engine, attr, make(orig))
    try:
        yield
    finally:
        setattr(engine, attr, orig)


@pytest.mark.parametrize("fault", [None] + sorted(FAULTS))
def test_run_decides_correct(fault, capsys):
    cell = small(ONE_CHIP, grid=2, steps=8)
    with planted(fault):
        result = run.run(cell, 7, 0.2, False, cpu(), check_kernels=False)
    assert result["correct"] is (fault is None), result["checks"]
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "checks"]
    json.dumps(result)
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-1] == f"correct {result['correct']}"
    for name in compare.NUMBERS:
        assert any(line.startswith(f"check {name} ") for line in err[-5:])


def test_result_line_reports_each_end_to_end_metric():
    cell = small(ONE_CHIP, grid=2, steps=4)
    result = run.run(cell, 3, 0.2, False, cpu(), check_kernels=False)
    want = {m["name"] for m in cell.end_to_end} - {"peak_hbm_bytes"}
    # the CPU backend reports no peak memory; the chip does
    assert want <= set(result["metrics"])
    for m in cell.end_to_end:
        if m["name"] in result["metrics"]:
            assert result["metrics"][m["name"]]["unit"] == m["unit"]
            assert result["metrics"][m["name"]]["value"] > 0
    assert result["attempted"] >= 5 and result["failed"] == 0
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}


# ---------------------------------------------------------------------------
# the trace reduction, on a trace recorded on the chip (two 4x4 steps)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def trace():
    return trace_reduce.reduce(TRACE, 1)


def test_trace_busy_and_gaps_fill_the_window(trace):
    assert 0 < trace.busy_s <= trace.window_s
    idle = sum(s for _, s in trace.gaps)
    assert idle == pytest.approx(trace.window_s - trace.busy_s, rel=1e-6)
    assert {n for n, _ in trace.gaps} <= {"window", "chunk", "none"}


def test_trace_finds_each_kernel_and_the_gathers(trace):
    for k in trace_reduce.KERNELS:
        assert trace.calls(lambda o: trace_reduce.kernel_of(o) == k) == 2, k
    # the four E-wide gathers and scatter of two steps, and no kernel among
    # them; at 4x4 they take most of the device time
    big = [o for o in trace.ops if trace_reduce.kind(o) == "gather_scatter"
           and "3200000" in o.text]
    assert len(big) == 8
    assert sum(o.dur_ns for o in big) / 1e9 > 0.5 * trace.busy_s


def test_trace_breakdown_shape(trace):
    b = trace.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(isinstance(n, str) and s > 0 for n, s in b["device_ops"])


@pytest.fixture(scope="module")
def trace_h4():
    return trace_reduce.reduce(TRACE_H4, 4)


def test_four_chip_trace_finds_the_collective_permutes(trace_h4):
    coll = [o for o in trace_h4.ops if trace_reduce.is_collective(o)]
    # three halo offsets, a start and a done each, two steps, four chips
    assert len(coll) == 3 * 2 * 2 * 4
    assert {o.device for o in coll} == {0, 1, 2, 3}
    assert {re.sub(r"\.\d+$", "", o.name) for o in coll} == {
        "collective-permute-start", "collective-permute-done"}
    # nothing else in the trace is a collective, and each chip runs each
    # kernel once a step
    assert not [o for o in trace_h4.ops if not trace_reduce.is_collective(o)
                and "collective-permute" in o.text.split("(", 1)[0]]
    for k in trace_reduce.KERNELS:
        assert trace_h4.calls(lambda o: trace_reduce.kernel_of(o) == k) == 2


def test_four_chip_trace_exposed_exchange_share(trace_h4):
    exposed = trace_h4.exposed_per_device(trace_reduce.is_collective)
    assert sorted(exposed) == [0, 1, 2, 3]
    assert all(0 < s < trace_h4.window_s for s in exposed.values())
    share = run.reader("per_layer", "exchange_exposed_share")(
        _record(trace_h4, chips=4))
    assert 0 < share < 100
    assert len(trace_h4.busy_per_device) == 4
    assert sum(trace_h4.busy_per_device) / 4 == pytest.approx(
        trace_h4.busy_s, rel=1e-12)


def test_step_roofline_counts_every_chip(trace_h4):
    one = run.reader("per_layer", "step_roofline")(_record(trace_h4))
    four = run.reader("per_layer", "step_roofline")(
        _record(trace_h4, chips=4))
    assert four == pytest.approx(one / 4, rel=1e-12)


# what the readers gave on the two one-chip traces before the reduction
# learnt collectives: no one-chip reading may move
PINNED = {
    "trace_4x4.xplane.pb": dict(
        device_idle_share=2.621492185150276,
        gather_scatter_share=97.06124945784029,
        izhikevich_roofline=28.111686600102853,
        stdp_arrival_roofline=13.325441340699305,
        stdp_ltp_roofline=8.622991473542916,
        step_roofline=0.1609894183461421),
    "trace_4x4_scoped.xplane.pb": dict(
        device_idle_share=2.5441963097965536,
        gather_scatter_share=97.05963447488405,
        izhikevich_roofline=28.095680705284046,
        stdp_arrival_roofline=13.306015252394214,
        stdp_ltp_roofline=8.618280831749528,
        step_roofline=0.16111453237401038),
}
ONE_CHIP_KINDS = {"other": 310, "gather_scatter": 14, "kernel:izhikevich": 2,
                  "kernel:stdp_arrival": 2, "kernel:stdp_ltp": 2}


def _record(t, n_synapses=3_200_000, n_neurons=16_000, chips=1):
    from repro.core.params import GridConfig
    return run.Record(setup={}, setup_s=0.0, window_s=t.window_s, steps=2,
                      dt_ms=1.0, n_neurons=n_neurons, n_synapses=n_synapses,
                      delay_slots=GridConfig(grid_x=4,
                                             grid_y=4).n_delay_slots,
                      peak_bytes=None, device_kind="TPU v5 lite",
                      chips=chips, trace=t)


@pytest.mark.parametrize("name", sorted(PINNED))
def test_one_chip_trace_readings_are_pinned(name):
    import collections
    t = trace_reduce.reduce(os.path.join(BENCH, "testdata", name), 1)
    rec = _record(t)
    for metric, want in PINNED[name].items():
        assert run.reader("per_layer", metric)(rec) == pytest.approx(
            want, rel=1e-12), metric
    assert run.reader("per_layer", "exchange_exposed_share")(rec) is None
    assert collections.Counter(map(trace_reduce.kind, t.ops)) == \
        ONE_CHIP_KINDS


def test_union_merges_overlaps():
    assert trace_reduce._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [
        (0, 3), (5, 8)]


def test_exposed_is_the_part_no_other_op_covers():
    """On one device a collective from 0 to 10 under compute from 2 to 5
    and 8 to 12 is exposed for 2 + 3; on another, wholly covered, for 0."""
    def op(text, a, b, d):
        return trace_reduce.Op(text=text, start_ns=a * 1e9,
                               dur_ns=(b - a) * 1e9, device=d)
    coll = "%cp = s32[8]{0} collective-permute-done(%cp-start)"
    comp = "%fusion = f32[8]{0} fusion(%p), kind=kLoop"
    t = trace_reduce.Reduction(
        window_s=20.0, busy_s=0.0, gaps=[], n_devices=2,
        busy_per_device=[], ops=[op(coll, 0, 10, 0), op(comp, 2, 5, 0),
                                 op(comp, 8, 12, 0), op(coll, 1, 2, 1),
                                 op(comp, 0, 3, 1)])
    assert t.exposed_per_device(trace_reduce.is_collective) == {0: 5.0,
                                                               1: 0.0}
    assert t.exposed(trace_reduce.is_collective) == 2.5


# ---------------------------------------------------------------------------
# kernel bytes against the kernels' own shapes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kernel,n", [("izhikevich", 576_000),
                                      ("stdp_arrival", 115_200_000),
                                      ("stdp_ltp", 28_800_000),
                                      ("stdp_ltp", 1000)])
def test_kernel_bytes_match_the_kernels_operands(kernel, n):
    import jax
    import jax.numpy as jnp

    from repro.kernels import izhikevich, ops, stdp

    x = jax.ShapeDtypeStruct((n,), jnp.float32)
    pad = jax.eval_shape(lambda a: ops._pad_to_2d(a)[0], x)
    f2 = jax.ShapeDtypeStruct(pad.shape, jnp.float32)
    b2 = jax.ShapeDtypeStruct(pad.shape, jnp.bool_)
    t1 = jax.ShapeDtypeStruct((1,), jnp.float32)
    kw = dict(w_min=0.0, w_max=10.0, neg_time=-1e9)
    args, call = {
        "izhikevich": ([f2] * 7, lambda *a: izhikevich.izhikevich_update(
            *a, v_peak=30.0)),
        "stdp_arrival": ([b2, f2, f2, f2, b2, t1], lambda *a:
                         stdp.stdp_arrival(*a, a_minus=0.1, tau_minus=20.0,
                                           **kw)),
        "stdp_ltp": ([b2, f2, f2, b2, b2, t1], lambda *a: stdp.stdp_ltp(
            *a, a_plus=0.1, tau_plus=20.0, **kw)),
    }[kernel]
    outs = jax.eval_shape(call, *args)
    moved = sum(a.size * a.dtype.itemsize for a in args if a.shape != (1,))
    moved += sum(o.size * o.dtype.itemsize for o in jax.tree.leaves(outs))
    assert getattr(kernel_bytes, kernel)(n) == moved


def test_step_bytes_per_synapse():
    assert kernel_bytes.step(1, 6) == 14 + 2 * (8 + 6)


# ---------------------------------------------------------------------------
# discovery by name, and the benchmark's own contract
# ---------------------------------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_every_cell_config_mix_and_metric_is_found_by_name():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for w in bench["workloads"]:
        cell = run.load_cell(w["name"])
        assert set(cell.checks["limits"]) == set(compare.NUMBERS)
        assert cell.checks["compare_steps"] % cell.traffic["chunk_steps"] == 0
        for m in cell.end_to_end:
            assert callable(run.reader("end_to_end", m["name"]))
        for m in cell.per_layer:
            assert callable(run.reader("per_layer", m["name"]))
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        conf = json.load(open(os.path.join(ROOT, c["file"])))
        assert conf["name"] == c["name"]
        assert set(c["reduced"]) <= set(conf["grid"])


def test_benchmark_names_units_and_layers():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    perf = open(os.path.join(ROOT, "PERF.md")).read()
    names = [e["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in bench[key]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert f"| {m['layer']} |" in perf, m["layer"]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


# ---------------------------------------------------------------------------
# no TPU, no result
# ---------------------------------------------------------------------------


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "chip_bench", "run.py"),
         "--workload", CELLS[-1], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _no_result(proc):
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        assert not line.lstrip().startswith("{"), line


def test_no_tpu_exits_nonzero_without_a_result():
    proc = _run_cli(ROOT)
    _no_result(proc)
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "chip_bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    _no_result(_run_cli(str(tmp_path)))
