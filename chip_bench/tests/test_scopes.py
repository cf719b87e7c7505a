"""Tests of the scope join (scopes.py), on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q chip_bench/tests

They cover: the join of a trace recorded on the chip (two 4x4 steps of the
program with its scopes, named kernels and `run_call` span) to the
compiled text of the program it ran; the join's rules on a small module;
kernels told by name and by results; and the readers the benchmark
already has, pinned on the older trace, recorded before the program named
its work.
"""
from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chip_bench import run, scopes, trace_reduce  # noqa: E402

DATA = os.path.join(BENCH, "testdata")
OLD = os.path.join(DATA, "trace_4x4.xplane.pb")
SCOPED = os.path.join(DATA, "trace_4x4_scoped.xplane.pb")
SCOPED_HLO = os.path.join(DATA, "trace_4x4_scoped.hlo.txt")
GATHERS = ("gather_last_post", "gather_post_spiked", "gather_src_spiked")


@pytest.fixture(scope="module")
def scoped():
    with open(SCOPED_HLO) as f:
        scope_of = scopes.of_instructions(f.read())
    return trace_reduce.reduce(SCOPED, 1), scope_of


def test_join_puts_busy_time_under_the_programs_scopes(scoped):
    trace, scope_of = scoped
    by = scopes.by_scope(trace, scope_of)
    assert sum(by.values()) == pytest.approx(
        trace.seconds(lambda o: True), rel=1e-9)
    assert by.get("none", 0.0) <= 0.01 * trace.busy_s, by
    assert set(GATHERS) | {"current_scatter"} <= set(by)


def test_gathers_and_scatter_are_the_e_wide_fusions(scoped):
    """The three gather scopes and the scatter's take the time of the
    E-wide gather and scatter fusions, within a point of busy time."""
    trace, scope_of = scoped
    by = scopes.by_scope(trace, scope_of)
    named = sum(by[s] for s in GATHERS + ("current_scatter",))
    e_wide = trace.seconds(
        lambda o: trace_reduce.kind(o) == "gather_scatter"
        and "3200000" in o.text)
    assert abs(named - e_wide) <= 0.01 * trace.busy_s
    assert by["current_scatter"] > 0
    assert min(by[s] for s in GATHERS) > 0


@pytest.mark.parametrize("path,named", [(SCOPED, True), (OLD, False)])
def test_kernels_by_name_and_by_results(path, named):
    """Where the program names its kernels, the name and the results tell
    the same kernel, twice each over two steps; the older trace's kernels
    carry no kernel's name."""
    trace = trace_reduce.reduce(path, 1)
    for k in trace_reduce.KERNELS:
        by_types = [o for o in trace.ops if trace_reduce.kernel_of(o) == k]
        by_name = [o for o in trace.ops if scopes.kernel_by_name(o) == k]
        assert len(by_types) == 2, k
        assert by_name == (by_types if named else []), k


def test_four_chip_collectives_are_the_exchange_scope():
    """On four chips every collective the reduction finds lies in the
    program's `exchange` scope."""
    with open(os.path.join(DATA, "trace_4x4_h4.hlo.txt")) as f:
        scope_of = scopes.of_instructions(f.read())
    trace = trace_reduce.reduce(os.path.join(DATA, "trace_4x4_h4.xplane.pb"),
                                4)
    coll = [o for o in trace.ops if trace_reduce.is_collective(o)]
    assert coll
    assert {scope_of.get(o.name) for o in coll} == {"exchange"}


def test_gap_names_come_from_the_span_list(scoped):
    trace, _ = scoped
    assert trace.gaps
    assert {n for n, _ in trace.gaps} <= set(trace_reduce.SPANS) | {"none"}
    idle = sum(s for _, s in trace.gaps)
    assert idle == pytest.approx(trace.window_s - trace.busy_s, rel=1e-6)


def test_run_call_spans_sit_inside_the_chunks():
    """The program's own span is on the profiler's clock: one `run_call`
    inside each of the two `chunk` spans, ending before the chunk does."""
    from jax.profiler import ProfileData

    from repro.core.step_program import RUN_SPAN

    spans = {}
    for plane in ProfileData.from_file(SCOPED).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in ("chunk", RUN_SPAN):
                        spans.setdefault(e.name, []).append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    chunks, calls = sorted(spans["chunk"]), sorted(spans[RUN_SPAN])
    assert len(chunks) == len(calls) == 2
    for (c0, c1), (r0, r1) in zip(chunks, calls):
        assert c0 <= r0 < r1 < c1


# ---------------------------------------------------------------------------
# the join's rules, on a small module
# ---------------------------------------------------------------------------

SCATTER = "f/vmap(phase_a_dynamics)/current_scatter/scatter-add"
GATHER = "f/while/body/vmap(phase_b)/gather_src_spiked/gather"
MODULE = '''\
%fused_computation.1 (p0: f32[8], p1: s32[4]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %p1 = s32[4]{0} parameter(1)
  ROOT %scatter.2 = f32[8]{0} scatter(%p0, %p1), metadata={op_name="@S"}
}

%fused_computation.2 (p0.1: f32[8]) -> f32[8] {
  %p0.1 = f32[8]{0} parameter(0)
  ROOT %fusion.9 = f32[8]{0} fusion(%p0.1), kind=kLoop, calls=%fused_computation.1
}

%fused_computation.3 (p0.2: s32[4]) -> s32[4] {
  %constant.1 = s32[] constant(0), metadata={op_name="f/vmap(phase_b)/max"}
  %p0.2 = s32[4]{0} parameter(0)
  %pad.1 = s32[4]{0} pad(%p0.2, %constant.1), metadata={op_name="@S"}
  ROOT %reduce.1 = s32[4]{0} reduce(%pad.1, %constant.1)
}

ENTRY %main.1 (a: f32[8], b: s32[4]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %b = s32[4]{0} parameter(1)
  %fusion.1 = f32[8]{0} fusion(%a, %b), kind=kCustom, calls=%fused_computation.1
  %fusion.2 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.2
  %fusion.3 = s32[4]{0} fusion(%b), kind=kLoop, calls=%fused_computation.3
  %gather.1 = f32[4]{0} gather(%a, %b), metadata={op_name="@G"}
  ROOT %copy.1 = f32[8]{0} copy(%fusion.1)
}
'''.replace("@S", SCATTER).replace("@G", GATHER)


def test_join_rules_on_a_small_module():
    got = scopes.of_instructions(MODULE)
    assert got["gather.1"] == "gather_src_spiked"      # innermost scope
    assert got["fusion.1"] == "current_scatter"        # its root's scope
    assert got["fusion.2"] == "current_scatter"        # nested fusions
    assert got["fusion.3"] == "current_scatter"        # constants left out
    assert "copy.1" not in got and "a" not in got


def test_program_without_scopes_joins_nothing():
    assert scopes.of_instructions(MODULE, scopes=()) == {}
    assert scopes.innermost("jit(f)/phase_b_extra/x", ("phase_b",)) is None


# ---------------------------------------------------------------------------
# the readers the benchmark already has read the older trace as before
# ---------------------------------------------------------------------------

PINNED = {
    "device_idle_share": 2.621492185150276,
    "step_roofline": 0.1609894183461421,
    "gather_scatter_share": 97.06124945784029,
    "izhikevich_roofline": 28.111686600102853,
    "stdp_arrival_roofline": 13.325441340699305,
    "stdp_ltp_roofline": 8.622991473542916,
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_existing_reader_reads_the_older_trace_as_before(name):
    trace = trace_reduce.reduce(OLD, 1)
    rec = run.Record(setup={}, setup_s=0.0, window_s=trace.window_s,
                     steps=2, dt_ms=1.0, n_neurons=16_000,
                     n_synapses=3_200_000, delay_slots=6, peak_bytes=None,
                     device_kind="TPU v5 lite", trace=trace)
    assert run.reader("per_layer", name)(rec) == pytest.approx(
        PINNED[name], rel=1e-12)
