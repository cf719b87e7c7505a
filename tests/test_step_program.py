"""`core.StepProgram` facade: the pipelined exchange schedule must be
bit-identical to sync (rasters AND weights) for every delivery backend,
exchange wire and shard count; the hierarchical exchange must reproduce
allgather; and the deprecated quartet entry points must warn and
delegate to the same machinery."""
import warnings

import numpy as np
import pytest

import repro.core as core
from repro.core import EngineConfig, GridConfig, StepProgram, observables
from repro.core import distributed as D

from _mp_helpers import run_with_devices

CFG = GridConfig(grid_x=2, grid_y=2, neurons_per_column=60,
                 synapses_per_neuron=24, seed=9)


# ---------------------------------------------------------------------------
# sync vs pipelined bit-identity, real shard_map at H in {1, 2, 4}
# ---------------------------------------------------------------------------

_SCHED_CODE = """
import numpy as np
from repro.core import EngineConfig, GridConfig, StepProgram, observables
from repro.core import distributed as D

cfg = GridConfig(grid_x=2, grid_y=2, neurons_per_column=60,
                 synapses_per_neuron=24, seed=9)
STEPS = 60
for exchange in ("halo", "allgather"):
    for H in (1, 2, 4):
        outs = {{}}
        for sched in ("sync", "pipelined"):
            eng = EngineConfig(n_shards=H, exchange=exchange,
                               delivery={delivery!r},
                               exchange_schedule=sched)
            sp = StepProgram(cfg, eng, mesh=D.make_mesh(H))
            state = sp.place(sp.init_state())
            state, raster, _ = sp.run(state, 0, STEPS)
            w = state.w if {delivery!r} == "dense" else state.base.w
            outs[sched] = (
                observables.raster_signature(np.asarray(raster),
                                             np.asarray(sp.plan.gid)),
                np.asarray(w))
        sig_s, w_s = outs["sync"]
        sig_p, w_p = outs["pipelined"]
        assert sig_s == sig_p, \\
            f"raster differs: {delivery!r} {{exchange}} H={{H}}"
        assert np.array_equal(w_s, w_p), \\
            f"weights differ: {delivery!r} {{exchange}} H={{H}}"
print("SCHED OK")
"""


@pytest.mark.slow
@pytest.mark.parametrize("delivery", ["dense", "event"])
def test_pipelined_bit_identical_to_sync(delivery):
    """Rasters AND weights must bit-match between schedules over
    H in {1,2,4} x {halo,allgather} — a schedule is an execution layout,
    never physics (the ISSUE's headline acceptance gate)."""
    out = run_with_devices(_SCHED_CODE.format(delivery=delivery), 4,
                           timeout=900)
    assert "SCHED OK" in out


# ---------------------------------------------------------------------------
# hierarchical exchange == allgather, and under both schedules
# ---------------------------------------------------------------------------

_HIER_CODE = """
import numpy as np
from repro.core import EngineConfig, GridConfig, StepProgram, observables
from repro.core import distributed as D

cfg = GridConfig(grid_x=2, grid_y=2, neurons_per_column=60,
                 synapses_per_neuron=24, seed=9)
sigs = {}
for exchange, sched in (("allgather", "sync"), ("hier", "sync"),
                        ("hier", "pipelined")):
    eng = EngineConfig(n_shards=4, exchange=exchange,
                       exchange_schedule=sched)
    sp = StepProgram(cfg, eng, mesh=D.make_mesh(4),
                     hier_groups=2 if exchange == "hier" else None)
    state = sp.place(sp.init_state())
    _, raster, _ = sp.run(state, 0, 60)
    sigs[(exchange, sched)] = observables.raster_signature(
        np.asarray(raster), np.asarray(sp.plan.gid))
assert len(set(sigs.values())) == 1, sigs
print("HIER OK")
"""


@pytest.mark.slow
def test_hier_exchange_matches_allgather():
    """The two-level exchange (intra-group gather + inter-group
    neighbourhood ppermute, emulated via hier_groups=2 in one process)
    must reproduce the flat allgather raster, under both schedules."""
    out = run_with_devices(_HIER_CODE, 4, timeout=900)
    assert "HIER OK" in out


# ---------------------------------------------------------------------------
# single-device (vmap) schedule identity — runs in the tier-1 parent
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("exchange", ["allgather", "halo"])
def test_time_phases_schedule_identity_vmap(exchange):
    """`time_phases` must produce identical rasters/counters (and final
    weights) under both schedules on the logical-shard path too."""
    outs = {}
    for sched in ("sync", "pipelined"):
        eng = EngineConfig(n_shards=2, exchange=exchange,
                           exchange_schedule=sched)
        sp = StepProgram(CFG, eng)
        s, times, rasters, counts = sp.time_phases(
            sp.init_state(), 0, 40, collect_rasters=True)
        assert set(times) == {"phase_a_s", "exchange_s", "phase_b_s"}
        outs[sched] = (np.stack(rasters), counts, np.asarray(s.w))
    r_s, c_s, w_s = outs["sync"]
    r_p, c_p, w_p = outs["pipelined"]
    assert np.array_equal(r_s, r_p)
    assert c_s == c_p
    assert np.array_equal(w_s, w_p)


def test_unknown_schedule_rejected():
    eng = EngineConfig(n_shards=2, exchange_schedule="bogus")
    sp = StepProgram(CFG, eng, mesh=None)
    with pytest.raises(ValueError, match="exchange_schedule"):
        D.make_run_program(sp.spec, sp.plan, D.make_mesh(1))


# ---------------------------------------------------------------------------
# deprecation shims: warn AND delegate
# ---------------------------------------------------------------------------

class TestDeprecatedShims:
    def test_build_delivery_warns_and_delegates(self):
        eng = EngineConfig(n_shards=2)
        with pytest.warns(DeprecationWarning, match="StepProgram"):
            spec, plan, eplan, state, cap_ev = core.build_delivery(CFG, eng)
        assert eplan is None and cap_ev is None
        sp = StepProgram(CFG, eng)
        assert np.array_equal(np.asarray(plan.gid), np.asarray(sp.plan.gid))
        assert np.array_equal(np.asarray(state.w),
                              np.asarray(sp.init_state().w))

    def test_run_delivery_warns_and_matches_step_program(self):
        eng = EngineConfig(n_shards=2)
        sp = StepProgram(CFG, eng)
        _, raster_new, _ = sp.run(sp.init_state(), 0, 30)
        with pytest.warns(DeprecationWarning, match="StepProgram"):
            _, raster_old, _ = core.run_delivery(
                sp.spec, sp.plan, None, sp.init_state(), 0, 30)
        assert np.array_equal(np.asarray(raster_old),
                              np.asarray(raster_new))

    def test_event_build_delivery_roundtrip(self):
        eng = EngineConfig(n_shards=2, delivery="event")
        with pytest.warns(DeprecationWarning):
            spec, plan, eplan, state, cap_ev = core.build_delivery(CFG, eng)
        assert eplan is not None and cap_ev == state.ev_ring.shape[-1]
        with pytest.warns(DeprecationWarning):
            _, raster_old, _ = core.run_delivery(spec, plan, eplan, state,
                                                 0, 30)
        _, raster_new, _ = StepProgram.from_parts(
            spec, plan, eplan).run(state, 0, 30)
        assert np.array_equal(np.asarray(raster_old),
                              np.asarray(raster_new))

    def test_make_sharded_run_warns_and_delegates(self):
        eng = EngineConfig(n_shards=1)
        sp = StepProgram(CFG, eng)
        mesh = D.make_mesh(1)
        with pytest.warns(DeprecationWarning, match="StepProgram"):
            runner = D.make_sharded_run(sp.spec, sp.plan, mesh)
        _, raster_old, _ = runner(sp.init_state(), 0, 30)
        _, raster_new, _ = StepProgram.from_parts(
            sp.spec, sp.plan, mesh=mesh).run(sp.init_state(), 0, 30)
        assert np.array_equal(np.asarray(raster_old),
                              np.asarray(raster_new))

    def test_make_phase_fns_warns_and_returns_triple(self):
        eng = EngineConfig(n_shards=1)
        sp = StepProgram(CFG, eng)
        mesh = D.make_mesh(1)
        with pytest.warns(DeprecationWarning, match="StepProgram"):
            pa, ex, pb = D.make_phase_fns(sp.spec, sp.plan, mesh)
        state = sp.init_state()
        s2, spiked, _ = pa(state, 0)
        s3 = pb(s2, ex(spiked), 0)
        assert np.asarray(s3.v).shape == np.asarray(state.v).shape

    def test_no_warning_on_step_program_itself(self):
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            sp = StepProgram(CFG, EngineConfig(n_shards=2))
            sp.run(sp.init_state(), 0, 5)


# ---------------------------------------------------------------------------
# the step's work, named: scopes, the run span, the counters
# ---------------------------------------------------------------------------

PHASES = ("phase_a_dynamics", "phase_a_plasticity", "exchange", "phase_b")


def _op_names(sp, n_steps=2):
    """The op_name metadata of the CPU-compiled run program."""
    import re
    state = sp.place(sp.init_state())
    text = sp.lower_run(state, 0, n_steps).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


def _scoped(op_names, name):
    import re
    return any(re.search(rf"(?<!\w){name}(?!\w)", o) for o in op_names)


@pytest.mark.parametrize("layout", ["dense", "event", "streamed", "mesh"])
def test_each_layout_names_its_phases(layout):
    """Every execution layout and delivery backend carries the four phase
    scopes of engine.SCOPES into the compiled program; the dense path
    also scopes its four E-wide index operations."""
    eng = {"dense": EngineConfig(n_shards=2),
           "event": EngineConfig(n_shards=2, delivery="event"),
           "streamed": EngineConfig(n_shards=2,
                                    connectivity="streamed:chunk=2"),
           "mesh": EngineConfig(n_shards=1)}[layout]
    mesh = D.make_mesh(1) if layout == "mesh" else None
    names = _op_names(StepProgram(CFG, eng, mesh=mesh))
    want = PHASES
    if layout in ("dense", "mesh"):
        want = core.engine.SCOPES
    assert set(PHASES) <= set(core.engine.SCOPES)
    assert [s for s in want if not _scoped(names, s)] == []


def test_scope_outside_the_list_is_refused():
    with pytest.raises(ValueError, match="SCOPES"):
        core.engine.scope("gather_everything")


def test_run_counters_match_an_independent_count():
    """StepProgram.run's per-step StepTimings, summed over chunks as the
    chip benchmark sums them, equal a numpy count from the raster and the
    synapse tables: spikes per step, and synapses whose source spiked
    `delay` steps earlier.  2x2 columns at the paper's widths, 20 steps
    in two chunks."""
    cfg = GridConfig(grid_x=2, grid_y=2)
    sp = StepProgram(cfg, EngineConfig())
    state = sp.place(sp.init_state())
    rasters, spikes, arrivals = [], [], []
    for t0 in (0, 10):
        state, raster, tm = sp.run(state, t0, 10)
        rasters.append(np.asarray(raster)[:, 0])
        spikes.append(np.asarray(tm.spikes)[:, 0])
        arrivals.append(np.asarray(tm.arrivals)[:, 0])
    raster = np.concatenate(rasters)                       # [T, N]
    spikes, arrivals = np.concatenate(spikes), np.concatenate(arrivals)

    plan = sp.plan
    gid = np.asarray(plan.gid)[0]
    by_gid = np.zeros((raster.shape[0], cfg.n_neurons), bool)
    by_gid[:, gid[gid >= 0]] = raster[:, gid >= 0]
    valid = np.asarray(plan.syn_valid)[0]
    src = np.asarray(plan.src_gid)[0][np.asarray(plan.syn_src)[0][valid]]
    delay = np.asarray(plan.syn_delay)[0][valid]
    want_arrivals = np.array([
        int(by_gid[t - delay[delay <= t], src[delay <= t]].sum())
        for t in range(raster.shape[0])])

    assert spikes.sum() > 0 and want_arrivals.sum() > 0
    assert np.array_equal(spikes, raster.sum(axis=1))
    assert np.array_equal(arrivals, want_arrivals)


def test_run_call_is_a_host_span_in_the_profile(tmp_path):
    """StepProgram.run writes its RUN_SPAN into a profiler trace, once a
    call, on the host's clock."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from repro.core.step_program import RUN_SPAN

    sp = StepProgram(CFG, EngineConfig(n_shards=2))
    state = sp.place(sp.init_state())
    state = jax.block_until_ready(sp.run(state, 0, 1))[0]
    jax.profiler.start_trace(str(tmp_path))
    try:
        for t in (1, 2, 3):
            state = jax.block_until_ready(sp.run(state, t, 1))[0]
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    spans = [e for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name == RUN_SPAN]
    assert len(spans) == 3
    assert all(e.duration_ns > 0 for e in spans)
