"""Core engine behaviour: Izhikevich dynamics, STDP rule, delay ring,
and the paper's headline property — identical rasters over any distribution.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DEFAULT_IZH, DEFAULT_STDP, EngineConfig, GridConfig,
                        build, observables, run)
from repro.core import engine as E

SMALL = GridConfig(grid_x=2, grid_y=2, neurons_per_column=100,
                   synapses_per_neuron=40, seed=7)


# ---------------------------------------------------------------------------
# Izhikevich neuron unit behaviour
# ---------------------------------------------------------------------------

class TestIzhikevich:
    def _run_single(self, current, steps=300, exc=True):
        from repro.core import neuron
        p = DEFAULT_IZH
        exc_mask = jnp.array([exc])
        st = neuron.init_state(exc_mask, p)
        vs, spikes = [], 0
        for _ in range(steps):
            st, spk = neuron.step(st, jnp.array([current], jnp.float32),
                                  exc_mask, p)
            vs.append(float(st.v[0]))
            spikes += int(spk[0])
        return np.array(vs), spikes

    def test_resting_neuron_stays_near_rest(self):
        vs, spikes = self._run_single(0.0)
        assert spikes == 0
        # equilibrium of 0.04v^2+5v+140-u = 0 with u = b v  ->  v = -70
        assert abs(vs[-1] + 70.0) < 5.0

    def test_dc_current_causes_regular_spiking(self):
        vs, spikes = self._run_single(10.0)
        assert spikes > 3
        assert np.isfinite(vs).all()

    def test_fs_spikes_faster_than_rs(self):
        _, rs = self._run_single(10.0, exc=True)
        _, fs = self._run_single(10.0, exc=False)
        assert fs > rs  # FS inhibitory neurons have a higher firing rate

    def test_reset_after_spike(self):
        from repro.core import neuron
        p = DEFAULT_IZH
        exc_mask = jnp.array([True])
        st = neuron.init_state(exc_mask, p)
        fired = False
        for _ in range(200):
            st, spk = neuron.step(st, jnp.array([15.0], jnp.float32),
                                  exc_mask, p)
            if bool(spk[0]):
                fired = True
                assert float(st.v[0]) == pytest.approx(p.c_exc)
                break
        assert fired


# ---------------------------------------------------------------------------
# engine end-to-end on a small grid
# ---------------------------------------------------------------------------

class TestEngineRun:
    def test_runs_and_spikes(self):
        spec, plan, state = build(SMALL, EngineConfig(n_shards=1))
        state, raster, tm = run(spec, plan, state, 0, 200)
        raster = np.asarray(raster)
        assert raster.shape == (200, 1, spec.n_local)
        rate = observables.mean_rate_hz(raster, SMALL.n_neurons)
        assert 1.0 < rate < 200.0      # alive, not epileptic
        assert np.isfinite(np.asarray(state.v)).all()
        assert np.isfinite(np.asarray(state.w)).all()

    def test_weights_stay_in_bounds(self):
        spec, plan, state = build(SMALL, EngineConfig(n_shards=1))
        state, _, _ = run(spec, plan, state, 0, 300)
        w = np.asarray(state.w)
        plastic = np.asarray(plan.syn_plastic)
        valid = np.asarray(plan.syn_valid)
        assert (w[plastic & valid] >= DEFAULT_STDP.w_min - 1e-6).all()
        assert (w[plastic & valid] <= DEFAULT_STDP.w_max + 1e-6).all()
        # inhibitory weights are non-plastic: exactly the initial value
        inh = valid & ~plastic
        assert np.all(w[inh] == SMALL.w_inh_init)

    def test_stdp_changes_weights(self):
        spec, plan, state = build(SMALL, EngineConfig(n_shards=1))
        w0 = np.asarray(state.w).copy()
        state, _, _ = run(spec, plan, state, 0, 300)
        w1 = np.asarray(state.w)
        plastic = np.asarray(plan.syn_plastic & plan.syn_valid)
        assert np.abs(w1[plastic] - w0[plastic]).max() > 1e-3

    def test_initial_rate_in_paper_band(self):
        """Paper Table 1: initial activity 20-48 Hz with strong init weights.
        (Single 1000-neuron column -> paper reports 20 Hz.)"""
        cfg = GridConfig(grid_x=1, grid_y=1, neurons_per_column=1000,
                         synapses_per_neuron=200)
        spec, plan, state = build(cfg, EngineConfig(n_shards=1))
        _, raster, _ = run(spec, plan, state, 0, 500)
        rate = observables.mean_rate_hz(np.asarray(raster), cfg.n_neurons)
        assert 10.0 < rate < 60.0


# ---------------------------------------------------------------------------
# THE paper property: identical spiking for every distribution
# ---------------------------------------------------------------------------

def _signature(cfg, eng, steps=150):
    spec, plan, state = build(cfg, eng)
    _, raster, _ = run(spec, plan, state, 0, steps)
    return observables.raster_signature(np.asarray(raster),
                                        np.asarray(plan.gid))


class TestDistributionInvariance:
    def test_identical_rasters_across_shard_counts(self):
        ref = _signature(SMALL, EngineConfig(n_shards=1))
        for h in (2, 4, 8):
            assert _signature(SMALL, EngineConfig(n_shards=h)) == ref, \
                f"raster changed at H={h}"

    def test_identical_rasters_block_vs_scatter(self):
        ref = _signature(SMALL, EngineConfig(n_shards=1))
        assert _signature(SMALL, EngineConfig(n_shards=4,
                                              placement="scatter")) == ref

    def test_identical_rasters_fractional_columns(self):
        # 3 shards over 4 columns: shards own 133.33 neurons -> column splits
        ref = _signature(SMALL, EngineConfig(n_shards=1))
        assert _signature(SMALL, EngineConfig(n_shards=3)) == ref

    def test_single_column_self_projection(self):
        # paper: a single column projects all synapses onto itself
        cfg = GridConfig(grid_x=1, grid_y=1, neurons_per_column=80,
                         synapses_per_neuron=30, seed=3)
        ref = _signature(cfg, EngineConfig(n_shards=1))
        assert _signature(cfg, EngineConfig(n_shards=2)) == ref


# ---------------------------------------------------------------------------
# delay / polychrony machinery
# ---------------------------------------------------------------------------

class TestDelays:
    def test_arrival_ring_slots(self):
        """A spike emitted at t with delay d must arrive exactly at t+d."""
        cfg = GridConfig(grid_x=1, grid_y=1, neurons_per_column=50,
                         synapses_per_neuron=10, seed=11,
                         stim_events_per_ms_per_column=0)  # silence
        spec, plan, state = build(cfg, EngineConfig(n_shards=1))
        step = E.make_step_fn(spec, plan)

        # force neuron 0 to spike at t=0 by injecting via v
        state = state._replace(v=jnp.asarray(state.v).at[0, 0].set(40.0))
        arrivals = []
        for t in range(8):
            state, (spiked, tm) = jax.jit(step)(state, jnp.int32(t))
            arrivals.append(int(tm.arrivals[0]))
        # synapses of neuron 0 (valid, src==0)
        src_gid = np.asarray(plan.src_gid[0])
        syn_src = np.asarray(plan.syn_src[0])
        valid = np.asarray(plan.syn_valid[0])
        from_n0 = valid & (src_gid[syn_src] == 0)
        delays = np.asarray(plan.syn_delay[0])[from_n0]
        expect = np.zeros(8, dtype=int)
        for d in delays:
            if d < 8:
                expect[d] += 1
        # no other activity: arrivals must match the delay histogram exactly
        assert arrivals == expect.tolist()

    def test_no_stimulus_no_activity(self):
        cfg = GridConfig(grid_x=1, grid_y=1, neurons_per_column=50,
                         synapses_per_neuron=10,
                         stim_events_per_ms_per_column=0)
        spec, plan, state = build(cfg, EngineConfig(n_shards=1))
        _, raster, _ = run(spec, plan, state, 0, 50)
        assert np.asarray(raster).sum() == 0
