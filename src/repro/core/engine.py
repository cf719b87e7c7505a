"""DPSNN-STDP engine: per-shard plan/state, the two-phase simulation step,
and a single-device multi-shard driver (vmap-based logical distribution).

Step structure (paper §Methods, "dynamic phase" 2.1-2.4):

  phase A (local compute):
    1. pop this step's slot of the arrival ring        (spikes reach synapses)
    2. synaptic currents I = sum of arrived weights    (current injection)
    3. LTD for arrived synapses (nearest post spike)   (STDP, event-driven)
    4. thalamic stimulus
    5. Izhikevich neuron update -> spikes              (time-driven dynamics)
    6. LTP for incoming synapses of spiking neurons    (STDP, event-driven)
  exchange:
    7. deliver axonal spikes (AER) to target shards    (two-phase delivery)
  phase B (local compute):
    8. expand arrived axons into synapses: set arrival flags at
       slot (t + delay) mod D                          (deferred arborization)

The engine is written against per-shard arrays so the same phase functions
run under `vmap` (single device, logical shards — used by tests/benchmarks)
and under `shard_map` (real collectives — repro.core.distributed).
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import connectivity, stimulus, topology
from .params import (DEFAULT_IZH, DEFAULT_STDP, EngineConfig, GridConfig,
                     IzhikevichParams, StdpParams)

# "never" sentinel for last-spike times; a host constant, so importing
# this module claims no device
NEG_TIME = np.float32(-1.0e9)


class ShardPlan(NamedTuple):
    """Static per-shard data (device arrays).  Leading dim stacks shards."""

    src_gid: jnp.ndarray      # [S] int32 global ids of sources (-1 pad)
    syn_src: jnp.ndarray      # [E] int32 -> index into src table
    syn_tgt: jnp.ndarray      # [E] int32 local target neuron
    syn_delay: jnp.ndarray    # [E] int32 steps
    syn_plastic: jnp.ndarray  # [E] bool
    syn_valid: jnp.ndarray    # [E] bool
    exc_mask: jnp.ndarray     # [N] bool
    neuron_valid: jnp.ndarray  # [N] bool (capacity padding)
    gid: jnp.ndarray          # [N] int32 global id of each local neuron (-1)
    columns: jnp.ndarray      # [C] int32 columns owned (padded -1)
    shard_id: jnp.ndarray     # [] int32


class ShardState(NamedTuple):
    """Dynamic per-shard state."""

    v: jnp.ndarray            # [N] fp32
    u: jnp.ndarray            # [N] fp32
    last_post: jnp.ndarray    # [N] fp32 (time of most recent spike)
    w: jnp.ndarray            # [E] fp32 synaptic weights
    last_arr: jnp.ndarray     # [E] fp32 (time of most recent arrival)
    arr_ring: jnp.ndarray     # [D, E] bool arrival flags


class SimSpec(NamedTuple):
    """Static python-side description shared by all shards."""

    cfg: GridConfig
    eng: EngineConfig
    izh: IzhikevichParams
    stdp: StdpParams
    n_local: int              # N capacity per shard
    e_cap: int
    s_cap: int
    n_total: int
    # streamed-connectivity geometry (core.stream_engine.StreamSpec) or
    # None for materialized tables; when set, e_cap is the padded
    # synapse-STATE length and the ShardPlan syn_* leaves are dummies.
    stream: object = None


# ----------------------------------------------------------------------------
# plan construction
# ----------------------------------------------------------------------------


def _owned_columns_padded(cfg, eng, shard, c_cap):
    gids = topology.owned_gids(cfg, shard, eng.n_shards, eng.placement)
    cols = np.unique(topology.gid_column(cfg, gids))
    out = np.full((c_cap,), -1, dtype=np.int32)
    out[:cols.shape[0]] = cols
    return out


def build(cfg: GridConfig, eng: EngineConfig,
          izh: IzhikevichParams = DEFAULT_IZH,
          stdp: StdpParams = DEFAULT_STDP,
          tables=None) -> Tuple[SimSpec, ShardPlan, ShardState]:
    """Build plans + initial state for all shards, stacked on a leading [H]
    axis.  Construction is fully local per shard (zero communication).
    `tables` optionally reuses prebuilt `connectivity.build_all_shards`
    output so callers layering extra plans on top (the event backend) pay
    the host-side construction once."""
    if tables is None:
        tables = connectivity.build_all_shards(cfg, eng)
    H = eng.n_shards
    n_cap = topology.max_local_size(cfg, H, eng.placement)
    e_cap = tables[0].src_idx.shape[0]
    s_cap = tables[0].src_gid.shape[0]
    c_cap = max(
        np.unique(topology.gid_column(
            cfg, topology.owned_gids(cfg, h, H, eng.placement))).shape[0]
        for h in range(H))

    plans = []
    for h, t in enumerate(tables):
        gids = topology.owned_gids(cfg, h, H, eng.placement)
        n_loc = gids.shape[0]
        gid_p = np.full((n_cap,), -1, dtype=np.int32)
        gid_p[:n_loc] = gids
        exc = np.zeros((n_cap,), dtype=bool)
        exc[:n_loc] = topology.is_excitatory(cfg, gids)
        nv = np.zeros((n_cap,), dtype=bool)
        nv[:n_loc] = True
        plans.append(ShardPlan(
            src_gid=t.src_gid.astype(np.int32),
            syn_src=t.src_idx, syn_tgt=t.tgt_local,
            syn_delay=t.delay, syn_plastic=t.plastic, syn_valid=t.valid,
            exc_mask=exc, neuron_valid=nv, gid=gid_p,
            columns=_owned_columns_padded(cfg, eng, h, c_cap),
            shard_id=np.int32(h)))

    # host arrays: placement (StepProgram.place) puts each shard straight
    # on its device, so no device ever holds the whole network on the way
    stacked = jax.tree.map(lambda *xs: np.stack(xs), *plans)
    spec = SimSpec(cfg=cfg, eng=eng, izh=izh, stdp=stdp, n_local=n_cap,
                   e_cap=e_cap, s_cap=s_cap, n_total=cfg.n_neurons)

    w0 = np.stack([t.weight0 for t in tables])
    state = init_state(spec, stacked)._replace(w=w0)
    return spec, stacked, state


def init_neurons(spec: SimSpec, exc_mask: np.ndarray):
    """Host (v, u, last_post) at rest for a stacked [H, N] `exc_mask`."""
    izh = spec.izh
    v = np.full(exc_mask.shape, izh.v_init, np.float32)
    b = np.where(exc_mask, np.float32(izh.b_exc), np.float32(izh.b_inh))
    return v, b * v, np.full(exc_mask.shape, NEG_TIME)


def init_state(spec: SimSpec, plan: ShardPlan) -> ShardState:
    """Fresh dynamic state (zero weights; `build` installs w0) [H, ...],
    as host arrays."""
    if spec.stream is not None:
        from . import stream_engine
        return stream_engine.init_state(spec, plan)
    v, u, last_post = init_neurons(spec, np.asarray(plan.exc_mask))
    e_shape = np.shape(plan.syn_valid)               # [H, E]
    return ShardState(
        v=v, u=u, last_post=last_post,
        w=np.zeros(e_shape, np.float32),
        last_arr=np.full(e_shape, NEG_TIME),
        arr_ring=np.zeros((e_shape[0], spec.cfg.n_delay_slots)
                          + e_shape[1:], bool))


# ----------------------------------------------------------------------------
# ownership maps (gid -> local index), placement-specific
# ----------------------------------------------------------------------------


def make_gid_to_local(spec: SimSpec, shard_id: jnp.ndarray) -> Callable:
    """Returns gid_to_local(gids) -> (local_idx, owned_mask) for one shard."""
    eng, cfg = spec.eng, spec.cfg
    if eng.placement == "block":
        bounds = topology.shard_bounds_block(cfg.n_neurons, eng.n_shards)
        starts = jnp.asarray(bounds[:-1], jnp.int32)
        ends = jnp.asarray(bounds[1:], jnp.int32)

        def f(gids):
            s = starts[shard_id]
            e = ends[shard_id]
            owned = (gids >= s) & (gids < e)
            return (gids - s).astype(jnp.int32), owned
        return f
    elif eng.placement == "scatter":
        H = eng.n_shards

        def f(gids):
            owned = (gids % H) == shard_id
            owned &= (gids >= 0) & (gids < cfg.n_neurons)
            return (gids // H).astype(jnp.int32), owned
        return f
    raise ValueError(eng.placement)


# ----------------------------------------------------------------------------
# the step, phase A / phase B
# ----------------------------------------------------------------------------


class StepTimings(NamedTuple):
    """Per-phase work markers (paper Table 2 instrumentation hooks)."""
    spikes: jnp.ndarray       # local spike count this step
    arrivals: jnp.ndarray     # synaptic arrival count this step


def phase_a_dynamics(spec: SimSpec, plan: ShardPlan, state: ShardState,
                     t: jnp.ndarray, stim_k: jax.Array
                     ) -> Tuple[ShardState, jnp.ndarray, StepTimings]:
    """Phase A steps 1-5: arrivals -> currents -> LTD -> stimulus -> neuron.

    Produces the spike mask — everything the exchange needs — WITHOUT the
    LTP pass, so a pipelined schedule can issue the spike exchange here
    and overlap it with `phase_a_plasticity`.  Returns (state', spiked,
    timings); `state'.last_post` is untouched (plasticity owns it).
    """
    from ..kernels import ops as kops

    cfg, stdp = spec.cfg, spec.stdp
    up = spec.eng.use_pallas or None   # None -> auto (Pallas iff on TPU)
    D = cfg.n_delay_slots
    tf = t.astype(jnp.float32)
    r = jnp.mod(t, D)

    arrivals = state.arr_ring[r] & plan.syn_valid            # [E]
    # 2+3. fused arrival pass: current contributions (pre-LTD weights, in
    # canonical (tgt, src, j) order => reproducible sum), LTD against the
    # nearest post spike, last_arrival refresh.
    lp = state.last_post[plan.syn_tgt]
    w, last_arr, contrib = kops.stdp_arrival(
        arrivals, state.w, lp, state.last_arr, plan.syn_plastic, tf,
        a_minus=stdp.a_minus, tau_minus=stdp.tau_minus, w_min=stdp.w_min,
        w_max=stdp.w_max, neg_time=float(NEG_TIME), use_pallas=up)
    i_syn = jax.ops.segment_sum(contrib, plan.syn_tgt,
                                num_segments=spec.n_local,
                                indices_are_sorted=True)
    arr_ring = state.arr_ring.at[r].set(False)

    # 4+5. stimulus + Izhikevich (shared with the streamed driver)
    v, u, spiked = neuron_update(spec, plan, state, i_syn, t, stim_k)

    new = ShardState(v=v, u=u, last_post=state.last_post, w=w,
                     last_arr=last_arr, arr_ring=arr_ring)
    tm = StepTimings(spikes=spiked.sum(), arrivals=arrivals.sum())
    return new, spiked, tm


def neuron_update(spec: SimSpec, plan: ShardPlan, state: ShardState,
                  i_syn: jnp.ndarray, t: jnp.ndarray, stim_k: jax.Array
                  ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Phase A steps 4-5: thalamic stimulus + Izhikevich update.

    Factored out so `core.stream_engine` runs the identical op sequence on
    a chunk-accumulated i_syn — the neuron-level halves of the two drivers
    cannot drift apart.  Returns (v, u, spiked).
    """
    from ..kernels import ops as kops

    cfg, izh = spec.cfg, spec.izh
    up = spec.eng.use_pallas or None

    # 4. thalamic stimulus
    g2l = make_gid_to_local(spec, plan.shard_id)
    i_ext = stimulus.stim_current(cfg, stim_k, plan.columns, t, g2l,
                                  spec.n_local)

    # 5. Izhikevich update (fused kernel on TPU)
    i_tot = i_syn + i_ext
    a = jnp.where(plan.exc_mask, izh.a_exc, izh.a_inh).astype(jnp.float32)
    b = jnp.where(plan.exc_mask, izh.b_exc, izh.b_inh).astype(jnp.float32)
    c = jnp.where(plan.exc_mask, izh.c_exc, izh.c_inh).astype(jnp.float32)
    d = jnp.where(plan.exc_mask, izh.d_exc, izh.d_inh).astype(jnp.float32)
    v, u, spiked = kops.izhikevich_update(
        state.v, state.u, i_tot, a, b, c, d, v_peak=izh.v_peak, dt=izh.dt,
        substeps=izh.v_substeps, use_pallas=up)
    spiked = spiked & plan.neuron_valid
    return v, u, spiked


def phase_a_plasticity(spec: SimSpec, plan: ShardPlan, state: ShardState,
                       spiked: jnp.ndarray, t: jnp.ndarray) -> ShardState:
    """Phase A step 6: LTP for incoming synapses of spiking neurons.

    dW = +a_plus * exp((last_arrival - t) / tau_plus), dt >= 0.
    Touches only {w, last_post} — disjoint from phase B's {arr_ring} — so
    it commutes with spike delivery and is the compute the pipelined
    schedule hides the exchange behind.
    """
    from ..kernels import ops as kops

    stdp = spec.stdp
    up = spec.eng.use_pallas or None
    tf = t.astype(jnp.float32)
    post = spiked[plan.syn_tgt]
    w = kops.stdp_ltp(post, state.w, state.last_arr, plan.syn_plastic,
                      plan.syn_valid, tf, a_plus=stdp.a_plus,
                      tau_plus=stdp.tau_plus, w_min=stdp.w_min,
                      w_max=stdp.w_max, neg_time=float(NEG_TIME),
                      use_pallas=up)
    last_post = jnp.where(spiked, tf, state.last_post)
    return state._replace(w=w, last_post=last_post)


def phase_a(spec: SimSpec, plan: ShardPlan, state: ShardState,
            t: jnp.ndarray, stim_k: jax.Array
            ) -> Tuple[ShardState, jnp.ndarray, StepTimings]:
    """Local dynamics: arrivals -> currents -> LTD -> neuron -> LTP.

    Composition of `phase_a_dynamics` + `phase_a_plasticity` (the split
    exists for the pipelined exchange schedule; composing them is
    bit-identical to the original fused phase A).  Returns
    (state', spiked[N] bool, timings).
    """
    state, spiked, tm = phase_a_dynamics(spec, plan, state, t, stim_k)
    state = phase_a_plasticity(spec, plan, state, spiked, t)
    return state, spiked, tm


def phase_b(spec: SimSpec, plan: ShardPlan, state: ShardState,
            spiked_src: jnp.ndarray, t: jnp.ndarray) -> ShardState:
    """Deferred axonal arborization: set arrival flags at t + delay.

    The update is a broadcast-compare against the D (=6) static slots
    instead of a scatter: a scatter into [D, E] lowers to iota+concat+
    scatter-max (~12 MB/step of index traffic at E=216k); the compare
    formulation is D fused selects (EXPERIMENTS.md §Perf, SNN iteration).
    """
    D = spec.cfg.n_delay_slots
    active = spiked_src[plan.syn_src] & plan.syn_valid       # [E]
    slot = jnp.mod(t + plan.syn_delay, D)                    # [E]
    hit = active[None, :] & (slot[None, :]
                             == jnp.arange(D, dtype=slot.dtype)[:, None])
    return state._replace(arr_ring=state.arr_ring | hit)


# ----------------------------------------------------------------------------
# single-device driver: logical shards via vmap, exchange via global mask
# ----------------------------------------------------------------------------


def _global_spike_mask(spec: SimSpec, plan: ShardPlan, spiked: jnp.ndarray
                       ) -> jnp.ndarray:
    """[N_total] bool from stacked per-shard spike masks."""
    gids = plan.gid.reshape(-1)
    spk = spiked.reshape(-1)
    return jnp.zeros((spec.n_total,), bool).at[gids].max(spk, mode="drop")


def make_step_fn(spec: SimSpec, plan: ShardPlan):
    """jit-able step over stacked shard states (single device, vmap comm)."""
    stim_k = stimulus.stim_key(spec.cfg)

    def step(state: ShardState, t: jnp.ndarray):
        state, spiked, tm = jax.vmap(
            lambda p, s: phase_a(spec, p, s, t, stim_k))(plan, state)
        glob = _global_spike_mask(spec, plan, spiked)        # the exchange
        spiked_src = jax.vmap(
            lambda p: glob.at[p.src_gid].get(mode="fill", fill_value=False)
            & (p.src_gid >= 0))(plan)
        state = jax.vmap(
            lambda p, s, ss: phase_b(spec, p, s, ss, t))(plan, state,
                                                         spiked_src)
        return state, (spiked, tm)

    return step


def run(spec: SimSpec, plan: ShardPlan, state: ShardState, t0: int,
        n_steps: int):
    """Scan the simulation; returns (state, raster[T, H, N], timings)."""
    step = make_step_fn(spec, plan)

    def body(s, t):
        s, out = step(s, t)
        return s, out

    ts = t0 + jnp.arange(n_steps, dtype=jnp.int32)   # t0 may be traced
    state, (raster, tm) = jax.lax.scan(body, state, ts)
    return state, raster, tm
