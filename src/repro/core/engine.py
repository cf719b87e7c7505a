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

import sys
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import connectivity, stimulus, topology
from .params import (DEFAULT_IZH, DEFAULT_STDP, EngineConfig, GridConfig,
                     IzhikevichParams, StdpParams)

# "never" sentinel for last-spike times; a host constant, so importing
# this module claims no device
NEG_TIME = np.float32(-1.0e9)

# Dense delivery's target windows (`expand_to_synapses`): blocks of
# TGT_BLOCK consecutive synapses, one row of 128 lanes, each reaching at
# most TGT_WINDOW_CAP consecutive targets, or the plain gather.
TGT_BLOCK = 128
TGT_WINDOW_CAP = 64

# The names the step's work carries in the compiled program's op metadata
# (`jax.named_scope`): the four phases, which every execution layout and
# delivery backend passes through, then the dense path's four index
# operations over the synapses.  The benchmark's trace join
# (chip_bench/scopes.py) maps each device op to the innermost of these.
SCOPES = ("phase_a_dynamics", "phase_a_plasticity", "exchange", "phase_b",
          "gather_last_post", "current_scatter", "gather_post_spiked",
          "gather_src_spiked")


def scope(name: str):
    """`jax.named_scope(name)` for a name of SCOPES; usable as a decorator.
    Scopes change op metadata only: they add no op."""
    if name not in SCOPES:
        raise ValueError(f"{name!r} is not one of engine.SCOPES")
    return jax.named_scope(name)


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
    # dense delivery's target windows (`tgt_windows`): every block of
    # tgt_block consecutive synapses reaches targets [lowest, lowest +
    # tgt_window); None -> the plain E-wide gather
    tgt_block: Optional[int] = None
    tgt_window: Optional[int] = None


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
    window = tgt_windows(stacked.syn_tgt)
    print("[engine] dense delivery: "
          + (f"tgt-window B={TGT_BLOCK} W={window}" if window
             else "gather"), file=sys.stderr, flush=True)
    spec = SimSpec(cfg=cfg, eng=eng, izh=izh, stdp=stdp, n_local=n_cap,
                   e_cap=e_cap, s_cap=s_cap, n_total=cfg.n_neurons,
                   tgt_block=TGT_BLOCK if window else None,
                   tgt_window=window)

    w0 = np.stack([t.weight0 for t in tables])
    state = init_state(spec, stacked)._replace(w=w0)
    return spec, stacked, state


def tgt_windows(syn_tgt: np.ndarray) -> Optional[int]:
    """W, the widest span of targets (highest - lowest + 1) of any block of
    TGT_BLOCK consecutive synapses, over the stacked [H, E] host tables;
    None where the dense delivery keeps the plain gather: E is not a whole
    number of blocks, or W is over TGT_WINDOW_CAP."""
    H, E = syn_tgt.shape
    if E == 0 or E % TGT_BLOCK:
        return None
    blocks = syn_tgt.reshape(H, E // TGT_BLOCK, TGT_BLOCK)
    window = int((blocks.max(axis=-1) - blocks.min(axis=-1)).max()) + 1
    return window if window <= TGT_WINDOW_CAP else None


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


def expand_to_synapses(spec: SimSpec, plan: ShardPlan, x: jnp.ndarray
                       ) -> jnp.ndarray:
    """x[plan.syn_tgt]: each neuron's value [N] over its incoming
    synapses [E].

    Each block of `spec.tgt_block` synapses reaches targets within
    `spec.tgt_window` of its lowest (`tgt_windows`, at build; synapses
    are stored target-major, so W stays small): gather each block's W
    consecutive values, E/B x W elements in place of E, then pick each
    synapse's by a chain of W - 1 selects in one elementwise pass.  The
    same values as the gather."""
    B, W = spec.tgt_block, spec.tgt_window
    if B is None:
        return x[plan.syn_tgt]
    # the barrier ties this pass's reads of the targets to `x`: without it
    # XLA shares the W - 1 compares between a step's two expansions and
    # keeps them in memory as E-wide masks
    tgt, x = jax.lax.optimization_barrier((plan.syn_tgt, x))
    tgt = tgt.reshape(-1, B)
    # one block a row, so a block's values broadcast along lanes only: per
    # (8, 128) tile, the sublane broadcasts compile to tens of megabytes of
    # program on the TPU, which the program holds in HBM.  A window that
    # would run past the last neuron starts W before it, so the gather's
    # clipping never moves a window away from the `d` it is read with
    lo = jnp.minimum(tgt.min(axis=1), x.shape[0] - W)
    xw = jax.lax.gather(
        x, lo[:, None], jax.lax.GatherDimensionNumbers(
            offset_dims=(1,), collapsed_slice_dims=(), start_index_map=(0,)),
        slice_sizes=(W,), mode="clip")                 # [E/B, W]
    d = tgt - lo[:, None]
    out = jnp.broadcast_to(xw[:, :1], tgt.shape)
    for k in range(1, W):
        out = jnp.where(d == k, xw[:, k:k + 1], out)
    return out.reshape(-1)


class StepTimings(NamedTuple):
    """Per-phase work markers (paper Table 2 instrumentation hooks)."""
    spikes: jnp.ndarray       # local spike count this step
    arrivals: jnp.ndarray     # synaptic arrival count this step


@scope("phase_a_dynamics")
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
    with scope("gather_last_post"):
        lp = expand_to_synapses(spec, plan, state.last_post)
    w, last_arr, contrib = kops.stdp_arrival(
        arrivals, state.w, lp, state.last_arr, plan.syn_plastic, tf,
        a_minus=stdp.a_minus, tau_minus=stdp.tau_minus, w_min=stdp.w_min,
        w_max=stdp.w_max, neg_time=float(NEG_TIME), use_pallas=up)
    with scope("current_scatter"):
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


@scope("phase_a_plasticity")
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
    with scope("gather_post_spiked"):
        post = expand_to_synapses(spec, plan, spiked)
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


@scope("phase_b")
def phase_b(spec: SimSpec, plan: ShardPlan, state: ShardState,
            spiked_src: jnp.ndarray, t: jnp.ndarray) -> ShardState:
    """Deferred axonal arborization: set arrival flags at t + delay.

    The update is a broadcast-compare against the D (=6) static slots
    instead of a scatter: a scatter into [D, E] lowers to iota+concat+
    scatter-max (~12 MB/step of index traffic at E=216k); the compare
    formulation is D fused selects (EXPERIMENTS.md §Perf, SNN iteration).
    """
    D = spec.cfg.n_delay_slots
    with scope("gather_src_spiked"):
        active = spiked_src[plan.syn_src]
    active = active & plan.syn_valid                         # [E]
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


@scope("exchange")
def global_exchange(spec: SimSpec, plan: ShardPlan, spiked: jnp.ndarray
                    ) -> jnp.ndarray:
    """The single-device exchange: each shard's sources [H, S] read off
    the global spike mask of the stacked [H, N] `spiked`."""
    glob = _global_spike_mask(spec, plan, spiked)
    return jax.vmap(
        lambda p: glob.at[p.src_gid].get(mode="fill", fill_value=False)
        & (p.src_gid >= 0))(plan)


def make_step_fn(spec: SimSpec, plan: ShardPlan):
    """jit-able step over stacked shard states (single device, vmap comm)."""
    stim_k = stimulus.stim_key(spec.cfg)

    def step(state: ShardState, t: jnp.ndarray):
        state, spiked, tm = jax.vmap(
            lambda p, s: phase_a(spec, p, s, t, stim_k))(plan, state)
        spiked_src = global_exchange(spec, plan, spiked)
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
