"""Distributed DPSNN runtime: the same phase-A/B step as `engine`, but with
real collectives under `shard_map` (via `repro.dist.compat`) over a
`cells` mesh axis.

Spike exchange modes (EngineConfig.exchange):

  'allgather' — every shard gathers all shards' spike masks and builds the
      global mask.  Simple, bandwidth ~ N_total bits/step; the right choice
      for small meshes and for `scatter` placement (whose halo is global).

  'halo' — the paper's two-phase sparse delivery, TPU-adapted: each shard
      packs a fixed-capacity AER buffer (ids + count lane, see core.aer) and
      `lax.ppermute`s it along the *static* set of shard offsets that the
      connectivity actually uses (discovered at build time, exactly like the
      paper's first construction step discovers the process subset).
      Received ids are matched against the local source table; the count
      lane is a compute-gating hint (processing cost scales with real
      spikes), while wire bytes are static — the SPMD trade documented in
      DESIGN.md §2.

  'hier' — two-level hierarchy matching the paper's cluster topology:
      level 1 is an intra-process `all_gather` restricted (via
      axis_index_groups) to the shards one OS process owns — shared-memory
      traffic, never crossing the NIC; level 2 AER-packs the whole group's
      spikes once and `ppermute`s the group buffer only along the *static
      group-stride* set the connectivity reaches (hier_offsets — the halo
      discovery re-run at process granularity).  Inter-process messages
      therefore go only to neighbouring processes, like the paper's
      subset-of-processes delivery, however many shards each process runs.

Exchange schedules (EngineConfig.exchange_schedule) — orthogonal to both:

  'sync'      — phase A -> exchange -> phase B in program order.
  'pipelined' — the exchange for step t is issued right after the
      dynamics half of phase A(t) (which produces the spike mask) and its
      result is consumed by a phase B(t) deferred into the NEXT loop
      iteration, double-buffered through the scan carry.  The collective
      therefore overlaps the LTP half of phase A plus the loop turnaround
      instead of exposing its full latency.  The per-step op sequence —
      B(t-1); A_dyn(t); X(t); A_plast(t) — is a rotation of the sync
      sequence with identical dataflow (A_plast writes {w, last_post},
      B writes the arrival rings; disjoint), so rasters AND weights are
      bit-identical to 'sync' (DESIGN.md §Pipelined exchange).

Delivery modes (EngineConfig.delivery) — orthogonal to the exchange:

  'dense' — O(E) masked delivery (`engine.phase_a/phase_b`).
  'event' — O(spikes x fan) event lists (`event_engine.phase_a/phase_b`),
      the paper's actual computational model.  The exchange wire is
      UNCHANGED: its output `spiked_src` is exactly the event backend's
      phase_b input, so halo/allgather schedules compose with event
      delivery for free.  Callers pass the `EventPlan` (threaded through
      the jitted programs as an argument alongside the ShardPlan — closure
      constants cannot span processes) and an `EventState` whose extra
      leaves (ev_ring, ev_count, sat) ride the same `cells` specs.
"""
from __future__ import annotations

import warnings
from typing import Callable, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from . import aer, engine, event_engine, stimulus, stream_engine, topology
from .engine import ShardPlan, ShardState, SimSpec
from ..dist import compat as dist_compat
from ..dist import mesh as dist_mesh
from ..dist import sharding as dist_sharding


def halo_offsets(spec: SimSpec, plan: ShardPlan) -> List[int]:
    """Static shard-to-shard offsets used by the connectivity.

    == the paper's construction-phase discovery of "the subset of processes
    that should be listened to", derived locally from the source tables.
    The source tables themselves are provisioned from the connectivity
    profile's `reach()` (topology.shard_halo_columns), so the exchange
    schedule follows the profile automatically: a ring1 kernel shrinks the
    offset set, a gaussian one widens it (DESIGN.md §Connectivity
    profiles) — no constant ring depth appears anywhere downstream.
    """
    H = spec.eng.n_shards
    src_gid = np.asarray(plan.src_gid)            # [H, S]
    offs = set()
    for h in range(H):
        s = src_gid[h]
        s = s[s >= 0]
        owners = np.unique(topology.owner_of(spec.cfg, s, H,
                                             spec.eng.placement))
        for o in owners.tolist():
            offs.add((h - o) % H)                 # sender o -> receiver h
    return sorted(offs)


def make_mesh(n_shards: int) -> Mesh:
    return dist_mesh.make_snn_mesh(n_shards)


@engine.scope("exchange")
def _spiked_src_allgather(spec, plan_gid_all, spiked, src_gid):
    spk_all = jax.lax.all_gather(spiked, "cells")            # [H, N]
    glob = jnp.zeros((spec.n_total,), bool).at[
        plan_gid_all.reshape(-1)].max(spk_all.reshape(-1), mode="drop")
    return glob.at[src_gid].get(mode="fill", fill_value=False) & (src_gid >= 0)


@engine.scope("exchange")
def _spiked_src_halo(spec, offsets, plan, spiked):
    """Sparse AER wire + dense local match.

    Wire: fixed-capacity AER buffers ppermute over the static halo offsets
    (the paper's two-phase delivery).  Match: received ids are scattered
    into a local [N_total] mask, then ONE gather by the source table — a
    per-offset searchsorted match measured 60x more HBM traffic
    (EXPERIMENTS.md §Perf, SNN iteration C)."""
    H = spec.eng.n_shards
    ids, _count = aer.pack(spiked, plan.gid, plan.gid.shape[0])
    received = []
    for d in offsets:
        if d == 0:
            received.append(ids)
        else:
            perm = [(i, (i + d) % H) for i in range(H)]
            received.append(jax.lax.ppermute(ids, "cells", perm=perm))
    # single scatter: one functional mask update instead of |offsets|
    # sequential ones (each re-copied the [N_total] mask: 25 MB/step at
    # 512 columns — §Perf SNN iteration D)
    all_ids = jnp.concatenate(received)
    mask = jnp.zeros((spec.n_total,), bool).at[all_ids].set(
        True, mode="drop")
    return mask.at[plan.src_gid].get(mode="fill", fill_value=False) \
        & (plan.src_gid >= 0)


def mesh_shard_groups(mesh: Mesh, n_shards: int) -> List[List[int]]:
    """Contiguous per-process shard groups of the `cells` axis.

    `jax.devices()` orders devices process-major, so a process's shards
    are a contiguous block of the axis; the hierarchical exchange needs
    that (and equal block sizes, an `axis_index_groups` requirement), so
    both are verified rather than assumed."""
    devs = list(mesh.devices.reshape(-1))[:n_shards]
    procs = [d.process_index for d in devs]
    groups: List[List[int]] = [[0]]
    for i in range(1, n_shards):
        if procs[i] == procs[i - 1]:
            groups[-1].append(i)
        else:
            groups.append([i])
    if len(groups) != len(set(procs)):
        raise ValueError(
            f"hier exchange needs contiguous per-process device blocks on "
            f"the cells axis; got process layout {procs}")
    if len({len(g) for g in groups}) != 1:
        raise ValueError(
            f"hier exchange needs equal shards per process; got "
            f"{[len(g) for g in groups]}")
    return groups


def hier_offsets(spec: SimSpec, plan: ShardPlan, group_size: int
                 ) -> List[int]:
    """`halo_offsets` at PROCESS-GROUP granularity: the static set of
    group strides the connectivity reaches.  Derived from the same source
    tables (provisioned from the profile's `reach()`), so a narrow kernel
    shrinks the inter-process neighbourhood and a wide one grows it."""
    H = spec.eng.n_shards
    G = H // group_size
    src_gid = np.asarray(plan.src_gid)
    offs = set()
    for h in range(H):
        s = src_gid[h]
        s = s[s >= 0]
        owners = np.unique(topology.owner_of(spec.cfg, s, H,
                                             spec.eng.placement))
        for o in owners.tolist():
            offs.add((h // group_size - o // group_size) % G)
    return sorted(offs)


@engine.scope("exchange")
def _spiked_src_hier(spec, groups, g_offsets, gid_all, plan, spiked):
    """Two-level exchange: intra-group all_gather, inter-group AER.

    Level 1 gathers the group's [L, N] spike block over shared memory
    (axis_index_groups keeps the collective inside one process).  Level 2
    packs ONE AER buffer for the whole group and ppermutes it at whole-
    group stride, so each inter-process message carries a process's full
    spike set and only neighbouring processes ever exchange bytes.
    Delivered mask == the allgather wire's, bit-for-bit."""
    H = spec.eng.n_shards
    L = len(groups[0])
    spk_grp = jax.lax.all_gather(spiked, "cells",
                                 axis_index_groups=groups)       # [L, N]
    g = jax.lax.axis_index("cells") // L
    gid_grp = jax.lax.dynamic_slice_in_dim(gid_all, g * L, L, axis=0)
    ids, _count = aer.pack(spk_grp.reshape(-1), gid_grp.reshape(-1),
                           gid_grp.size)
    received = [ids]                                  # own group (stride 0)
    for d in g_offsets:
        if d == 0:
            continue
        perm = [(i, (i + d * L) % H) for i in range(H)]
        received.append(jax.lax.ppermute(ids, "cells", perm=perm))
    all_ids = jnp.concatenate(received)
    mask = jnp.zeros((spec.n_total,), bool).at[all_ids].set(
        True, mode="drop")
    return mask.at[plan.src_gid].get(mode="fill", fill_value=False) \
        & (plan.src_gid >= 0)


def _resolve_groups(spec: SimSpec, mesh: Optional[Mesh],
                    hier_groups) -> List[List[int]]:
    """Shard groups for the 'hier' exchange: an explicit group count (for
    single-process emulation/tests), an explicit group list, or — the
    production path — the mesh's per-process device blocks."""
    H = spec.eng.n_shards
    if hier_groups is None:
        if mesh is None:
            raise ValueError("exchange='hier' needs a mesh (to derive "
                             "per-process groups) or hier_groups=")
        return mesh_shard_groups(mesh, H)
    if isinstance(hier_groups, int):
        G = hier_groups
        if G <= 0 or H % G:
            raise ValueError(f"hier_groups={G} must divide n_shards={H}")
        L = H // G
        return [list(range(g * L, (g + 1) * L)) for g in range(G)]
    return [list(g) for g in hier_groups]


def _make_exchange(spec: SimSpec, plan: ShardPlan,
                   groups: Optional[Sequence[Sequence[int]]] = None):
    """Per-shard exchange callable (plan_1, spiked_1) -> spiked_src_1.

    Closes over host-side statics only (halo/group offsets / replicated
    gid table), so the returned callable is safe inside `shard_map` bodies
    on process-spanning meshes.  `plan` must be host-addressable."""
    if spec.eng.exchange == "halo":
        offsets = halo_offsets(spec, plan)
        return lambda p1, s1: _spiked_src_halo(spec, offsets, p1, s1)
    gid_all = jnp.asarray(np.asarray(plan.gid))   # replicated [H, N]
    if spec.eng.exchange == "hier":
        if groups is None:
            raise ValueError("exchange='hier': no shard groups resolved")
        g_offsets = hier_offsets(spec, plan, len(groups[0]))
        return lambda p1, s1: _spiked_src_hier(spec, groups, g_offsets,
                                               gid_all, p1, s1)
    return lambda p1, s1: _spiked_src_allgather(spec, gid_all, s1, p1.src_gid)


# ---------------------------------------------------------------------------
# delivery dispatch: both backends share the plan/state/exchange plumbing
# ---------------------------------------------------------------------------


def _is_event(spec: SimSpec) -> bool:
    return spec.eng.delivery == "event"


def _is_streamed(spec: SimSpec) -> bool:
    return spec.stream is not None


def _base_plan(planT):
    """The ShardPlan inside a delivery-dependent plan tree (event mode
    carries (ShardPlan, EventPlan), streamed mode (ShardPlan,
    StreamedPlan); NamedTuples are tuples, so dispatch on the concrete
    type, not tuple-ness)."""
    return planT if isinstance(planT, ShardPlan) else planT[0]


def _plan_tree(spec: SimSpec, plan: ShardPlan, eplan, splan=None):
    if _is_streamed(spec):
        if splan is None:
            raise ValueError("streamed connectivity needs the StreamedPlan: "
                             "pass splan= (from stream_engine.build)")
        return (plan, splan)
    if not _is_event(spec):
        return plan
    if eplan is None:
        raise ValueError("delivery='event' needs the EventPlan: pass "
                         "eplan= (from event_engine.build)")
    return (plan, eplan)


class _Phases(NamedTuple):
    """Per-shard phase callables over the delivery-dependent plan tree.

    `pa` (full phase A) returns (state', spiked, StepTimings); the
    pipelined schedule uses its split halves `pa_dyn` (same return
    contract, LTP pending) + `pa_plast` instead — composing them is the
    definition of `pa`, so both schedules run the same ops."""
    pa: Callable
    pb: Callable
    pa_dyn: Callable
    pa_plast: Callable


def _delivery_phases(spec: SimSpec, stim_k,
                     caps: Optional[dict] = None) -> _Phases:
    """Phase callables with the signature (planT_1, state_1, ...) -> ...,
    dispatched on EngineConfig.delivery (+ streamed connectivity); all
    backends share it."""
    caps = caps or {}
    if _is_streamed(spec):
        def pa(planT, st, t):
            p, sp = planT
            return stream_engine.phase_a(spec, p, sp, st, t, stim_k)

        def pb(planT, st, ss, t):
            p, sp = planT
            return stream_engine.phase_b(spec, p, sp, st, ss, t)

        def pa_dyn(planT, st, t):
            p, sp = planT
            return stream_engine.phase_a_dynamics(spec, p, sp, st, t,
                                                  stim_k)

        def pa_plast(planT, st, spiked, t):
            p, sp = planT
            return stream_engine.phase_a_plasticity(spec, p, sp, st,
                                                    spiked, t)

        return _Phases(pa, pb, pa_dyn, pa_plast)
    if _is_event(spec):
        c_post, c_src = caps.get("c_post"), caps.get("c_src")

        def pa(planT, st, t):
            p, ep = planT
            return event_engine.phase_a(spec, p, ep, st, t, stim_k,
                                        c_post=c_post)

        def pb(planT, st, ss, t):
            p, ep = planT
            return event_engine.phase_b(spec, p, ep, st, ss, t, c_src=c_src)

        def pa_dyn(planT, st, t):
            p, ep = planT
            return event_engine.phase_a_dynamics(spec, p, ep, st, t, stim_k)

        def pa_plast(planT, st, spiked, t):
            p, ep = planT
            return event_engine.phase_a_plasticity(spec, p, ep, st, spiked,
                                                   t, c_post=c_post)

        return _Phases(pa, pb, pa_dyn, pa_plast)

    def pa(planT, st, t):
        return engine.phase_a(spec, planT, st, t, stim_k)

    def pb(planT, st, ss, t):
        return engine.phase_b(spec, planT, st, ss, t)

    def pa_dyn(planT, st, t):
        return engine.phase_a_dynamics(spec, planT, st, t, stim_k)

    def pa_plast(planT, st, spiked, t):
        return engine.phase_a_plasticity(spec, planT, st, spiked, t)

    return _Phases(pa, pb, pa_dyn, pa_plast)


def _specs(spec: SimSpec, planT):
    """(plan, state, per-step-timings) partition specs over `cells`."""
    pspec = P("cells")
    plan_specs = jax.tree.map(lambda _: pspec, planT)
    base = ShardState(*([pspec] * len(ShardState._fields)))
    if _is_event(spec):
        state_specs = event_engine.EventState(
            base=base, ev_ring=pspec, ev_count=pspec, sat=pspec)
    else:
        state_specs = base
    tm_specs = engine.StepTimings(spikes=pspec, arrivals=pspec)
    return pspec, plan_specs, state_specs, tm_specs


def _drop_lead(tree):
    """shard_map passes [1, ...] slices; drop the leading axis."""
    return jax.tree.map(lambda x: x[0], tree)


def _src_false(planT):
    """All-False spiked_src of the right per-shard width — the pipelined
    prologue buffer.  Phase B of an all-False mask is an exact no-op for
    both backends (dense: no hits; event: zero compacted sources, zero
    ranks, zero saturation), so priming the double buffer with it keeps
    step t0 bit-identical to the sync schedule."""
    S = _base_plan(planT).src_gid.shape[0]
    return jnp.zeros((S,), bool)


def make_run_program(spec: SimSpec, plan: ShardPlan, mesh: Mesh,
                     eplan=None, caps: Optional[dict] = None,
                     hier_groups=None, splan=None) -> "RunProgram":
    """Returns the RunProgram whose run(state, t0, n_steps) -> (state,
    raster, timings) executes one shard per device of the `cells` mesh
    axis.  (Constructed via
    `core.StepProgram`; this is the machinery behind its `.run` handle.)

    `plan` must be HOST-addressable (the stacked tree `build` returns):
    halo discovery reads it with numpy, and it is then placed on `mesh`
    here and threaded through the jitted program as an *argument* — a
    closure constant cannot span processes, and even single-process it
    re-materializes ~50x slower on CPU (EXPERIMENTS.md §Perf).

    With spec.eng.delivery == 'event', `eplan` (host-addressable, from
    `event_engine.build`) rides along the same way and `state` must be an
    EventState; `caps` optionally overrides the event compaction
    capacities (dict with 'c_post'/'c_src' — tests force tiny ones).

    spec.eng.exchange_schedule selects the loop body: 'sync' is the
    program-order A -> X -> B step; 'pipelined' rotates it to
    B(t-1) -> A_dyn(t) -> X(t) -> A_plast(t) with the exchange result
    double-buffered through the scan carry (all-False prologue, epilogue
    flush after the scan), so X(t) is issued before the LTP pass it
    overlaps.  Identical op sequence per step => bit-identical outputs."""
    stim_k = stimulus.stim_key(spec.cfg)
    groups = (_resolve_groups(spec, mesh, hier_groups)
              if spec.eng.exchange == "hier" else None)
    exchange = _make_exchange(spec, plan, groups)
    planT = _plan_tree(spec, plan, eplan, splan)
    if spec.eng.exchange_schedule not in ("sync", "pipelined"):
        raise ValueError(
            f"unknown exchange_schedule {spec.eng.exchange_schedule!r}")
    ph = _delivery_phases(spec, stim_k, caps)
    pspec, plan_specs, state_specs, tm_specs = _specs(spec, planT)
    plan_d = dist_sharding.shard_put(mesh, planT, "cells")
    pipelined = spec.eng.exchange_schedule == "pipelined"

    def shard_body(plan_s, state_s, ts):
        plan_1 = _drop_lead(plan_s)
        state_1 = _drop_lead(state_s)

        def step_sync(state, t):
            state, spiked, tm = ph.pa(plan_1, state, t)
            spiked_src = exchange(_base_plan(plan_1), spiked)
            state = ph.pb(plan_1, state, spiked_src, t)
            return state, (spiked, tm)

        def step_pipelined(carry, t):
            state, ss_prev = carry
            state = ph.pb(plan_1, state, ss_prev, t - 1)  # deliver step t-1
            state, spiked, tm = ph.pa_dyn(plan_1, state, t)
            ss = exchange(_base_plan(plan_1), spiked)     # issued pre-LTP
            state = ph.pa_plast(plan_1, state, spiked, t)
            return (state, ss), (spiked, tm)

        if pipelined:
            carry0 = (state_1, _src_false(plan_1))
            (state_1, ss_last), (raster, tm) = jax.lax.scan(
                step_pipelined, carry0, ts)
            state_1 = ph.pb(plan_1, state_1, ss_last, ts[-1])  # flush
        else:
            state_1, (raster, tm) = jax.lax.scan(step_sync, state_1, ts)
        out_state = jax.tree.map(lambda x: x[None], state_1)
        return (out_state, raster[:, None],
                jax.tree.map(lambda x: x[:, None], tm))

    # scan outputs carry a leading time axis in front of each per-call spec
    run = jax.jit(dist_compat.shard_map(
        shard_body, mesh,
        in_specs=(plan_specs, state_specs, P()),
        out_specs=(state_specs, P(None, *pspec),
                   jax.tree.map(lambda s: P(None, *s), tm_specs))))

    def times(t0: int, n_steps: int):
        return dist_sharding.replicated_put(
            mesh, jnp.arange(t0, t0 + n_steps, dtype=jnp.int32))

    return RunProgram(
        run=lambda state, t0, n_steps: run(plan_d, state,
                                           times(t0, n_steps)),
        lower=lambda state, t0, n_steps: run.lower(plan_d, state,
                                                   times(t0, n_steps)))


class RunProgram(NamedTuple):
    """`run(state, t0, n_steps) -> (state, raster[T, H, N], timings)` and
    `lower(state, t0, n_steps)`, the `jax.stages.Lowered` of the program
    `run` executes for those arguments (for its compiled text and memory
    analysis)."""
    run: Callable
    lower: Callable


class PhasePrograms(NamedTuple):
    """Separately-jitted shard_map'd phase handles over one mesh.

    `phase_a(state, t)` / `exchange(spiked)` / `phase_b(state, ss, t)` is
    the paper's Table 2 split; `phase_a_dynamics(state, t)` and
    `phase_a_plasticity(state, spiked, t)` are phase A's halves, timed
    separately under the pipelined schedule (the exchange is dispatched
    between them).  All five thread the placed plan as a jit argument."""
    phase_a: Callable
    exchange: Callable
    phase_b: Callable
    phase_a_dynamics: Callable
    phase_a_plasticity: Callable


def make_phase_programs(spec: SimSpec, plan: ShardPlan, mesh: Mesh,
                        eplan=None, caps: Optional[dict] = None,
                        hier_groups=None, splan=None) -> PhasePrograms:
    """Separately-jitted shard_map'd phases over `mesh` — the machinery
    behind `StepProgram.phase_fns` / `.time_phases`, used by
    `repro.cluster` and the bench suites to attribute wall-clock to
    phase A / spike exchange / phase B per process (paper Table 2,
    across the process axis).  The placed plan is bound into each
    returned fn as a jit argument; `plan` must be host-addressable and
    `eplan`/`caps` follow the `make_run_program` contract, so per-phase
    walls are comparable across backends and schedules."""
    stim_k = stimulus.stim_key(spec.cfg)
    groups = (_resolve_groups(spec, mesh, hier_groups)
              if spec.eng.exchange == "hier" else None)
    exchange = _make_exchange(spec, plan, groups)
    planT = _plan_tree(spec, plan, eplan, splan)
    ph = _delivery_phases(spec, stim_k, caps)
    pspec, plan_specs, state_specs, tm_specs = _specs(spec, planT)
    plan_d = dist_sharding.shard_put(mesh, planT, "cells")

    def a_body(plan_s, state_s, t):
        state_1, spiked, tm = ph.pa(_drop_lead(plan_s),
                                    _drop_lead(state_s), t)
        return (jax.tree.map(lambda x: x[None], state_1), spiked[None],
                jax.tree.map(lambda x: x[None], tm))

    def adyn_body(plan_s, state_s, t):
        state_1, spiked, tm = ph.pa_dyn(_drop_lead(plan_s),
                                        _drop_lead(state_s), t)
        return (jax.tree.map(lambda x: x[None], state_1), spiked[None],
                jax.tree.map(lambda x: x[None], tm))

    def aplast_body(plan_s, state_s, spiked_s, t):
        state_1 = ph.pa_plast(_drop_lead(plan_s), _drop_lead(state_s),
                              spiked_s[0], t)
        return jax.tree.map(lambda x: x[None], state_1)

    def ex_body(plan_s, spiked_s):
        return exchange(_base_plan(_drop_lead(plan_s)), spiked_s[0])[None]

    def b_body(plan_s, state_s, spiked_src_s, t):
        state_1 = ph.pb(_drop_lead(plan_s), _drop_lead(state_s),
                        spiked_src_s[0], t)
        return jax.tree.map(lambda x: x[None], state_1)

    sm = dist_compat.shard_map
    a_j = jax.jit(sm(a_body, mesh, in_specs=(plan_specs, state_specs, P()),
                     out_specs=(state_specs, pspec, tm_specs)))
    adyn_j = jax.jit(sm(adyn_body, mesh,
                        in_specs=(plan_specs, state_specs, P()),
                        out_specs=(state_specs, pspec, tm_specs)))
    aplast_j = jax.jit(sm(aplast_body, mesh,
                          in_specs=(plan_specs, state_specs, pspec, P()),
                          out_specs=state_specs))
    ex_j = jax.jit(sm(ex_body, mesh, in_specs=(plan_specs, pspec),
                      out_specs=pspec))
    b_j = jax.jit(sm(b_body, mesh,
                     in_specs=(plan_specs, state_specs, pspec, P()),
                     out_specs=state_specs))

    def tput(x):
        return dist_sharding.replicated_put(mesh, jnp.int32(x))

    return PhasePrograms(
        phase_a=lambda state, t: a_j(plan_d, state, tput(t)),
        exchange=lambda spiked: ex_j(plan_d, spiked),
        phase_b=lambda state, ss, t: b_j(plan_d, state, ss, tput(t)),
        phase_a_dynamics=lambda state, t: adyn_j(plan_d, state, tput(t)),
        phase_a_plasticity=lambda state, spiked, t: aplast_j(
            plan_d, state, spiked, tput(t)))


# ---------------------------------------------------------------------------
# deprecated entry points (PR 6 API redesign): use core.StepProgram
# ---------------------------------------------------------------------------


def _warn_deprecated(old: str) -> None:
    warnings.warn(
        f"core.distributed.{old} is deprecated; construct a "
        f"core.StepProgram instead (its .run / .phase_fns handles cover "
        f"this, plus the pipelined schedule and hier exchange)",
        DeprecationWarning, stacklevel=3)


def make_sharded_run(spec: SimSpec, plan: ShardPlan, mesh: Mesh,
                     eplan=None, caps: Optional[dict] = None):
    """Deprecated alias of the `StepProgram.run` machinery."""
    _warn_deprecated("make_sharded_run")
    return make_run_program(spec, plan, mesh, eplan=eplan, caps=caps).run


def make_phase_fns(spec: SimSpec, plan: ShardPlan, mesh: Mesh,
                   eplan=None, caps: Optional[dict] = None):
    """Deprecated: returns the legacy (phase_a, exchange, phase_b) triple
    of what is now `StepProgram.phase_fns`."""
    _warn_deprecated("make_phase_fns")
    pp = make_phase_programs(spec, plan, mesh, eplan=eplan, caps=caps)
    return pp.phase_a, pp.exchange, pp.phase_b


def shard_put(mesh: Mesh, tree):
    """Place a stacked [H, ...] tree with each shard on its device."""
    return dist_sharding.shard_put(mesh, tree, "cells")
