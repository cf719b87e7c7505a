"""`StepProgram`: the one constructor for the DPSNN step.

Every execution surface of the simulator used to be reached through a
quartet of near-duplicate entry points (`core.build_delivery` +
`core.run_delivery` + `distributed.make_sharded_run` +
`distributed.make_phase_fns`), each re-implementing the
delivery/exchange/placement dispatch.  `StepProgram` replaces them with a
single object:

    sp = StepProgram(cfg, eng)                  # single-device reference
    sp = StepProgram(cfg, eng, mesh=mesh)       # shard_map, real collectives
    state = sp.place(sp.init_state())
    state, raster, tm = sp.run(state, 0, 500)   # fused scan
    pa, ex, pb = sp.phase_fns()[:3]             # Table 2 phase split
    state, times, rasters, counts = sp.time_phases(state, 0, 100)

One dispatch point means every caller — the snn launcher, the cluster
worker, the profiler, the bench suites — constructs and times the SAME
compiled programs, and new execution knobs (`exchange_schedule`,
`exchange='hier'`) appear everywhere at once.

Two execution modes share the phase callables (`distributed` dispatches
them on EngineConfig.delivery):

  mesh=None — logical shards via `vmap` on one device; the exchange is
      emulated (allgather/hier: global spike mask; halo: `jnp.roll` of
      packed AER buffers over the shard axis), preserving each wire's
      compute graph so per-phase profiles are meaningful without a
      multi-device platform.  `run` here is the reference scan that
      defines the physics — schedules are execution layouts, so it is
      schedule-independent by construction.
  mesh=Mesh — one shard per device of the `cells` axis via `shard_map`;
      collectives, schedules and the hier exchange are all real.

Plans are threaded through every jitted program as ARGUMENTS, never
closures (a closure constant cannot span processes, and even
single-process it re-materializes ~50x slower on CPU — EXPERIMENTS.md
§Perf); `planT` and `fused` are exposed for HLO cost analysis under the
same rule.
"""
from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from . import (aer, connectivity, distributed, engine, event_engine,
               stimulus, stream_engine)
from .engine import ShardPlan, SimSpec
from .params import (DEFAULT_IZH, DEFAULT_STDP, EngineConfig, GridConfig,
                     IzhikevichParams, StdpParams)
from ..dist import sharding as dist_sharding


class StepProgram:
    """Run/phase/timing handles for one (GridConfig, EngineConfig, mesh).

    Construct from configs (builds connectivity + initial state) or wrap
    prebuilt parts with `from_parts` (bench suites sweeping knobs over one
    expensive build).  All handles are built lazily and cached, so
    constructing a StepProgram compiles nothing by itself."""

    def __init__(self, cfg: GridConfig, eng: EngineConfig, *,
                 mesh: Optional[Mesh] = None,
                 izh: Optional[IzhikevichParams] = None,
                 stdp: Optional[StdpParams] = None,
                 caps: Optional[dict] = None,
                 hier_groups=None):
        izh, stdp = izh or DEFAULT_IZH, stdp or DEFAULT_STDP
        mode, _ = connectivity.parse_mode(eng.connectivity)
        splan = None
        if mode == "streamed":
            spec, plan, splan, state = stream_engine.build(cfg, eng, izh,
                                                           stdp)
            eplan = None
        elif eng.delivery == "event":
            spec, plan, eplan, state = event_engine.build(cfg, eng, izh,
                                                          stdp)
        else:
            spec, plan, state = engine.build(cfg, eng, izh, stdp)
            eplan = None
        self._init(spec, plan, eplan, state, mesh, caps, hier_groups,
                   splan=splan)

    @classmethod
    def from_parts(cls, spec: SimSpec, plan: ShardPlan, eplan=None, *,
                   state0=None, mesh: Optional[Mesh] = None,
                   caps: Optional[dict] = None, hier_groups=None,
                   splan=None) -> "StepProgram":
        """Wrap an already-built (spec, plan[, eplan][, splan][, state])
        without re-running connectivity construction."""
        sp = cls.__new__(cls)
        sp._init(spec, plan, eplan, state0, mesh, caps, hier_groups,
                 splan=splan)
        return sp

    def _init(self, spec, plan, eplan, state0, mesh, caps, hier_groups,
              splan=None):
        if spec.eng.delivery == "event" and eplan is None:
            raise ValueError("delivery='event' needs an EventPlan")
        if spec.stream is not None and splan is None:
            raise ValueError("streamed connectivity needs a StreamedPlan")
        self.spec: SimSpec = spec
        self.plan: ShardPlan = plan
        self.eplan = eplan
        self.splan = splan
        self.mesh = mesh
        self.caps = caps or {}
        self.hier_groups = hier_groups
        self._state0 = state0
        self._run = None
        self._plan_d = None
        self._phases = None
        self._fused = None
        self._stim_k = stimulus.stim_key(spec.cfg)

    # -- construction-time data ------------------------------------------

    @property
    def cap_ev(self) -> Optional[int]:
        """Event-ring capacity (what `checkpoint.load` needs); None for
        the dense backend."""
        if self._state0 is not None and self.eplan is not None:
            return int(self._state0.ev_ring.shape[-1])
        return None

    @property
    def planT(self):
        """The delivery-dependent plan tree every jitted program takes as
        its first argument (dense: ShardPlan; event: (ShardPlan,
        EventPlan); streamed: (ShardPlan, StreamedPlan))."""
        return distributed._plan_tree(self.spec, self.plan, self.eplan,
                                      self.splan)

    def _placed_plan(self):
        """`planT` on the default device, placed once and shared by the
        mesh=None run and phase programs."""
        if self._plan_d is None:
            self._plan_d = jax.device_put(self.planT)
        return self._plan_d

    def init_state(self):
        """The freshly-built initial state (host-side, unplaced)."""
        if self._state0 is None:
            raise ValueError(
                "no initial state: this StepProgram wraps prebuilt parts "
                "(from_parts without state0) — pass state0= or construct "
                "from configs")
        return self._state0

    def place(self, state):
        """Put host `state` on the device(s): each shard on its device of
        the mesh, or all shards on the default device when mesh=None."""
        if self.mesh is None:
            return jax.device_put(state)
        return dist_sharding.shard_put(self.mesh, state, "cells")

    def load(self, path: str):
        """Restore (state, t0) from a checkpoint into this layout."""
        from . import checkpoint
        return checkpoint.load(path, self.spec, self.plan,
                               cap_ev=self.cap_ev)

    def weight_signature(self, state) -> bytes:
        """sha256 over the valid synapse weights in canonical per-shard
        order — the plastic-state counterpart of the raster signature
        (comparable across connectivity residency modes: both lay valid
        weights out in (tgt_gid, src_gid, j) order per shard).  `state`
        must be host-addressable (gather first on a multi-process mesh).
        """
        import hashlib
        w = np.asarray(state.base.w if hasattr(state, "base") else state.w)
        h = hashlib.sha256()
        if self.splan is not None:
            e_start = np.asarray(self.splan.e_start)   # [H, n_chunks + 1]
            for hh in range(w.shape[0]):
                h.update(w[hh, :int(e_start[hh, -1])].tobytes())
        else:
            valid = np.asarray(self.plan.syn_valid)
            for hh in range(w.shape[0]):
                h.update(w[hh][valid[hh]].tobytes())
        return h.digest()

    # -- run handle ------------------------------------------------------

    def run(self, state, t0: int, n_steps: int):
        """Fused scan: (state, raster[T, H, N], timings).

        mesh=None runs the single-device reference driver (vmap shards,
        global-mask exchange — the physics definition both schedules must
        reproduce); with a mesh it is the shard_map program honouring
        exchange/schedule.  Either is compiled once per `n_steps`."""
        return self._run_program().run(state, t0, n_steps)

    def lower_run(self, state, t0: int, n_steps: int):
        """The `jax.stages.Lowered` of the program `run` executes for these
        arguments: its `.compile()` gives the compiled text (which kernels
        entered the program) and the memory analysis."""
        return self._run_program().lower(state, t0, n_steps)

    def _run_program(self) -> distributed.RunProgram:
        if self._run is None:
            if self.mesh is None:
                self._run = self._vmap_run_program()
            else:
                self._run = distributed.make_run_program(
                    self.spec, self.plan, self.mesh, eplan=self.eplan,
                    caps=self.caps, hier_groups=self.hier_groups,
                    splan=self.splan)
        return self._run

    def _vmap_run_program(self) -> distributed.RunProgram:
        spec, caps = self.spec, self.caps

        def scan(planT, state, t0, n_steps):
            if self.splan is not None:
                return stream_engine.run(spec, *planT, state, t0, n_steps)
            if self.eplan is not None:
                return event_engine.run(spec, *planT, state, t0, n_steps,
                                        c_post=caps.get("c_post"),
                                        c_src=caps.get("c_src"))
            return engine.run(spec, planT, state, t0, n_steps)

        # the plan is an argument (see module doc)
        run_j = jax.jit(scan, static_argnums=3)
        plan_d = self._placed_plan()
        return distributed.RunProgram(
            run=lambda state, t0, n_steps: run_j(plan_d, state,
                                                 jnp.int32(t0), n_steps),
            lower=lambda state, t0, n_steps: run_j.lower(
                plan_d, state, jnp.int32(t0), n_steps))

    # -- phase handles (paper Table 2 split) -----------------------------

    def phase_fns(self) -> distributed.PhasePrograms:
        """Separately-jitted phase handles with unified signatures:

            phase_a(state, t) -> (state, spiked, tm)
            exchange(spiked) -> spiked_src
            phase_b(state, spiked_src, t) -> state
            phase_a_dynamics(state, t) / phase_a_plasticity(state, spiked, t)

        — identical shapes in both execution modes, so profiling code is
        mesh-agnostic."""
        if self._phases is None:
            if self.mesh is None:
                self._phases = self._vmap_phase_programs()
            else:
                self._phases = distributed.make_phase_programs(
                    self.spec, self.plan, self.mesh, eplan=self.eplan,
                    caps=self.caps, hier_groups=self.hier_groups,
                    splan=self.splan)
        return self._phases

    def _vmap_exchange(self):
        """Single-device emulation of the exchange wire over stacked
        [H, ...] arrays, preserving each mode's compute graph."""
        spec, plan = self.spec, self.plan

        def ex_allgather(planT, spiked):
            p = distributed._base_plan(planT)
            glob = engine._global_spike_mask(spec, p, spiked)
            return jax.vmap(
                lambda p1: glob.at[p1.src_gid].get(
                    mode="fill", fill_value=False) & (p1.src_gid >= 0))(p)

        if spec.eng.exchange == "halo":
            offsets = distributed.halo_offsets(spec, plan)

            def ex_halo(planT, spiked):
                p = distributed._base_plan(planT)
                ids_all, _ = jax.vmap(
                    lambda p1, s: aer.pack(s, p1.gid, p1.gid.shape[0])
                )(p, spiked)
                # receiver h hears sender (h - d) % H: the single-device
                # analogue of distributed._spiked_src_halo's ppermute
                received = [jnp.roll(ids_all, d, axis=0) for d in offsets]
                all_ids = jnp.concatenate(received, axis=1)

                def match(p1, ids_row):
                    mask = jnp.zeros((spec.n_total,), bool).at[
                        ids_row].set(True, mode="drop")
                    return mask.at[p1.src_gid].get(
                        mode="fill", fill_value=False) & (p1.src_gid >= 0)

                return jax.vmap(match)(p, all_ids)

            return ex_halo

        if spec.eng.exchange == "hier":
            groups = distributed._resolve_groups(spec, None,
                                                 self.hier_groups)
            L = len(groups[0])
            G = len(groups)
            g_offsets = distributed.hier_offsets(spec, plan, L)

            def ex_hier(planT, spiked):
                p = distributed._base_plan(planT)
                N = spiked.shape[-1]
                # level 1: group-local gather == reshape on one device
                gid_g = p.gid.reshape(G, L * N)
                spk_g = spiked.reshape(G, L * N)
                ids, _ = jax.vmap(
                    lambda s, g: aer.pack(s, g, g.shape[0]))(spk_g, gid_g)
                # level 2: whole-group roll at the static group strides
                received = [jnp.roll(ids, d, axis=0) for d in g_offsets]
                all_ids = jnp.repeat(jnp.concatenate(received, axis=1),
                                     L, axis=0)           # [H, ...]

                def match(p1, ids_row):
                    mask = jnp.zeros((spec.n_total,), bool).at[
                        ids_row].set(True, mode="drop")
                    return mask.at[p1.src_gid].get(
                        mode="fill", fill_value=False) & (p1.src_gid >= 0)

                return jax.vmap(match)(p, all_ids)

            return ex_hier

        return ex_allgather

    def _vmap_phase_programs(self) -> distributed.PhasePrograms:
        spec = self.spec
        ph = distributed._delivery_phases(spec, self._stim_k, self.caps)
        exchange = self._vmap_exchange()
        planT = self._placed_plan()

        a_j = jax.jit(lambda pT, s, t: jax.vmap(
            ph.pa, in_axes=(0, 0, None))(pT, s, t))
        adyn_j = jax.jit(lambda pT, s, t: jax.vmap(
            ph.pa_dyn, in_axes=(0, 0, None))(pT, s, t))
        aplast_j = jax.jit(lambda pT, s, spk, t: jax.vmap(
            ph.pa_plast, in_axes=(0, 0, 0, None))(pT, s, spk, t))
        ex_j = jax.jit(exchange)
        b_j = jax.jit(lambda pT, s, ss, t: jax.vmap(
            ph.pb, in_axes=(0, 0, 0, None))(pT, s, ss, t))

        ti = jnp.int32
        return distributed.PhasePrograms(
            phase_a=lambda state, t: a_j(planT, state, ti(t)),
            exchange=lambda spiked: ex_j(planT, spiked),
            phase_b=lambda state, ss, t: b_j(planT, state, ss, ti(t)),
            phase_a_dynamics=lambda state, t: adyn_j(planT, state, ti(t)),
            phase_a_plasticity=lambda state, spiked, t: aplast_j(
                planT, state, spiked, ti(t)))

    @property
    def fused(self):
        """Jitted fused step (planT, state, t) -> (state, spiked, tm) —
        for HLO cost analysis (`fused.lower(sp.planT, state, t)`); the
        plan stays an argument per the no-closure-constants rule."""
        if self._fused is None:
            spec = self.spec
            ph = distributed._delivery_phases(spec, self._stim_k,
                                              self.caps)
            exchange = (self._vmap_exchange() if self.mesh is None
                        else None)
            if exchange is None:
                raise ValueError("fused is a single-device (mesh=None) "
                                 "analysis handle; use run() on a mesh")

            def _fused(planT, state, t):
                state, spiked, tm = jax.vmap(
                    ph.pa, in_axes=(0, 0, None))(planT, state, t)
                ss = exchange(planT, spiked)
                state = jax.vmap(
                    ph.pb, in_axes=(0, 0, 0, None))(planT, state, ss, t)
                return state, spiked, tm

            self._fused = jax.jit(_fused)
        return self._fused

    # -- timing handle (per-phase wall-clock attribution) ----------------

    def time_phases(self, state, t0: int, n_steps: int,
                    collect_rasters: bool = False):
        """Per-step wall-clock attribution — the paper's Table 2 split,
        shared by the cluster worker, the profiler and the bench suites
        so the warmup/blocking discipline cannot drift between them.

        Returns (final_state, times, rasters, counts): `times` accumulates
        phase_a_s / exchange_s / phase_b_s over `n_steps` (each phase
        `block_until_ready`-fenced), `rasters` is a list of per-step
        [H, N] numpy spike masks when `collect_rasters` else None, and
        `counts` totals the deterministic spike/arrival counters.

        Schedule-aware: under 'sync' the exchange is fenced between A and
        B, so exchange_s is its full exposed latency.  Under 'pipelined'
        the exchange is DISPATCHED between the two phase-A halves and
        only blocked on right before the phase B that consumes it (one
        step later, mirroring the fused program's rotated order), so
        exchange_s records just the dispatch + residual wait — the
        exposed remainder after hiding behind the LTP half.  Keys are
        identical across schedules, so hidden-vs-exposed comparisons are
        direct."""
        if self.spec.eng.exchange_schedule == "pipelined":
            return self._time_phases_pipelined(state, t0, n_steps,
                                               collect_rasters)
        return self._time_phases_sync(state, t0, n_steps, collect_rasters)

    def _time_phases_sync(self, state, t0, n_steps, collect_rasters):
        pp = self.phase_fns()
        s_w, spk_w, _ = pp.phase_a(state, t0)
        src_w = pp.exchange(spk_w)
        jax.block_until_ready(pp.phase_b(s_w, src_w, t0))

        times = dict(phase_a_s=0.0, exchange_s=0.0, phase_b_s=0.0)
        counts = dict(spikes=0, arrivals=0)
        rasters = [] if collect_rasters else None
        s = state
        for t in range(t0, t0 + n_steps):
            c0 = time.perf_counter()
            s2, spiked, tm = pp.phase_a(s, t)
            jax.block_until_ready(spiked)
            times["phase_a_s"] += time.perf_counter() - c0
            c0 = time.perf_counter()
            spiked_src = pp.exchange(spiked)
            jax.block_until_ready(spiked_src)
            times["exchange_s"] += time.perf_counter() - c0
            c0 = time.perf_counter()
            s = pp.phase_b(s2, spiked_src, t)
            jax.block_until_ready(s)
            times["phase_b_s"] += time.perf_counter() - c0
            self._tally(counts, rasters, spiked, tm)
        return s, times, rasters, counts

    def _time_phases_pipelined(self, state, t0, n_steps, collect_rasters):
        pp = self.phase_fns()
        # warmup: compile all four programs on throwaway outputs
        s_w, spk_w, _ = pp.phase_a_dynamics(state, t0)
        src_w = pp.exchange(spk_w)
        s_w = pp.phase_a_plasticity(s_w, spk_w, t0)
        jax.block_until_ready(pp.phase_b(s_w, src_w, t0))

        times = dict(phase_a_s=0.0, exchange_s=0.0, phase_b_s=0.0)
        counts = dict(spikes=0, arrivals=0)
        rasters = [] if collect_rasters else None
        s = state
        # all-False prologue buffer (phase B of it is an exact no-op)
        H, S = np.asarray(self.plan.src_gid).shape
        ss_buf = self.place(jnp.zeros((H, S), bool))
        for t in range(t0, t0 + n_steps):
            # residual exchange wait surfaces only here, right before the
            # consuming phase B — everything since dispatch was hidden
            c0 = time.perf_counter()
            jax.block_until_ready(ss_buf)
            times["exchange_s"] += time.perf_counter() - c0
            c0 = time.perf_counter()
            s = pp.phase_b(s, ss_buf, t - 1)
            jax.block_until_ready(s)
            times["phase_b_s"] += time.perf_counter() - c0
            c0 = time.perf_counter()
            s, spiked, tm = pp.phase_a_dynamics(s, t)
            jax.block_until_ready(spiked)
            times["phase_a_s"] += time.perf_counter() - c0
            c0 = time.perf_counter()
            ss_buf = pp.exchange(spiked)       # dispatch, do NOT block
            times["exchange_s"] += time.perf_counter() - c0
            c0 = time.perf_counter()
            s = pp.phase_a_plasticity(s, spiked, t)
            jax.block_until_ready(s)
            times["phase_a_s"] += time.perf_counter() - c0
            self._tally(counts, rasters, spiked, tm)
        # epilogue flush: deliver the last step's spikes
        c0 = time.perf_counter()
        jax.block_until_ready(ss_buf)
        times["exchange_s"] += time.perf_counter() - c0
        c0 = time.perf_counter()
        s = pp.phase_b(s, ss_buf, t0 + n_steps - 1)
        jax.block_until_ready(s)
        times["phase_b_s"] += time.perf_counter() - c0
        return s, times, rasters, counts

    @staticmethod
    def _tally(counts, rasters, spiked, tm):
        # in a multi-process job the per-step arrays span non-addressable
        # devices; workers gather what they need themselves
        # (cluster.runtime.gather), so tally only process-local arrays
        if not getattr(tm.spikes, "is_fully_addressable", True):
            return
        counts["spikes"] += int(np.asarray(tm.spikes).sum())
        counts["arrivals"] += int(np.asarray(tm.arrivals).sum())
        if rasters is not None:
            rasters.append(np.asarray(spiked))
