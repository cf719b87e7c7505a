"""Shape-keyed program cache + the vmapped multi-tenant round.

The service's whole compilation story is one observation about the
engine: the tenant seed enters the computation ONLY through values —
connectivity tables / initial weights (host-built into the plan/state)
and the stimulus PRNG key (`stimulus.stim_key(cfg)`).  Nothing traced
reads `cfg.seed`.  So every config that differs only by seed lowers to
the same jaxpr, and a whole fleet of such tenants can share ONE jitted
round program with the per-tenant data stacked on a free leading batch
axis:

    round(plans[B,...], states[B,...], t0s[B], stim_keys[B])
        -> (states', rasters[B, R, H, N])

`shape_key` captures what the trace semantically depends on: the full
GridConfig with the seed zeroed, the EngineConfig (delivery, shards,
placement, exchange, schedule), and the event-capacity overrides.  One
wrinkle: the REALIZED static capacities (source-table width `s_cap`,
valid-synapse capacity `e_cap`, event fan-out paddings Kf/Ki) depend on
the drawn connectivity, i.e. on the seed.  The batcher therefore
canonicalizes: each group negotiates `GroupCaps` (first tenant's
realized capacities + headroom, rounded), and every admitted tenant's
tables are re-padded to them (`connectivity.repad_shard` — the exact
mechanism `build_all_shards` already uses to unify capacities across
shards; pad entries carry `valid=False`/`-1` and are masked out of every
reduction, so padding is numerics-free).  A tenant that overflows the
group's capacities forces a regroup (scheduler evicts + re-admits — rare
by construction of the headroom, counted in metrics, and bit-exact via
the checkpoint round-trip).

The per-tenant round body is `engine.make_step_fn` /
`event_engine.make_step_fn` verbatim (same phase callables via
`distributed._delivery_phases`, same global-mask exchange, same scan),
with `t0` and the stimulus key promoted from closure constants to traced
arguments.  `jax.vmap` over tenants adds a leading axis to every op but
changes no per-tenant reduction order, so each slot's raster is
bit-identical to the same config run solo through `StepProgram` — the
service's correctness spine, asserted in tests and the CI soak.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import (connectivity, distributed, engine, event_engine,
                    observables, stimulus)
from ..core.engine import NEG_TIME
from ..core.event_engine import EventPlan, EventState
from ..core.params import EngineConfig, GridConfig

ShapeKey = Tuple[GridConfig, EngineConfig,
                 Optional[Tuple[int, int]], Optional[int]]


def shape_key(cfg: GridConfig, eng: EngineConfig,
              caps: Optional[Tuple[int, int]] = None,
              cap_ev: Optional[int] = None) -> ShapeKey:
    """Program identity: everything that shapes the traced computation.

    The seed is zeroed out — it reaches the program only through jit
    arguments (plan values, initial weights, stimulus key).  Both configs
    are frozen dataclasses, so the tuple is hashable."""
    return (dataclasses.replace(cfg, seed=0), eng, caps, cap_ev)


# ---------------------------------------------------------------------------
# capacity canonicalization
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GroupCaps:
    """Canonical static capacities every member of a batch group is
    padded to.  `cap_ev` is the event-ring capacity implied by the padded
    e_cap (or the tenant override, which is part of the shape key)."""
    e_cap: int
    s_cap: int
    kf: int              # event forward-row padding (0 for dense)
    ki: int              # event incoming-row padding (0 for dense)
    cap_ev: int          # event ring capacity (0 for dense)

    def fits(self, other: "GroupCaps") -> bool:
        return (self.e_cap >= other.e_cap and self.s_cap >= other.s_cap
                and self.kf >= other.kf and self.ki >= other.ki)


def _round_up(x: int, m: int) -> int:
    return max(m, -(-x // m) * m)


def measure_caps(spec, planT, state) -> GroupCaps:
    """Realized capacities of one tenant's build."""
    if isinstance(state, EventState):   # NamedTuples ARE tuples: dispatch
        _, eplan = planT                # on the state type, not tuple-ness
        return GroupCaps(e_cap=spec.e_cap, s_cap=spec.s_cap,
                         kf=int(eplan.fwd_rows.shape[-1]),
                         ki=int(eplan.in_rows.shape[-1]),
                         cap_ev=int(state.ev_ring.shape[-1]))
    return GroupCaps(e_cap=spec.e_cap, s_cap=spec.s_cap, kf=0, ki=0,
                     cap_ev=0)


def negotiate(realized: GroupCaps, cap_ev: Optional[int] = None,
              prior: Optional[GroupCaps] = None) -> GroupCaps:
    """Realized capacities -> group capacities with headroom, so sibling
    tenants (different seeds, slightly different realized paddings) fit
    without a regroup.  Deterministic; monotone over `prior` on regroup."""
    e = _round_up(realized.e_cap + realized.e_cap // 8, 16)
    s = _round_up(realized.s_cap + realized.s_cap // 8, 16)
    kf = _round_up(realized.kf + max(2, realized.kf // 4), 4) \
        if realized.kf else 0
    ki = _round_up(realized.ki + max(2, realized.ki // 4), 4) \
        if realized.ki else 0
    if prior is not None:
        e, s = max(e, prior.e_cap), max(s, prior.s_cap)
        kf, ki = max(kf, prior.kf), max(ki, prior.ki)
    if cap_ev is not None:
        cev = cap_ev
    elif realized.cap_ev:
        # same rule event_engine.build_event_plan applies, over padded E
        cev = max(256, _round_up(e // 4, 128))
    else:
        cev = 0
    return GroupCaps(e_cap=e, s_cap=s, kf=kf, ki=ki, cap_ev=cev)


def _pad_rows_to(rows: jnp.ndarray, n_rows: int, k: int) -> jnp.ndarray:
    """Pad [H, R, K] event rows to [H, n_rows, k] with -1."""
    H, R, K = rows.shape
    out = np.full((H, n_rows, k), -1, dtype=np.int32)
    out[:, :R, :K] = np.asarray(rows)
    return jnp.asarray(out)


def build_parts(cfg: GridConfig, eng: EngineConfig,
                caps: Optional[Tuple[int, int]] = None,
                cap_ev: Optional[int] = None,
                pad: Optional[GroupCaps] = None,
                tables=None):
    """(spec, planT, state0) for one tenant.

    planT is the delivery-dependent plan tree every jitted program takes
    as an argument (dense: ShardPlan; event: (ShardPlan, EventPlan)).
    With `pad`, the connectivity tables are re-padded to the group's
    canonical capacities before the plan/state derive from them, so all
    members of a batch group stack exactly."""
    if tables is None:
        tables = connectivity.build_all_shards(cfg, eng)
    if pad is not None:
        tables = [connectivity.repad_shard(t, pad.e_cap, pad.s_cap)
                  for t in tables]
    spec, plan, state = engine.build(cfg, eng, tables=tables)
    if eng.delivery != "event":
        return spec, plan, state
    eplan, cap_default = event_engine.build_event_plan(spec, tables=tables)
    if pad is not None:
        eplan = EventPlan(
            fwd_rows=_pad_rows_to(eplan.fwd_rows, spec.s_cap, pad.kf),
            in_rows=_pad_rows_to(eplan.in_rows, spec.n_local, pad.ki))
    resolved = cap_ev if cap_ev is not None else (
        pad.cap_ev if pad is not None else cap_default)
    estate = event_engine.init_event_state(spec, state, resolved)
    return spec, (plan, eplan), estate


def unpad_state(state, e_real: int):
    """Slice a group-padded state back to its realized synapse capacity
    (padding is a pure suffix never written by the engine), so the
    layout-free checkpoint writer sees the shapes its connectivity
    rebuild produces."""
    if isinstance(state, EventState):
        return state._replace(base=unpad_state(state.base, e_real))
    return state._replace(w=state.w[..., :e_real],
                          last_arr=state.last_arr[..., :e_real],
                          arr_ring=state.arr_ring[..., :e_real])


def pad_state(state, e_pad: int):
    """Inverse of `unpad_state` for checkpoint-loaded states: grow the
    synapse axis to the group capacity with the engine's init fill values
    (w=0, last_arr=never, no pending arrivals)."""
    if isinstance(state, EventState):
        return state._replace(base=pad_state(state.base, e_pad))
    d = e_pad - state.w.shape[-1]
    if d == 0:
        return state
    padf = lambda a, v: jnp.concatenate(
        [a, jnp.full(a.shape[:-1] + (d,), v, a.dtype)], axis=-1)
    return state._replace(w=padf(state.w, 0.0),
                          last_arr=padf(state.last_arr, NEG_TIME),
                          arr_ring=padf(state.arr_ring, False))


def caps_dict(caps: Optional[Tuple[int, int]]) -> Optional[dict]:
    """(c_post, c_src) tuple -> the dict `StepProgram`/phase fns take."""
    if caps is None:
        return None
    return {"c_post": caps[0], "c_src": caps[1]}


def solo_signature(cfg: GridConfig, eng: EngineConfig, n_steps: int,
                   caps: Optional[Tuple[int, int]] = None,
                   cap_ev: Optional[int] = None) -> bytes:
    """Reference signature: the same tenant run alone through
    `StepProgram` (no batching, no padding, no service).  This is the
    right-hand side of the service's correctness contract."""
    from ..core.step_program import StepProgram
    spec, planT, state = build_parts(cfg, eng, caps, cap_ev)
    plan = distributed._base_plan(planT)
    eplan = planT[1] if eng.delivery == "event" else None
    prog = StepProgram.from_parts(spec, plan, eplan, state0=state,
                                  mesh=None, caps=caps_dict(caps),
                                  hier_groups=None)
    _, raster, _ = prog.run(state, 0, n_steps)
    return observables.raster_signature(np.asarray(raster),
                                        np.asarray(plan.gid))


def stim_key_data(cfg: GridConfig) -> np.ndarray:
    """Host-side uint32 key data for one tenant's stimulus key.  The
    batched round wraps a stacked [B, 2] array back into a key array, so
    slot refills are plain array writes."""
    return np.asarray(jax.random.key_data(stimulus.stim_key(cfg)))


# ---------------------------------------------------------------------------
# the compiled round + program cache
# ---------------------------------------------------------------------------


class CompiledRound:
    """One jitted multi-tenant round program for a shape key.

    `traces` counts how many times jax actually traced the batched body;
    it must stay at 1 for any number of same-key tenants, rounds and
    refills (the zero-recompile acceptance criterion).  A group regrow
    (rare) changes argument shapes and retraces the same jitted fn."""

    def __init__(self, spec, caps: Optional[Tuple[int, int]],
                 round_steps: int):
        # normalize the closed-over spec's seed so correctness cannot
        # silently depend on which tenant built the program first; the
        # target windows are one tenant's tables', so the batched round
        # keeps the plain gather
        self.spec = spec._replace(
            cfg=dataclasses.replace(spec.cfg, seed=0), tgt_block=None,
            tgt_window=None)
        self.round_steps = int(round_steps)
        self.traces = 0
        spec_n = self.spec
        cd = caps_dict(caps)

        def one(planT, state, t0, stim_k):
            ph = distributed._delivery_phases(spec_n, stim_k, cd)
            bp = distributed._base_plan(planT)

            def step(st, t):
                st, spiked, tm = jax.vmap(
                    lambda pT, s: ph.pa(pT, s, t))(planT, st)
                glob = engine._global_spike_mask(spec_n, bp, spiked)
                ss = jax.vmap(
                    lambda p: glob.at[p.src_gid].get(
                        mode="fill", fill_value=False)
                    & (p.src_gid >= 0))(bp)
                st = jax.vmap(
                    lambda pT, s, s2: ph.pb(pT, s, s2, t))(planT, st, ss)
                return st, spiked

            ts = t0 + jnp.arange(round_steps, dtype=jnp.int32)
            state, raster = jax.lax.scan(step, state, ts)
            return state, raster

        def batched(plans, states, t0s, stim_key_data):
            self.traces += 1      # fires at trace time only
            ks = jax.random.wrap_key_data(stim_key_data)
            return jax.vmap(one)(plans, states, t0s, ks)

        self.fn = jax.jit(batched)

    def __call__(self, plans, states, t0s, key_data):
        return self.fn(plans, states, t0s, key_data)


class ProgramCache:
    """Shape key -> CompiledRound.  One compile per key, ever."""

    def __init__(self, round_steps: int):
        self.round_steps = int(round_steps)
        self._programs: Dict[ShapeKey, CompiledRound] = {}
        self.hits = 0
        self.misses = 0

    def get(self, key: ShapeKey, spec) -> CompiledRound:
        prog = self._programs.get(key)
        if prog is not None:
            # the traced body reads e_cap/s_cap statically off the spec
            # (event compaction fill indices), so a regrouped key with
            # grown capacities needs a fresh program
            if (prog.spec.e_cap, prog.spec.s_cap) == (spec.e_cap,
                                                      spec.s_cap):
                self.hits += 1
                return prog
        self.misses += 1
        prog = CompiledRound(spec, caps=key[2],
                             round_steps=self.round_steps)
        self._programs[key] = prog
        return prog

    @property
    def builds(self) -> int:
        return len(self._programs)

    def trace_counts(self) -> Dict[str, int]:
        return {f"{k[1].delivery}/H{k[1].n_shards}"
                f"/{k[0].grid_x}x{k[0].grid_y}x{k[0].neurons_per_column}":
                p.traces for k, p in self._programs.items()}


# ---------------------------------------------------------------------------
# the live batch group
# ---------------------------------------------------------------------------


class BatchGroup:
    """Live batch of same-shape tenants: stacked device buffers + slots.

    The buffers are [slots, ...]-stacked copies of the delivery plan tree
    and dynamic state, all padded to `caps`; free slots keep whatever
    payload last occupied them (a valid plan of the same shape — its
    output is simply ignored), so the batch width never changes and the
    round program never retraces."""

    def __init__(self, key: ShapeKey, prog: CompiledRound, slots: int,
                 caps: GroupCaps, planT, state):
        self.key = key
        self.prog = prog
        self.slots = int(slots)
        self.caps = caps
        self.sessions = [None] * self.slots
        self.admit_round = [0] * self.slots     # scheduler round of admission
        tile = lambda x: jnp.repeat(x[None], self.slots, axis=0)
        self.plans = jax.tree.map(tile, planT)
        self.states = jax.tree.map(tile, state)
        kd = stim_key_data(key[0])
        self._key_data = np.repeat(kd[None], self.slots, axis=0)

    def free_slot(self) -> Optional[int]:
        for b, s in enumerate(self.sessions):
            if s is None:
                return b
        return None

    def live(self):
        return [(b, s) for b, s in enumerate(self.sessions)
                if s is not None]

    def install(self, b: int, sess, planT, state, round_no: int) -> None:
        upd = lambda full, one: full.at[b].set(one)
        self.plans = jax.tree.map(upd, self.plans, planT)
        self.states = jax.tree.map(upd, self.states, state)
        self._key_data[b] = stim_key_data(sess.request.cfg)
        self.sessions[b] = sess
        self.admit_round[b] = round_no

    def release(self, b: int) -> None:
        self.sessions[b] = None

    def slot_state(self, b: int):
        return jax.tree.map(lambda x: x[b], self.states)

    def round(self) -> np.ndarray:
        """Advance every slot `round_steps` steps; returns the stacked
        raster [slots, R, H, N] (host numpy)."""
        t0s = jnp.asarray(
            [s.t if s is not None else 0 for s in self.sessions],
            jnp.int32)
        self.states, rasters = self.prog(
            self.plans, self.states, t0s, jnp.asarray(self._key_data))
        return np.asarray(rasters)
