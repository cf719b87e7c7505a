"""Streamed connectivity execution: regenerate synapse chunks inside the step.

`EngineConfig.connectivity='streamed:chunk=<K>'` trades the materialized
O(E) per-shard synapse tables for in-step regeneration: every phase scans
over fixed chunks of K target columns and rebuilds that chunk's incoming
synapses from the SAME counter-based splitmix64 draw lanes the host builder
uses (`core.connectivity.forward_synapses`), so only one chunk's tables —
O(K * neighbourhood * M) slots — are ever live.  Weight/arrival STATE stays
O(E) (it is genuine state), laid out in the identical canonical
(tgt_gid, src_gid, j) order as materialized mode, which is why rasters AND
weights are bit-identical and checkpoints round-trip across modes' shard
counts and chunk sizes (DESIGN.md §Streamed connectivity).

Bit-identity hinges on two facts:

  1. The draw is counter-based: synapse (g, j) is a pure function of
     (seed, g, j, grid), independent of which shard/chunk asks.  The jitted
     generator below reimplements splitmix64 on uint32 limb pairs (jax here
     runs with 32-bit ints) and derives ring/member/target/delay with exact
     integer arithmetic — no float draw is ever compared differently from
     the numpy path (tests wall this per profile).
  2. Chunks partition targets by whole local index ranges, so each target's
     incoming synapses live wholly inside one chunk and the concatenation
     of per-chunk canonical slices IS the shard's canonical synapse list.
     Per-target accumulation order — the paper's Table 1 bit-identity
     argument — is therefore unchanged.

The scan windows [e_start[c], e_start[c] + k_cap) of the state arrays
overlap the next chunk's live region (k_cap is a static capacity, chunk
fill varies).  That is safe because the STDP oracles are no-ops at
non-arrival/invalid slots and the scan is sequential (read-modify-write),
and the arrival-ring clear masks to the chunk's own valid slots.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import connectivity, engine, profiles, stimulus, topology
from .engine import (NEG_TIME, ShardPlan, ShardState, SimSpec, StepTimings,
                     make_gid_to_local)
from .params import (DEFAULT_IZH, DEFAULT_STDP, EngineConfig, GridConfig,
                     IzhikevichParams, StdpParams)

_MASK32 = 0xFFFFFFFF


class StreamSpec(NamedTuple):
    """Static streamed-mode geometry (rides on SimSpec.stream)."""

    chunk_cols: int           # K: target columns per chunk
    q: int                    # owned-neuron slots per chunk (K * npc)
    n_chunks: int
    c_cap: int                # candidate-source cap per chunk
    k_cap: int                # generation slots per chunk (c_cap * M)
    e_pad: int                # padded synapse-state length (>= E + k_cap)


class StreamedPlan(NamedTuple):
    """Per-shard streamed metadata (leading dim stacks shards).

    O(n_chunks * c_cap) ints — the only per-synapse-table data kept live
    across the whole run; actual tables are regenerated per chunk.
    """

    cand: jnp.ndarray         # [n_chunks, c_cap] int32 src-table rows (-1 pad)
    e_start: jnp.ndarray      # [n_chunks + 1] int32 canonical chunk offsets


class ChunkTables(NamedTuple):
    """One regenerated chunk, canonical order, valid-first.  All [k_cap]."""

    src: jnp.ndarray          # int32 index into plan.src_gid (0 when invalid)
    tgt_rel: jnp.ndarray      # int32 in [0, q]; q = segment-sum dump slot
    delay: jnp.ndarray        # int32
    plastic: jnp.ndarray      # bool
    valid: jnp.ndarray        # bool
    j: Optional[jnp.ndarray] = None   # int32 forward slot (test/debug only)


# ----------------------------------------------------------------------------
# uint32-limb splitmix64 (bit-identical to connectivity.splitmix64)
# ----------------------------------------------------------------------------


def _add64(ah, al, bh, bl):
    lo = al + bl
    carry = (lo < al).astype(jnp.uint32)
    return ah + bh + carry, lo


def _shr64(ah, al, k: int):
    # all splitmix64 shifts (30/27/31) satisfy 0 < k < 32
    return ah >> k, (al >> k) | (ah << (32 - k))


def _mul32(a, b):
    """Full 32x32 -> 64 product as (hi, lo) uint32 limbs."""
    a0, a1 = a & 0xFFFF, a >> 16
    b0, b1 = b & 0xFFFF, b >> 16
    t00 = a0 * b0
    t01 = a0 * b1
    t10 = a1 * b0
    mid = (t00 >> 16) + (t01 & 0xFFFF) + (t10 & 0xFFFF)
    lo = (t00 & 0xFFFF) | ((mid & 0xFFFF) << 16)
    hi = a1 * b1 + (mid >> 16) + (t01 >> 16) + (t10 >> 16)
    return hi, lo


def _mul64(ah, al, bh, bl):
    """Low 64 bits of the product (wrapping, like uint64 multiply)."""
    hi, lo = _mul32(al, bl)
    return hi + al * bh + ah * bl, lo


def _c64(x: int):
    """Split a python uint64 constant into jnp.uint32 limbs (hi, lo).

    Explicit wrapping: a bare python int above 2^31 fails jax's weak-type
    promotion with an int32 OverflowError.
    """
    return jnp.uint32((x >> 32) & _MASK32), jnp.uint32(x & _MASK32)


def _splitmix64(h, l):
    h, l = _add64(h, l, *_c64(0x9E3779B97F4A7C15))   # += GOLDEN
    xh, xl = _shr64(h, l, 30)
    h, l = h ^ xh, l ^ xl
    h, l = _mul64(h, l, *_c64(0xBF58476D1CE4E5B9))   # *= MIX1
    xh, xl = _shr64(h, l, 27)
    h, l = h ^ xh, l ^ xl
    h, l = _mul64(h, l, *_c64(0x94D049BB133111EB))   # *= MIX2
    xh, xl = _shr64(h, l, 31)
    return h ^ xh, l ^ xl


def _mod64(h, l, m):
    """(h * 2^32 + l) mod m for small m (m <= 2^16, so no limb overflows)."""
    m = jnp.asarray(m, jnp.uint32)
    r16 = jnp.uint32(1 << 16) % m
    r32 = (r16 * r16) % m
    return ((h % m) * r32 + (l % m)) % m


# ----------------------------------------------------------------------------
# in-jit chunk regeneration
# ----------------------------------------------------------------------------


def _gen_consts(cfg: GridConfig):
    """Host-side generation constants (profile tables, draw-lane seeds)."""
    prof = profiles.from_config(cfg)
    reach = prof.reach()
    off_tab, start = profiles.offset_tables(reach)
    # U[k] = ceil(fr[k] * 2^53): `fr[k] <= bits53 * 2^-53` iff `bits53 >=
    # U[k]` (power-of-two scaling is exact), so the integer comparison
    # reproduces np.searchsorted(fr, r, side='right') bit-for-bit,
    # including every equality edge case.
    U = [math.ceil(float(f) * 2.0 ** 53) for f in prof.cum_fractions()]
    with np.errstate(over="ignore"):
        lanes = [int(connectivity.splitmix64(
            np.uint64(cfg.seed)
            + connectivity._GOLDEN * np.uint64(k + 1)))
            for k in range(4)]
    return reach, off_tab, start, U, lanes


def make_chunk_tables(spec: SimSpec, plan: ShardPlan):
    """Returns f(c, cand_row, with_j=False) -> ChunkTables for ONE shard.

    Bit-identical (over valid slots) to `connectivity._chunk_synapses`
    restricted to chunk c; invalid slots sort to the tail, so valid entries
    occupy the contiguous prefix [0, e_start[c+1] - e_start[c]).
    """
    cfg = spec.cfg
    ss = spec.stream
    assert ss is not None
    M = cfg.synapses_per_neuron
    npc = cfg.neurons_per_column
    nexc = cfg.n_exc_per_column
    # _mod64's limb arithmetic needs every modulus < 2^16
    assert npc < (1 << 16) and M < (1 << 16), \
        "streamed generation assumes npc, M < 65536"
    reach, off_tab, start, U, lanes = _gen_consts(cfg)
    start_j = jnp.asarray(start, jnp.int32)
    off_j = jnp.asarray(off_tab, jnp.int32)
    dspan = cfg.delay_max - cfg.delay_min + 1
    gx, gy = cfg.grid_x, cfg.grid_y
    g2l = make_gid_to_local(spec, plan.shard_id)
    int_max = jnp.iinfo(jnp.int32).max

    def draw(lane, ch, cl):
        sh, sl = _c64(lanes[lane])
        return _splitmix64(ch ^ sh, cl ^ sl)

    src_gid = jnp.asarray(plan.src_gid)      # the plan may be host arrays

    def tables(c, cand_row, with_j: bool = False) -> ChunkTables:
        cvalid = cand_row >= 0                               # [c_cap]
        sidx = jnp.where(cvalid, cand_row, 0)
        g = jnp.where(cvalid, src_gid[sidx], 0)              # [c_cap] int32
        g_u = g.astype(jnp.uint32)

        # counter = g * M + j (64-bit, exact)
        jj = jnp.arange(M, dtype=jnp.uint32)[None, :]        # [1, M]
        ch_, cl_ = _mul32(g_u[:, None], jnp.uint32(M))       # [c_cap, 1]
        ch_, cl_ = _add64(ch_, cl_, jnp.uint32(0), jj)       # [c_cap, M]

        # lane 0: ring selection via 53-bit threshold comparison
        b0h, b0l = draw(0, ch_, cl_)
        rh = b0h >> 11                                       # top 21 bits
        rl = (b0l >> 11) | (b0h << 21)
        ring = jnp.zeros(rh.shape, jnp.int32)
        for Uk in U:
            uh, ul = _c64(Uk)
            ring = ring + ((rh > uh)
                           | ((rh == uh) & (rl >= ul))).astype(jnp.int32)
        ring = jnp.clip(ring, 0, reach)

        # lane 1: member within ring
        b1h, b1l = draw(1, ch_, cl_)
        rsize = (start_j[ring + 1] - start_j[ring]).astype(jnp.uint32)
        member = _mod64(b1h, b1l, rsize).astype(jnp.int32)
        off = off_j[start_j[ring] + member]                  # [c_cap, M, 2]

        # lane 2: target neuron within column
        b2h, b2l = draw(2, ch_, cl_)
        col = g // npc
        cx, cy = col % gx, col // gx
        tcol = (((cy[:, None] + off[..., 1]) % gy) * gx
                + ((cx[:, None] + off[..., 0]) % gx))
        n_exc_tgt = _mod64(b2h, b2l, jnp.uint32(npc)).astype(jnp.int32)
        tgt_exc = tcol * npc + n_exc_tgt
        n_inh_tgt = _mod64(b2h, b2l, jnp.uint32(nexc)).astype(jnp.int32)
        tgt_inh = col[:, None] * npc + n_inh_tgt

        # lane 3: delay
        b3h, b3l = draw(3, ch_, cl_)
        delay_exc = (1 + _mod64(b3h, b3l, jnp.uint32(dspan)).astype(jnp.int32)
                     + (cfg.delay_min - 1))

        exc = (g % npc) < nexc                               # [c_cap] bool
        excb = exc[:, None]
        tgt = jnp.where(excb, tgt_exc, tgt_inh)
        delay = jnp.where(excb, delay_exc, jnp.int32(cfg.delay_min))

        # ownership + chunk-range filter, then canonical stable sort:
        # generation order is (src gid asc, j asc), so a stable sort on
        # target-local index reproduces lexsort((j, src, tgt)).
        tloc, owned = g2l(tgt)
        lo = c * ss.q
        keep = cvalid[:, None] & owned & (tloc >= lo) & (tloc < lo + ss.q)
        keepf = keep.reshape(-1)
        tlocf = tloc.reshape(-1)
        key = jnp.where(keepf, tlocf, int_max)
        order = jnp.argsort(key, stable=True)
        valid = keepf[order]
        srcf = jnp.where(valid,
                         jnp.broadcast_to(sidx[:, None],
                                          keep.shape).reshape(-1)[order], 0)
        tgt_rel = jnp.where(valid, tlocf[order] - lo, ss.q)
        delayf = delay.reshape(-1)[order]
        plasticf = jnp.broadcast_to(excb, keep.shape).reshape(-1)[order] & valid
        jf = None
        if with_j:
            jf = jnp.where(valid, jnp.broadcast_to(
                jnp.arange(M, dtype=jnp.int32)[None, :],
                keep.shape).reshape(-1)[order], 0)
        return ChunkTables(src=srcf, tgt_rel=tgt_rel.astype(jnp.int32),
                           delay=delayf, plastic=plasticf, valid=valid, j=jf)

    return tables


# ----------------------------------------------------------------------------
# streamed phases: lax.scan over chunks with windowed state
# ----------------------------------------------------------------------------


def _chunk_xs(spec: SimSpec, splan: StreamedPlan):
    cs = jnp.arange(spec.stream.n_chunks, dtype=jnp.int32)
    return cs, splan.cand, splan.e_start[:-1]


@engine.scope("phase_a_dynamics")
def phase_a_dynamics(spec: SimSpec, plan: ShardPlan, splan: StreamedPlan,
                     state: ShardState, t: jnp.ndarray, stim_k: jax.Array
                     ) -> Tuple[ShardState, jnp.ndarray, StepTimings]:
    """Streamed phase A steps 1-5 (see engine.phase_a_dynamics)."""
    from ..kernels import ops as kops

    cfg, stdp = spec.cfg, spec.stdp
    ss = spec.stream
    up = spec.eng.use_pallas or None
    D = cfg.n_delay_slots
    tf = t.astype(jnp.float32)
    r = jnp.mod(t, D)
    tables = make_chunk_tables(spec, plan)

    def body(carry, xs):
        w, la, ring, i_buf, n_arr = carry
        c, cand_row, e0 = xs
        tb = tables(c, cand_row)
        w_win = jax.lax.dynamic_slice_in_dim(w, e0, ss.k_cap)
        la_win = jax.lax.dynamic_slice_in_dim(la, e0, ss.k_cap)
        ring_win = jax.lax.dynamic_slice(ring, (jnp.int32(0), e0),
                                         (D, ss.k_cap))
        arrivals = ring_win[r] & tb.valid
        lp = state.last_post[tb.tgt_rel + c * ss.q]
        w2, la2, contrib = kops.stdp_arrival(
            arrivals, w_win, lp, la_win, tb.plastic, tf,
            a_minus=stdp.a_minus, tau_minus=stdp.tau_minus,
            w_min=stdp.w_min, w_max=stdp.w_max, neg_time=float(NEG_TIME),
            use_pallas=up)
        # per-chunk segment sum: every target's synapses live wholly in
        # this chunk and arrive in canonical order, so the per-target add
        # order is identical to the materialized full-table segment_sum;
        # invalid slots dump into segment q (contributions are exactly 0.0,
        # and no valid contribution is -0.0 — exc weights clip to
        # [0, w_max], inh weights are a fixed negative — so the dump adds
        # are bit-inert anyway).
        seg = jax.ops.segment_sum(contrib, tb.tgt_rel,
                                  num_segments=ss.q + 1,
                                  indices_are_sorted=True)
        i_buf = jax.lax.dynamic_update_slice_in_dim(i_buf, seg[:ss.q],
                                                    c * ss.q, 0)
        # clear this step's slot ONLY at this chunk's valid slots: the
        # window tail overlaps the next chunk's live region.
        row = ring_win[r] & ~tb.valid
        ring_win = jax.lax.dynamic_update_slice(ring_win, row[None, :],
                                                (r, jnp.int32(0)))
        ring = jax.lax.dynamic_update_slice(ring, ring_win,
                                            (jnp.int32(0), e0))
        w = jax.lax.dynamic_update_slice_in_dim(w, w2, e0, 0)
        la = jax.lax.dynamic_update_slice_in_dim(la, la2, e0, 0)
        return (w, la, ring, i_buf, n_arr + arrivals.sum()), None

    i_buf0 = jnp.zeros((ss.n_chunks * ss.q,), jnp.float32)
    carry0 = (state.w, state.last_arr, state.arr_ring, i_buf0, jnp.int32(0))
    (w, la, ring, i_buf, n_arr), _ = jax.lax.scan(
        body, carry0, _chunk_xs(spec, splan))
    i_syn = i_buf[:spec.n_local]

    v, u, spiked = engine.neuron_update(spec, plan, state, i_syn, t, stim_k)
    new = ShardState(v=v, u=u, last_post=state.last_post, w=w,
                     last_arr=la, arr_ring=ring)
    tm = StepTimings(spikes=spiked.sum(), arrivals=n_arr)
    return new, spiked, tm


@engine.scope("phase_a_plasticity")
def phase_a_plasticity(spec: SimSpec, plan: ShardPlan, splan: StreamedPlan,
                       state: ShardState, spiked: jnp.ndarray,
                       t: jnp.ndarray) -> ShardState:
    """Streamed phase A step 6 (see engine.phase_a_plasticity)."""
    from ..kernels import ops as kops

    stdp = spec.stdp
    ss = spec.stream
    up = spec.eng.use_pallas or None
    tf = t.astype(jnp.float32)
    tables = make_chunk_tables(spec, plan)

    def body(w, xs):
        c, cand_row, e0 = xs
        tb = tables(c, cand_row)
        w_win = jax.lax.dynamic_slice_in_dim(w, e0, ss.k_cap)
        la_win = jax.lax.dynamic_slice_in_dim(state.last_arr, e0, ss.k_cap)
        post = spiked[tb.tgt_rel + c * ss.q]
        w2 = kops.stdp_ltp(post, w_win, la_win, tb.plastic, tb.valid, tf,
                           a_plus=stdp.a_plus, tau_plus=stdp.tau_plus,
                           w_min=stdp.w_min, w_max=stdp.w_max,
                           neg_time=float(NEG_TIME), use_pallas=up)
        return jax.lax.dynamic_update_slice_in_dim(w, w2, e0, 0), None

    w, _ = jax.lax.scan(body, state.w, _chunk_xs(spec, splan))
    last_post = jnp.where(spiked, tf, state.last_post)
    return state._replace(w=w, last_post=last_post)


def phase_a(spec: SimSpec, plan: ShardPlan, splan: StreamedPlan,
            state: ShardState, t: jnp.ndarray, stim_k: jax.Array
            ) -> Tuple[ShardState, jnp.ndarray, StepTimings]:
    state, spiked, tm = phase_a_dynamics(spec, plan, splan, state, t, stim_k)
    state = phase_a_plasticity(spec, plan, splan, state, spiked, t)
    return state, spiked, tm


@engine.scope("phase_b")
def phase_b(spec: SimSpec, plan: ShardPlan, splan: StreamedPlan,
            state: ShardState, spiked_src: jnp.ndarray, t: jnp.ndarray
            ) -> ShardState:
    """Streamed deferred arborization (see engine.phase_b)."""
    ss = spec.stream
    D = spec.cfg.n_delay_slots
    tables = make_chunk_tables(spec, plan)

    def body(ring, xs):
        c, cand_row, e0 = xs
        tb = tables(c, cand_row)
        active = spiked_src[tb.src] & tb.valid
        slot = jnp.mod(t + tb.delay, D)
        hit = active[None, :] & (slot[None, :]
                                 == jnp.arange(D, dtype=slot.dtype)[:, None])
        ring_win = jax.lax.dynamic_slice(ring, (jnp.int32(0), e0),
                                         (D, ss.k_cap))
        ring = jax.lax.dynamic_update_slice(ring, ring_win | hit,
                                            (jnp.int32(0), e0))
        return ring, None

    ring, _ = jax.lax.scan(body, state.arr_ring, _chunk_xs(spec, splan))
    return state._replace(arr_ring=ring)


# ----------------------------------------------------------------------------
# build + single-device driver
# ----------------------------------------------------------------------------


def build(cfg: GridConfig, eng: EngineConfig,
          izh: IzhikevichParams = DEFAULT_IZH,
          stdp: StdpParams = DEFAULT_STDP
          ) -> Tuple[SimSpec, ShardPlan, StreamedPlan, ShardState]:
    """Build streamed plans + initial state, stacked on a leading [H] axis.

    The returned ShardPlan carries the full candidate-source table (the
    exchange wires and halo provisioning read only `src_gid`/`gid`) but
    1-element dummies for the per-synapse arrays — those are regenerated
    per chunk by `make_chunk_tables`.
    """
    mode, chunk_cols = connectivity.parse_mode(eng.connectivity)
    if mode != "streamed":
        raise ValueError(f"stream_engine.build called with connectivity="
                         f"{eng.connectivity!r}")
    if eng.delivery != "dense":
        raise ValueError(
            "connectivity='streamed' requires delivery='dense': the event "
            "backend's fwd/in row tables are an O(E) synapse-id "
            "permutation, which contradicts O(chunk) table residency")
    shards = connectivity.build_all_streamed(cfg, eng, chunk_cols)
    H = eng.n_shards
    n_cap, q, n_chunks = connectivity.stream_geometry(cfg, eng, chunk_cols)
    c_cap = shards[0].cand.shape[1]
    s_cap = shards[0].src_gid.shape[0]
    k_cap = c_cap * cfg.synapses_per_neuron
    e_max = max(s.n_valid for s in shards)
    # + k_cap: the last chunk's [e0, e0 + k_cap) window must fit without
    # dynamic_slice clamping (a clamped window would shift the read).
    e_pad = connectivity._round_up(max(e_max, 1), 8) + k_cap
    col_cap = max(
        np.unique(topology.gid_column(
            cfg, topology.owned_gids(cfg, h, H, eng.placement))).shape[0]
        for h in range(H))

    plans, splans = [], []
    for h, sh in enumerate(shards):
        gids = topology.owned_gids(cfg, h, H, eng.placement)
        n_loc = gids.shape[0]
        gid_p = np.full((n_cap,), -1, dtype=np.int32)
        gid_p[:n_loc] = gids
        exc = np.zeros((n_cap,), dtype=bool)
        exc[:n_loc] = topology.is_excitatory(cfg, gids)
        nv = np.zeros((n_cap,), dtype=bool)
        nv[:n_loc] = True
        plans.append(ShardPlan(
            src_gid=sh.src_gid.astype(np.int32),
            syn_src=np.zeros((1,), np.int32),
            syn_tgt=np.zeros((1,), np.int32),
            syn_delay=np.ones((1,), np.int32),
            syn_plastic=np.zeros((1,), bool),
            syn_valid=np.zeros((1,), bool),
            exc_mask=exc, neuron_valid=nv, gid=gid_p,
            columns=engine._owned_columns_padded(cfg, eng, h, col_cap),
            shard_id=np.int32(h)))
        splans.append(StreamedPlan(cand=sh.cand,
                                   e_start=sh.e_start.astype(np.int32)))

    # host arrays until StepProgram.place (see engine.build)
    plan = jax.tree.map(lambda *xs: np.stack(xs), *plans)
    splan = jax.tree.map(lambda *xs: np.stack(xs), *splans)
    spec = SimSpec(cfg=cfg, eng=eng, izh=izh, stdp=stdp, n_local=n_cap,
                   e_cap=e_pad, s_cap=s_cap, n_total=cfg.n_neurons,
                   stream=StreamSpec(chunk_cols=chunk_cols, q=q,
                                     n_chunks=n_chunks, c_cap=c_cap,
                                     k_cap=k_cap, e_pad=e_pad))
    w0 = np.zeros((H, e_pad), np.float32)
    for h, sh in enumerate(shards):
        w0[h, :sh.n_valid] = sh.weight0
    state = init_state(spec, plan)._replace(w=w0)
    return spec, plan, splan, state


def init_state(spec: SimSpec, plan: ShardPlan) -> ShardState:
    """Fresh streamed state (host arrays): synapse-state arrays sized
    [e_pad]."""
    ss = spec.stream
    assert ss is not None
    v, u, last_post = engine.init_neurons(spec, np.asarray(plan.exc_mask))
    H = v.shape[0]
    return ShardState(
        v=v, u=u, last_post=last_post,
        w=np.zeros((H, ss.e_pad), np.float32),
        last_arr=np.full((H, ss.e_pad), NEG_TIME),
        arr_ring=np.zeros((H, spec.cfg.n_delay_slots, ss.e_pad), bool))


def make_step_fn(spec: SimSpec, plan: ShardPlan, splan: StreamedPlan):
    """jit-able step over stacked shard states (single device, vmap comm)."""
    stim_k = stimulus.stim_key(spec.cfg)

    def step(state: ShardState, t: jnp.ndarray):
        state, spiked, tm = jax.vmap(
            lambda p, sp, s: phase_a(spec, p, sp, s, t, stim_k)
        )(plan, splan, state)
        spiked_src = engine.global_exchange(spec, plan, spiked)
        state = jax.vmap(
            lambda p, sp, s, ssrc: phase_b(spec, p, sp, s, ssrc, t)
        )(plan, splan, state, spiked_src)
        return state, (spiked, tm)

    return step


def run(spec: SimSpec, plan: ShardPlan, splan: StreamedPlan,
        state: ShardState, t0: int, n_steps: int):
    """Scan the simulation; returns (state, raster[T, H, N], timings)."""
    step = make_step_fn(spec, plan, splan)

    def body(s, t):
        s, out = step(s, t)
        return s, out

    ts = t0 + jnp.arange(n_steps, dtype=jnp.int32)   # t0 may be traced
    state, (raster, tm) = jax.lax.scan(body, state, ts)
    return state, raster, tm


# ----------------------------------------------------------------------------
# table-residency accounting (memory tests + weak_scaling suite)
# ----------------------------------------------------------------------------

# bytes per synapse-table slot: src/tgt/delay int32 + plastic/valid bool.
# Matches the materialized ShardPlan per-synapse leaves (syn_src, syn_tgt,
# syn_delay, syn_plastic, syn_valid) so the two modes compare honestly.
TABLE_BYTES_PER_SLOT = 4 + 4 + 4 + 1 + 1


def chunk_table_bytes(spec: SimSpec) -> int:
    """Peak LIVE regenerated-table bytes per shard (one chunk resident)."""
    return spec.stream.k_cap * TABLE_BYTES_PER_SLOT


def metadata_bytes(spec: SimSpec) -> int:
    """Persistent streamed metadata bytes per shard (cand + e_start)."""
    ss = spec.stream
    return ss.n_chunks * ss.c_cap * 4 + (ss.n_chunks + 1) * 4


def streamed_table_bytes(spec: SimSpec) -> int:
    """Peak live synapse-table bytes per shard in streamed mode."""
    return chunk_table_bytes(spec) + metadata_bytes(spec)


def materialized_table_bytes(e_cap: int) -> int:
    """Synapse-table bytes per shard when fully materialized."""
    return e_cap * TABLE_BYTES_PER_SLOT
