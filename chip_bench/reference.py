"""Plain reference of the DPSNN-STDP network (arXiv:1310.8478), in numpy.

It shares no code with the program under test.  From the configuration
file and the seed it regenerates the network by the published draw.  Each
forward synapse (source g, slot j) is a function of (seed, g, j) through
four splitmix64 counter lanes: ring, member column, target neuron and delay.
It then steps event by event: a spike of g at step t reaches every synapse
(g, j) at step t + delay[g, j].  Per step it does, in this order:

  arrivals -> current (pre-LTD weights) -> LTD (nearest post spike)
  -> thalamic stimulus -> Izhikevich (two half-steps) -> LTP.

Synapses are held in forward order.  `canonical_order` gives the
permutation to the (target, source, slot) order in which the program lays
out its per-synapse state.

`precision` is "float64" (the reference) or "bfloat16" (the control: every
stored value and every arithmetic result rounded to bfloat16).
"""
from __future__ import annotations

import dataclasses
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List

import numpy as np

_GOLDEN = np.uint64(0x9E3779B97F4A7C15)
_MIX1 = np.uint64(0xBF58476D1CE4E5B9)
_MIX2 = np.uint64(0x94D049BB133111EB)
_STIM_SALT = 0x57D11
# sources per block: a block's uint64 draws and temporaries take about
# 200 MB, and a few blocks are made at once
_BLOCK = 1 << 13


def _splitmix64(x: np.ndarray) -> np.ndarray:
    with np.errstate(over="ignore"):
        x = x + _GOLDEN
        x = (x ^ (x >> np.uint64(30))) * _MIX1
        x = (x ^ (x >> np.uint64(27))) * _MIX2
        return x ^ (x >> np.uint64(31))


def _lane(seed: int, counter: np.ndarray, lane: int) -> np.ndarray:
    with np.errstate(over="ignore"):
        key = _splitmix64(np.uint64(seed) + _GOLDEN * np.uint64(lane + 1))
    return _splitmix64(counter ^ key)


def _ring_offsets(reach: int) -> List[np.ndarray]:
    """(dx, dy) at Chebyshev distance r, dy outer and dx inner."""
    out = []
    for r in range(reach + 1):
        out.append(np.array([(dx, dy) for dy in range(-r, r + 1)
                             for dx in range(-r, r + 1)
                             if max(abs(dx), abs(dy)) == r],
                            dtype=np.int64).reshape(-1, 2))
    return out


@dataclasses.dataclass
class Network:
    """Forward synapses, flat index g * M + j."""

    n: int
    m: int
    tgt: np.ndarray        # [N * M] int32
    delay: np.ndarray      # [N * M] int8
    exc: np.ndarray        # [N] bool: excitatory source (its synapses plastic)


def make_network(grid: dict, seed: int) -> Network:
    gx, gy = grid["grid_x"], grid["grid_y"]
    npc, m = grid["neurons_per_column"], grid["synapses_per_neuron"]
    n_exc = int(round(npc * grid["exc_fraction"]))
    dmin, dmax = grid["delay_min"], grid["delay_max"]
    if grid["connectivity"] != "ring3":
        raise ValueError("the reference implements the ring3 kernel only")
    frac = np.asarray(grid["ring_fractions"], dtype=np.float64)
    cum = np.cumsum(frac) / np.sum(frac)
    reach = len(frac) - 1
    rings = _ring_offsets(reach)
    off = np.concatenate(rings)
    start = np.concatenate([[0], np.cumsum([len(r) for r in rings])])
    size = np.diff(start)
    n = gx * gy * npc
    tgt = np.empty(n * m, np.int32)
    delay = np.empty(n * m, np.int8)
    slot = np.arange(m, dtype=np.uint64)

    def block(g0):
        g = np.arange(g0, min(n, g0 + _BLOCK), dtype=np.int64)
        c = g.astype(np.uint64)[:, None] * np.uint64(m) + slot[None, :]
        u_ring = (_lane(seed, c, 0) >> np.uint64(11)).astype(np.float64) \
            * 2.0 ** -53
        ring = np.searchsorted(cum, u_ring, side="right").clip(0, reach)
        member = (_lane(seed, c, 1) % size[ring].astype(np.uint64)
                  ).astype(np.int64)
        d = off[start[ring] + member]
        col = g // npc
        cx, cy = col % gx, col // gx
        tcol = ((cy[:, None] + d[..., 1]) % gy) * gx \
            + (cx[:, None] + d[..., 0]) % gx
        r_tgt = _lane(seed, c, 2)
        exc = (g % npc < n_exc)[:, None]
        t_exc = tcol * npc + (r_tgt % np.uint64(npc)).astype(np.int64)
        t_inh = col[:, None] * npc \
            + (r_tgt % np.uint64(n_exc)).astype(np.int64)
        d_exc = dmin + (_lane(seed, c, 3) % np.uint64(dmax - dmin + 1)
                        ).astype(np.int64)
        sl = slice(g0 * m, (g0 + g.size) * m)
        tgt[sl] = np.where(exc, t_exc, t_inh).ravel()
        delay[sl] = np.where(exc, d_exc, dmin).ravel()

    # numpy lets go of the interpreter lock in these array operations, and
    # the blocks write disjoint slices
    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        list(pool.map(block, range(0, n, _BLOCK)))
    exc_n = np.arange(n) % npc < n_exc
    return Network(n=n, m=m, tgt=tgt, delay=delay, exc=exc_n)


def canonical_order(net: Network) -> np.ndarray:
    """Forward indices in (target, source, slot) order.

    The forward index already grows with (source, slot), so a stable sort
    by target gives the order: two stable 16-bit radix passes."""
    if net.n > 1 << 32:
        raise ValueError("more neurons than two 16-bit passes can order")
    lo = (net.tgt & 0xFFFF).astype(np.uint16)
    p = np.argsort(lo, kind="stable")
    del lo
    hi = (net.tgt >> 16).astype(np.uint16)[p]
    return p[np.argsort(hi, kind="stable")]


def stimulus(grid: dict, traffic: dict, seed: int, steps: int
             ) -> np.ndarray:
    """[steps, columns * events] target gids of the thalamic events.

    Event k of column c at step t targets neuron
    randint(fold_in(fold_in(key(seed ^ salt), t), c)) of that column."""
    import jax
    import jax.numpy as jnp

    npc = grid["neurons_per_column"]
    k_ev = traffic["stim_events_per_ms_per_column"]
    n_col = grid["grid_x"] * grid["grid_y"]
    key = jax.random.key(seed ^ _STIM_SALT)

    def one(t, col):
        k = jax.random.fold_in(jax.random.fold_in(key, t), col)
        return col * npc + jax.random.randint(k, (k_ev,), 0, npc,
                                              dtype=jnp.int32)

    cols = jnp.arange(n_col, dtype=jnp.int32)
    ts = jnp.arange(steps, dtype=jnp.int32)
    f = jax.vmap(lambda t: jax.vmap(lambda c: one(t, c))(cols))
    return np.asarray(jax.jit(f)(ts)).reshape(steps, -1)


@dataclasses.dataclass
class Result:
    raster: np.ndarray     # [T, N] bool
    v: np.ndarray          # [N]
    u: np.ndarray          # [N]
    w: np.ndarray          # [N * M], forward order
    counts: Dict[str, int]


def simulate(net: Network, grid: dict, traffic: dict, izh: dict,
             stdp: dict, seed: int, steps: int,
             precision: str = "float64") -> Result:
    """Steps 0 .. steps-1 from rest."""
    if precision == "float64":
        dt = np.float64

        def q(x):
            return x
    elif precision == "bfloat16":
        import ml_dtypes
        dt = np.float32

        def q(x):
            return np.asarray(x, np.float32).astype(
                ml_dtypes.bfloat16).astype(np.float32)
    else:
        raise ValueError(f"unknown precision {precision!r}")

    def c(x):
        return q(np.asarray(x, dt))

    n, m = net.n, net.m
    exc = net.exc
    a = c(np.where(exc, izh["a_exc"], izh["a_inh"]))
    b = c(np.where(exc, izh["b_exc"], izh["b_inh"]))
    cr = c(np.where(exc, izh["c_exc"], izh["c_inh"]))
    d = c(np.where(exc, izh["d_exc"], izh["d_inh"]))
    h = c(izh["dt"] / izh["v_substeps"])
    dt_ms = c(izh["dt"])
    v_peak = c(izh["v_peak"])
    a_minus, a_plus = c(stdp["a_minus"]), c(stdp["a_plus"])
    tau_minus, tau_plus = c(stdp["tau_minus"]), c(stdp["tau_plus"])
    w_min, w_max = c(stdp["w_min"]), c(stdp["w_max"])
    amp = c(traffic["stim_amplitude"])
    k004, k5, k140 = c(0.04), c(5.0), c(140.0)

    v = np.full(n, c(izh["v_init"]), dt)
    u = q(b * v)
    last_post = np.full(n, -np.inf, dt)
    w = np.repeat(np.where(exc, c(grid["w_exc_init"]),
                           c(grid["w_inh_init"])), m).astype(dt)
    last_arr = np.full(n * m, -np.inf, dt)
    seen = np.zeros(0, np.int64)           # synapses with an arrival
    stim = stimulus(grid, traffic, seed, steps)
    spikes: List[np.ndarray] = []
    raster = np.zeros((steps, n), bool)
    counts = dict(spikes=0, arrivals=0, ltd=0, ltp=0)
    slots = np.arange(m, dtype=np.int64)
    for t in range(steps):
        tf = c(t)
        # arrivals: spikes of step t - d over synapses of delay d
        got = []
        for lag in range(1, min(t, grid["delay_max"]) + 1):
            src = spikes[t - lag]
            if src.size:
                f = (src[:, None] * m + slots[None, :]).ravel()
                got.append(f[net.delay[f] == lag])
        arr = (np.sort(np.concatenate(got)) if got
               else np.zeros(0, np.int64))
        tgt_a = net.tgt[arr]
        w_a = w[arr]
        i_syn = q(np.bincount(tgt_a, weights=w_a, minlength=n).astype(dt))
        lp = last_post[tgt_a]
        ltd = exc[arr // m] & np.isfinite(lp)
        dep = q(a_minus * q(np.exp(q(q(lp[ltd] - tf) / tau_minus))))
        w[arr[ltd]] = np.clip(q(w_a[ltd] - dep), w_min, w_max)
        last_arr[arr] = tf
        seen = np.union1d(seen, arr)
        i_ext = q(np.bincount(stim[t], minlength=n).astype(dt) * amp)
        cur = q(i_syn + i_ext)
        for _ in range(izh["v_substeps"]):
            dv = q(q(q(q(q(k004 * v) * v) + q(k5 * v)) + k140) - u)
            v = q(v + q(h * q(dv + cur)))
        u = q(u + q(q(dt_ms * a) * q(q(b * v) - u)))
        spk = v >= v_peak
        v = np.where(spk, cr, v)
        u = np.where(spk, q(u + d), u)
        # LTP: synapses that have had an arrival, onto neurons that spiked
        s = seen[spk[net.tgt[seen]] & exc[seen // m]]
        pot = q(a_plus * q(np.exp(q(q(last_arr[s] - tf) / tau_plus))))
        w[s] = np.clip(q(w[s] + pot), w_min, w_max)
        last_post[spk] = tf
        spikes.append(np.flatnonzero(spk).astype(np.int64))
        raster[t] = spk
        counts["spikes"] += int(spk.sum())
        counts["arrivals"] += int(arr.size)
        counts["ltd"] += int(ltd.sum())
        counts["ltp"] += int(s.size)
    return Result(raster=raster, v=v, u=u, w=w, counts=counts)
