#!/usr/bin/env python3
"""Smoke run of the DPSNN-STDP engine on a TPU at the paper's widths.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded path, four-chip host

Everything runs in this one process: a chip belongs to one process at a
time.  The phases, in order, each print their readings; any failure
raises, exits non-zero and prints no result line.

  device    require a TPU; with none, run nothing and exit non-zero
  kernels   the three SNN Pallas kernels against their jnp oracles at one
            24x24 shard's widths; largest ulp difference of each output
  main      a 24x24 grid of paper columns (576,000 neurons, 115.2 M
            synapses) through StepProgram, the launcher's path: a short
            run, the kernels inside the compiled program, finite bounded
            state
  table 1   a 4x4 grid at 1 and 4 logical shards (vmap on one chip):
            bit-identical rasters and weights; rate against the CPU's

With --chips 4 only the sharded phase runs: one 12x12 build with 4 shards
over a four-chip mesh, with halo+pipelined and with allgather+sync, each
compared bit for bit with the one-chip vmap reference of the same build.

The readings are smoke readings, not benchmark metrics.  The last line of
standard output is the JSON result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

# Paper widths are GridConfig's defaults (core/params.py): 1000 Izhikevich
# neurons per column, M = 200 synapses per neuron, ring3, STDP on.
# The cuts are for time, not memory.  The dense step takes about 6.5 s at
# 115.2 M synapses on a TPU v5e (cause not yet traced, ROADMAP S2), so 200
# steps would take 22 minutes; the script has to finish well inside 20
# minutes, so the main path runs 10.  The four-chip phase uses a 12x12
# grid to keep a four-chip call short: a 24x24 one pays about 3 minutes of
# host build and about 65 s of one-chip reference at four chips' rate.
# The 24x24 four-chip check is still to be run.
MAIN_GRID = (24, 24)
MAIN_STEPS = 10
TABLE1_GRID = (4, 4)
TABLE1_STEPS = 100
SHARDED_GRID = (12, 12)
SHARDED_STEPS = 10

# One 24x24 shard's widths for the kernel phase.
KERNEL_SYNAPSES = 24 * 24 * 1000 * 200
KERNEL_NEURONS = 24 * 24 * 1000

# The oracles (kernels/ref.py) run on the host's CPU backend, where XLA
# keeps the written arithmetic; XLA's TPU simplifier rewrites the oracle's
# 0.04*v*v + 5*v as (0.04*v + 5)*v (visible in the compiled HLO).  Kernel
# and oracle still round differently: the Mosaic compiler may contract or
# keep excess precision, and the chip's `exp` differs from the CPU's.  So
# each difference is counted in ulps of the operation's own scale, the
# largest magnitude the update adds or subtracts:
#   Izhikevich v: max over both half-steps of |v| and
#       h * (0.04 v^2 + 5 |v| + 140 + |u| + |I|);
#     u: max(|u|, a (b |v| + |u|)), and for a neuron that spiked the reset
#       sum u' + d as well, |u| + a (b |v| + |u|) + |d|
#   STDP w: max(|w|, 1), the weights' order of magnitude, above the most
#       LTD/LTP removes or adds (a_minus, a_plus <= 0.12)
# Izhikevich: up to two rounding differences per half-step, those of the
# first amplified in the second by at most 1 + h (0.08 v + 5) < 5, give at
# most 2 * 5 + 2 = 12 ulps of v's scale.  u's scale holds a b |v|, so u
# carries v's difference at the same relative size (up to twice as many
# ulps across a binade) plus its own three roundings: 2 * 2 + 3 = 7 ulps
# at v's usual 2.  On a TPU v5e, seeds 0-3 read v <= 2 and u 5-7 ulps;
# IZH_ULP_BOUND = 16 leaves a factor 2 over the largest u.
# STDP: a float32 `exp` within 2**-19 relative gives at most 2 ulps of a
# weight of 1 at ltd <= 0.12, STDP_ULP_BOUND leaves a factor 4.
IZH_ULP_BOUND = 16
STDP_ULP_BOUND = 8

# Mean rate of the TABLE1_GRID network over TABLE1_STEPS on the CPU,
# printed by:
#   JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.snn \
#       --grid 4x4 --steps 100
CPU_RATE_4X4_HZ = 56.7
RATE_TOLERANCE = 0.10


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(phase: str, **readings) -> None:
    body = ", ".join(f"{k}={v}" for k, v in readings.items())
    print(f"[{phase}] {body}", flush=True)


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------


def device_phase(chips: int):
    import jax
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: JAX found {devs[0].platform!r} devices; nothing run")
    check(len(devs) >= chips, f"--chips {chips} needs {chips} TPU devices, "
          f"found {len(devs)}")
    log("device", platform=devs[0].platform, kind=devs[0].device_kind,
        count=len(devs))
    return devs[0]


def peak_bytes(dev) -> int:
    return int(dev.memory_stats()["peak_bytes_in_use"])


def require_kernels(compiled_text: str, what: str) -> None:
    """The Pallas kernels entered the compiled program, and the oracle
    fallback (ops._resolve) never fired."""
    from repro.kernels import ops
    check("tpu_custom_call" in compiled_text,
          f"{what}: no tpu_custom_call in the compiled program")
    check(not ops._warned_fallback,
          f"{what}: kernels.ops fell back to the jnp oracle")


# ---------------------------------------------------------------------------
# kernels against their oracles
# ---------------------------------------------------------------------------


def ulps(got, want, scale) -> float:
    """Largest |got - want| in float32 ulps of `scale` (host arrays)."""
    gap = np.abs(got.astype(np.float64) - want.astype(np.float64))
    return float(np.max(gap / np.spacing(scale.astype(np.float32))))


def against_oracle(name: str, kernel, oracle, args):
    """Run `kernel` on the chip and `oracle` on the host CPU backend over
    the same inputs; returns both outputs and the inputs as host arrays."""
    import jax
    kern = jax.jit(kernel)
    t0 = time.perf_counter()
    require_kernels(kern.lower(*args).compile().as_text(), name)
    compile_s = time.perf_counter() - t0
    cpu = jax.devices("cpu")[0]
    got = jax.tree.leaves(kern(*args))
    want = jax.tree.leaves(jax.jit(oracle)(*jax.device_put(args, cpu)))
    log("kernels", kernel=name, compile_s=compile_s)
    host = lambda xs: [np.asarray(x) for x in xs]  # noqa: E731
    return host(got), host(want), host(args)


def izh_scales(v, u, cur, a, b, d, spiked, h, substeps):
    """Per-neuron operation scales of the Izhikevich update (float64)."""
    v, u, cur, a, b, d = (x.astype(np.float64) for x in (v, u, cur, a, b, d))
    sv = np.abs(v)
    for _ in range(substeps):
        terms = 0.04 * v * v + 5 * np.abs(v) + 140 + np.abs(u) + np.abs(cur)
        sv = np.maximum(sv, h * terms)
        v = v + h * (0.04 * v * v + 5 * v + 140 - u + cur)
        sv = np.maximum(sv, np.abs(v))
    du = np.abs(a) * (np.abs(b) * sv + np.abs(u))
    su = np.maximum(np.abs(u), du)
    return sv, np.where(spiked, np.maximum(su, np.abs(u) + du + np.abs(d)),
                        su)


def kernel_phase(seed: int, n_syn: int, n_neur: int) -> None:
    import functools

    import jax
    import jax.numpy as jnp

    from repro.core.engine import NEG_TIME
    from repro.core.params import DEFAULT_IZH as izh
    from repro.core.params import DEFAULT_STDP as stdp
    from repro.kernels import ops, ref

    f32 = jnp.float32
    k = jax.random.split(jax.random.key(seed), 12)
    # neurons spread over the sub- and supra-threshold range
    exc = jax.random.bernoulli(k[0], 0.8, (n_neur,))
    sel = lambda e, i: jnp.where(exc, f32(e), f32(i))  # noqa: E731
    nargs = (jax.random.uniform(k[1], (n_neur,), f32, -80.0, 30.0),
             jax.random.uniform(k[2], (n_neur,), f32, -20.0, 10.0),
             jax.random.uniform(k[3], (n_neur,), f32, -5.0, 25.0),
             sel(izh.a_exc, izh.a_inh), sel(izh.b_exc, izh.b_inh),
             sel(izh.c_exc, izh.c_inh), sel(izh.d_exc, izh.d_inh))
    nkw = dict(v_peak=izh.v_peak, dt=izh.dt, substeps=izh.v_substeps)
    (vk, uk, sk), (vo, uo, so), (v, u, cur, a, b, _, d) = against_oracle(
        "izhikevich",
        functools.partial(ops.izhikevich_update, use_pallas=True, **nkw),
        functools.partial(ref.izhikevich_update, **nkw), nargs)
    sv, su = izh_scales(v, u, cur, a, b, d, so, izh.dt / izh.v_substeps,
                        izh.v_substeps)
    res = dict(izhikevich_v_ulp=ulps(vk, vo, sv),
               izhikevich_u_ulp=ulps(uk, uo, su),
               izhikevich_spike_mismatch=int(np.sum(sk != so)))
    del nargs

    # synapses: times in the last 100 ms, or never (NEG_TIME)
    def times(kk, p):
        a, b = jax.random.split(kk)
        return jnp.where(jax.random.bernoulli(a, p, (n_syn,)),
                         jax.random.uniform(b, (n_syn,), f32, 0.0, 99.0),
                         NEG_TIME)
    t = f32(100.0)
    w = jax.random.uniform(k[4], (n_syn,), f32, stdp.w_min, stdp.w_max)
    plastic = jax.random.bernoulli(k[5], 0.8, (n_syn,))
    skw = dict(w_min=stdp.w_min, w_max=stdp.w_max, neg_time=float(NEG_TIME))
    akw = dict(a_minus=stdp.a_minus, tau_minus=stdp.tau_minus, **skw)
    got, want, _ = against_oracle(
        "stdp_arrival",
        functools.partial(ops.stdp_arrival, use_pallas=True, **akw),
        functools.partial(ref.stdp_arrival, **akw),
        (jax.random.bernoulli(k[6], 0.05, (n_syn,)), w, times(k[7], 0.7),
         times(k[8], 0.7), plastic, t))
    for name, g, o in zip(("w", "last_arr", "contrib"), got, want):
        scale = np.maximum(np.maximum(np.abs(g), np.abs(o)), 1.0)
        res[f"stdp_arrival_{name}_ulp"] = ulps(g, o, scale)
    del got, want
    lkw = dict(a_plus=stdp.a_plus, tau_plus=stdp.tau_plus, **skw)
    (g,), (o,), _ = against_oracle(
        "stdp_ltp", functools.partial(ops.stdp_ltp, use_pallas=True, **lkw),
        functools.partial(ref.stdp_ltp, **lkw),
        (jax.random.bernoulli(k[9], 0.05, (n_syn,)), w, times(k[10], 0.7),
         plastic, jax.random.bernoulli(k[11], 0.95, (n_syn,)), t))
    res["stdp_ltp_w_ulp"] = ulps(
        g, o, np.maximum(np.maximum(np.abs(g), np.abs(o)), 1.0))
    del g, o, w, plastic

    log("kernels", synapses=n_syn, neurons=n_neur,
        izh_bound_ulp=IZH_ULP_BOUND, stdp_bound_ulp=STDP_ULP_BOUND, **res)
    check(res["izhikevich_spike_mismatch"] == 0,
          "izhikevich: kernel and oracle disagree on spikes")
    for key, v in res.items():
        if key.endswith("_ulp"):
            bound = IZH_ULP_BOUND if key.startswith("izh") else STDP_ULP_BOUND
            check(v <= bound, f"{key}: {v} ulp > {bound}")


# ---------------------------------------------------------------------------
# the main path
# ---------------------------------------------------------------------------


def grid_config(grid):
    from repro.core import GridConfig
    return GridConfig(grid_x=grid[0], grid_y=grid[1])


def check_state(sp, state, what: str) -> None:
    """v, u, w finite; plastic weights within [w_min, w_max]; the other
    weights never changed."""
    st = state.base if hasattr(state, "base") else state
    stdp = sp.spec.stdp
    v, u, w = (np.asarray(x) for x in (st.v, st.u, st.w))
    w0 = np.asarray(sp.init_state().w)
    plastic = np.asarray(sp.plan.syn_plastic) & np.asarray(sp.plan.syn_valid)
    check(all(np.isfinite(x).all() for x in (v, u, w)),
          f"{what}: non-finite v, u or w")
    wp = w[plastic]
    check(bool(((wp >= stdp.w_min) & (wp <= stdp.w_max)).all()),
          f"{what}: plastic weight outside [{stdp.w_min}, {stdp.w_max}]")
    check(np.array_equal(w[~plastic], w0[~plastic]),
          f"{what}: a non-plastic weight changed")


def main_phase(grid, steps: int, dev) -> None:
    import jax

    from repro.core import EngineConfig, StepProgram, observables

    cfg = grid_config(grid)
    t0 = time.perf_counter()
    sp = StepProgram(cfg, EngineConfig(n_shards=1))
    state = sp.place(sp.init_state())
    log("main", grid=f"{grid[0]}x{grid[1]}", neurons=cfg.n_neurons,
        synapses=cfg.n_synapses, build_s=time.perf_counter() - t0)

    t0 = time.perf_counter()
    compiled = sp.lower_run(state, 0, steps).compile()
    compile_s = time.perf_counter() - t0
    require_kernels(compiled.as_text(), "main path")
    mem = compiled.memory_analysis()
    del compiled
    log("main", compile_s=compile_s,
        program_arg_bytes=mem.argument_size_in_bytes,
        program_out_bytes=mem.output_size_in_bytes,
        program_temp_bytes=mem.temp_size_in_bytes)

    t0 = time.perf_counter()
    jax.block_until_ready(sp.run(state, 0, steps))
    log("main", steps=steps, first_run_s=time.perf_counter() - t0)
    t0 = time.perf_counter()
    out = sp.run(state, 0, steps)
    jax.block_until_ready(out)
    run_s = time.perf_counter() - t0
    state_f, raster, _ = out

    check_state(sp, state_f, "main path")
    rate = observables.mean_rate_hz(np.asarray(raster), cfg.n_neurons)
    log("main", steps=steps, run_s=run_s,
        wall_s_per_sim_s=run_s / (steps * 1e-3), rate_hz=rate,
        peak_bytes_in_use=peak_bytes(dev))
    check(rate > 0, "main path: no neuron spiked")


# ---------------------------------------------------------------------------
# Table 1 on the chip: one network, any shard count, the same bits
# ---------------------------------------------------------------------------


def first_difference(ra, ga, rb, gb) -> str:
    """First step and neurons at which two [T, H, N] rasters differ."""
    from repro.core import observables
    ea = observables.raster_events(ra, ga)
    eb = observables.raster_events(rb, gb)
    for t in range(ra.shape[0]):
        a = set(ea[1][ea[0] == t].tolist())
        b = set(eb[1][eb[0] == t].tolist())
        if a != b:
            return (f"first at step {t}: neurons {sorted(a - b)[:8]} only "
                    f"in the first, {sorted(b - a)[:8]} only in the second")
    return "rasters equal"


def compare_runs(name: str, ref, other) -> None:
    """`ref` and `other` are (raster, gid, weight_sig) triples."""
    ra, ga, wa = ref
    rb, gb, wb = other
    from repro.core import observables
    same_raster = (observables.raster_signature(ra, ga)
                   == observables.raster_signature(rb, gb))
    if not same_raster:
        print(f"[{name}] {first_difference(ra, ga, rb, gb)}", flush=True)
    check(same_raster, f"{name}: rasters differ")
    check(wa == wb, f"{name}: weights differ (rasters equal)")


def table1_phase(grid, steps: int) -> None:
    import jax

    from repro.core import EngineConfig, StepProgram, observables

    cfg = grid_config(grid)
    runs, rates = {}, {}
    for shards in (1, 4):
        sp = StepProgram(cfg, EngineConfig(n_shards=shards))
        state_f, raster, _ = sp.run(sp.place(sp.init_state()), 0, steps)
        jax.block_until_ready(raster)
        raster = np.asarray(raster)
        check_state(sp, state_f, f"table1 H={shards}")
        runs[shards] = (raster, np.asarray(sp.plan.gid),
                        sp.weight_signature(state_f))
        rates[shards] = observables.mean_rate_hz(raster, cfg.n_neurons)
    compare_runs("table1", runs[1], runs[4])
    rel = abs(rates[1] - CPU_RATE_4X4_HZ) / CPU_RATE_4X4_HZ
    log("table1", grid=f"{grid[0]}x{grid[1]}", steps=steps,
        bit_identical_1_vs_4=True, rate_hz=rates[1],
        cpu_rate_hz=CPU_RATE_4X4_HZ, rate_rel_diff=rel)
    check(rel <= RATE_TOLERANCE,
          f"table1: chip rate {rates[1]} Hz vs CPU {CPU_RATE_4X4_HZ} Hz")


# ---------------------------------------------------------------------------
# four chips: the sharded program against the one-chip reference
# ---------------------------------------------------------------------------


def tree_bytes(tree) -> int:
    import jax
    return sum(int(np.asarray(x).nbytes) for x in jax.tree.leaves(tree))


def check_quarters(state, n_dev: int) -> None:
    """Every leaf is split over `n_dev` devices, one shard row each."""
    import jax
    for leaf in jax.tree.leaves(state):
        shards = leaf.addressable_shards
        check(len({s.device for s in shards}) == n_dev
              and all(s.data.shape[0] == 1 for s in shards),
              f"sharded: a state leaf of shape {leaf.shape} is not split "
              f"one shard per device")


def sharded_phase(grid, steps: int) -> None:
    import jax

    from repro.core import EngineConfig, StepProgram, engine
    from repro.core import distributed as D

    n_dev = 4
    cfg = grid_config(grid)
    eng = EngineConfig(n_shards=n_dev)
    t0 = time.perf_counter()
    spec, plan, state0 = engine.build(cfg, eng)
    build_s = time.perf_counter() - t0
    net_bytes = tree_bytes(plan) + tree_bytes(state0)
    log("sharded", grid=f"{grid[0]}x{grid[1]}", build_s=build_s,
        network_bytes=net_bytes)

    mesh = D.make_mesh(n_dev)
    gid = np.asarray(plan.gid)
    runs = {}
    for exchange, schedule in (("halo", "pipelined"), ("allgather", "sync")):
        name = f"{exchange}+{schedule}"
        spec_x = spec._replace(eng=dataclasses.replace(
            eng, exchange=exchange, exchange_schedule=schedule))
        sp = StepProgram.from_parts(spec_x, plan, state0=state0, mesh=mesh)
        state = sp.place(state0)
        check_quarters(state, n_dev)
        t0 = time.perf_counter()
        compiled = sp.lower_run(state, 0, steps).compile()
        compile_s = time.perf_counter() - t0
        require_kernels(compiled.as_text(), name)
        del compiled
        t0 = time.perf_counter()
        state_f, raster, _ = sp.run(state, 0, steps)
        jax.block_until_ready(raster)
        run_s = time.perf_counter() - t0
        check_state(sp, state_f, name)
        runs[name] = (np.asarray(raster), gid, sp.weight_signature(state_f))
        log("sharded", layout=name, compile_s=compile_s, run_s=run_s)
        del state, state_f, raster, sp

    # read before the reference run, which puts the whole network on one
    # chip by design
    peaks = [peak_bytes(d) for d in jax.devices()[:n_dev]]
    log("sharded", peak_bytes_in_use=peaks,
        peak_over_quarter=[p / (net_bytes / n_dev) for p in peaks])
    check(max(peaks) < net_bytes,
          "sharded: a device held as much as the whole network")

    sp = StepProgram.from_parts(spec, plan, state0=state0)
    t0 = time.perf_counter()
    state_f, raster, _ = sp.run(sp.place(state0), 0, steps)
    jax.block_until_ready(raster)
    log("sharded", layout="one-chip vmap reference",
        run_s_with_compile=time.perf_counter() - t0)
    ref = (np.asarray(raster), gid, sp.weight_signature(state_f))
    for name, other in runs.items():
        compare_runs(f"sharded {name}", ref, other)
    log("sharded", bit_identical_to_one_chip_reference=sorted(runs))


# ---------------------------------------------------------------------------


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip sharded phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the kernel phase's random inputs")
    args = ap.parse_args(argv)

    check(os.path.isdir(os.path.join(SRC, "repro")),
          f"no src/repro beside {os.path.basename(__file__)}: run it from "
          f"a checkout of the repository")
    sys.path.insert(0, SRC)
    import jax

    dev = device_phase(args.chips)
    from repro import compile_cache
    log("cache", dir=compile_cache.enable())
    if args.chips == 4:
        sharded_phase(SHARDED_GRID, SHARDED_STEPS)
    else:
        kernel_phase(args.seed, KERNEL_SYNAPSES, KERNEL_NEURONS)
        main_phase(MAIN_GRID, MAIN_STEPS, dev)
        table1_phase(TABLE1_GRID, TABLE1_STEPS)
    return {"ok": True, "device": {"platform": dev.platform,
                                   "kind": dev.device_kind,
                                   "count": jax.device_count()}}


if __name__ == "__main__":
    try:
        result = main()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
    print(json.dumps(result))
