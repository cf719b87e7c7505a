"""DPSNN simulation launcher (the paper's workload).

  python -m repro.launch.snn --grid 4x4 --steps 500 [--shards 4]
      [--exchange halo|allgather|hier] [--exchange-schedule sync|pipelined]
      [--placement block|scatter] [--delivery dense|event]
      [--profile ring3|gaussian:sigma=1.5|...] [--ckpt-dir DIR]

`--delivery event` runs the paper's event-driven synaptic formulation
(O(spikes x fan-out) per step) instead of the dense O(E) masked one; both
support every layout knob — shard counts, exchange modes, placements,
cluster jobs, checkpointing.

With --shards > 1 this process needs that many devices: the chips of a
multi-chip host, or on the CPU forced host devices
(XLA_FLAGS=--xla_force_host_platform_device_count=<H>).  Under
`repro.cluster.local` (the REPRO_CLUSTER_* env variables set), the same
launcher becomes one worker of a
multi-process job: `--shards` then counts GLOBAL shards across all
processes, rasters are gathered for the rate report, and only process 0
writes checkpoints.
"""
from __future__ import annotations

import argparse
import os

from repro.cluster import runtime as cluster_runtime

# Joining a cluster job must precede ANY jax computation.  No-op outside
# a cluster job (REPRO_CLUSTER_* absent).
cluster_runtime.ensure_initialized()

import jax
import numpy as np

from repro import compile_cache
from repro.core import (EngineConfig, GridConfig, StepProgram, checkpoint,
                        observables, profiles)
from repro.core import distributed as D


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--grid", default="2x2")
    ap.add_argument("--neurons-per-column", type=int, default=1000)
    ap.add_argument("--synapses", type=int, default=200)
    ap.add_argument("--steps", type=int, default=500)
    ap.add_argument("--shards", type=int, default=1)
    ap.add_argument("--exchange", default="allgather",
                    choices=["allgather", "halo", "hier"])
    ap.add_argument("--exchange-schedule", default="sync",
                    choices=["sync", "pipelined"],
                    help="'pipelined' issues the spike exchange before the "
                         "LTP half of phase A and delivers one loop "
                         "iteration later (bit-identical outputs)")
    ap.add_argument("--delivery", default="dense",
                    choices=["dense", "event"])
    ap.add_argument("--placement", default="block",
                    choices=["block", "scatter"])
    ap.add_argument("--profile", default="ring3",
                    help="lateral-connectivity profile spec "
                         "(repro.core.profiles): ring3 | ringN | "
                         "ring:max_ring=N | gaussian:sigma=S | "
                         "exponential:lambda=L")
    ap.add_argument("--connectivity-mode", default="materialized",
                    help="synapse-table residency: 'materialized' (full "
                         "tables live) or 'streamed:chunk=K' (regenerate "
                         "per-chunk tables inside the step; O(chunk) live "
                         "bytes, bit-identical rasters AND weights; "
                         "requires --delivery dense)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    args = ap.parse_args()

    gx, gy = (int(v) for v in args.grid.split("x"))
    cfg = GridConfig(grid_x=gx, grid_y=gy,
                     neurons_per_column=args.neurons_per_column,
                     synapses_per_neuron=args.synapses,
                     connectivity=args.profile)
    eng = EngineConfig(n_shards=args.shards, exchange=args.exchange,
                       exchange_schedule=args.exchange_schedule,
                       placement=args.placement, delivery=args.delivery,
                       connectivity=args.connectivity_mode)
    prof = profiles.from_config(cfg)       # fail fast on a bad spec
    compile_cache.enable()
    if cluster_runtime.is_primary():
        dev = jax.devices()[0]
        print(f"[snn] platform {dev.platform}, {dev.device_kind}, "
              f"{jax.device_count()} devices")
        procs = (f", {jax.process_count()} processes"
                 if cluster_runtime.is_distributed() else "")
        print(f"[snn] {cfg.n_neurons} neurons / {cfg.n_synapses} synapses "
              f"on {args.shards} shards ({args.exchange}, "
              f"{args.placement}, {prof.spec()} reach={prof.reach()}"
              f"{procs})")

    # Build: one StepProgram per process covers both delivery backends,
    # every exchange wire and both schedules; the run loop, checkpoint,
    # sharding and cluster gather are backend-generic from here on.
    event = args.delivery == "event"
    sharded = args.shards > 1
    if sharded:
        # jax.devices() is global: across every process of a cluster job
        if jax.device_count() < args.shards:
            raise SystemExit(
                f"--shards {args.shards} needs {args.shards} devices, "
                f"found {jax.device_count()} ({jax.default_backend()}): "
                f"run on a host with that many chips, launch more "
                f"processes (repro.cluster.local), or on the CPU force "
                f"host devices with XLA_FLAGS="
                f"--xla_force_host_platform_device_count={args.shards}")
    sp = StepProgram(cfg, eng,
                     mesh=D.make_mesh(args.shards) if sharded else None)
    spec, plan, state = sp.spec, sp.plan, sp.init_state()
    t0 = 0
    if args.ckpt_dir:
        latest = checkpoint.latest(args.ckpt_dir)
        if latest:
            state, t0 = sp.load(latest)
            if cluster_runtime.is_primary():
                print(f"[snn] resumed at t={t0} from {latest}")

    if sharded:
        state_d = sp.place(state)
        chunk = args.ckpt_every or args.steps
        t = t0
        while t < t0 + args.steps:
            n = min(chunk, t0 + args.steps - t)
            state_d, raster, tm = sp.run(state_d, t, n)
            t += n
            if args.ckpt_dir:
                # gather is a collective (all processes), the write is not
                state_h = cluster_runtime.gather(state_d)
                if cluster_runtime.is_primary():
                    checkpoint.save(os.path.join(args.ckpt_dir,
                                                 f"ckpt_{t}.npz"),
                                    spec, plan, state_h, t)
        state, raster = state_d, raster
    else:
        chunk = args.ckpt_every or args.steps
        t = t0
        while t < t0 + args.steps:
            n = min(chunk, t0 + args.steps - t)
            state, raster, tm = sp.run(state, t, n)
            t += n
            # primary-only for the same reason as the sharded branch: a
            # cluster job with --shards 1 runs one replica per process,
            # and they must not race on the checkpoint path
            if args.ckpt_dir and cluster_runtime.is_primary():
                checkpoint.save(os.path.join(args.ckpt_dir,
                                             f"ckpt_{t}.npz"),
                                spec, plan, state, t)

    raster_h = cluster_runtime.gather(raster)
    rate = observables.mean_rate_hz(np.asarray(raster_h), cfg.n_neurons)
    sat = None
    if event:
        # sharded state spans processes -> gather assembles each global
        # shard once (a collective; every process participates).  In the
        # replica case (--shards 1, one copy per process) every replica
        # holds the identical counter, and gathering would stack P copies
        # and over-count the sum P-fold — read it locally instead.
        sat_arr = cluster_runtime.gather(state.sat) if args.shards > 1 \
            else state.sat
        sat = int(np.asarray(sat_arr).sum())
    if cluster_runtime.is_primary():
        tail = f", saturated {sat}" if event else ""
        print(f"[snn] final-window rate {rate:.1f} Hz; done at t={t} ms"
              f"{tail}")


if __name__ == "__main__":
    main()
