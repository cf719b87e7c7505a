#!/usr/bin/env python3
"""Chip benchmark of the DPSNN-STDP simulator: one run of one cell.

    python3 chip_bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell is an entry of `workloads` in BENCHMARK.json.  It names a
configuration (its file under chip_bench/configs/), a traffic mix
(chip_bench/traffic/<mix>.json) and the chips it needs; how many steps
are compared, and each compared number's limit, are in
chip_bench/checks/<cell>.json.  Metrics are files too: an end-to-end
metric is read by chip_bench/end_to_end/<name>.py, a per-layer one by
chip_bench/layer_metrics/<name>.py, each a `read(record)` that returns a
number or None.  Nothing here lists cells, mixes or metrics.

One process, in this order:

  1. require a TPU with the cell's chips; otherwise exit non-zero, no result
  2. turn on the persistent compilation cache (repro.compile_cache)
  3. build the network from --seed (StepProgram), place it on the chip; a
     cell of several chips runs one shard per chip on a one-axis `cells`
     mesh of exactly its devices (its configuration's n_shards equals its
     chips, and its placement is block), and every state leaf is checked
     to be split one shard row per device
  4. compile the cell's run program; it must hold the Pallas kernels
  5. drive the network from rest through the compared steps, chunk by
     chunk, with the window's own call (this warms it up), and copy the
     whole network's raster, v, u and w to the host, each value at its
     global index: everything up to here is set-up
  6. the window: StepProgram.run chunk after chunk, carrying the state,
     each chunk ended by block_until_ready, until --seconds have passed;
     with --trace 1 the window is profiled
  7. read the peak device memory and free the device
  8. run the plain reference over the compared steps and compare
     (compare.py)
  9. print the compared numbers on standard error, then the result line

The compared steps are a fixed number from rest, set in the cell's checks
file, and not the window's: float32 and the float64 reference part ways,
as a chaotic network's trajectories do, some tens of steps in, and a
faster program must not be compared over more steps than a slower one.

The last line of standard output is one JSON object: correct, attempted
(steps simulated), failed (those steps, in a run that is not correct),
metrics and device; with --trace 1 also breakdown; and last the compared
numbers with their limits.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

from chip_bench import compare, reference  # noqa: E402


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def host_peak_gib() -> float:
    """This process's peak resident memory on the host."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20


# ---------------------------------------------------------------------------
# the cell, found by name
# ---------------------------------------------------------------------------


def _load(path: str):
    with open(path) as f:
        return json.load(f)


def _by_name(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    checks: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = _load(os.path.join(root, "BENCHMARK.json"))
    w = _by_name(bench["workloads"], name, "workload")
    conf = _by_name(bench["configs"], w["config"], "config")
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_load(os.path.join(root, conf["file"])),
        traffic=_load(os.path.join(HERE, "traffic", w["traffic"] + ".json")),
        checks=_load(os.path.join(HERE, "checks", name + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


READERS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}


def reader(kind: str, name: str):
    """`read` of the metric's file: chip_bench/end_to_end/<name>.py for an
    end-to-end metric, chip_bench/layer_metrics/<name>.py for a per-layer
    one."""
    path = os.path.join(HERE, READERS[kind], name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"chip_bench.{kind}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------


def require_chips(chips: int):
    """The TPU devices, or exit non-zero before anything runs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devs[0].platform!r} devices; "
                     f"nothing run")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} TPU chips, JAX found "
                     f"{len(devs)}")
    return devs


def peaks_of(kind: str) -> dict:
    table = _load(os.path.join(HERE, "peaks.json"))
    if kind not in table:
        raise SystemExit(f"device kind {kind!r} is not in "
                         f"chip_bench/peaks.json")
    return table[kind]


def peak_bytes(devices) -> Optional[int]:
    """peak_bytes_in_use of the fullest device, where the backend says."""
    vals = [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]
    vals = [int(v) for v in vals if v is not None]
    return max(vals) if vals else None


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Record:
    """What a run measured; the metric readers take their numbers here."""

    setup: Dict[str, float]       # host-clock seconds: build, place, ...
    setup_s: float
    window_s: float
    steps: int                    # steps in the window
    dt_ms: float
    n_neurons: int
    n_synapses: int
    delay_slots: int
    peak_bytes: Optional[int]
    device_kind: str
    chips: int = 1                # devices the network is split over
    trace: object = None          # trace_reduce.Reduction with --trace 1

    def peak(self, key: str) -> float:
        """A peak rate of this device from peaks.json; a device that is
        not in the table is an error."""
        return float(peaks_of(self.device_kind)[key])


def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def check_layout(cell: Cell) -> None:
    """A cell runs one shard per chip, block-placed, or not at all: exit
    non-zero before anything is built."""
    eng = cell.config["engine"]
    if int(eng["n_shards"]) != cell.chips:
        raise SystemExit(f"{cell.name}: the configuration has "
                         f"{eng['n_shards']} shards for {cell.chips} chips; "
                         f"a cell runs one shard per chip")
    if eng["placement"] != "block":
        # the fetch concatenates each shard's synapses in shard order,
        # which is the reference's global order only when the shards hold
        # ascending ranges of neurons
        raise SystemExit(f"{cell.name}: placement {eng['placement']!r}; "
                         f"the benchmark takes block placement only")


def _program(cell: Cell, seed: int, devices=None):
    """(GridConfig, StepProgram) of the cell from the seed; on a mesh of
    `devices` where the cell has more than one chip."""
    from repro.core.params import (EngineConfig, GridConfig,
                                   IzhikevichParams, StdpParams)
    from repro.core.step_program import StepProgram

    conf, tr = cell.config, cell.traffic
    grid = dict(conf["grid"], ring_fractions=tuple(
        conf["grid"]["ring_fractions"]))
    cfg = GridConfig(**grid, seed=seed,
                     stim_events_per_ms_per_column=tr[
                         "stim_events_per_ms_per_column"],
                     stim_amplitude=tr["stim_amplitude"])
    mesh = None
    if cell.chips > 1:
        from jax.sharding import AxisType, Mesh
        mesh = Mesh(np.array(devices[:cell.chips]), ("cells",),
                    axis_types=(AxisType.Auto,))
    return cfg, StepProgram(cfg, EngineConfig(**conf["engine"]), mesh=mesh,
                            izh=IzhikevichParams(**conf["izhikevich"]),
                            stdp=StdpParams(**conf["stdp"]))


def check_split(state, devices) -> None:
    """Every state leaf lies on the cell's devices, one shard row each."""
    import jax
    want = set(devices)
    for leaf in jax.tree.leaves(state):
        shards = leaf.addressable_shards
        if ({s.device for s in shards} != want or len(shards) != len(want)
                or any(s.data.shape[0] != 1 for s in shards)):
            raise SystemExit(f"a state leaf of shape {leaf.shape} is not "
                             f"split one shard per device over "
                             f"{len(want)} devices")


def fetch(plan, state, rasters, n_neurons: int,
          n_synapses: int) -> compare.Outputs:
    """The whole network's outputs on the host, in the reference's order:
    each neuron's raster column, v and u at its global id (`plan.gid`),
    and each shard's valid weights, concatenated in shard order.  Block
    placement gives each shard an ascending range of targets, and each
    shard holds its synapses in (target, source, slot) order, so that is
    the reference's canonical order."""
    gid = np.asarray(plan.gid)                        # [H, N_local]
    own = gid >= 0
    ids = gid[own]
    if not np.array_equal(np.sort(ids), np.arange(n_neurons)):
        raise SystemExit(f"the shards' neurons are not each of the "
                         f"{n_neurons} neurons once")

    def spread(x, lead=()):
        out = np.zeros(lead + (n_neurons,), x.dtype)
        out[..., ids] = x[..., own]
        return out

    raster = np.concatenate([np.asarray(r) for r in rasters])  # [T, H, N]
    valid = np.asarray(plan.syn_valid)                # [H, E]
    w_all = np.asarray(state.w)
    w = np.concatenate([w_all[h][valid[h]] for h in range(w_all.shape[0])])
    if w.shape[0] != n_synapses:
        raise SystemExit(f"the program holds {w.shape[0]} weights for "
                         f"{n_synapses} synapses")
    return compare.Outputs(raster=spread(raster, raster.shape[:1]),
                           v=spread(np.asarray(state.v)),
                           u=spread(np.asarray(state.u)), w=w)


def simulate(cell: Cell, seed: int, seconds: float, trace: bool,
             devices, check_kernels: bool = True):
    """Steps 1-7.  Returns (Record, program Outputs)."""
    import jax

    from repro import compile_cache

    check_layout(cell)
    compile_cache.enable()
    setup: Dict[str, float] = {}
    chunk = int(cell.traffic["chunk_steps"])

    t0 = time.perf_counter()
    with _span("build"):
        cfg, sp = _program(cell, seed, devices)
        state = sp.init_state()
    setup["build_s"] = time.perf_counter() - t0
    log(f"built in {setup['build_s']} s; host peak {host_peak_gib()} GiB")

    t0 = time.perf_counter()
    with _span("place"):
        state = jax.block_until_ready(sp.place(state))
    setup["place_s"] = time.perf_counter() - t0
    check_split(state, devices)

    t0 = time.perf_counter()
    with _span("compile"):
        compiled = sp.lower_run(state, 0, chunk).compile()
    setup["compile_s"] = time.perf_counter() - t0
    if check_kernels and "tpu_custom_call" not in compiled.as_text():
        raise SystemExit("the compiled run program holds no Pallas kernel "
                         "(no tpu_custom_call)")
    del compiled

    n_cmp = int(cell.checks["compare_steps"])
    if n_cmp < 1 or n_cmp % chunk:
        raise SystemExit(f"compare_steps {n_cmp} is not a positive multiple "
                         f"of chunk_steps {chunk}")
    t0 = time.perf_counter()
    rasters = []
    with _span("warmup"):
        for t in range(0, n_cmp, chunk):
            state, raster, _ = jax.block_until_ready(sp.run(state, t, chunk))
            rasters.append(raster)
    setup["warmup_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    with _span("fetch"):
        n_syn = cfg.n_synapses
        got = fetch(sp.plan, state, rasters, cfg.n_neurons, n_syn)
        del raster, rasters
    setup["fetch_s"] = time.perf_counter() - t0
    setup_s = time.perf_counter() - T_START

    trace_dir = tempfile.mkdtemp(prefix="chip_bench_trace_") if trace \
        else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    t = n_cmp
    w0 = time.perf_counter()
    with _span("window"):
        while True:
            with _span("chunk"):
                state = jax.block_until_ready(sp.run(state, t, chunk))[0]
            t += chunk
            if time.perf_counter() - w0 >= seconds:
                break
    window_s = time.perf_counter() - w0
    if trace:
        jax.profiler.stop_trace()
    peak = peak_bytes(devices)
    del state, sp
    gc.collect()

    rec = Record(setup=setup, setup_s=setup_s, window_s=window_s,
                 steps=t - n_cmp, dt_ms=cell.config["izhikevich"]["dt"],
                 n_neurons=cfg.n_neurons, n_synapses=n_syn,
                 delay_slots=cfg.n_delay_slots, peak_bytes=peak,
                 device_kind=devices[0].device_kind, chips=len(devices))
    if trace:
        from chip_bench import trace_reduce
        try:
            rec.trace = trace_reduce.reduce(_xplane(trace_dir),
                                            len(devices))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    return rec, got


def _xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise SystemExit(f"expected one trace file, found {found}")
    return found[0]


def reference_outputs(cell: Cell, seed: int, steps: int,
                      precisions=("float64",)):
    """{precision: (Outputs, event counts)} of the plain reference over the
    first `steps` steps, w in the program's order."""
    conf = cell.config
    net = reference.make_network(conf["grid"], seed)
    order = reference.canonical_order(net)
    out = {}
    for p in precisions:
        r = reference.simulate(net, conf["grid"], cell.traffic,
                               conf["izhikevich"], conf["stdp"], seed, steps,
                               p)
        out[p] = (compare.Outputs(raster=r.raster, v=r.v, u=r.u,
                                  w=r.w[order]), r.counts)
    return out


def check(cell: Cell, seed: int, got: compare.Outputs):
    """Step 8: (correct, checks, reference counts)."""
    want, counts = reference_outputs(cell, seed,
                                     got.raster.shape[0])["float64"]
    ok, checks = compare.judge(compare.readings(got, want),
                               cell.checks["limits"])
    return ok, checks, counts


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices,
        check_kernels: bool = True) -> dict:
    """Steps 2-9 after the device check; returns the result object."""
    rec, got = simulate(cell, seed, seconds, trace, devices, check_kernels)
    steps = got.raster.shape[0] + rec.steps
    log(f"window done; host peak {host_peak_gib()} GiB")
    t0 = time.perf_counter()
    ok, checks, counts = check(cell, seed, got)
    check_s = time.perf_counter() - t0
    log(f"reference done in {check_s} s; host peak {host_peak_gib()} GiB")

    spikes = int(got.raster.sum())
    sim_s = got.raster.shape[0] * rec.dt_ms / 1000.0
    rate = spikes / (rec.n_neurons * sim_s)
    log(f"setup {json.dumps(rec.setup)} setup_s {rec.setup_s}")
    # equal digests on two layouts of one seed: the same bits (Table 1)
    log(f"compared outputs sha256 {got.digest()}")
    log(f"window {rec.window_s} s, {rec.steps} steps from step "
        f"{got.raster.shape[0]}; compared steps 0-{got.raster.shape[0] - 1}: "
        f"{spikes} spikes, rate {rate} Hz; reference counts "
        f"{json.dumps(counts)}; check {check_s} s")
    if spikes:
        # the paper's normalised figure, wall s per synapse per simulated
        # s per Hz, at the compared steps' rate (the window's is not read)
        norm = rec.window_s / (rec.n_synapses * rec.steps * rec.dt_ms
                               / 1000.0 * rate)
        log(f"paper-normalised {norm} s/(synapse sim-s Hz)")

    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = reader(kind, m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": rec.peak_bytes}
    result = {"correct": ok, "attempted": steps,
              "failed": 0 if ok else steps,
              "metrics": metrics, "device": device}
    if trace:
        from chip_bench import trace_reduce
        t = rec.trace
        device["busy_s"] = t.busy_s
        device["window_s"] = t.window_s
        result["breakdown"] = t.breakdown()
        exposed = t.exposed_per_device(trace_reduce.is_collective)
        log(f"per device: busy_s {t.busy_per_device}; exposed collective "
            f"s {list(exposed.values())}")
    result["checks"] = checks
    for k, c in checks.items():
        print(f"check {k} {c['value']} limit {c['limit']}", file=sys.stderr)
    print(f"correct {ok}", file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("--seed must be a whole number >= 0")
    cell = load_cell(args.workload)
    devices = require_chips(cell.chips)[:cell.chips]
    log(f"{args.workload} seed {args.seed} on {len(devices)} x "
        f"{devices[0].device_kind}")
    result = run(cell, args.seed, args.seconds, bool(args.trace), devices)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
