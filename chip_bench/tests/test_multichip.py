"""Tests of the benchmark's four-chip path, on the CPU with four forced host
devices.

    JAX_PLATFORMS=cpu python -m pytest -q chip_bench/tests

JAX fixes its device count when it starts, so one child process with
`XLA_FLAGS=--xla_force_host_platform_device_count=4` drives every run
below and prints what each read; the tests judge those readings.  The cell
is `ring3_12x12_h4.paper_drive` cut to a 2x2 grid (one column a shard, 4
shards on a `cells` mesh of the 4 devices, halo wire, pipelined schedule,
every width as the cell has it).  They cover: a sound run reads `correct`
true; its fetched raster, v, u and w equal the one-shard run's of the
same seed, bit for bit; each fault a four-chip cell can have (two shards'
rows swapped in the fetch, the exchange between chips left out, a step
that returns its state unchanged, a spike altered where it is produced)
reads `correct` false; and a cell whose shards are not one per chip, or
whose placement is not block, exits before anything is built.
"""
from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from repro.bench.subproc import run_subprocess  # noqa: E402

CELL = "ring3_12x12_h4.paper_drive"
ONE_CHIP = "ring3_12x12.paper_drive"
FAULTS = ("rows_swapped_in_fetch", "exchange_between_chips_left_out",
          "state_unchanged", "spike_altered_where_produced")

CHILD = r'''
import contextlib, json, sys
sys.path[:0] = [ROOT, ROOT + "/src"]
import jax, jax.numpy as jnp, numpy as np
from chip_bench import run
from repro.core import aer, distributed

SEED = 2**31 + 977
devices = jax.devices("cpu")
assert len(devices) == 4, devices


def small(name, **engine):
    cell = run.load_cell(name)
    cell.config["grid"].update(grid_x=2, grid_y=2)
    cell.config["engine"].update(engine)
    cell.checks["compare_steps"] = 8
    return cell


fetched = {}
orig_fetch = run.fetch


def keep(plan, state, rasters, n, e):
    got = orig_fetch(plan, state, rasters, n, e)
    fetched["last"] = got
    return got


def swap01(x):
    x = np.asarray(x)
    return x[[1, 0] + list(range(2, x.shape[0]))]


def rows_swapped(plan, state, rasters, n, e):
    state = state._replace(v=swap01(state.v), u=swap01(state.u),
                           w=swap01(state.w))
    rasters = [np.asarray(r)[:, [1, 0, 2, 3]] for r in rasters]
    return keep(plan, state, rasters, n, e)


def no_remote(x, axis_name, perm):
    return jnp.full_like(x, aer.INVALID)


def wrap_program(make_bad):
    orig = distributed.make_run_program

    def make(*a, **k):
        prog = orig(*a, **k)
        return prog._replace(run=make_bad(prog.run))
    return ("distributed", "make_run_program", make)


def unchanged(orig_run):
    def bad(state, t0, n):
        _, raster, tm = orig_run(state, t0, n)
        return state, raster, tm
    return bad


def flipped(orig_run):
    def bad(state, t0, n):
        state, raster, tm = orig_run(state, t0, n)
        return state, raster.at[0, 0, 0].set(~raster[0, 0, 0]), tm
    return bad


PATCHES = {
    None: ("run", "fetch", keep),
    "rows_swapped_in_fetch": ("run", "fetch", rows_swapped),
    "exchange_between_chips_left_out": ("lax", "ppermute", no_remote),
    "state_unchanged": wrap_program(unchanged),
    "spike_altered_where_produced": wrap_program(flipped),
}
MODULES = {"run": run, "distributed": distributed, "lax": jax.lax}


@contextlib.contextmanager
def planted(fault):
    patches = [PATCHES[None]] + ([PATCHES[fault]] if fault else [])
    saved = []
    for mod, attr, new in patches:
        saved.append((MODULES[mod], attr, getattr(MODULES[mod], attr)))
        setattr(MODULES[mod], attr, new)
    try:
        yield
    finally:
        for m, attr, old in reversed(saved):
            setattr(m, attr, old)


out = {}
for fault in [None] + list(FAULTS):
    with planted(fault):
        res = run.run(small(CELL), SEED, 0.2, False, devices,
                      check_kernels=False)
    out[str(fault)] = {"correct": res["correct"], "checks": res["checks"],
                       "count": res["device"]["count"]}
    if fault is None:
        four = fetched["last"]

with planted(None):
    res = run.run(small(ONE_CHIP), SEED, 0.2, False, devices[:1],
                  check_kernels=False)
one = fetched["last"]
out["one_shard"] = {"correct": res["correct"], "checks": res["checks"]}
out["equal"] = {k: bool(np.array_equal(getattr(four, k), getattr(one, k)))
                for k in ("raster", "v", "u", "w")}
out["shapes"] = {k: list(getattr(four, k).shape)
                 for k in ("raster", "v", "u", "w")}
out["spikes"] = int(four.raster.sum())

built = []
run._program = lambda *a, **k: built.append(1)
refused = {}
for what, cell in (("shards_not_chips", small(CELL, n_shards=2)),
                   ("placement_scatter", small(CELL, placement="scatter"))):
    try:
        run.run(cell, SEED, 0.2, False, devices, check_kernels=False)
        refused[what] = None
    except SystemExit as e:
        refused[what] = str(e)
out["refused"] = refused
out["built"] = len(built)
print("RESULT " + json.dumps(out))
'''


@pytest.fixture(scope="module")
def child():
    code = (f"ROOT = {ROOT!r}\nCELL = {CELL!r}\nONE_CHIP = {ONE_CHIP!r}\n"
            f"FAULTS = {FAULTS!r}\n" + CHILD)
    out = run_subprocess(code, 4, timeout=900)
    (line,) = [s for s in out.splitlines() if s.startswith("RESULT ")]
    return json.loads(line[len("RESULT "):])


def test_four_shard_run_is_correct(child):
    sound = child["None"]
    assert sound["correct"], sound["checks"]
    assert sound["count"] == 4
    assert sound["checks"]["raster_mismatch"]["value"] == 0
    assert child["spikes"] > 0


def test_four_shard_fetch_equals_one_shard_bit_for_bit(child):
    assert child["one_shard"]["correct"], child["one_shard"]["checks"]
    assert child["equal"] == {"raster": True, "v": True, "u": True,
                              "w": True}
    assert child["shapes"]["raster"] == [8, 4000]
    assert child["shapes"]["w"] == [4000 * 200]
    # the same bits give the same compared numbers, digit for digit
    assert child["one_shard"]["checks"] == child["None"]["checks"]


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_fault_is_not_correct(child, fault):
    assert child[fault]["correct"] is False, child[fault]["checks"]


@pytest.mark.parametrize("what,says", [("shards_not_chips", "one shard per"),
                                       ("placement_scatter", "block")])
def test_layout_refused_before_building(child, what, says):
    assert child["refused"][what] is not None
    assert says in child["refused"][what]
    assert child["built"] == 0
