"""Dense delivery's target windows: `engine.expand_to_synapses` gives each
neuron's value over its incoming synapses from E/B x W gathered values,
where the tables allow, and must equal the plain gather `x[syn_tgt]` bit
for bit; where they do not, the plain gather stays.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EngineConfig, GridConfig
from repro.core import connectivity as C
from repro.core import engine as E

# 16,000 synapses over 400 neurons; at 2 and 4 shards E is not a whole
# number of blocks
SMALL = GridConfig(grid_x=2, grid_y=2, neurons_per_column=100,
                   synapses_per_neuron=40, seed=7)
# 1 synapse a neuron: a block of 128 spans about 130 targets
SPARSE = GridConfig(grid_x=2, grid_y=2, neurons_per_column=100,
                    synapses_per_neuron=1, seed=3)
# 4 synapses a neuron over 600 targets: a dozen receive none
LOW_IN = GridConfig(grid_x=3, grid_y=2, neurons_per_column=100,
                    synapses_per_neuron=4, seed=11)
# tables padded to whole (8, 128) tiles: a whole number of blocks, and a
# padded tail on every shard of these configs
TILE = 1024


def _tables(cfg, H, blocks=True):
    tabs = C.build_all_shards(cfg, EngineConfig(n_shards=H))
    if not blocks:
        return tabs
    e_cap = -(-tabs[0].src_idx.shape[0] // TILE) * TILE
    return [C.repad_shard(t, e_cap, t.src_gid.shape[0]) for t in tabs]


def _build(cfg, H, blocks=True):
    return E.build(cfg, EngineConfig(n_shards=H),
                   tables=_tables(cfg, H, blocks))


def _plain(spec):
    return spec._replace(tgt_block=None, tgt_window=None)


def _values(spec, dtype, seed=0):
    rng = np.random.default_rng(seed)
    shape = (spec.eng.n_shards, spec.n_local)
    if dtype == "bool":
        return jnp.asarray(rng.random(shape) < 0.3)
    x = rng.normal(-50.0, 30.0, shape).astype(np.float32)
    x[rng.random(shape) < 0.4] = E.NEG_TIME
    return jnp.asarray(x)


def _expand(spec, plan, x):
    return np.asarray(jax.jit(jax.vmap(
        lambda p, v: E.expand_to_synapses(spec, p, v)))(plan, x))


def _gather(plan, x):
    return np.asarray(jax.vmap(lambda p, v: v[p.syn_tgt])(plan, x))


def _same_bits(a, b):
    assert a.dtype == b.dtype and a.shape == b.shape
    if a.dtype == np.float32:
        a, b = a.view(np.uint32), b.view(np.uint32)
    np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("dtype", ["f32", "bool"])
@pytest.mark.parametrize("H", [1, 2, 4])
def test_window_expansion_equals_gather(H, dtype):
    spec, plan, _ = _build(SMALL, H)
    assert spec.tgt_block == E.TGT_BLOCK and 1 < spec.tgt_window <= 64
    valid = np.asarray(plan.syn_valid)
    assert (~valid).any(axis=1).all(), "every shard has a padded tail"
    x = _values(spec, dtype, seed=H)
    _same_bits(_expand(spec, plan, x), _gather(plan, x))


def test_window_takes_a_block_in_any_order():
    spec, plan, _ = _build(SMALL, 1)
    rng = np.random.default_rng(1)
    tgt = np.asarray(plan.syn_tgt).reshape(-1, E.TGT_BLOCK)
    tgt = rng.permuted(tgt, axis=1).reshape(1, -1)
    plan = plan._replace(syn_tgt=tgt)
    spec = spec._replace(tgt_window=E.tgt_windows(tgt))
    assert spec.tgt_window is not None
    x = _values(spec, "f32")
    _same_bits(_expand(spec, plan, x), _gather(plan, x))


@pytest.mark.parametrize("dtype", ["f32", "bool"])
def test_window_skips_neurons_with_no_incoming_synapse(dtype):
    spec, plan, _ = _build(LOW_IN, 1)
    tgt = np.asarray(plan.syn_tgt)[0][np.asarray(plan.syn_valid)[0]]
    assert np.bincount(tgt, minlength=spec.n_local).min() == 0
    assert spec.tgt_window is not None
    x = _values(spec, dtype)
    _same_bits(_expand(spec, plan, x), _gather(plan, x))


@pytest.mark.parametrize("cfg, blocks", [(SPARSE, True), (SMALL, False)],
                         ids=["window_over_cap", "e_not_whole_blocks"])
def test_fallback_keeps_the_plain_gather(cfg, blocks):
    spec, plan, _ = _build(cfg, 2, blocks)
    assert spec.tgt_block is None and spec.tgt_window is None
    x = _values(spec, "f32")
    _same_bits(_expand(spec, plan, x), _gather(plan, x))


def test_windows_span_each_block_from_its_lowest_to_its_highest_target():
    tgt = np.repeat(np.arange(8, dtype=np.int32), E.TGT_BLOCK // 8)[None]
    assert E.tgt_windows(tgt) == 8
    shuffled = np.random.default_rng(0).permutation(tgt[0])[None]
    assert E.tgt_windows(shuffled) == 8
    assert E.tgt_windows(tgt[:, :-8]) is None
    wide = tgt.copy()
    wide[0, 0] = E.TGT_WINDOW_CAP + 7
    assert E.tgt_windows(wide) is None


@pytest.mark.parametrize("H", [1, 2, 4])
def test_windowed_run_matches_the_plain_run(H):
    spec, plan, state = _build(SMALL, H)
    assert spec.tgt_window is not None
    got = E.run(spec, plan, state, 0, 30)
    want = E.run(_plain(spec), plan, state, 0, 30)
    raster = np.asarray(got[1])
    assert raster.sum() > 0
    np.testing.assert_array_equal(raster, np.asarray(want[1]))
    for name in ("w", "v", "u", "last_post", "last_arr"):
        _same_bits(np.asarray(getattr(got[0], name)),
                   np.asarray(getattr(want[0], name)))


def _gathers_over_synapses(spec, plan, state):
    """Gathers of the compiled dense step that take one index a synapse
    (each gathers one element an index, so the result has H x E)."""
    txt = jax.jit(E.make_step_fn(spec, plan)).lower(
        state, jnp.int32(0)).compile().as_text()
    n = spec.eng.n_shards * spec.e_cap
    shapes = re.findall(r"= \w+\[([\d,]*)\]\S* gather\(", txt)
    return sum(int(np.prod([int(d) for d in s.split(",") if d])) == n
               for s in shapes)


@pytest.mark.parametrize("window", [True, False], ids=["window", "plain"])
def test_step_keeps_only_the_source_side_e_wide_gather(window):
    spec, plan, state = _build(SMALL, 2)
    assert spec.tgt_window is not None
    if not window:
        spec = _plain(spec)
    assert _gathers_over_synapses(spec, plan, state) == (1 if window else 3)


@pytest.mark.parametrize("blocks", [False, True],
                         ids=["build_all_shards", "repad_shard"])
@pytest.mark.parametrize("H", [2, 4])
def test_padded_tail_of_tgt_local_repeats_the_last_target(H, blocks):
    tabs = _tables(SMALL, H, blocks)
    assert any(t.n_valid < t.tgt_local.shape[0] for t in tabs)
    for t in tabs:
        assert (np.diff(t.tgt_local) >= 0).all()
        assert (t.tgt_local[t.n_valid:] == t.tgt_local[t.n_valid - 1]).all()


_SHARD_MAP_CODE = """
import sys
import numpy as np
sys.path.insert(0, {tests!r})
from test_delivery_window import SMALL, _plain, _tables
from repro.core import EngineConfig, StepProgram, engine as E
from repro.core import distributed as D

eng = EngineConfig(n_shards=2, exchange="halo")
spec, plan, state = E.build(SMALL, eng, tables=_tables(SMALL, 2))
assert spec.tgt_window is not None
ref = StepProgram.from_parts(_plain(spec), plan, state0=state)
_, raster_ref, _ = ref.run(ref.place(state), 0, 30)
sp = StepProgram.from_parts(spec, plan, state0=state, mesh=D.make_mesh(2))
st, raster, _ = sp.run(sp.place(state), 0, 30)
assert np.asarray(raster).sum() > 0
assert np.array_equal(np.asarray(raster), np.asarray(raster_ref))
print("OK")
"""


def test_shard_map_window_run_matches_the_plain_reference():
    """The dense shard_map program (one shard a device) takes the windowed
    path from the same spec and reproduces the plain vmap driver."""
    from _mp_helpers import run_with_devices
    tests = os.path.dirname(os.path.abspath(__file__))
    out = run_with_devices(_SHARD_MAP_CODE.format(tests=tests), 2)
    assert "OK" in out
