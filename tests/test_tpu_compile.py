"""Compile the SNN kernels and the step program for a described TPU v5e.

Nothing runs: the TPU compiler, installed beside the CPU backend, compiles
for a chip that is described and not attached, and refuses what the chip
would refuse (tiling, VMEM, HBM).  The topology is described inside a
module-scoped fixture, never at import: only one process may load the TPU
library, and test workers import every test file.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import EngineConfig, GridConfig, StepProgram
from repro.core.engine import NEG_TIME, SCOPES
from repro.core.params import DEFAULT_IZH as IZH
from repro.core.params import DEFAULT_STDP as STDP
from repro.kernels import ops

HBM_BYTES = 16 * 10**9                 # one v5e chip
# one 24x24 shard at the paper's widths (1000 neurons/column, M = 200)
E_24 = 24 * 24 * 1000 * 200            # 115.2 M synapses = 900,000 x 128
N_24 = 24 * 24 * 1000                  # 576,000 neurons = 4,500 x 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs go to /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described-chip compile written to the persistent cache cannot be
    # read back without the chip; keep the cache out of these tests
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_for_chip(jitted, *args):
    compiled = jitted.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Pallas kernel"
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, f"{total} B does not fit one chip"
    return compiled


def _izhikevich(sh):
    f = functools.partial(ops.izhikevich_update, v_peak=IZH.v_peak,
                          dt=IZH.dt, substeps=IZH.v_substeps,
                          use_pallas=True)
    return f, [_sds((N_24,), jnp.float32, sh)] * 7


def _stdp_arrival(sh):
    f = functools.partial(ops.stdp_arrival, a_minus=STDP.a_minus,
                          tau_minus=STDP.tau_minus, w_min=STDP.w_min,
                          w_max=STDP.w_max, neg_time=float(NEG_TIME),
                          use_pallas=True)
    b, x = _sds((E_24,), jnp.bool_, sh), _sds((E_24,), jnp.float32, sh)
    return f, [b, x, x, x, b, _sds((), jnp.float32, sh)]


def _stdp_ltp(sh):
    f = functools.partial(ops.stdp_ltp, a_plus=STDP.a_plus,
                          tau_plus=STDP.tau_plus, w_min=STDP.w_min,
                          w_max=STDP.w_max, neg_time=float(NEG_TIME),
                          use_pallas=True)
    b, x = _sds((E_24,), jnp.bool_, sh), _sds((E_24,), jnp.float32, sh)
    return f, [b, x, x, b, b, _sds((), jnp.float32, sh)]


@pytest.mark.parametrize("make", [_izhikevich, _stdp_arrival, _stdp_ltp],
                         ids=["izhikevich", "stdp_arrival", "stdp_ltp"])
def test_kernel_compiles_at_paper_shard_width(monkeypatch, one_chip, make):
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    fn, args = make(one_chip)
    _compile_for_chip(jax.jit(fn), *args)


@pytest.fixture(scope="module")
def fused_4x4(one_chip):
    """StepProgram's own fused step, 4x4 at paper widths, compiled for the
    chip once the backend reads as a TPU."""
    sp = StepProgram(GridConfig(grid_x=4, grid_y=4), EngineConfig())
    shapes = jax.tree.map(
        lambda x: _sds(np.shape(x), np.asarray(x).dtype, one_chip),
        (sp.planT, sp.init_state()))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_on_tpu", lambda: True)
        return _compile_for_chip(sp.fused, *shapes,
                                 _sds((), jnp.int32, one_chip))


def test_fused_step_compiles_with_kernels(fused_4x4):
    """The kernels enter the fused step, which fits one chip
    (_compile_for_chip checks both)."""
    assert "tpu_custom_call" in fused_4x4.as_text()


def test_fused_step_names_its_kernels_and_scopes(fused_4x4):
    """Each Pallas kernel is an instruction named after it, so the device
    trace names its events so; each name of engine.SCOPES reaches the op
    metadata that joins the trace to the program's phases."""
    text = fused_4x4.as_text()
    for kernel in ("izhikevich", "stdp_arrival", "stdp_ltp"):
        assert re.search(rf"%{kernel}(\.\d+)? = .*custom-call\(", text), \
            kernel
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    missing = [s for s in SCOPES
               if not any(re.search(rf"(?<!\w){s}(?!\w)", o)
                          for o in op_names)]
    assert missing == []
