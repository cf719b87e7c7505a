"""Pallas TPU kernel: fused Izhikevich neuron update.

Fuses the two membrane half-steps, the recovery-variable step, spike
detection, and the post-spike reset into a single VMEM pass (the jnp path
materializes ~8 intermediates in HBM).  Elementwise, VPU-only; tiles are
(8, 128)-aligned fp32.

Layout: the ops wrapper reshapes the [N] neuron arrays to [N/128, 128]
(padded), so the kernel sees 2-D refs as the TPU vector unit wants.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(v_ref, u_ref, i_ref, a_ref, b_ref, c_ref, d_ref,
            vout_ref, uout_ref, spk_ref, *, v_peak: float, dt: float,
            substeps: int):
    v = v_ref[...]
    u = u_ref[...]
    cur = i_ref[...]
    a, b, c, d = a_ref[...], b_ref[...], c_ref[...], d_ref[...]

    h = jnp.float32(dt / substeps)
    for _ in range(substeps):
        v = v + h * (0.04 * v * v + 5.0 * v + 140.0 - u + cur)
    u = u + jnp.float32(dt) * a * (b * v - u)

    spiked = v >= jnp.float32(v_peak)
    vout_ref[...] = jnp.where(spiked, c, v)
    uout_ref[...] = jnp.where(spiked, u + d, u)
    spk_ref[...] = spiked


def izhikevich_update(v, u, current, a, b, c, d, *, v_peak: float,
                      dt: float = 1.0, substeps: int = 2,
                      block_rows: int = 8, interpret: bool = False):
    """All inputs [R, 128] fp32; returns (v', u', spiked)."""
    R = v.shape[0]
    grid = (pl.cdiv(R, block_rows),)
    spec = pl.BlockSpec((block_rows, 128), lambda i: (i, 0))
    import functools
    kern = functools.partial(_kernel, v_peak=v_peak, dt=dt,
                             substeps=substeps)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[spec] * 7,
        out_specs=(spec, spec, spec),
        out_shape=(jax.ShapeDtypeStruct(v.shape, jnp.float32),
                   jax.ShapeDtypeStruct(v.shape, jnp.float32),
                   jax.ShapeDtypeStruct(v.shape, jnp.bool_)),
        interpret=interpret,
        name="izhikevich",
    )(v, u, current, a, b, c, d)
