"""Pallas TPU kernels: fused STDP passes over the synapse array.

Two kernels, both elementwise over the flat [E] synapse dimension (tiled
(8, 128) fp32).  The companion gathers (last_post[tgt], spiked[tgt],
spiked_src[src]) are XLA HBM gathers — cheap and already fused by XLA; the
win here is collapsing the 6-8 elementwise HBM round-trips of the jnp path
into one VMEM pass each (see EXPERIMENTS.md §Perf for the roofline math).

  arrival kernel (step phase 3+2): given this step's arrival flags,
      apply LTD (nearest post spike), refresh last_arrival, and emit the
      per-synapse current contribution to be segment-summed by target.

  ltp kernel (step phase 6): given post-spike flags gathered onto
      synapses, apply LTP against last_arrival.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _arrival_kernel(arr_ref, w_ref, lp_ref, la_in_ref, plastic_ref, t_ref,
                     wout_ref, la_ref, contrib_ref, *,
                     a_minus: float, tau_minus: float, w_min: float,
                     w_max: float, neg_time: float):
    arr = arr_ref[...]
    w = w_ref[...]
    lp = lp_ref[...]
    t = t_ref[0]

    ltd = jnp.float32(a_minus) * jnp.exp((lp - t) / jnp.float32(tau_minus))
    apply = arr & plastic_ref[...] & (lp > jnp.float32(neg_time / 2))
    wout_ref[...] = jnp.where(
        apply, jnp.clip(w - ltd, jnp.float32(w_min), jnp.float32(w_max)), w)
    la_ref[...] = jnp.where(arr, t, la_in_ref[...])
    contrib_ref[...] = jnp.where(arr, w, 0.0)


def _ltp_kernel(post_ref, w_ref, la_ref, plastic_ref, valid_ref, t_ref,
                wout_ref, *, a_plus: float, tau_plus: float, w_min: float,
                w_max: float, neg_time: float):
    post = post_ref[...]
    w = w_ref[...]
    la = la_ref[...]
    t = t_ref[0]

    ltp = jnp.float32(a_plus) * jnp.exp((la - t) / jnp.float32(tau_plus))
    apply = post & plastic_ref[...] & valid_ref[...] \
        & (la > jnp.float32(neg_time / 2))
    wout_ref[...] = jnp.where(
        apply, jnp.clip(w + ltp, jnp.float32(w_min), jnp.float32(w_max)), w)


def stdp_arrival(arr, w, last_post_g, last_arr, plastic, t, *,
                 a_minus, tau_minus, w_min, w_max, neg_time,
                 block_rows: int = 8, interpret: bool = False):
    """All array inputs [R, 128]; t is a [1] fp32 array.

    Returns (w', last_arr', contrib)."""
    R = w.shape[0]
    grid = (pl.cdiv(R, block_rows),)
    spec = pl.BlockSpec((block_rows, 128), lambda i: (i, 0))
    tspec = pl.BlockSpec((1,), lambda i: (0,))
    kern = functools.partial(_arrival_kernel, a_minus=a_minus,
                             tau_minus=tau_minus, w_min=w_min, w_max=w_max,
                             neg_time=neg_time)
    return pl.pallas_call(
        kern, grid=grid,
        in_specs=[spec, spec, spec, spec, spec, tspec],
        out_specs=(spec, spec, spec),
        out_shape=(jax.ShapeDtypeStruct(w.shape, jnp.float32),
                   jax.ShapeDtypeStruct(w.shape, jnp.float32),
                   jax.ShapeDtypeStruct(w.shape, jnp.float32)),
        interpret=interpret,
        name="stdp_arrival",
    )(arr, w, last_post_g, last_arr, plastic, t)


def stdp_ltp(post_g, w, last_arr, plastic, valid, t, *,
             a_plus, tau_plus, w_min, w_max, neg_time,
             block_rows: int = 8, interpret: bool = False):
    """All array inputs [R, 128]; t is a [1] fp32 array.  Returns w'."""
    R = w.shape[0]
    grid = (pl.cdiv(R, block_rows),)
    spec = pl.BlockSpec((block_rows, 128), lambda i: (i, 0))
    tspec = pl.BlockSpec((1,), lambda i: (0,))
    kern = functools.partial(_ltp_kernel, a_plus=a_plus, tau_plus=tau_plus,
                             w_min=w_min, w_max=w_max, neg_time=neg_time)
    return pl.pallas_call(
        kern, grid=grid,
        in_specs=[spec, spec, spec, spec, spec, tspec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(w.shape, jnp.float32),
        interpret=interpret,
        name="stdp_ltp",
    )(post_g, w, last_arr, plastic, valid, t)
