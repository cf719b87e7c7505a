"""Least bytes each kernel call and each whole step must move, from shapes.

The three Pallas kernels are elementwise: each element is read once and
written once, so the bytes are the sizes of their operands and results.
The kernels' wrapper lays an [n] array out as [R, 128] with R a multiple
of 8, and the kernel moves that padded block.  Their operations are a few
per element, far below the bytes' time at the chip's peak, so the bytes
bound them.

The whole step must at least read the synapse tables once and read and
write the per-synapse state once.  Per synapse those are the source,
target and delay (int32 each) and the plastic and valid flags (bool); and
the weight and last arrival (float32 each) and one arrival flag per delay
slot (bool).  The neuron arrays are 1/M of that and are left out.
"""
from __future__ import annotations

F32, BOOL, I32 = 4, 1, 4


def padded(n: int) -> int:
    """Elements of the [R, 128] block that holds n, R a multiple of 8."""
    rows = -(-n // 128)
    return -(-rows // 8) * 8 * 128


def izhikevich(n_neurons: int) -> int:
    """v, u, current, a, b, c, d in; v, u, spiked out."""
    return padded(n_neurons) * (7 * F32 + 2 * F32 + BOOL)


def stdp_arrival(n_synapses: int) -> int:
    """arrived, w, last_post[tgt], last_arr, plastic in (t is a scalar);
    w, last_arr, contribution out."""
    return padded(n_synapses) * (BOOL + 3 * F32 + BOOL + 3 * F32)


def stdp_ltp(n_synapses: int) -> int:
    """post[tgt], w, last_arr, plastic, valid in; w out."""
    return padded(n_synapses) * (BOOL + 2 * F32 + 2 * BOOL + F32)


def step(n_synapses: int, delay_slots: int) -> int:
    tables = 3 * I32 + 2 * BOOL
    state = 2 * F32 + delay_slots * BOOL
    return n_synapses * (tables + 2 * state)
