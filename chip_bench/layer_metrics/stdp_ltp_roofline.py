"""The stdp_ltp Pallas kernel's least time over its device time: the bytes
each call moves (kernel_bytes.py) at the chip's peak bandwidth, times its
calls in the window, over its summed device time in the trace."""
from chip_bench import kernel_bytes, trace_reduce


def read(rec):
    t = rec.trace

    def mine(o):
        return trace_reduce.kernel_of(o) == "stdp_ltp"

    s = t.seconds(mine)
    if s <= 0:
        return None
    least = t.calls(mine) * kernel_bytes.stdp_ltp(rec.n_synapses) \
        / rec.peak("hbm_bytes_per_s")
    return 100.0 * least / s
