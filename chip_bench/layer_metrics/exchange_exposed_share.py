"""Share of the traced window in which a device waits on the exchange: per
device, the time of its collective operations (trace_reduce.kind) that no
other operation on that device covers, over the window, averaged over the
cell's devices.  On the TPU that is mostly the async collectives' `-done`
halves; the part of a transfer that compute hides shows as compute, not
here.  A trace with no collective reads nothing."""
from chip_bench import trace_reduce


def read(rec):
    t = rec.trace
    if not t.calls(trace_reduce.is_collective):
        return None
    return 100.0 * t.exposed(trace_reduce.is_collective) / t.window_s
