"""Share of device busy time in gathers and scatters: the dense delivery's
E-wide gathers (last_post[tgt], spiked[tgt], spiked_src[src]) and the
scatter-add behind segment_sum, with the small ones of the stimulus and
the spike mask.  The trace shows them as kCustom fusions over s32 indices
(trace_reduce.kind)."""
from chip_bench import trace_reduce


def read(rec):
    t = rec.trace
    s = t.seconds(lambda o: trace_reduce.kind(o) == "gather_scatter")
    return 100.0 * s / t.busy_s if s > 0 else None
