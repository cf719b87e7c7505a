"""Share of device busy time in gathers and scatters: the dense delivery's
one E-wide gather (spiked_src[src]), the two windowed expansions of
last_post and spiked over the target-sorted synapses (each a gather of
W-wide slices, one per 128-synapse block), the scatter-add behind
segment_sum, and the small ones of the stimulus and the spike mask.  The
trace shows them as kCustom fusions over s32 indices (trace_reduce.kind)."""
from chip_bench import trace_reduce


def read(rec):
    t = rec.trace
    s = t.seconds(lambda o: trace_reduce.kind(o) == "gather_scatter")
    return 100.0 * s / t.busy_s if s > 0 else None
