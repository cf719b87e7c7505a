"""The whole step's least time over the traced window: the bytes a dense
step must move (kernel_bytes.step) at the peak bandwidth of the cell's
chips together, times the window's steps, over the window.  It bounds
what the kernels' shares can claim end to end."""
from chip_bench import kernel_bytes


def read(rec):
    least = rec.steps * kernel_bytes.step(rec.n_synapses, rec.delay_slots) \
        / (rec.chips * rec.peak("hbm_bytes_per_s"))
    return 100.0 * least / rec.trace.window_s
