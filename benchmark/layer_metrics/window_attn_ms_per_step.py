"""Device milliseconds a step (a decoded batch) in the window layers' prefix
attention: the self time of the kernel ``window_attn_prefill``'s operations in
the traced stretch (the flash kernel that walks, for a tile of queries, only
the key tiles its band of ``sliding_window`` positions reaches, the sink in
the denominator; the decode steps' one query a lane run as compiled
operations without a name of their own and are not in it). A program without
the kernel (this metric's parent commit, a configuration without window
layers) has no such operation and reads None."""

from benchmark.layer_metrics._kernels import kernel_ms_per_step


def read(reading):
    return kernel_ms_per_step(reading, "window_attn_prefill")
