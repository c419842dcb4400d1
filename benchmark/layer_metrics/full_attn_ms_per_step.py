"""Device milliseconds a step (a decoded batch) in the full layers' prefix
attention: the self time of the kernel ``full_attn_prefill``'s operations in
the traced stretch (the causal grouped-query flash kernel: a key/value head's
query heads share each key tile's copy, tiles past the diagonal are skipped;
the decode steps' one query a lane over the prefix keys run as compiled
operations without a name of their own and are not in it). A program without
the kernel reads None."""

from benchmark.layer_metrics._kernels import kernel_ms_per_step


def read(reading):
    return kernel_ms_per_step(reading, "full_attn_prefill")
