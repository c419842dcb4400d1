"""Share of its roofline the compressed-latent layers' prefix attention
reaches: the least time the chip could take for the causal pairs in the latent
(the cost model's ``mechanism_cost``: the pairs under the diagonal and no
other; q, k, v read once, the output written) over the kernel's device time.
The cost model counts every slot of a clip, the kernel skips the query tiles
past a clip's valid slots: the pairs being quadratic in the slots, at the
corpus' mean this reads up to 31 % high (the configuration's ``assumed``)."""

from benchmark.layer_metrics._kernels import kernel_roofline_share


def read(reading):
    return kernel_roofline_share(reading, "cca_attn_prefill", "cca_attn")
