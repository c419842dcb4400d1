"""Hand-written TPU kernels (Pallas) for the hot ops.

XLA's fusions cover this model well (SURVEY.md §2: "the TPU build's native
layer is XLA itself plus optional Pallas kernels"); this package holds the
optional kernels where explicit VMEM blocking beats the default:

- the weight-stationary fused decode step (attention + LSTM stack + output
  projection in one launch, ``model.decode_impl="pallas"`` — README
  "Decode fast path");
- the vocab-sharded stride/beam variants for flagship-XL model parallelism
  (ops/decode_mp.py — README "Model parallelism").
"""

from cst_captioning_tpu.ops.decode_mp import mp_beam_step, mp_decode_stride
from cst_captioning_tpu.ops.decode_pallas import fused_decode_step

__all__ = [
    "fused_decode_step",
    "mp_beam_step",
    "mp_decode_stride",
]
