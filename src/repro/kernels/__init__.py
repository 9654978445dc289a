"""Pallas TPU kernels for FibecFed's compute hot-spots.

Each kernel module pairs with an oracle in :mod:`repro.kernels.ref` and a
jit'd public wrapper in :mod:`repro.kernels.ops`. Interpret mode follows the
platform: compiled Mosaic on a TPU backend, the Pallas interpreter on CPU
(where the tests sweep shapes/dtypes against the oracles).

Kernels:

- ``fisher_diag`` — fused momentum diag-FIM update (FIM warmup loop);
- ``sparse_lora`` — row-sparse (neuron-masked) LoRA apply;
- ``flash_attention`` — GQA flash attention;
- ``ssd_chunk`` — intra-chunk SSD scan;
- ``masked_update`` — fused masked SGD-momentum / AdamW optimizer step:
  reads each (param, grad, mask, moments) tile once and writes
  (new_param, new_moments) once, folding grad masking, the moment update,
  bias correction, weight decay, and the per-step ``active`` no-op predicate
  into a single pass with frozen-neuron semantics (masked entries keep
  parameter AND moments bit-for-bit). Wired in behind
  ``repro.optim.make_optimizer(..., fused=True)``.
"""
from repro.kernels.ops import (
    fisher_diag_update,
    sparse_lora_apply,
    flash_attention,
    ssd_chunk_intra,
    masked_sgd_update,
    masked_adamw_update,
)
