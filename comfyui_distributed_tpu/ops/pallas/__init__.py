"""Pallas TPU kernels for the hot ops.

The reference has no custom kernels (its compute is entirely ComfyUI's torch
stack); these exist because the UNet's attention is the dominant non-conv
cost on TPU and a fused VMEM-resident kernel avoids materializing the
[N, N] attention matrix in HBM, and because a language model's decode step
of a few rows is bound by how its weights are streamed
(``fewrow_dense.fewrow_dense``, imported where it is used: its name here
would hide the module), and because the rows an expert's tile adds to a
sum in HBM each wait out the memory's latency unless all are in flight
at once (``row_scatter_add.row_scatter_add``, imported the same way).
"""

from comfyui_distributed_tpu.ops.pallas.flash_attention import (  # noqa: F401
    flash_attention,
)
