"""Hand-written Pallas TPU kernels for the framework's hot ops.

The reference consumes its fused kernels from cudnn/ATen binaries
(SURVEY.md §2.3); here they are in-repo, written against the TPU memory
hierarchy (HBM→VMEM pipelines, MXU matmuls, VPU elementwise), with
interpreter-mode fallback so the same kernels run in CPU tests.
"""

from tpudist.ops.pallas.flash_attention import (  # noqa: F401
    flash_attention, flash_attention_laid, flash_attention_qkv,
    flash_attention_spmd)
from tpudist.ops.pallas.mla_attention import (  # noqa: F401,E402
    flash_attention_latent, flash_attention_latent_laid)
