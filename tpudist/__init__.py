"""tpudist — TPU-native (JAX/XLA/pjit/shard_map) distributed training framework.

A ground-up rebuild of the capabilities of the reference PyTorch template
(xiezheng-cs/PyTorch_Distributed_Template, mounted at /root/reference): ImageNet
classifier training with data-parallel SPMD execution, bf16 mixed precision and
cross-replica (sync) batch normalization. The reference's four recipes
(dataparallel.py, distributed.py, distributed_syncBN_amp.py and its two flag
states) collapse into configurations of ONE SPMD trainer, because on TPU the
DataParallel/DDP distinction does not exist: XLA SPMD over a `jax.sharding.Mesh`
is always "DDP", and AMP / SyncBN are flags (bf16 compute policy; `lax.pmean`
over batch-norm statistics) exactly as they are flags in the reference
(`distributed_syncBN_amp.py:74-75`).

Package map (see SURVEY.md §7 for the reference-to-layer correspondence):

- ``config``    — typed run config + argparse surface (reference C1/C12).
- ``dist``      — runtime/mesh init, process-role helpers, ``reduce_mean``
                  (reference C5/C9's torch.distributed/NCCL layer).
- ``utils``     — logging, meters, experiment dirs (reference C10-C13, C17).
- ``ops``       — jnp/Pallas numerics: accuracy, losses (reference C14).
- ``models``    — flax model zoo with a by-name registry (reference C3) and a
                  torch-semantics BatchNorm with optional cross-replica axis.
- ``parallel``  — mesh/sharding rules, ring attention / sequence parallelism.
- ``data``      — ImageFolder-compatible input pipeline with per-host sharding
                  (reference C7: ImageFolder + DistributedSampler + DataLoader).
- ``train``     — compiled train/eval steps (SGD+momentum+wd, MultiStepLR,
                  bf16 policy, grad pmean) (reference C4-C6, C8).
- ``trainer``   — epoch driver: meters, TB scalars, checkpoint/best/resume
                  (reference C15, C16 + the resume path the reference lacks).
- ``checkpoint``— topology-independent pytree checkpointing (reference C15).
"""

__version__ = "0.1.0"

# NOTE: keep this module jax-free — the launcher/supervisor process
# (tpudist.launch) imports the package but must not pay a jax import (or
# die on a broken jax install) just to supervise ranks.
from tpudist.config import Config  # noqa: F401
