#!/usr/bin/env bash
# Launcher (reference start.sh, TPU-native).
#
# The reference's three invocations map to:
#   1) DataParallel  (start.sh:2)  → single-host SPMD over local chips:
#        python -m tpudist --outpath ./output_dp
#   2) DDP           (start.sh:3)  → identical program; on a TPU pod run it
#        once per host (no torch.distributed.launch — the TPU runtime knows
#        the slice topology):
#        TPUDIST_COORDINATOR=$COORD:8476 python -m tpudist --distributed \
#            --outpath ./output_ddp
#   3) DDP+amp+SyncBN (start.sh:4) →
#        python -m tpudist --use_amp --sync_batchnorm --outpath ./output_amp_syncbn
#
# On Cloud TPU pods, each host launches the same command (e.g. via
# `gcloud compute tpus tpu-vm ssh --worker=all --command=...`); coordinator
# address/process counts are discovered from the TPU metadata by
# jax.distributed.initialize when flags are omitted.

set -euo pipefail
# Build the native data-transform kernels before jax starts (the import path
# itself never builds — it only loads). A failed build stops the launch:
# training on the PIL path is a different input pipeline, not a detail.
make -s -C "$(dirname "$0")/../native"
exec python -m tpudist "$@"
