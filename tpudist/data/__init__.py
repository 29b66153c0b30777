"""Input pipeline (reference C7: ImageFolder + transforms + DataLoader +
DistributedSampler, ``distributed.py:156-179``)."""

from tpudist.data.imagefolder import ImageFolder                     # noqa: F401
from tpudist.data.synthetic import (SyntheticDataset,  # noqa: F401
                                    SyntheticTokens)
from tpudist.data.sampler import ShardedSampler                      # noqa: F401
from tpudist.data.loader import DataLoader                           # noqa: F401
from tpudist.data import transforms                                  # noqa: F401
from tpudist.data.pipeline import build_train_val_loaders            # noqa: F401
