"""Numerics that fold into the compiled step (reference C14 + loss math)."""

from tpudist.ops.metrics import accuracy            # noqa: F401
from tpudist.ops.loss import (Scored, cross_entropy_loss,  # noqa: F401
                              lm_head_loss)
