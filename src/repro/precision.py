"""The matmul precision of the serving path, in one place.

On TPU an f32 matmul defaults to one bf16 pass.  The even-p estimate is
``na + nb + sum`` of large signed cross terms, so that default moves the
estimate enough to reorder near neighbours.  Every sketch, strip and kernel
matmul on the serving path uses ``MATMUL_PRECISION``.
"""

from __future__ import annotations

import jax

__all__ = ["MATMUL_PRECISION"]

MATMUL_PRECISION = jax.lax.Precision.HIGHEST
