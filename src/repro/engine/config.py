"""Engine configuration: backend choice + strip block sizes, per platform.

The streaming engine processes the packed factors in (row_block, col_block)
strips, so peak live memory for the distance estimate is one strip — never
the (n, m) matrix.  Defaults are tuned per platform:

  * tpu: the Pallas ``pairwise_lp`` kernel with MXU-friendly 1024x1024 strips
    (the kernel tiles further into bm x bn x bk internally).
  * cpu: pure-XLA strips, 512x512 — small enough that tests exercise multiple
    strips, big enough that Eigen GEMMs stay efficient.

Any other platform is an error, never a silent CPU default: a device the
table does not know has no tuned strips and no tested kernel.

``backend="interpret"`` forces the Pallas kernel through the interpreter —
slow, but it executes the exact kernel program on CPU (a test-only backend).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax

__all__ = ["EngineConfig", "BACKENDS", "default_backend"]

BACKENDS = ("auto", "pallas", "interpret", "xla")

# platform -> (backend, row_block, col_block)
_PLATFORM_DEFAULTS = {
    "tpu": ("pallas", 1024, 1024),
    "cpu": ("xla", 512, 512),
}


def _platform_defaults(platform: Optional[str]) -> Tuple[str, int, int]:
    platform = platform or jax.default_backend()
    try:
        return _PLATFORM_DEFAULTS[platform]
    except KeyError:
        raise ValueError(
            f"no engine defaults for platform {platform!r} (known: "
            f"{sorted(_PLATFORM_DEFAULTS)})") from None


def default_backend(platform: Optional[str] = None) -> str:
    return _platform_defaults(platform)[0]


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static engine knobs.  ``None`` block sizes mean the platform default.

    Attributes:
      backend: "auto" (resolve by platform), "pallas" (TPU kernel),
        "interpret" (Pallas interpreter on CPU, for tests), or "xla" (pure
        jnp strips).
      row_block: strip height over the left/query rows.
      col_block: strip width over the right/corpus rows.
    """

    backend: str = "auto"
    row_block: Optional[int] = None
    col_block: Optional[int] = None

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}, got {self.backend!r}")
        for name in ("row_block", "col_block"):
            v = getattr(self, name)
            if v is not None and v < 1:
                raise ValueError(f"{name} must be >= 1, got {v}")

    def resolve(self, platform: Optional[str] = None) -> Tuple[str, int, int]:
        """(backend, row_block, col_block) with platform defaults filled in."""
        dflt_backend, dflt_rb, dflt_cb = _platform_defaults(platform)
        backend = dflt_backend if self.backend == "auto" else self.backend
        return backend, self.row_block or dflt_rb, self.col_block or dflt_cb
