"""Where the Pallas kernels run: compiled by Mosaic on an accelerator,
interpreted on the CPU backend (the test suite and the CPU simulations),
and which read path a paged decode takes on each platform.

This is the one place that decides.  There is no silent fallback: on a
TPU a kernel compiles for the chip or the call fails.
"""

from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """An explicit ``interpret`` wins; otherwise interpret exactly when
    the default backend is the CPU."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() == "cpu"


def paged_decode_kernel(cfg) -> bool:
    """Whether a paged decode (or verify) read runs the block-table
    kernel (``flash_attention_decode_paged``) rather than the XLA
    gather.  ``cfg.decode_flash`` forces one path when it is set;
    ``None`` leaves it to the platform: the kernel on a TPU, where it
    reads only the live pages, the gather elsewhere, where the kernel
    would only be interpreted.  The kernel copies whole pages out of
    HBM, which Mosaic slices only where a head fills whole 128-lane
    rows; narrower heads keep the gather."""
    if cfg.decode_flash is not None:
        return bool(cfg.decode_flash)
    return jax.default_backend() == "tpu" and cfg.head_dim % 128 == 0
