"""Where the Pallas kernels run: compiled by Mosaic on an accelerator,
interpreted on the CPU backend (the test suite and the CPU simulations).

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
