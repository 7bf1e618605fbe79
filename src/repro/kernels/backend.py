"""Where the Pallas kernels run: compiled on an accelerator, interpreted
only on the CPU backend, which has no Mosaic compiler."""
from __future__ import annotations

import jax


def pallas_interpret() -> bool:
    """True when ``pallas_call`` must run in interpret mode: the default
    backend is the CPU. No caller chooses; the platform decides."""
    return jax.default_backend() == "cpu"
