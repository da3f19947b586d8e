"""How a Pallas kernel runs when the caller does not say: compiled by
Mosaic on a TPU, in the Pallas interpreter on the CPU."""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """An explicit ``interpret`` wins; ``None`` follows the default
    backend — interpret on ``cpu``, compile on ``tpu``.  Any other
    backend is an error: no kernel here targets it."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"no Pallas kernel mode for backend {backend!r}")
