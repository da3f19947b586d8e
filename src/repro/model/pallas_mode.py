"""Process-wide switch wiring PolyTOPS-planned Pallas kernels into the
model layers.

The model layers (:mod:`.attention`, :mod:`.mlp`, :mod:`.ssm`) consult
:func:`mode` at trace time: when ``enabled``, the jnp einsum paths are
replaced by the Pallas kernels in :mod:`repro.kernels` — block geometry
from ``repro.core.akg`` plans — wherever the operand shapes clear the
per-kernel thresholds below.  Everything stays a pure function of the
same inputs, so a jit retrace picks the mode up and numerical parity
against the jnp path is a plain ``allclose`` (asserted by
``tests/test_serve.py``).

The thresholds are the same on every backend.  They were tuned on the
CPU, where the kernels run in the Pallas interpreter: the
flash-attention kernel beat the materialized-softmax jnp path from ~64
query rows up, while a 32-row matmul was cheaper as one XLA dot.  On a
TPU the kernels compile with Mosaic (:mod:`repro.kernels._mode`) under
these same thresholds, so a serving chunk must reach ``min_attn_q`` /
``min_matmul_rows`` rows for a kernel to run; no threshold has been
tuned on the chip yet.

Follows the module-level-config idiom of ``transformer.UNROLL`` /
``attention.ATTN_CHUNK``: the launcher installs the mode once, layers
read it at trace time.
"""
from __future__ import annotations

from contextlib import contextmanager
from dataclasses import dataclass, replace


@dataclass(frozen=True)
class PallasMode:
    enabled: bool = False
    #: route a matmul through the planned kernel only at/above this many
    #: output rows (tokens) — below it one XLA dot wins
    min_matmul_rows: int = 256
    #: flash attention only for query chunks at/above this length
    min_attn_q: int = 32
    #: fused scan+gate kernel only for sequence chunks at/above this
    min_scan_seq: int = 32
    #: use the fused scan+gate kernel (vs the plain selective_scan one)
    fused_scan_gate: bool = True


_MODE = PallasMode()


def mode() -> PallasMode:
    return _MODE


def configure(**kw) -> PallasMode:
    """Install a new mode (fields as keyword overrides); returns it."""
    global _MODE
    _MODE = replace(PallasMode(), **kw)
    return _MODE


@contextmanager
def pallas_mode(**kw):
    """Scoped :func:`configure` — restores the previous mode on exit."""
    global _MODE
    prev = _MODE
    _MODE = replace(PallasMode(), **kw)
    try:
        yield _MODE
    finally:
        _MODE = prev
