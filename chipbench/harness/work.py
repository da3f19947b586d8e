"""Operations and bytes of the operations the kernels stand for, counted
from each operation's own inputs and outputs at the model's layer
boundary (never from what the program feeds a kernel), and the chip's
peaks, keyed by JAX's ``device_kind``.

A kernel's least time is the larger of its operations over the peak
rate and its bytes over the memory bandwidth; its roofline share is the
least time over the time the trace gives it.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass

PEAKS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "peaks.json")
BF16 = 2


@dataclass
class Work:
    flops: float = 0.0
    bytes: float = 0.0

    def __add__(self, o: "Work") -> "Work":
        return Work(self.flops + o.flops, self.bytes + o.bytes)

    def __mul__(self, k: float) -> "Work":
        return Work(self.flops * k, self.bytes * k)


def peaks(device_kind: str) -> dict:
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}; known: {sorted(table)}")
    return table[device_kind]


def least_seconds(w: Work, pk: dict) -> tuple:
    """(least time, bound) with bound 'compute' or 'memory'."""
    tc = w.flops / pk["bf16_flops_per_s"]
    tm = w.bytes / pk["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


def matmul(m: int, k: int, n: int, itemsize: int = BF16) -> Work:
    """C[m, n] = A[m, k] B[k, n]."""
    return Work(2.0 * m * k * n, float(m * k + k * n + m * n) * itemsize)


def causal_attention(offset: int, rows: int, heads: int, kv_heads: int,
                     head_dim: int, itemsize: int = BF16) -> Work:
    """``rows`` queries at positions [offset, offset + rows) attending
    causally to keys [0, offset + rows): only the causal pairs, two
    products (q k^T and p v) each; q, out at ``heads``, k, v at
    ``kv_heads``."""
    pairs = rows * offset + rows * (rows + 1) / 2
    keys = offset + rows
    return Work(4.0 * pairs * heads * head_dim,
                float(2 * rows * heads * head_dim
                      + 2 * keys * kv_heads * head_dim) * itemsize)

