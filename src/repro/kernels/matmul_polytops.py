"""PolyTOPS-planned tiled matmul Pallas kernel.

The grid order and BlockSpec tile shapes come from a PolyTOPS schedule
of the matmul SCoP (repro.core.akg.plan_matmul): tensor-style
(contiguity ≻ proximity) scheduling yields the (i, k, j) loop order with
j vectorized — mapped here to a (mi, ni, ki) grid where the k grid axis
is minormost (sequential accumulation into a VMEM f32 scratch) and the
j/lane dimension lives in the 128-wide minor axis of every tile.

TPU notes: tiles are multiples of (8, 128); the MXU consumes
(bm×bk)·(bk×bn) per grid step; accumulation dtype is f32 regardless of
input dtype.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.akg import KernelPlan, plan_matmul
from ._mode import resolve_interpret


def _kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _pick(block: int, dim: int) -> int:
    b = min(block, dim)
    while dim % b:
        b //= 2
    return max(b, 1)


def matmul(a: jnp.ndarray, b: jnp.ndarray,
           plan: Optional[KernelPlan] = None,
           interpret: Optional[bool] = None) -> jnp.ndarray:
    """C[M,N] = A[M,K] @ B[K,N] with PolyTOPS-planned tiling."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    plan = plan or plan_matmul(m, n, k)
    bm = _pick(plan.tile.get("i", 128), m)
    bn = _pick(plan.tile.get("j", 128), n)
    bk = _pick(plan.tile.get("kk", 128), k)
    k_steps = k // bk
    grid = (m // bm, n // bn, k_steps)
    return pl.pallas_call(
        functools.partial(_kernel, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=resolve_interpret(interpret),
        name="polytops_matmul",
    )(a, b)
