"""Operations and bytes of the selective scan (Mamba-1's recurrence with
its skip and gate, the operation the ``scan_gate`` kernel stands for),
counted from the operation's own inputs and outputs at the layer
boundary.

For ``rows`` tokens of one sequence, with ``d`` = d_inner channels and
``n`` = state size:

    h_t = exp(Δ_t A) h_{t-1} + Δ_t B_t x_t      (d × n each token)
    y_t = (h_t · C_t + D x_t) silu(z_t)          (d each token)

* operations: 8 per (row, channel, state) element — Δ·A, its exp, Δ·B,
  the product with x, the recurrence's multiply and add, and h·C's
  multiply and add — and 5 per (row, channel) — D·x and its add, the
  sigmoid of z, its product with z, and the gate's product;
* bytes: x, Δ, z and y (rows × d) and B, C (rows × n) in bfloat16, the
  model's activations; A (d × n), D (d), the state in (h0) and out
  (h_last) (d × n each) in float32, as the program keeps its SSM
  parameters and state.

The discretised ``a_bar``/``b_bar`` (rows × d × n) that the program
builds before the kernel are not the operation's inputs and are not
counted: their traffic is what the roofline share shows.
"""
from __future__ import annotations

from .work import BF16, Work

F32 = 4
OPS_PER_STATE = 8
OPS_PER_CHANNEL = 5


def selective_scan(rows: int, d_inner: int, state: int,
                   itemsize: int = BF16) -> Work:
    """One layer's selective scan over ``rows`` tokens of one sequence."""
    flops = rows * d_inner * (OPS_PER_STATE * state + OPS_PER_CHANNEL)
    acts = (4 * rows * d_inner + 2 * rows * state) * itemsize
    params_state = (3 * d_inner * state + d_inner) * F32
    return Work(float(flops), float(acts + params_state))
