"""Falcon-Mamba 7B — pure Mamba-1, attention-free.

Source: hf:tiiuae/falcon-mamba-7b (config.json) and arXiv:2410.05355.
64 layers, d_model 4096, d_inner 8192 (expand 2), SSM state 16, conv
width 4, time-step rank 256, vocab 65024; an RMSNorm (ε 1e-5) before
each mixer, and weight-free RMSNorms (ε 1e-6) of B, C and Δ's low-rank
input inside the mixer, after ``x_proj``; the residual stream between
layers is kept in float32 (``residual_in_fp32``)."""
from .registry import ArchConfig

CONFIG = ArchConfig(
    name="falcon-mamba-7b", family="ssm",
    n_layers=64, d_model=4096, n_heads=1, n_kv_heads=1,
    d_ff=0, vocab=65024,
    ssm_state=16, d_inner_mult=2, bcdt_rms_eps=1e-6, norm_eps=1e-5,
    residual_f32=True,
    fsdp=True, sub_quadratic=True,
)
