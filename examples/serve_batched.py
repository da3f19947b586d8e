"""Batched serving: prefill a batch of prompts, then decode with the
serve step (KV/SSM caches), greedy sampling.

    PYTHONPATH=src python examples/serve_batched.py [--arch falcon_mamba_7b] [--smoke]

Without ``--smoke`` the arch runs at its published widths (random
weights), which needs an accelerator for reasonable speed.
"""
import argparse
import sys
import time
sys.path.insert(0, "src")

import jax
import jax.numpy as jnp

from repro.configs.registry import get_arch
from repro.model import transformer as T


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite_3_2b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--smoke", action="store_true",
                    help="use the arch's reduced smoke config")
    args = ap.parse_args()

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    key = jax.random.PRNGKey(0)
    params = jax.jit(T.init_params, static_argnums=1)(key, cfg)
    b, pl_, max_len = args.batch, args.prompt_len, args.prompt_len + args.gen

    prompts = jax.random.randint(key, (b, pl_), 2, cfg.vocab)
    t0 = time.time()
    prefill = jax.jit(lambda p, t: T.prefill(p, cfg, t))
    logits, pre_cache = prefill(params, prompts)
    print(f"prefill {b}×{pl_}: {time.time()-t0:.2f}s "
          f"(logits {logits.shape})")

    # widen the prefill cache to max_len
    cache = T.init_cache(cfg, b, max_len)

    def widen(dst, src):
        if dst.ndim == src.ndim and dst.shape[-2:] == src.shape[-2:] \
                and src.shape[-3] <= dst.shape[-3]:
            return jax.lax.dynamic_update_slice(
                dst, src.astype(dst.dtype), (0,) * dst.ndim)
        return src.astype(dst.dtype)

    cache = jax.tree.map(widen, cache, pre_cache)

    step = jax.jit(lambda p, tok, c, n: T.decode_step(p, cfg, tok, c, n))
    tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    out_tokens = [tok]
    t0 = time.time()
    for i in range(args.gen - 1):
        logits_i, cache = step(params, tok, cache, jnp.int32(pl_ + i))
        tok = jnp.argmax(logits_i, -1).astype(jnp.int32)[:, None]
        out_tokens.append(tok)
    gen = jnp.concatenate(out_tokens, axis=1)
    dt = time.time() - t0
    print(f"decoded {args.gen-1} steps × {b} seqs in {dt:.2f}s "
          f"({(args.gen-1)*b/max(dt,1e-9):.1f} tok/s, {cfg.name} on "
          f"{jax.devices()[0].platform} {jax.devices()[0].device_kind})")
    print("sample tokens:", gen[0][:12].tolist())


if __name__ == "__main__":
    main()
