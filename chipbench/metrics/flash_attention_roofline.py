"""Roofline share of the ``flash_attention`` kernel, %: the least time
of causal attention over each traced prefill chunk (its queries against
the prefix, at the model's key-value heads, causal pairs only; every
attention layer) over the kernel's device time."""


def read(r):
    a, layers = r.shapes["attention"], r.shapes["layers"]
    if not a:
        return None
    return r.kernel_share("flash_attention", lambda off, rows: r.work.causal_attention(
        off, rows, a["heads"], a["kv_heads"], a["head_dim"]) * layers)
