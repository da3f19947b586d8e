"""Roofline share of the ``polytops_matmul`` kernel, %: the least time
of the MLP's three products (gate and up: rows x d x ff; down: rows x ff
x d) over each traced prefill chunk, every layer, over the kernel's
device time."""


def read(r):
    m, layers = r.shapes["mlp"], r.shapes["layers"]
    if not m:
        return None
    w = r.work

    def chunk(off, rows):
        return (w.matmul(rows, m["d"], m["ff"]) * 2
                + w.matmul(rows, m["ff"], m["d"])) * layers
    return r.kernel_share("polytops_matmul", chunk)
