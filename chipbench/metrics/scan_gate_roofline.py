"""Roofline share of the ``scan_gate`` kernel, %: the least time of the
selective scan over each traced prefill chunk (its own inputs and
outputs, ``harness/work_ssm.py``; every SSM layer) over the kernel's
device time."""
from harness import work_ssm as WS


def read(r):
    s = r.shapes.get("ssm")
    if not s:
        return None
    return r.kernel_share("scan_gate", lambda off, rows: WS.selective_scan(
        rows, s["d_inner"], s["state"]) * s["layers"])
