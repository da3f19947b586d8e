"""Share of the device's busy time in the traced slice spent in the
model's ``ssm_inputs`` scope (``model/ssm.py`` ``_ssm_inputs``: the
selective scan's discretisation into ``a_bar``/``b_bar``, in prefill
chunks and decode ticks), %: each instant given to the innermost device
operation, by its whole name stack (``harness/scope_paths.py``)."""
from harness import scope_paths as SP


def read(r):
    return SP.share(r, "ssm_inputs")
