"""Shared start-up of the tools: paths, environment, the cell."""
import os
import sys
import time

T_START = time.time()
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")
for _var in ("POLYTOPS_SCHEDD_SOCK", "POLYTOPS_SCHEDD_ADDR"):
    os.environ.pop(_var, None)
OUT = os.path.join(os.path.dirname(HERE), "chiprun_out")


def cell(name: str) -> dict:
    from harness import spec
    return spec.resolve(spec.benchmark(), name)
