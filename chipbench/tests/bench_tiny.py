"""Small cells for the CPU tests: the harness's whole run, from the
warm-up walk to the check, at a width the CPU holds, with the chip's
look skipped and kernels interpreted."""
import os
import time

from harness import spec

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELLS = {
    "granite_chat": ("tiny_granite", "granite_3_2b", "tiny_chat"),
}


def cell(name: str) -> dict:
    conf, ref, mix = CELLS[name]
    return {"name": name, "chips": 1,
            "conf": spec.load_json(os.path.join(DATA, conf + ".json")),
            "mix": spec.load_json(os.path.join(DATA, mix + ".json")),
            "ref": spec.load_module(
                os.path.join(spec.BENCH_DIR, "reference", ref + ".py"),
                "chipbench_reference_tiny_" + ref),
            "end_to_end": [], "per_layer": [], "readers": {}}


def run(name: str, seed: int = 2 ** 31 + 3, seconds: float = 1.5) -> dict:
    from harness import cell as C
    return C.run(cell(name), seed, seconds, False, time.time(),
                 check_device=False)
