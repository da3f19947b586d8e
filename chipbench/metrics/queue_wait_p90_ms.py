"""Engine admission: from a request's due time to the tick that admitted
it into a slot, 90th percentile, ms, over the window's requests."""
from harness.cell import percentile


def read(r):
    v = [(s.admitted - s.due) * 1e3 for s in r.served if s.admitted]
    return percentile(v, 90)
