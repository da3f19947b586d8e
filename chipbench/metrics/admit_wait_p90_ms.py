"""Engine admission, 90th percentile, ms: from each window request's due
time to the engine's own stamp of its admission into a slot
(``Request.t_admit``)."""
from harness.cell import percentile


def read(r):
    v = [(s.req.t_admit - s.due) * 1e3 for s in r.served
         if getattr(s.req, "t_admit", 0)]
    return percentile(v, 90)
