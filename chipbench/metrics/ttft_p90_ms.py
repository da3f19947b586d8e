"""Time to first token, 90th percentile, ms: from each request's due
time (open loop) to its first token reaching the host, over every
request due in the window (those unfinished at its close are drained)."""
from harness.cell import percentile


def read(r):
    v = [(s.req.t_first - s.due) * 1e3 for s in r.served if s.req.t_first]
    return percentile(v, 90)
