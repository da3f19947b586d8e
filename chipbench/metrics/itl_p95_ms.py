"""Inter-token latency, 95th percentile, ms: gaps between consecutive
tokens of a request, as the host received them, over every token that
arrived before the window closed."""
from harness.cell import percentile


def read(r):
    gaps = []
    for s in r.served:
        tt = [t for t in s.req.token_times if t <= r.t_close]
        gaps += [(b - a) * 1e3 for a, b in zip(tt, tt[1:])]
    return percentile(gaps, 95)
