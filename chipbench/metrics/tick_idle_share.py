"""Device idle inside engine ticks, %: the share of the time inside the
benchmark's ``bench.tick`` spans (one per ``ContinuousEngine.tick``) in
which no operation ran on the device, over the traced slice."""


def read(r):
    if r.trace is None:
        return None
    inside, idle = r.trace.span_idle("bench.tick")
    return 100.0 * idle / inside if inside > 0 else None
