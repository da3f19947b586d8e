"""Host work per engine tick, median, ms: each ``engine.tick`` span (one
``ContinuousEngine.tick`` that had work) wholly inside the traced slice,
less the ``engine.fetch`` spans inside it, where the host waits on a
blocking device read.  Also notes the slice's device idle time by
innermost host span and its device time by the model's named scopes."""
from harness import engine_spans as ES
from harness.cell import percentile


def read(r):
    prof = ES.of(r)
    if prof is None:
        return None
    r.notes += ES.notes(prof)
    return percentile(ES.tick_host_ms(prof.red), 50)
