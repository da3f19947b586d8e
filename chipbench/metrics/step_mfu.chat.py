"""Model FLOP utilisation of the serving steps, %: the model operations
of every token the traced ticks processed (prefill and decode) over the
device's busy time in the traced slice times the chip's peak."""


def read(r):
    if r.trace is None or r.trace.busy_s <= 0 or not r.traced_ticks:
        return None
    return 100.0 * r.tick_flops(r.traced_ticks) / (
        r.trace.busy_s * r.peaks["bf16_flops_per_s"])
