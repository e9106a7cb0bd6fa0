"""Share of the traced window in which no operation ran on the chip."""
import tracered


def read(ctx, params: dict):
    window = (ctx.trace_t1_ns - ctx.trace_t0_ns) / 1e9
    busy = tracered.busy_seconds(ctx.trace)
    if window <= 0 or busy <= 0:
        return None
    return 100.0 * (1.0 - busy / window)
