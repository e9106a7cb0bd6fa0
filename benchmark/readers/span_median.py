"""Median of the program's sampled `engine/queue_wait` spans, in ms:
how long a query sat in the service's queue before its batch was taken."""
import statistics


def read(ctx, params: dict):
    if not ctx.queue_wait_us:
        return None
    return statistics.median(ctx.queue_wait_us) / 1000.0
