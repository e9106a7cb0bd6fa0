"""Device time of one launched program, by its name in the trace: mean
over its launches in the traced window, in us per batch (one launch =
one batch). No launch of that program gives nothing."""
import tracered


def read(ctx, params: dict):
    launches, seconds = tracered.programs(ctx.trace).get(
        params["program"], (0, 0.0))
    if launches == 0:
        return None
    return seconds * 1e6 / launches
