"""A ratio of two of the service's counters over the measured window,
e.g. device_queries / dispatches = queries per device batch."""


def read(ctx, params: dict):
    num = ctx.counters_close[params["num"]] - ctx.counters_open[params["num"]]
    den = ctx.counters_close[params["den"]] - ctx.counters_open[params["den"]]
    return num / den if den > 0 else None
