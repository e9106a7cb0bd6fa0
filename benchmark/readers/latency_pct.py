"""A percentile of submit -> callback over every query submitted inside
the measured window, in ms. In a closed loop the median is W / rate
(Little), so it stands here, beside the rate, and not among the
end-to-end metrics."""
import importlib

import numpy as np


def read(ctx, params: dict):
    driver = importlib.import_module("drivers." + ctx.plan.traffic["driver"])
    lat = driver.latencies_ms(ctx.win)
    if len(lat) == 0:
        return None
    return float(np.percentile(lat, params["pct"]))
