"""`latency_pct` over the queries of some kinds alone, in ms: a
percentile of submit -> callback of the queries submitted inside the
measured window whose pool rank asks one of `params["kinds"]` (the
classify+picks inside a mix of four kinds). No such query gives
nothing."""
import importlib
from types import SimpleNamespace

import numpy as np


def read(ctx, params: dict):
    driver = importlib.import_module("drivers." + ctx.plan.traffic["driver"])
    win = ctx.win
    of_kind = np.array([k in params["kinds"] for k, _q in ctx.plan.pool])
    mine = of_kind[win.rank]
    lat = driver.latencies_ms(SimpleNamespace(
        t_sub=win.t_sub[mine], t_done=win.t_done[mine],
        t_open=win.t_open, t_close=win.t_close))
    if len(lat) == 0:
        return None
    return float(np.percentile(lat, params["pct"]))
