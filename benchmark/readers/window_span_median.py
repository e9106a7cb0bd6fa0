"""Median, in us, of the stretches a driver timed itself inside the
measured window: two arrays of the window object, starts and ends in ns
(`params["start"]`, `params["end"]`), e.g. a burst driver's
`burst_t0` / `burst_t1`, one pair a call. A window without those
arrays (another driver's), or no stretch begun inside it, gives
nothing."""
import numpy as np


def read(ctx, params: dict):
    t0 = getattr(ctx.win, params["start"], None)
    t1 = getattr(ctx.win, params["end"], None)
    if t0 is None or t1 is None:
        return None
    n = min(len(t0), len(t1))
    inside = (t0[:n] >= ctx.win.t_open) & (t0[:n] < ctx.win.t_close)
    if not inside.any():
        return None
    return float(np.median((t1[:n] - t0[:n])[inside])) / 1000.0
