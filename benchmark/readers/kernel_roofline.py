"""A kernel's share of its roofline, in %: the least time the chip
could take for the lookups the traced window served (their bytes, as
work.py counts them from the queries and the rule semantics, over the
peak memory bandwidth) over the device time of the kernel's program in
the trace. Nothing to read (no event of that program, no query of its
kinds) gives nothing, never 0."""
import numpy as np

import tracered


def read(ctx, params: dict):
    progs = tracered.programs(ctx.trace)
    if params["program"] not in progs or progs[params["program"]][1] <= 0:
        return None
    if ctx.device_kind not in ctx.peaks:
        raise KeyError(f"device kind {ctx.device_kind!r} is not in "
                       f"peaks.json")
    peak = ctx.peaks[ctx.device_kind]
    win = ctx.win
    served = (win.t_done >= ctx.trace_t0_ns) & (win.t_done < ctx.trace_t1_ns)
    ranks, counts = np.unique(win.rank[served], return_counts=True)
    nbytes = 0
    for r, c in zip(ranks.tolist(), counts.tolist()):
        kind, q = ctx.plan.pool[r]
        if kind in params["kinds"]:
            nbytes += ctx.dep.work(kind, q) * c
    if nbytes == 0:
        return None
    least = nbytes / peak["hbm_bytes_per_s"]
    return 100.0 * least / progs[params["program"]][1]
