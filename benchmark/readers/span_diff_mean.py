"""The mean of the spans of `params["a"]` that are not also spans of
`params["b"]`, in us: (`sum_ns` of a - `sum_ns` of b) / (`n` of a - `n`
of b), where every span of b is noted over the interval of one of a's
(`engine/kernel_wait` over the `engine/d2h_sync` of a result that was
not ready: the difference is the sync of the results that were). No b
counts as none of them; no a, or every a also a b, gives nothing."""
import program_trace

NONE = {"n": 0, "sum_ns": 0}


def compute(totals: dict, params: dict):
    a, b = totals.get(params["a"]), totals.get(params["b"], NONE)
    if not a or a["n"] - b["n"] <= 0:
        return None
    return max(0, a["sum_ns"] - b["sum_ns"]) / 1000.0 / (a["n"] - b["n"])


def read(ctx, params: dict):
    return compute(program_trace.span_totals(), params)
