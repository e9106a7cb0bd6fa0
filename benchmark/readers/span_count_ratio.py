"""How many of one of the program's spans there were for each of
another: `n` of `params["num"]` over `n` of `params["den"]` in its span
totals (device batches a dispatcher wake = `engine/dispatch` over
`engine/cycle`). A program without the denominator's span, or none
counted, gives nothing."""
import program_trace


def compute(totals: dict, params: dict):
    den = totals.get(params["den"])
    if not den or den["n"] <= 0:
        return None
    return totals.get(params["num"], {"n": 0})["n"] / den["n"]


def read(ctx, params: dict):
    return compute(program_trace.span_totals(), params)
