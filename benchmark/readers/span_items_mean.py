"""Items a span of the program's handled, on average: `sum_items / n` of
the span `params["span"]` in its span totals (tables a device batch of a
table set names = `engine/table_set`). No such span, or none counted,
gives nothing."""
import program_trace


def compute(totals: dict, params: dict):
    tot = totals.get(params["span"])
    if not tot or tot["n"] <= 0:
        return None
    return tot["sum_items"] / tot["n"]


def read(ctx, params: dict):
    return compute(program_trace.span_totals(), params)
