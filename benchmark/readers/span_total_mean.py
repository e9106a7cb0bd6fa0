"""One of the program's own span totals as a mean, in us: `num` over
`den` of the span `params["span"]` (`sum_ns / n` = time a span,
`sum_ns / sum_items` = time an item, `sum_cpu_ns / sum_items` = CPU time
an item). No such span, or none counted, gives nothing."""
import program_trace


def compute(totals: dict, params: dict):
    tot = totals.get(params["span"])
    if not tot or tot[params["den"]] <= 0:
        return None
    return tot[params["num"]] / 1000.0 / tot[params["den"]]


def read(ctx, params: dict):
    return compute(program_trace.span_totals(), params)
