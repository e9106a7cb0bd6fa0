"""What a thread does in no span, in % of a stretch: 100 minus the
`sum_ns` of the top-level spans `params["top"]`, which tile the thread
(no two overlap, none lies inside another), over the time from the
first start to the last end of those same spans. The dispatcher's loop:
`engine/wait`, `engine/swap`, `engine/cycle`, `engine/drain`. A span of
the list never seen counts nothing; none seen gives nothing; spans that
overlap after all (more time in them than the stretch holds) read 0."""
import program_trace


def compute(totals: dict, params: dict):
    of = [totals[s] for s in params["top"]
          if s in totals and totals[s]["n"] > 0]
    if not of:
        return None
    stretch = max(t["last_ns"] for t in of) - min(t["first_ns"] for t in of)
    if stretch <= 0:
        return None
    return max(0.0, 100.0 - 100.0 * sum(t["sum_ns"] for t in of) / stretch)


def read(ctx, params: dict):
    return compute(program_trace.span_totals(), params)
