"""Share of the spans' wall time in which their thread was not on a
CPU, in %: (sum_ns - sum_cpu_ns) / sum_ns over `params["spans"]`. The
spans named make no blocking call, so what is left is time the thread
was runnable and not running (the GIL, the scheduler)."""
import program_trace


def compute(totals: dict, params: dict):
    of = [totals[s] for s in params["spans"] if s in totals]
    wall = sum(t["sum_ns"] for t in of)
    if wall <= 0:
        return None
    return 100.0 * (wall - sum(t["sum_cpu_ns"] for t in of)) / wall


def read(ctx, params: dict):
    return compute(program_trace.span_totals(), params)
