"""Share of a stretch that one span took, in %: the span's `sum_ns`
over the time from the first start to the last end of the spans of
`params["stretch_plane"]`; with `"complement": true`, 100 minus that
(the dispatcher busy = not parked in `engine/wait`). A span never seen
reads 0 where the stretch exists; no stretch gives nothing."""
import program_trace


def stretch_ns(totals: dict, plane: str) -> int:
    """From the first start to the last end over the plane's spans."""
    of = [t for k, t in totals.items()
          if k.startswith(plane + "/") and t["n"] > 0]
    if not of:
        return 0
    return max(t["last_ns"] for t in of) - min(t["first_ns"] for t in of)


def compute(totals: dict, params: dict):
    stretch = stretch_ns(totals, params["stretch_plane"])
    if stretch <= 0:
        return None
    share = 100.0 * totals.get(params["span"], {"sum_ns": 0})["sum_ns"] \
        / stretch
    return 100.0 - share if params.get("complement") else share


def read(ctx, params: dict):
    return compute(program_trace.span_totals(), params)
