"""A span's self time: the `sum_ns` of the spans `params["spans"]` minus
what their child spans `params["children"]` cover (every child lies
inside one of them, no two children overlap) — the time spent there in
no child. `params["per"]`: "share" = in % of the spans' own `sum_ns`;
"sum_items" / "n" = in us an item / a span of the first of `spans`. A
child never seen covers nothing; no parent seen gives nothing; children
that cover more than their parents (a child that ran outside them) read
0, which is reported."""
import program_trace


def compute(totals: dict, params: dict):
    of = [totals[s] for s in params["spans"]
          if s in totals and totals[s]["n"] > 0]
    wall = sum(t["sum_ns"] for t in of)
    if wall <= 0:
        return None
    rest = max(0, wall - sum(totals[c]["sum_ns"]
                             for c in params["children"] if c in totals))
    if params["per"] == "share":
        return 100.0 * rest / wall
    den = totals.get(params["spans"][0], {}).get(params["per"], 0)
    if den <= 0:
        return None
    return rest / 1000.0 / den


def read(ctx, params: dict):
    return compute(program_trace.span_totals(), params)
