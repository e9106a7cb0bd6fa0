"""Host time of one benchmark span per item it handled, in us: the
`bench/encode` span counts the queries each encode call was given."""


def read(ctx, params: dict):
    tot = ctx.spans.get(params["span"])
    if not tot or tot[2] <= 0:
        return None
    return tot[1] / 1000.0 / tot[2]
