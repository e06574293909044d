"""Mean host time of the serving engine's ``serve/pack`` span (voxel sort,
dedup and packing of a request on the host), over the requests of the
window, from the program's metrics registry."""

SPAN = "serve/pack"


def read(ctx):
    n, total = ctx["spans"].get(SPAN, (0, 0.0))
    if n <= 0:
        return None
    return {"value": 1e3 * total / n}
