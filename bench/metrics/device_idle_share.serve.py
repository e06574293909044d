"""Share of the traced window in which no operation ran on the device:
one minus the union of the device operations' intervals over the window."""


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    return {"value": 100.0 * t.idle_share}
