"""Device time of every operation that is not a Pallas kernel (kernel-map
search, downsampling, BN application, the classifier) in the traced
window, per scan served."""


def read(ctx):
    t = ctx["trace"]
    if t is None or ctx["units"] == 0:
        return None
    return {"value": 1e3 * t.xla_s / ctx["units"]}
