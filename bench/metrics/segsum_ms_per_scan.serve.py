"""Device time of the Pallas segment-sum operations (per-scan BN moments)
in the traced window, per scan served."""

KERNEL = "segment_sum_pallas"


def read(ctx):
    t = ctx["trace"]
    if t is None or ctx["units"] == 0:
        return None
    secs = t.kernel_s.get(KERNEL, 0.0)
    if secs <= 0.0:
        return None
    return {"value": 1e3 * secs / ctx["units"]}
