"""Useful operations of the window's scans (the configuration's
``forward_flops``; by default 2 nnz Cin Cout over the real voxels' kernel
maps, and the classifier, bench/opcount.py) over the traced window, as a
share of the chip's bf16 peak (bench/peaks.py)."""


def read(ctx):
    t = ctx["trace"]
    if t is None or "forward_flops" not in ctx["work"]:
        return None
    rate = ctx["work"]["forward_flops"] / t.window_s
    return {"value": 100.0 * rate / ctx["peaks"]["bf16_flops_per_s"]}
