"""The OS gather-GEMM kernel's share of its roofline: the least time the
chip needs for the kernel's useful work (operations and bytes counted from
the real voxels' kernel maps, bench/opcount.py; in training the forward
and the input-gradient calls) over the summed device time of the
``spconv_gather_gemm`` operations. ``bound`` says whether operations or
bytes set the least time."""

KERNEL = "spconv_gather_gemm"


def read(ctx):
    from bench.opcount import roofline_share
    t = ctx["trace"]
    if t is None:
        return None
    secs = t.kernel_s.get(KERNEL, 0.0)
    if secs <= 0.0:
        return None
    w = ctx["work"]
    return roofline_share(w["os_flops"], w["os_bytes"], secs, ctx["peaks"])
