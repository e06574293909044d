"""Operation and byte counts, on maps small enough to count by hand."""
import numpy as np
import pytest

from bench import opcount, reference
from bench.reference import Layer, Net

# voxels on one z line: 0, 1 and 3 apart; 0 and 1 are neighbours
COORDS = np.array([[16, 16, 16], [16, 16, 17], [16, 16, 19]])
PEAKS = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def net(cin=2, cout=3):
    return Net((Layer("a", cin, cout, 3, 0, 0),
                Layer("down", cout, cout, 3, 0, 1)), cin, 4)


def test_submanifold_and_strided_nnz():
    scan = reference.build_scan(COORDS, net())
    w = opcount.layer_work(scan, net())
    # submanifold: 3 centres + the pair (16,16,16)-(16,16,17) both ways
    assert w[0]["nnz"] == 5 and w[0]["n_out"] == 3
    # level 1 holds (16,16,16) and (16,16,18); each reads the level-0
    # voxels within one step: (16,16,16) reads 16, 17; (16,16,18) reads 17, 19
    assert scan.count(1) == 2
    assert w[1]["nnz"] == 4 and w[1]["n_in"] == 3 and w[1]["n_out"] == 2


def test_flops_and_bytes():
    n = net()
    scan = reference.build_scan(COORDS, n)
    assert opcount.forward_flops(scan, n) == 2 * 5 * 2 * 3 + 2 * 4 * 3 * 3 \
        + 2 * 2 * 3 * 4
    fwd = opcount.os_call_work(scan, n, backward=False)
    assert fwd["flops"] == 2 * 5 * 2 * 3 + 2 * 4 * 3 * 3
    assert fwd["bytes"] == 4 * ((5 * 2 + 27 * 2 * 3 + 3 * 3)
                                + (4 * 3 + 27 * 3 * 3 + 2 * 3))
    both = opcount.os_call_work(scan, n, backward=True)
    # the second layer's input-gradient call: same pairs, Cout -> Cin
    assert both["flops"] == fwd["flops"] + 2 * 4 * 3 * 3
    assert both["bytes"] == fwd["bytes"] + 4 * (4 * 3 + 27 * 3 * 3 + 3 * 3)


@pytest.mark.parametrize("flops,nbytes,bound,share", [
    (1000.0, 10.0, "compute", 50.0),     # 10 s of compute in 20 s
    (10.0, 100.0, "memory", 50.0),       # 10 s of bytes in 20 s
])
def test_roofline_share(flops, nbytes, bound, share):
    got = opcount.roofline_share(flops, nbytes, 20.0, PEAKS)
    assert got == {"value": pytest.approx(share), "bound": bound}


def test_unknown_device_is_an_error():
    from bench import peaks
    assert peaks.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peak("a chip nobody measured")
