"""Useful operations and bytes of the networks' convolutions.

Counted from the kernel maps of the real voxels, which the benchmark
builds itself (``bench.reference.build_scan``): ``nnz`` is the number of
(output voxel, offset) pairs whose input voxel exists. Padding rows and
map entries that find no voxel are not work, so a change that skips them
leaves these counts as they are.

One OS (output-stationary gather-GEMM) call of a layer computes
``2 nnz Cin Cout`` operations and must at least read the gathered rows
(``nnz Cin`` values), the weights (``K^3 Cin Cout``) and write the output
(``n_out Cout``), at 4 bytes a value in float32. Its input-gradient call
in training runs the same pairs on the transposed map: ``2 nnz Cout Cin``
operations, ``nnz Cout`` gathered values, the weights, and ``n_in Cin``
written. The weight gradient is another ``2 nnz Cin Cout``.
"""
from __future__ import annotations

from typing import Dict, List

BYTES = 4


def layer_work(scan, net) -> List[Dict[str, int]]:
    """Per layer of ``net``: nnz, n_in, n_out, cin, cout, k3."""
    out = []
    for L in net.layers:
        m = scan.maps[(L.m_in, L.m_out, L.K)]
        out.append({"name": L.name, "nnz": int((m >= 0).sum()),
                    "n_in": scan.count(L.m_in), "n_out": scan.count(L.m_out),
                    "cin": L.cin, "cout": L.cout, "k3": L.K ** 3})
    return out


def forward_flops(scan, net) -> float:
    """Convolutions plus the classifier, one scan, forward only."""
    f = sum(2.0 * w["nnz"] * w["cin"] * w["cout"]
            for w in layer_work(scan, net))
    return f + 2.0 * scan.count(net.out_level) * net.layers[-1].cout \
        * net.n_classes


def os_call_work(scan, net, *, backward: bool) -> Dict[str, float]:
    """Operations and bytes of the OS kernel calls of one scan: the
    forward call of every layer, and with ``backward`` the input-gradient
    call of every layer but the first (whose input needs no gradient)."""
    flops = nbytes = 0.0
    for i, w in enumerate(layer_work(scan, net)):
        wb = w["k3"] * w["cin"] * w["cout"]
        flops += 2.0 * w["nnz"] * w["cin"] * w["cout"]
        nbytes += BYTES * (w["nnz"] * w["cin"] + wb + w["n_out"] * w["cout"])
        if backward and i > 0:
            flops += 2.0 * w["nnz"] * w["cout"] * w["cin"]
            nbytes += BYTES * (w["nnz"] * w["cout"] + wb
                               + w["n_in"] * w["cin"])
    return {"flops": flops, "bytes": nbytes}


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peaks: dict) -> Dict[str, object]:
    """Least time the chip needs for the work over the time it took, in
    percent, and which bound sets the least time."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    return {"value": 100.0 * max(t_flops, t_bytes) / seconds, "bound": bound}
