"""Scene traffic for the benchmark, generated from a seed.

A copy of the repository's synthetic scene generator (walls, floor and
furniture for rooms; a rough ground plane, object shells and radial
thinning for LiDAR sweeps), of its per-voxel labels and of its
coordinate-derived input features, kept here so that no change to the
program can move the yardstick. The draws from the random generator are
made in the same order as the original, so a seed gives the same voxels;
only the deduplication is written as a sort of integer keys, which gives
the same rows in the same (x, y, z) order.

Coordinates are integer voxels, biased by ``GUARD`` so that every real
coordinate sits at least ``GUARD`` from the origin.

A workload's ``traffic`` entry holds the parameters (``kind``,
``extent``, ``overlap``, ``pool`` of distinct scans, ``scans_per_item``
scans per request or training step, ``labels``, ``n_classes`` and
``max_voxels``), and
:func:`scan_pool` turns them into scans.
"""
from __future__ import annotations

import dataclasses

import numpy as np

GUARD = 16


@dataclasses.dataclass(frozen=True)
class Scan:
    coords: np.ndarray             # int32 [N, 3], unique, sorted, >= GUARD
    extent: tuple                  # voxel extent the scan was drawn in
    labels: np.ndarray | None = None   # int32 [N] class per voxel


def _keys(coords: np.ndarray, dims) -> np.ndarray:
    c = coords.astype(np.int64)
    return (c[:, 0] * int(dims[1]) + c[:, 1]) * int(dims[2]) + c[:, 2]


def _unique_rows(coords: np.ndarray, dims) -> np.ndarray:
    """``np.unique(coords, axis=0)`` for rows inside ``[0, dims)``."""
    k = np.unique(_keys(coords, dims))
    dy, dz = int(dims[1]), int(dims[2])
    return np.stack([k // (dy * dz), (k // dz) % dy, k % dz], axis=1)


def _unique(coords: np.ndarray, extent: np.ndarray) -> np.ndarray:
    coords = coords[(coords >= 0).all(1) & (coords < extent).all(1)]
    return _unique_rows(coords, extent)


def _surface_plane(rng, extent, axis: int, level: int, density: float):
    dims = [d for d in range(3) if d != axis]
    g = np.stack(np.meshgrid(np.arange(extent[dims[0]]),
                             np.arange(extent[dims[1]]), indexing="ij"), -1)
    g = g.reshape(-1, 2)
    keep = rng.random(len(g)) < density
    g = g[keep]
    out = np.zeros((len(g), 3), np.int64)
    out[:, dims[0]] = g[:, 0]
    out[:, dims[1]] = g[:, 1]
    out[:, axis] = level + rng.integers(0, 2, len(g))
    return out


def _surface_sphere(rng, center, radius, n):
    v = rng.normal(size=(n, 3))
    v /= np.linalg.norm(v, axis=1, keepdims=True)
    return np.round(center + v * radius).astype(np.int64)


def _surface_box(rng, corner, size, density):
    pts = []
    for axis in range(3):
        for side in (0, size[axis] - 1):
            face = _surface_plane(rng, np.array(size), axis, 0, density)
            face[:, axis] = side
            pts.append(face + corner)
    return np.concatenate(pts)


def indoor_scan(seed: int, extent: tuple, density: float = 0.7) -> Scan:
    """A room: floor, ceiling, four walls and six furniture boxes."""
    rng = np.random.default_rng(seed)
    ext = np.asarray(extent)
    pts = [
        _surface_plane(rng, ext, 2, 0, density),
        _surface_plane(rng, ext, 2, ext[2] - 2, density * 0.6),
        _surface_plane(rng, ext, 0, 0, density),
        _surface_plane(rng, ext, 0, ext[0] - 2, density),
        _surface_plane(rng, ext, 1, 0, density),
        _surface_plane(rng, ext, 1, ext[1] - 2, density),
    ]
    for _ in range(6):
        hi = np.minimum(40, ext - 8)
        size = rng.integers(6, hi, 3)
        size[2] = min(size[2], ext[2] - 4)
        corner = np.array([rng.integers(2, ext[0] - size[0] - 2),
                           rng.integers(2, ext[1] - size[1] - 2), 1])
        pts.append(_surface_box(rng, corner, size, density * 0.8))
    coords = _unique(np.concatenate(pts), ext)
    return Scan((coords + GUARD).astype(np.int32), tuple(extent))


def outdoor_scan(seed: int, extent: tuple, n_objects: int = 24,
                 thin: float = 0.35) -> Scan:
    """A LiDAR sweep: rough ground, object shells, density falling with
    range from the sensor at the centre."""
    rng = np.random.default_rng(seed)
    ext = np.asarray(extent)
    pts = [_surface_plane(rng, ext, 2, 0, thin * 0.5)]
    center = ext[:2] // 2
    for _ in range(n_objects):
        c = np.array([rng.integers(32, ext[0] - 32),
                      rng.integers(32, ext[1] - 32), rng.integers(2, 10)])
        if rng.random() < 0.5:
            pts.append(_surface_sphere(rng, c, rng.integers(4, 14), 2000))
        else:
            size = rng.integers(6, 28, 3)
            size[2] = min(size[2], ext[2] - c[2] - 2)
            pts.append(_surface_box(rng, c, size, 0.9))
    coords = np.concatenate(pts)
    r = np.linalg.norm(coords[:, :2] - center, axis=1)
    keep = rng.random(len(coords)) < 1.0 / (1.0 + r / (ext[0] / 8))
    coords = _unique(coords[keep], ext)
    return Scan((coords + GUARD).astype(np.int32), tuple(extent))


_KINDS = {"indoor": indoor_scan, "outdoor": outdoor_scan}


def semantic_labels(coords: np.ndarray, extent: tuple,
                    n_classes: int) -> np.ndarray:
    """Height bands, and one class for voxels against an x or y wall."""
    c = coords.astype(np.int64) - GUARD
    bands = max(n_classes - 1, 1)
    lab = np.clip((c[:, 2] * bands) // max(int(extent[2]), 1), 0, bands - 1)
    wall = ((c[:, 0] <= 1) | (c[:, 1] <= 1)
            | (c[:, 0] >= extent[0] - 2) | (c[:, 1] >= extent[1] - 2))
    return np.where(wall, n_classes - 1, lab).astype(np.int32)


def scan_batch(seed: int, batch: int, kind: str, extent: tuple,
               overlap: float, labels: bool = False,
               n_classes: int = 8) -> list:
    """``batch`` scans over one extent. Each keeps about ``overlap`` of a
    common base scan's voxels and adds its own (seed + 101 + index), as
    consecutive sweeps or rooms of one building share static geometry."""
    if not 0.0 <= overlap <= 1.0:
        raise ValueError(f"overlap must lie in [0, 1], got {overlap}")
    make = _KINDS[kind]
    rng = np.random.default_rng(seed)
    base = make(seed, extent)
    dims = np.asarray(extent) + 2 * GUARD
    out = []
    for b in range(batch):
        own = make(seed + 101 + b, extent)
        keep = rng.random(len(base.coords)) < overlap
        coords = _unique_rows(np.concatenate([base.coords[keep], own.coords]),
                              dims).astype(np.int32)
        lab = semantic_labels(coords, extent, n_classes) if labels else None
        out.append(Scan(coords, tuple(extent), lab))
    return out


def scan_features(scan: Scan, channels: int) -> np.ndarray:
    """Normalised (x, y, z) and a constant, tiled to ``channels``."""
    c = (scan.coords.astype(np.float32) - GUARD) / np.asarray(
        scan.extent, np.float32)
    base = np.concatenate([c, np.ones((len(c), 1), np.float32)], axis=1)
    reps = -(-channels // base.shape[1])
    return np.tile(base, (1, reps))[:, :channels].astype(np.float32)


def thin(scan: Scan, n: int, rng) -> Scan:
    """``n`` of the scan's voxels, drawn without replacement, in their
    order; a scan of ``n`` voxels or fewer is kept whole."""
    if len(scan.coords) <= n:
        return scan
    keep = np.sort(rng.choice(len(scan.coords), n, replace=False))
    lab = None if scan.labels is None else scan.labels[keep]
    return Scan(scan.coords[keep], scan.extent, lab)


def crop_cells(scan: Scan, level: int, count: int, rng) -> Scan:
    """The part of the scan in ``count`` cells of ``2^level`` voxels a
    side: those nearest a voxel drawn from ``rng``, as the part of a room
    in a sensor's view. A scan of ``count`` cells or fewer is kept whole."""
    c = scan.coords.astype(np.int64)
    cells, inv = np.unique(c >> level, axis=0, return_inverse=True)
    inv = inv.reshape(-1)
    if len(cells) <= count:
        return scan
    centre = c[rng.integers(len(c))] >> level
    d = np.square(cells - centre).sum(1)
    near = np.argsort(d, kind="stable")[:count]
    keep = np.zeros(len(cells), bool)
    keep[near] = True
    rows = np.flatnonzero(keep[inv])
    lab = None if scan.labels is None else scan.labels[rows]
    return Scan(scan.coords[rows], scan.extent, lab)


def thin_keeping_cells(scan: Scan, n: int, level: int, rng) -> Scan:
    """``n`` of the scan's voxels as :func:`thin` draws them, but one
    voxel of every cell of ``2^level`` voxels a side always kept, so that
    the scan's voxels at that level stay the same."""
    if len(scan.coords) <= n:
        return scan
    c = scan.coords.astype(np.int64)
    _, first = np.unique(c >> level, axis=0, return_index=True)
    rest = np.setdiff1d(np.arange(len(c)), first)
    if len(first) > n:
        raise ValueError(f"{len(first)} cells at level {level} need more "
                         f"than {n} voxels")
    keep = np.sort(np.concatenate(
        [first, rng.choice(rest, n - len(first), replace=False)]))
    lab = None if scan.labels is None else scan.labels[keep]
    return Scan(scan.coords[keep], scan.extent, lab)


def scan_pool(seed: int, traffic: dict) -> list:
    """The workload's distinct items: ``traffic["pool"]`` lists of
    ``traffic["scans_per_item"]`` scans each, drawn from ``seed``. With
    ``traffic["cells"]`` (``{"level": m, "count": k}``), each scan keeps
    the part of it in ``k`` cells of level ``m`` (:func:`crop_cells`), so
    that its voxels at level ``m`` number ``k``; with
    ``traffic["max_voxels"]``, each scan keeps that many of its voxels,
    drawn from the seed, as training pipelines cap the points per scan,
    and every cell of level ``m`` keeps at least one. Every item then
    carries the same work, whatever the seed."""
    per = int(traffic.get("scans_per_item", 1))
    n = int(traffic["pool"])
    scans = scan_batch(seed, n * per, traffic["kind"],
                       tuple(traffic["extent"]), float(traffic["overlap"]),
                       labels=bool(traffic.get("labels", False)),
                       n_classes=int(traffic.get("n_classes", 8)))
    cap = traffic.get("max_voxels")
    cells = traffic.get("cells")
    rng = np.random.default_rng([seed, 0x7415])
    if cells is not None:
        level, count = int(cells["level"]), int(cells["count"])
        scans = [crop_cells(s, level, count, rng) for s in scans]
        if cap is not None:
            scans = [thin_keeping_cells(s, int(cap), level, rng)
                     for s in scans]
    elif cap is not None:
        scans = [thin(s, int(cap), rng) for s in scans]
    return [scans[i * per:(i + 1) * per] for i in range(n)]
