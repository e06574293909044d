"""Voxel coordinate set operations on packed coordinates.

Everything here is packed-native (Spira §5.3): sorting, dedup and
downsampling operate on single int words; no unpack/repack anywhere.

Static-shape discipline: JAX needs static array sizes, so deduplicated
coordinate sets keep their input-sized buffer with the *valid prefix* sorted
ascending and the tail padded with ``PAD`` (int max), plus an explicit scalar
count. Every downstream operator (z-delta search, dataflows) understands this
(sorted-array + count) representation — PAD sorts after every real coordinate,
which is exactly what binary search wants.

Single-sort discipline (Spira §5.5, this engine's strengthening of it): the
network performs exactly **one** true sort, on the raw V0 coordinates in
:func:`build_coord_set`. Downsampled levels are *not* re-sorted —
``round_down`` is not order-preserving on packed words (see
``packing.round_down``), but it maps a sorted array onto at most ``4^Δ``
interleaved sorted runs keyed by the cleared (x, y) bit residues, and
:func:`downsample` re-establishes sortedness with a run partition + pairwise
``searchsorted`` merges (O(N·Δ + N log N_compare) rank computation, no
compare-exchange sort network). The classic sort-per-level path is kept as
the documented fallback (``method="sort"``): XLA lowers scatter element-
sequentially on CPU, where a fresh ``std::sort`` is cheaper than the merge's
rank/scatter passes — the default "auto" method therefore resolves to merge
on TPU and sort off-TPU (:func:`resolve_downsample_method`).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .packing import BitLayout, round_down

PAD32 = np.iinfo(np.int32).max
PAD64 = np.iinfo(np.int64).max


def pad_value(dtype) -> int:
    return PAD64 if jnp.dtype(dtype) == jnp.int64 else PAD32


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CoordSet:
    """A sorted, deduplicated, padded set of packed voxel coordinates.

    ``packed[: count]`` is strictly ascending; ``packed[count :] == PAD``.
    """

    packed: jax.Array  # int32/int64 [N_max]
    count: jax.Array   # int32 scalar — number of valid coordinates

    def tree_flatten(self):
        return (self.packed, self.count), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def capacity(self) -> int:
        return self.packed.shape[0]


def _dedup_compact(s: jax.Array, capacity: int) -> CoordSet:
    """Sorted (non-decreasing), PAD-tailed array -> deduplicated CoordSet of
    ``capacity`` (first occurrence kept; kept elements stay in order because
    scatter destinations ``cumsum(keep)-1`` are ascending; dropped elements
    go out of bounds and are eliminated by ``mode="drop"``)."""
    pad = pad_value(s.dtype)
    keep = jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]])
    keep &= s != pad
    count = keep.sum(dtype=jnp.int32)
    dest = jnp.where(keep, jnp.cumsum(keep) - 1, capacity)
    out = jnp.full((capacity,), pad, s.dtype).at[dest].set(s, mode="drop")
    return CoordSet(packed=out, count=count)


def build_coord_set(packed: jax.Array) -> CoordSet:
    """Sort + dedup raw packed coordinates into a :class:`CoordSet`.

    This is the *single* true sort the whole network performs on coordinates.
    Downsampled levels are derived from it by the run-aware merge in
    :func:`downsample` — sortedness is re-established per level by merging,
    never by re-sorting.
    """
    n = packed.shape[0]
    return _dedup_compact(jnp.sort(packed), n)


# ---------------------------------------------------------------------------
# run-aware merge downsample (the single-sort plan pipeline)
# ---------------------------------------------------------------------------

def _merge_two_sorted(a: jax.Array, b: jax.Array, capacity: int) -> jax.Array:
    """Merge two sorted PAD-tailed arrays into one sorted ``capacity`` array
    without sorting: each element's output rank is its own index plus its
    ``searchsorted`` insertion point in the other array (ties broken
    a-before-b via the left/right sides, so ranks are a permutation).

    ``capacity`` may be smaller than len(a)+len(b) when the caller knows the
    combined *real* (non-PAD) element count is bounded by it — real ranks
    are then < capacity and only PAD elements fall off the end (dropped;
    the tail is PAD-initialized anyway)."""
    pad = pad_value(a.dtype)
    na, nb = a.shape[0], b.shape[0]
    pos_a = jnp.arange(na, dtype=jnp.int32) + \
        jnp.searchsorted(b, a, side="left").astype(jnp.int32)
    pos_b = jnp.arange(nb, dtype=jnp.int32) + \
        jnp.searchsorted(a, b, side="right").astype(jnp.int32)
    out = jnp.full((capacity,), pad, a.dtype)
    return out.at[pos_a].set(a, mode="drop").at[pos_b].set(b, mode="drop")


def _partition_runs(rounded: jax.Array, run_id: jax.Array, n_runs: int) -> list:
    """Stable-partition ``rounded`` by ``run_id`` into ``n_runs`` contiguous
    PAD-tailed buffers. Each buffer comes out sorted (non-decreasing) by the
    run-structure lemma in ``packing.round_down``. Pure rank + one scatter —
    a counting partition, not a sort."""
    n = rounded.shape[0]
    pad = pad_value(rounded.dtype)
    rank = jnp.zeros((n,), jnp.int32)
    for q in range(n_runs):
        mask = run_id == q
        rank = jnp.where(mask, jnp.cumsum(mask) - 1, rank)
    flat = jnp.full((n_runs * n,), pad, rounded.dtype)
    flat = flat.at[run_id * n + rank].set(rounded)
    return [flat[q * n: (q + 1) * n] for q in range(n_runs)]


def downsample_merge(coords: CoordSet, layout: BitLayout, m: int,
                     *, from_m: int = 0) -> CoordSet:
    """Downsample a sorted level-``from_m`` CoordSet to level ``m`` without
    sorting: round, split into the ``4^Δ`` sorted runs keyed by the cleared
    (x, y) bit residues, then merge-tree + dedup. Bit-identical to the sort
    path by construction (same multiset of rounded values, same dedup)."""
    delta = m - from_m
    assert delta > 0, (from_m, m)
    pad = pad_value(coords.packed.dtype)
    p = coords.packed
    rounded = jnp.where(p == pad, pad, round_down(p, layout, m))
    # Run residue: the x/y bits cleared by this rounding step. Level-from_m
    # coordinates have zero bits below from_m, so the residue is the Δ bits
    # [from_m, m) of each field. PAD rows land in run 0's tail (PAD = int
    # max sorts last there, keeping the run sorted).
    rmask = (1 << delta) - 1
    rx = (p >> (layout.shift_x + from_m)) & rmask
    ry = (p >> (layout.shift_y + from_m)) & rmask
    run_id = jnp.where(p == pad, 0, (rx << delta) | ry).astype(jnp.int32)
    runs = _partition_runs(rounded, run_id, 1 << (2 * delta))
    # Merge tree. Total real elements across all runs is the input count
    # <= capacity, so every merge stage (and the final dedup) can stay at
    # the input capacity — only PAD falls off the end.
    while len(runs) > 1:
        runs = [_merge_two_sorted(runs[i], runs[i + 1], coords.capacity)
                for i in range(0, len(runs), 2)]
    return _dedup_compact(runs[0], coords.capacity)


def resolve_downsample_method(method: str) -> str:
    """The one place the "auto" platform policy lives: the run merge
    replaces per-level O(N log²N) bitonic sorts with linear rank/scatter
    passes on TPU, but XLA lowers scatter element-sequentially on CPU where
    ``std::sort`` is nearly free — so "auto" resolves to merge on TPU and
    sort elsewhere (both bit-identical; measured in
    benchmarks/bench_indexing)."""
    if method == "auto":
        from repro.kernels import ops
        return "merge" if ops.on_tpu() else "sort"
    if method not in ("merge", "sort"):
        raise ValueError(f"unknown downsample method {method!r}")
    return method


def downsample(coords: CoordSet, layout: BitLayout, m: int,
               *, from_m: int = 0, method: str = "auto") -> CoordSet:
    """Closed-form downsample to stride ``2^m`` (Spira §5.5, Eq. 1):
    ``V_m = floor(V_0 / 2^m) * 2^m`` applied directly to level-``from_m``
    coordinates — one bitmask AND + run-merge/dedup. No recursive dependency
    on feature computation, which is what makes network-wide indexing legal.

    ``method="merge"`` is the run-aware merge (:func:`downsample_merge`);
    ``method="sort"`` is the documented fallback that re-sorts via
    :func:`build_coord_set` — kept because it is the simplest possible
    oracle (used by parity tests and as the baseline in
    ``benchmarks/bench_indexing``); ``method="auto"`` (default) picks per
    platform via :func:`resolve_downsample_method`.
    """
    if m == from_m:
        return coords
    if resolve_downsample_method(method) == "merge":
        return downsample_merge(coords, layout, m, from_m=from_m)
    pad = pad_value(coords.packed.dtype)
    rounded = jnp.where(coords.packed == pad, pad,
                        round_down(coords.packed, layout, m))
    return build_coord_set(rounded)


def downsample_all(v0: CoordSet, layout: BitLayout, levels: Tuple[int, ...],
                   method: str = "auto") -> Tuple[CoordSet, ...]:
    """All downsample levels from V0 — the network-wide form, and the one
    implementation plan building routes through.

    With ``method="merge"`` the levels are *chained*: each level is derived
    from the previous (already sorted, already deduplicated) level, so the
    per-step residue is only Δ = gap bits (4 runs for consecutive levels) and
    the whole plan performs exactly one true sort (at V0, in
    ``build_coord_set``). Chaining is legal because per-field flooring
    composes: round(round(v, a), b) == round(v, b) for b >= a. The chain
    trades the sort-per-level concurrency XLA could exploit for strictly
    less work per level — measured in ``benchmarks/bench_indexing``.
    """
    out = []
    prev_m = 0
    prev = v0
    for m in sorted(levels):
        cur = prev if m == prev_m else downsample(
            prev, layout, m, from_m=prev_m, method=method)
        out.append(cur)
        prev, prev_m = cur, m
    order = {m: i for i, m in enumerate(sorted(levels))}
    return tuple(out[order[m]] for m in levels)
