"""Network-wide voxel indexing (Spira §5.5).

Key observation from the paper: the voxel-indexing step of every SpC layer
is independent of every other layer's indexing *and* of all feature
computation, because downsampled coordinates have the closed form
``V_m = floor(V_0 / 2^m) * 2^m`` (Eq. 1) — no recursive dependency.

GPU Spira exploits this with concurrent CUDA streams across SMs. The TPU
adaptation: **one jitted graph** (`build_network_plan`) computes every
level's coordinate set and every layer's kernel map from V0. XLA's scheduler
is free to interleave the (data-independent) search pipelines, and under a
mesh the plan builder can be sharded so different devices index different
layers (see dist/). Feature computation then consumes the plan's kernel maps
layer by layer — indexing never sits on the critical path.

Indexing-cost discipline (PR 2):

* **One true sort per plan.** Levels come from ``voxel.downsample_all``,
  which sorts V0 once and derives every coarser level with a run-aware
  merge (``downsample_method``: "sort" keeps the old sort-per-level path as
  the documented fallback / baseline; "auto" — the default — uses the merge
  on TPU and the sort fallback off-TPU, where XLA's scalar scatter makes
  the merge a net loss).
* **Symmetry-aware submanifold maps.** Layers with ``m_in == m_out`` and
  ``spec.symmetry`` search only ⌈K³/2⌉ offset columns and fill the mirrors
  via ``zdelta.symmetrize_kernel_map`` (§5.4) — for both engines below.
* **Superwindow Pallas engine.** ``engine="zdelta_pallas"`` issues ONE
  window DMA per output tile shared by all anchor groups
  (kernels/zdelta_window.zdelta_superwindow_search); the per-group-window
  kernel of PR 1 stays available as ``engine="zdelta_pallas_window"`` for
  the DMA-count comparison in benchmarks/bench_indexing.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from .packing import BitLayout
from .voxel import CoordSet, build_coord_set, downsample, downsample_all
from .zdelta import (zdelta_offsets, zdelta_search, zdelta_search_symmetric,
                     simple_bsearch, symmetry_anchor_count, expand_half_map,
                     symmetrize_kernel_map)
from .kernel_map import KernelMap
from .spconv import SpConvSpec
from . import hashmap
from .packing import offset_grid, pack_offsets


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class NetworkPlan:
    """All coordinate sets (by stride level) + all kernel maps (by layer).

    ``stats`` carries per-layer degradation counters computed as a
    byproduct of plan building — today the number of Pallas superwindow
    (tile, offset-group) cells that overflowed their DMA'd window and were
    repaired by the XLA fallback (0 for non-Pallas engines). Serving
    surfaces them in ``SpiraSession``'s per-call HealthReport and lifts
    them into per-layer gauges on the session's metrics registry
    (``plan_window_overflow_cells_<layer>``, see ``repro.obs``); a
    persistent nonzero count means the tuner's ``plan_superwindow`` W is
    undersized for the traffic."""

    coords: Dict[int, CoordSet]       # level m -> coordinate set
    kmaps: Dict[str, KernelMap]       # layer name -> kernel map
    stats: Dict[str, jax.Array] = dataclasses.field(default_factory=dict)
    # layer name -> int32 scalar: overflowed window cells (see class doc)

    def tree_flatten(self):
        ck = sorted(self.coords)
        kk = sorted(self.kmaps)
        sk = sorted(self.stats)
        return ([self.coords[k] for k in ck] + [self.kmaps[k] for k in kk]
                + [self.stats[k] for k in sk],
                (tuple(ck), tuple(kk), tuple(sk)))

    @classmethod
    def tree_unflatten(cls, aux, children):
        ck, kk, sk = aux
        coords = dict(zip(ck, children[: len(ck)]))
        kmaps = dict(zip(kk, children[len(ck): len(ck) + len(kk)]))
        stats = dict(zip(sk, children[len(ck) + len(kk):]))
        return cls(coords, kmaps, stats)


def plan_levels(specs: Sequence[SpConvSpec]) -> Tuple[int, ...]:
    lv = set()
    for s in specs:
        lv.add(s.m_in)
        lv.add(s.m_out)
    return tuple(sorted(lv))


PLAN_BM = 128   # output-tile rows for the Pallas engines; the tuner's
                # plan_window / plan_superwindow model the same split


def _pallas_map(inputs: CoordSet, outputs: CoordSet, anchors, zstep,
                *, K: int, W: int = 0, superwindow: bool = True):
    """Windowed Pallas z-delta search with per-tile XLA overflow fallback.

    Any (tile, offset-group) cell whose queries ran past the DMA'd window
    is recomputed by the XLA search; `lax.cond` keeps the fallback off the
    execution path when nothing overflowed (the common case once the
    tuner's ``plan_superwindow`` sizes W exactly). Returns
    ``(map, overflowed_cells)`` — the overflow count is a degradation
    *signal* (the map itself is exact either way) that the plan exports in
    ``NetworkPlan.stats``.

    Outputs are PAD-padded here to a multiple of ``PLAN_BM`` so the kernel
    always runs full 128-row tiles regardless of the caller's capacity
    (PAD rows resolve to −1 and never count as overflow); the map is
    sliced back to the caller's capacity."""
    from repro.kernels import ops
    from repro.kernels.zdelta_window import (zdelta_superwindow_search,
                                             zdelta_window_search)

    if ops.on_tpu():
        raise NotImplementedError(
            "the z-delta Pallas search kernels (engine 'zdelta_pallas' / "
            "'zdelta_pallas_window') do not compile for TPU yet: Mosaic "
            "refuses their (1, bm) block shapes and in-VMEM vector gathers "
            "(ROADMAP). Use engine='zdelta', the XLA search.")
    mcap = outputs.packed.shape[0]
    bm = PLAN_BM
    mcap2 = ((mcap + bm - 1) // bm) * bm
    if mcap2 == mcap:       # already tile-aligned (e.g. bucketed serving)
        out_padded = outputs
    else:
        from .voxel import pad_value
        outp = jnp.full((mcap2,), pad_value(outputs.packed.dtype),
                        outputs.packed.dtype).at[:mcap].set(outputs.packed)
        out_padded = CoordSet(packed=outp, count=outputs.count)
    n = inputs.packed.shape[0]
    if superwindow:
        W = min(W or max(16 * bm, 2048), n)
        m_p, ovf = zdelta_superwindow_search(inputs, out_padded, anchors,
                                             zstep, K=K, W=W, bm=bm,
                                             interpret=True)
    else:
        W = min(W or max(4 * bm, 512), n)
        m_p, ovf = zdelta_window_search(inputs, out_padded, anchors, zstep,
                                        K=K, W=W, bm=bm, interpret=True)
    m_p = m_p[:mcap]

    def patched():
        m_x = zdelta_search(inputs, outputs, anchors, zstep, K=K)
        bad = jnp.repeat(jnp.repeat(ovf > 0, bm, axis=0), K, axis=1)[:mcap]
        return jnp.where(bad, m_x, m_p)

    m = jax.lax.cond(ovf.sum() > 0, patched, lambda: m_p)
    return m, (ovf > 0).sum().astype(jnp.int32)


def _layer_map(inputs: CoordSet, outputs: CoordSet, s: SpConvSpec,
               layout: BitLayout, engine: str):
    """One layer's kernel map, symmetry-aware for submanifold layers.
    Returns ``(map, window_overflow_cells)`` — the counter is 0 for every
    non-Pallas engine (their searches have no window to overflow)."""
    no_ovf = jnp.zeros((), jnp.int32)
    stride = s.offset_stride
    if engine in ("bsearch", "hash"):
        offs = pack_offsets(jnp.asarray(offset_grid(s.K, stride)), layout)
        if engine == "bsearch":
            return simple_bsearch(inputs, outputs, offs, K=s.K), no_ovf
        tk, tv = hashmap.build_table(
            inputs, table_size=hashmap.table_size_for(inputs.capacity))
        return hashmap.hash_kernel_map(tk, tv, outputs, offs, K=s.K), no_ovf
    if engine not in ("zdelta", "zdelta_pallas", "zdelta_pallas_window"):
        raise ValueError(f"unknown engine {engine!r}")

    _, anchors, zstep = zdelta_offsets(s.K, stride, layout)
    # §5.4: submanifold symmetry — search only the first ⌈K³/2⌉ columns
    # (groups [0, K²//2]) and fill mirrors by the M[i,k]=j ⇒ M[j,k̄]=i
    # identity. Legal because inputs and outputs are the same set.
    use_sym = (s.symmetry and s.submanifold
               and engine in ("zdelta", "zdelta_pallas"))
    if engine == "zdelta":
        if use_sym:
            return zdelta_search_symmetric(inputs, outputs, anchors, zstep,
                                           K=s.K), no_ovf
        return zdelta_search(inputs, outputs, anchors, zstep, K=s.K), no_ovf
    if use_sym:
        anchors = anchors[: symmetry_anchor_count(s.K)]
    m, ovf = _pallas_map(inputs, outputs, anchors, zstep, K=s.K, W=s.window,
                         superwindow=(engine == "zdelta_pallas"))
    if use_sym:
        m = symmetrize_kernel_map(expand_half_map(m, K=s.K), K=s.K)
    return m, ovf


@partial(jax.jit, static_argnames=("specs", "layout", "engine",
                                   "downsample_method"))
def build_network_plan(
    packed_raw: jax.Array,
    *,
    specs: Tuple[SpConvSpec, ...],
    layout: BitLayout,
    engine: str = "zdelta",   # "zdelta" | "zdelta_pallas" |
                              # "zdelta_pallas_window" | "bsearch" | "hash"
    downsample_method: str = "auto",   # "merge" (single-sort) | "sort" |
                                       # "auto" (merge on TPU, sort off-TPU)
) -> NetworkPlan:
    """One-shot, network-wide indexing: a single XLA module containing every
    layer's downsample + mapping, all derived from V0 with exactly one sort
    (``downsample_method="merge"``).

    ``downsample_method="auto"`` resolves per backend, same pattern as the
    Pallas interpret fallback: the run-merge replaces per-level O(N log²N)
    bitonic sorts with linear rank/scatter passes on TPU, but XLA lowers
    scatter element-sequentially on CPU where ``std::sort`` is nearly free,
    so off-TPU hosts keep the sort path (measured in
    benchmarks/bench_indexing; both are bit-identical).

    ``engine`` selects the mapping algorithm (zdelta = Spira; bsearch and
    hash are the paper's baselines) so benchmarks compare within one code
    path. ``zdelta_pallas`` runs the superwindow Pallas kernel (one DMA per
    output tile; CPU interpreter only — it raises on TPU, whose compiler
    refuses it) per layer, with a per-tile fallback
    to the XLA search for window-overflow cells — maps are identical to
    ``zdelta`` by construction; ``zdelta_pallas_window`` keeps PR 1's
    per-group-window kernel for comparison. The per-layer window W comes
    from each spec (``spec.window``, 0 = auto; the tuner's
    ``plan_superwindow`` sizes it exactly). Submanifold layers with
    ``spec.symmetry`` use the §5.4 half-search for the zdelta engines.

    The plan's operations carry the ``jax.named_scope`` names
    ``plan/sort``, ``plan/downsample`` and ``plan/search`` in their op-name
    metadata, so a device trace can charge their time to each stage.
    """
    with jax.named_scope("plan/sort"):
        v0 = build_coord_set(packed_raw)
    levels = plan_levels(specs)
    with jax.named_scope("plan/downsample"):
        coords: Dict[int, CoordSet] = dict(zip(
            levels, downsample_all(v0, layout, levels,
                                   method=downsample_method)))

    kmaps: Dict[str, KernelMap] = {}
    stats: Dict[str, jax.Array] = {}
    for s in specs:
        inputs, outputs = coords[s.m_in], coords[s.m_out]
        # One scope for every layer's search: XLA merges identical searches
        # of different layers, so a per-layer name would be ambiguous.
        with jax.named_scope("plan/search"):
            m, ovf = _layer_map(inputs, outputs, s, layout, engine)
        kmaps[s.name] = KernelMap(m=m, out_count=outputs.count,
                                  in_count=inputs.count)
        stats[s.name] = ovf
    return NetworkPlan(coords=coords, kmaps=kmaps, stats=stats)


def sequential_plan_fns(specs: Tuple[SpConvSpec, ...], layout: BitLayout):
    """Sequential-indexing baseline for the paper's Fig. 12: one jitted
    downsample function per level and one jitted mapping function per layer,
    each its own XLA module, called back-to-back — nothing can overlap
    across layers (vs. the single fused module of build_network_plan), and
    every level pays its own full sort (the pre-PR-2 cost model)."""
    @jax.jit
    def sort_fn(packed_raw):
        return build_coord_set(packed_raw)

    level_fns = {}
    for m in plan_levels(specs):
        if m == 0:
            continue
        level_fns[m] = jax.jit(
            lambda c, m=m: downsample(c, layout, m, method="sort"))

    map_fns = {}
    for s in specs:
        _, anchors, zstep = zdelta_offsets(s.K, s.offset_stride, layout)

        def make(s=s, anchors=anchors, zstep=zstep):
            @jax.jit
            def one(inputs: CoordSet, outputs: CoordSet) -> KernelMap:
                m = zdelta_search(inputs, outputs, anchors, zstep, K=s.K)
                return KernelMap(m=m, out_count=outputs.count, in_count=inputs.count)
            return one
        map_fns[s.name] = make()
    return sort_fn, level_fns, map_fns
