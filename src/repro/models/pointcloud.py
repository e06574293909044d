"""The paper's evaluation networks on the Spira engine:

* SparseResNet-21 (ResN)      — 21 SpC layers, K=3 backbone
* MinkUNet-42 (UNet)          — 42 layers, encoder/decoder with inverse convs
* CenterPoint-Large (ResNL)   — ResNet backbone with K=5 submanifold stages

All voxel indexing (coord sets + kernel maps for every layer) happens once,
up front, via ``core.build_network_plan`` — the network-wide indexing of
Spira §5.5 — then the feature pass consumes the plan's kernel maps.

Segmented-reduction bit-invariance lemma
----------------------------------------
Batched rows are batch-major-sorted, so every per-scene statistic in this
module (train-mode BN moments, and through the same engine the scene
pooling and loss reductions in ``train.pointcloud``) is a reduction over a
*contiguous* row segment. The engine (``kernels.segsum``) computes it in
one O(N) pass under an explicitly specified add schedule — rows chunked by
*segment-relative* position, strictly sequential fp32 adds within a chunk
and across chunk partials, invalid rows skipped. Because the schedule
depends only on each row's position relative to its segment's start:

* a scene's statistics are **bitwise alignment-invariant** — identical
  whether its rows sit at offset 0 (a single-scene run) or mid-buffer in a
  batch, which is what makes a batch-of-B forward *and its gradients*
  bit-identical to B single-scene runs (tests/test_session.py,
  tests/test_segsum.py);
* they are **bitwise zero-extension invariant** — padding to a larger pow2
  capacity bucket appends rows outside every segment, which the schedule
  skips (tests/test_train_pointcloud.py pins this for parameter grads).

Whole-buffer (S-static) reductions still use ``core.dataflow.rowsum``'s
fixed-blocking dot — see its docstring for why *that* shape needs a
library dot, and why per-scene segments (arbitrary offsets) need the
engine's explicit schedule instead. The backward never meets an XLA
scatter-add: ``segment_gather``'s VJP *is* ``segment_sum``.

The retired O(S·cap) formulation (``dynamic_slice`` per scene + a
``[cap, S]`` one-hot application matmul) survives only as
:func:`_relu_bn_sliced`, the reference baseline benchmarks compare
against; its trace counter must stay at zero in compiled session/train
graphs (tests/test_segsum.py asserts this).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (KernelMap, SpConvSpec, apply_spconv, init_spconv,
                        build_network_plan)
from repro.core.dataflow import (bcast_rows as _bcast_rows,
                                 rowdot_matmul, rowsum as _rowsum)
from repro.core.packing import BitLayout
from repro.kernels.segsum import (SegmentSpec, segment_gather,
                                  segment_moments)


@dataclasses.dataclass(frozen=True)
class PointCloudNet:
    name: str
    specs: Tuple[SpConvSpec, ...]
    in_channels: int
    n_classes: int

    def conv_specs(self) -> Tuple[SpConvSpec, ...]:
        return self.specs


def _res_stage(name: str, c_in: int, c_out: int, m: int, n_blocks: int,
               K: int = 3, dataflow: str = "os", t: int = 0,
               backend: str = "auto") -> List[SpConvSpec]:
    """Downsample conv (except stage 0) + n_blocks residual submanifold pairs."""
    specs: List[SpConvSpec] = []
    if m > 0:
        specs.append(SpConvSpec(f"{name}_down", c_in, c_out, K=3,
                                m_in=m - 1, m_out=m, dataflow=dataflow,
                                backend=backend))
        c_in = c_out
    for b in range(n_blocks):
        specs.append(SpConvSpec(f"{name}_b{b}a", c_in, c_out, K=K, m_in=m,
                                m_out=m, dataflow=dataflow, t=t, backend=backend))
        specs.append(SpConvSpec(f"{name}_b{b}b", c_out, c_out, K=K, m_in=m,
                                m_out=m, dataflow=dataflow, t=t, backend=backend))
        c_in = c_out
    return specs


def sparse_resnet21(in_channels: int = 4, n_classes: int = 20,
                    width: Sequence[int] = (16, 32, 64, 128),
                    dataflow: str = "os", backend: str = "auto") -> PointCloudNet:
    """21 SpC layers: stem + 4 stages × (down + 2 res-pairs)... matching the
    paper's ResN layer count."""
    specs: List[SpConvSpec] = [
        SpConvSpec("stem", in_channels, width[0], K=3, m_in=0, m_out=0,
                   dataflow=dataflow, backend=backend)]
    c = width[0]
    for s, w in enumerate(width):
        n_blocks = 1 if s < 2 else 1
        specs += _res_stage(f"s{s}", c, w, m=s, n_blocks=n_blocks,
                            dataflow=dataflow, backend=backend)
        c = w
    # head convs to reach 21
    while len(specs) < 21:
        specs.append(SpConvSpec(f"head{len(specs)}", c, c, K=3,
                                m_in=len(width) - 1, m_out=len(width) - 1,
                                dataflow=dataflow, backend=backend))
    return PointCloudNet("sparse_resnet21", tuple(specs), in_channels, n_classes)


def minkunet42(in_channels: int = 4, n_classes: int = 20,
               width: Sequence[int] = (32, 64, 128, 256),
               dataflow: str = "os", backend: str = "auto") -> PointCloudNet:
    # NB: the paper finds UNet favors weight-stationary **on GPU**. On TPU
    # there are no atomics, so WS merges row by row; "os" is the default
    # on that argument alone — no chip run has compared the two yet
    # (ROADMAP 1c). Pass dataflow="ws" to reproduce the GPU preference.
    """Encoder (4 downsample stages) + decoder (4 inverse-conv stages) with
    submanifold pairs at each level — 42 SpC layers total."""
    specs: List[SpConvSpec] = [
        SpConvSpec("stem0", in_channels, width[0], K=3, m_in=0, m_out=0,
                   dataflow=dataflow, backend=backend),
        SpConvSpec("stem1", width[0], width[0], K=3, m_in=0, m_out=0,
                   dataflow=dataflow, backend=backend)]
    c = width[0]
    for s, w in enumerate(width):  # encoder: 4 × (down + 2 sub) = 12
        specs.append(SpConvSpec(f"enc{s}_down", c, w, K=3, m_in=s, m_out=s + 1,
                                dataflow=dataflow, backend=backend))
        specs.append(SpConvSpec(f"enc{s}_a", w, w, K=3, m_in=s + 1, m_out=s + 1,
                                dataflow=dataflow, backend=backend))
        specs.append(SpConvSpec(f"enc{s}_b", w, w, K=3, m_in=s + 1, m_out=s + 1,
                                dataflow=dataflow, backend=backend))
        c = w
    dec_width = (128, 96, 96, 96)
    for s in range(4):             # decoder: 4 × (up + skip-merge sub ×2)
        lvl = 4 - s - 1
        w = dec_width[s]
        specs.append(SpConvSpec(f"dec{s}_up", c, w, K=3, m_in=lvl + 1,
                                m_out=lvl, dataflow=dataflow, backend=backend))
        skip_c = width[lvl - 1] if lvl > 0 else width[0]
        specs.append(SpConvSpec(f"dec{s}_a", w + skip_c, w, K=3, m_in=lvl,
                                m_out=lvl, dataflow=dataflow, backend=backend))
        specs.append(SpConvSpec(f"dec{s}_b", w, w, K=3, m_in=lvl, m_out=lvl,
                                dataflow=dataflow, backend=backend))
        c = w
    # extra submanifold pairs to reach 42 layers (paper count)
    i = 0
    while len(specs) < 42:
        specs.append(SpConvSpec(f"tail{i}", c, c, K=3, m_in=0, m_out=0,
                                dataflow=dataflow, backend=backend))
        i += 1
    return PointCloudNet("minkunet42", tuple(specs), in_channels, n_classes)


def centerpoint_large(in_channels: int = 5, n_classes: int = 10,
                      width: Sequence[int] = (16, 32, 32, 64),
                      dataflow: str = "hybrid", t: int = 3,
                      backend: str = "auto") -> PointCloudNet:
    """CenterPoint-Large (ResNL): K=5 submanifold layers in all stages."""
    specs: List[SpConvSpec] = [
        SpConvSpec("stem", in_channels, width[0], K=5, m_in=0, m_out=0,
                   dataflow=dataflow, t=t, backend=backend)]
    c = width[0]
    for s, w in enumerate(width):
        specs += _res_stage(f"s{s}", c, w, m=s, n_blocks=1, K=5,
                            dataflow=dataflow, t=t, backend=backend)
        c = w
    while len(specs) < 20:
        specs.append(SpConvSpec(f"head{len(specs)}", c, c, K=5, m_in=3,
                                m_out=3, dataflow=dataflow, t=t, backend=backend))
    return PointCloudNet("centerpoint_large", tuple(specs), in_channels,
                         n_classes)


def tiny_segnet(in_channels: int = 4, n_classes: int = 8, width: int = 16,
                depth: int = 4, dataflow: str = "os",
                backend: str = "auto") -> PointCloudNet:
    """A small all-submanifold segmentation net (stride-0 throughout, so
    logits land on the INPUT coordinate set — the shape the per-voxel
    training loss wants). The smoke-scale workload for
    ``train.pointcloud`` / examples/train_pointcloud.py: big enough to
    exercise BN + the custom-VJP dataflows at every layer, small enough to
    train in seconds on CPU."""
    specs: List[SpConvSpec] = [
        SpConvSpec("stem", in_channels, width, K=3, m_in=0, m_out=0,
                   dataflow=dataflow, backend=backend)]
    for i in range(depth - 1):
        specs.append(SpConvSpec(f"sub{i}", width, width, K=3, m_in=0, m_out=0,
                                dataflow=dataflow, backend=backend))
    return PointCloudNet("tiny_segnet", tuple(specs), in_channels, n_classes)


NETWORKS = {
    "sparse_resnet21": sparse_resnet21,
    "minkunet42": minkunet42,
    "centerpoint_large": centerpoint_large,
    "tiny_segnet": tiny_segnet,
}


# ---------------------------------------------------------------------------
# parameters + feature pass
# ---------------------------------------------------------------------------

def init_pointcloud(key: jax.Array, net: PointCloudNet, dtype=jnp.float32) -> dict:
    params = {}
    keys = jax.random.split(key, len(net.specs) + 1)
    for k, spec in zip(keys, net.specs):
        params[spec.name] = init_spconv(k, spec, dtype)
    params["head"] = (jax.random.normal(keys[-1],
                                        (net.specs[-1].cout, net.n_classes),
                                        dtype) * 0.02)
    return params


# trace-time counter for the retired O(S·cap) BN formulation — the
# acceptance gate "batched BN issues zero per-scene dynamic_slice / [cap, S]
# one-hot passes" is asserted by tracing compiled graphs and checking this
# stays 0 while kernels.segsum.segment_call_count() grows (test_segsum.py)
SLICED_BN_CALLS = {"count": 0}


def reset_sliced_bn_calls() -> None:
    SLICED_BN_CALLS["count"] = 0


def sliced_bn_call_count() -> int:
    return SLICED_BN_CALLS["count"]


def _relu_bn(x: jax.Array, count: jax.Array, seg: "tuple | None" = None, *,
             segment: SegmentSpec | None = None) -> jax.Array:
    """ReLU + masked feature standardization (train-mode BN), per scene —
    one O(N) pass over the segmented-reduction engine, both directions.

    ``seg = (sid, starts, counts, S)`` describes the scene segmentation of
    this level's rows (scene id per row, each scene's first row and row
    count, static scene-slot count S) — :func:`level_segments` derives it
    from the batch bits. ``seg=None`` is the single-scene case, expressed
    as the S=1 segmentation of the valid prefix so every path runs the one
    engine (the single substrate).

    Moments are one segment sum over ``concat([z, z²])`` (one-pass
    var = E[x²] − mean²: a (x − mean)² second pass would re-feed a
    reduction result through another reduction). The per-scene application
    is a ``segment_gather`` broadcast of ``concat([mean, inv])`` — its VJP
    is the engine's segment sum, so autodiff's transposed reductions keep
    the segment-relative grouping (module doc lemma) instead of lowering
    to a scatter-add or an S-wide one-hot dot. Everything here is
    bit-invariant under scene alignment and zero extension, which is what
    makes batched-vs-looped runs and their gradients bit-identical."""
    x = jax.nn.relu(x)
    cap, c = x.shape
    if seg is None:
        sid = jnp.where(jnp.arange(cap) < count, 0, 1).astype(jnp.int32)
        starts = jnp.zeros((1,), jnp.int32)
        counts = jnp.asarray(count, jnp.int32).reshape(1)
        S = 1
    else:
        sid, starts, counts, S = seg
    sx, sx2 = segment_moments(x, sid, starts, counts, num_segments=S,
                              spec=segment)                     # [S, c] × 2
    denom = jnp.maximum(counts.astype(jnp.float32), 1.0)[:, None]
    mean = sx / denom
    var = jnp.maximum(sx2 / denom - mean * mean, 0.0)
    inv = jax.lax.rsqrt(var + 1e-5)
    stats = jnp.concatenate([mean, inv], axis=1).astype(x.dtype)
    r = segment_gather(stats, sid, starts, counts, num_segments=S,
                       spec=segment)                            # [cap, 2c]
    return jnp.where((sid < S)[:, None], (x - r[:, :c]) * r[:, c:], 0)


def _relu_bn_sliced(x: jax.Array, count: jax.Array,
                    seg: "tuple | None" = None) -> jax.Array:
    """The RETIRED O(S·cap) per-scene BN: S capacity-wide ``dynamic_slice``
    alignment passes for the statistics plus a ``[cap, S]`` one-hot
    application matmul (whose backward is another S-wide dot). Kept only
    as the baseline the benchmarks price the segment engine against
    (bench_train's ``segment_vs_sliced_bn``, fig11) and as a numerical
    cross-check in tests — nothing on the compiled session/train path may
    call it (SLICED_BN_CALLS pins that)."""
    SLICED_BN_CALLS["count"] += 1
    x = jax.nn.relu(x)
    cap = x.shape[0]

    def stats(v, valid, cnt):
        c = v.shape[1]
        z = jnp.where(valid, v, 0)
        s = _rowsum(jnp.concatenate([z, z * z], axis=1))
        denom = jnp.maximum(cnt.astype(v.dtype), 1.0)
        mean, ex2 = s[:c] / denom, s[c:] / denom
        var = jnp.maximum(ex2 - mean * mean, 0.0)
        return mean, jax.lax.rsqrt(var + 1e-5)

    if seg is None or seg[3] == 1:
        mask = (jnp.arange(cap) < count)[:, None]
        mean, inv = stats(x, mask, count)
        return jnp.where(mask,
                         (x - _bcast_rows(mean, cap)) * _bcast_rows(inv, cap),
                         0)
    sid, starts, counts, S = seg
    xpad = jnp.concatenate([x, jnp.zeros_like(x)])
    local = jnp.arange(cap)
    means, invs = [], []
    for b in range(S):
        sl = jax.lax.dynamic_slice(xpad, (starts[b], 0), (cap, x.shape[1]))
        mean, inv = stats(sl, (local < counts[b])[:, None], counts[b])
        means.append(mean)
        invs.append(inv)
    sid_c = jnp.clip(sid, 0, S - 1)
    onehot = (sid_c[:, None] == jnp.arange(S)[None, :]).astype(x.dtype)
    mean_r = jnp.dot(onehot, jnp.stack(means))
    inv_r = jnp.dot(onehot, jnp.stack(invs))
    valid = (sid < S)[:, None]
    return jnp.where(valid, (x - mean_r) * inv_r, 0)


def packed_segments(packed: jax.Array, count: jax.Array,
                    layout: BitLayout) -> tuple:
    """Scene segmentation ``(sid, starts, counts, S)`` of one packed-row
    buffer, from its batch bits — the engine's input contract
    (``kernels.segsum`` module doc). Rows are batch-major-sorted, so each
    scene is one contiguous segment; ``searchsorted`` on the per-row scene
    ids yields each scene's start and count. Invalid (PAD) rows get scene
    id S, which sorts after every real scene."""
    S = 1 << layout.bb
    rows = jnp.arange(packed.shape[0])
    sid_raw = (packed >> layout.shift_b).astype(jnp.int32) & (S - 1)
    sid = jnp.where(rows < count, sid_raw, S)
    scene_ids = jnp.arange(S, dtype=sid.dtype)
    starts = jnp.searchsorted(sid, scene_ids, side="left").astype(jnp.int32)
    ends = jnp.searchsorted(sid, scene_ids, side="right").astype(jnp.int32)
    return (sid, starts, ends - starts, S)


def level_segments(plan, layout: BitLayout) -> Dict[int, tuple]:
    """Scene segmentation of every level's rows (:func:`packed_segments`
    per coordinate set), keyed by stride level."""
    return {m: packed_segments(cs.packed, cs.count, layout)
            for m, cs in plan.coords.items()}


def pointcloud_forward(params: dict, net: PointCloudNet, plan,
                       features: jax.Array, *,
                       layout: BitLayout | None = None,
                       segment: SegmentSpec | None = None) -> jax.Array:
    """Run the feature-computation pass over a precomputed NetworkPlan.

    Handles UNet skip connections by stashing encoder outputs per level and
    concatenating at ``dec*_a`` layers (channel concat on the fine coords).

    ``layout`` enables batched multi-scene execution: when given and it
    carries batch bits, BN statistics and masking are computed *per scene*
    (scene segments recovered from the batch bits of each level's packed
    coordinates) through the O(N) segmented-reduction engine, so a
    batch-of-B run is bit-identical to B single-scene runs (module doc
    lemma). Without it (legacy single-scene calls), statistics span the
    whole valid prefix — the same engine with S=1. ``segment`` selects the
    engine backend/chunking (``kernels.segsum.SegmentSpec``, tuner-owned
    via the session).

    Each layer's operations run under the ``jax.named_scope`` names
    ``<layer>/conv`` and ``<layer>/norm``, the classifier under ``head``
    and the scene segmentation under ``plan/segments``: the op-name
    metadata a device trace charges their time by."""
    from repro.core.sparse_tensor import SparseTensor

    if isinstance(features, SparseTensor):
        raise TypeError(
            "pointcloud_forward takes a raw feature array aligned with the "
            "plan's V0 rows; you passed a SparseTensor. Either run it "
            "through a compiled session (repro.serve.compile_network(net, "
            "layout)(st) — the recommended front door) or pass st.features "
            "with a plan built from st.packed.")
    missing = [s.name for s in net.specs if s.name not in plan.kmaps]
    if missing:
        raise ValueError(
            f"plan has no kernel map for layer(s) {missing[:3]}{'...' if len(missing) > 3 else ''} — "
            "it was built for different specs than this network's. Build "
            "plan and network together, or let the session API own both: "
            "repro.serve.compile_network(net, layout).")
    cap0 = plan.kmaps[net.specs[0].name].m.shape[0] if net.specs else None
    lvl0 = net.specs[0].m_in if net.specs else 0
    in_cap = plan.coords[lvl0].capacity if lvl0 in plan.coords else cap0
    if in_cap is not None and features.shape[0] != in_cap:
        raise ValueError(
            f"features rows ({features.shape[0]}) != plan input capacity "
            f"({in_cap}) — plan and features were bucketed differently. The "
            "session API (repro.serve.compile_network) pads both "
            "consistently; if hand-stitching, pad features to the plan's "
            "V0 capacity.")
    with jax.named_scope("plan/segments"):
        segs = level_segments(plan, layout) if (layout and layout.bb) else {}
    skips: Dict[int, jax.Array] = {}
    x = features
    for spec in net.specs:
        kmap = plan.kmaps[spec.name]
        if spec.name.startswith("dec") and spec.name.endswith("_a"):
            skip = skips.get(spec.m_in)
            if skip is not None:
                x = jnp.concatenate([x, skip], axis=-1)
        with jax.named_scope(f"{spec.name}/conv"):
            x = apply_spconv(params[spec.name], spec, x, kmap)
        with jax.named_scope(f"{spec.name}/norm"):
            x = _relu_bn(x, kmap.out_count, segs.get(spec.m_out),
                         segment=segment)
        if spec.name.startswith("enc") and spec.name.endswith("_b"):
            skips[spec.m_out] = x
        if spec.name.startswith("stem"):
            skips[0] = x
    # head dW reduces over the capacity axis — rowdot_matmul keeps that
    # contraction's grouping capacity-stable (core.dataflow doc)
    with jax.named_scope("head"):
        return rowdot_matmul(x, params["head"].astype(x.dtype))
