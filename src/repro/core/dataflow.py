"""Feature computation dataflows (Spira §5.4), TPU-native.

Output-stationary (OS): gather + GEMM per offset, no filtering — wasted MACs
on invalid entries but no merge step. Weight-stationary (WS): per-offset
filtering/compaction of valid (input→output) pairs to a static capacity,
GEMM over valid pairs only, then a *deterministic* merge. The GPU version
merges with atomicAdd; TPU has no atomics, so the merge is a scatter with
unique per-offset indices accumulated across offsets by the scan carry —
bitwise-reproducible (DESIGN.md §2).

Hybrid: a static L1-norm threshold t splits offsets into a dense set (OS)
and a sparse set (WS); both partial results sum into the output. The split
is host-static so XLA sees a fixed graph (kernel_map.l1_partition).

Backend-dispatch contract
-------------------------
Every dataflow takes ``backend`` ∈ {"auto", "xla", "pallas"}:

* ``"xla"``    — the jnp paths below: OS materializes the gathered
  features (``[M, Cin]`` per offset, or ``[M, Kd, Cin]`` with ``fuse``)
  in HBM; WS scans offsets with a cumsum-compaction + scatter merge.
* ``"pallas"`` — the fused implicit-GEMM kernels
  (``kernels/spconv_gather_gemm.py`` / ``kernels/ws_scatter_gemm.py``):
  the kernel-map gather/compaction happens *inside* the kernel from
  HBM-resident F_in, so no gathered-feature intermediate ever exists in
  HBM. On non-TPU hosts the kernels run in interpreter mode (identical
  numerics, CPU-speed) so Pallas-tuned specs remain runnable anywhere.
* ``"auto"``   — "pallas" on TPU, "xla" elsewhere
  (``kernels.ops.resolve_backend``).

Numerics are identical across backends: fp32 accumulation per offset over
the same operands in the same offset order (the parity suite in
tests/test_dataflow_backends.py asserts bit-equality on valid rows).
Tile sizes ``bm``/``bn`` (0 = auto: 128-row tiles with padding, 128- or
whole-``Cout`` channel tiles) come from the layer spec and are chosen by
``core.tuner.tune_layer_measure``, which co-tunes (t, backend, bm, bn, W)
per layer. The kernel-map side has the same split: ``network_plan``'s
``engine="zdelta_pallas"`` uses the windowed Pallas search with a per-tile
XLA fallback when a window overflows (see build_network_plan).

``hbm_bytes_model`` is the shared analytic traffic model benchmarks use to
report the bytes the fused path saves next to wall-clock.

Differentiability (the training subsystem's contract)
-----------------------------------------------------
``output_stationary`` and ``weight_stationary`` carry a ``jax.custom_vjp``
built on the kernel-map transposition identity (Spira §5.4, TorchSparse's
transposed-map training): ``M[i,k] = j ⇒ Mᵀ[j, mirror(k)] = i``. The
backward pass therefore needs **no new kernel-map search**:

* **dF_in** is the *same dataflow run over the transposed map*
  (``kernel_map.transpose_kernel_map`` — one flat int32 scatter, the
  rectangular generalization of ``zdelta.symmetrize_kernel_map``; for
  submanifold maps it equals the forward map outright) with the weights
  mirrored along the offset axis and transposed in (Cin, Cout). The same
  backend dispatch applies, so on TPU the backward runs the *same fused
  Pallas kernels* as forward (``spconv_gather_gemm`` for OS,
  ``ws_scatter_gemm`` for WS) — training never materializes the
  ``[M, Kd, Cin]`` intermediate either direction.
* **dW** is Kd per-offset gathered-feature GEMMs ``Gₖᵀ @ g`` in a scan —
  an ``[M, Cin]`` working set per offset, never ``[M, Kd, Cin]``.
* WS drop semantics are honored exactly: pairs beyond ``capacity`` are
  masked out of the map *before* transposition (``ws_kept_map``), so the
  VJP is the true derivative of the capacity-dropped forward function.

``hybrid`` composes the two custom VJPs; ``apply_spconv`` (and the whole
``pointcloud_forward`` pass) differentiates through them with plain
``jax.grad``. The raw XLA implementations stay exposed as :func:`os_xla` /
:func:`ws_xla` (no custom VJP) so tests can compare our backward against
JAX's autodiff of the reference path.

Backward precondition — mirror-closed column sets: the transposition
mirrors column position ``p`` to ``Kd−1−p``, which equals the true offset
mirror ``δ → −δ`` only when the map's columns are a *mirror-closed,
offset-ordered subset* of the K³ grid. The full map trivially qualifies,
and so do ``l1_partition`` subsets (L1 is symmetric under negation and
negation reverses the sorted order), which is every subset the engine
itself ever takes a gradient through. Differentiating a hand-sliced
arbitrary column subset would produce a correct forward but silently
mispaired dF_in weights — don't.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .kernel_map import KernelMap, l1_partition, transpose_kernel_map


def _mask_rows(x: jax.Array, count: jax.Array) -> jax.Array:
    """Zero rows at and beyond ``count``. Skippable when the caller knows
    statically that ``count == capacity`` (``SpConvSpec.dense``)."""
    return jnp.where((jnp.arange(x.shape[0]) < count)[:, None], x, 0)


def rowsum(x: jax.Array) -> jax.Array:
    """Column sums as a ``[1, N] @ [N, C]`` matmul — the only *whole-buffer*
    reduction we found whose result is **bitwise zero-extension invariant**
    in practice.

    The batched-vs-looped bit-identity contract needs: padding the buffer
    with zero rows (a larger capacity bucket) must not change the sum by
    even one ulp. ``jnp.sum`` regroups operands when the extent changes.
    Hand-built elementwise reduction trees (halving adds, adjacent-pair
    reshapes, with or without optimization_barriers) are mathematically
    invariant but NOT in practice: embedded in a large jitted graph, XLA CPU
    re-codegens the add chain per shape (fusion recomputation + FMA
    contraction) and results drift by an ulp between capacity buckets —
    observed and bisected on MinkUNet-42. A dot is a library call with
    materialized operands and fixed k-panel blocking: the shared row prefix
    is grouped identically at any N, and zero rows only append exact ``+0``
    panel contributions. It is also the TPU-native choice (reductions ride
    the MXU).

    This is the single home of the bit-invariant reduction idiom: BN's
    cross-scene totals, the spconv bias backward (via :func:`bcast_rows`)
    and the segment engine's S-static combines all route through it. For
    *per-scene* reductions — a segment at an arbitrary row offset, where a
    dot's internal grouping can't be pinned — the segmented-reduction
    engine (``kernels.segsum``) extends the same fixed-grouping guarantee
    with an explicitly specified, segment-relative add schedule."""
    return jnp.dot(jnp.ones((1, x.shape[0]), x.dtype), x,
                   preferred_element_type=jnp.float32)[0].astype(x.dtype)


def bcast_rows(v: jax.Array, cap: int) -> jax.Array:
    """Broadcast a [C] vector over ``cap`` rows as a rank-1 matmul
    ``ones[cap, 1] @ v[None, :]`` instead of a plain broadcast.

    Forward-exact (each element is ``1·v + nothing``), but the point is the
    *backward*: the transpose of a dot is a dot, so the cotangent reduction
    over rows that autodiff inserts here is a ``[1, cap] @ [cap, C]``
    matmul — :func:`rowsum`, which documents why that property needs a
    dot — instead of an XLA elementwise reduce whose grouping drifts
    between capacity buckets. Every whole-buffer broadcast on the training
    forward path (conv bias, single-scene BN totals) routes through this
    one helper so the invariance-critical idiom has a single home; the
    per-scene analogue is ``kernels.segsum.segment_gather``."""
    return jnp.dot(jnp.ones((cap, 1), v.dtype), v[None, :])


def chunked_rowdot(x: jax.Array, g: jax.Array, q: int = 256) -> jax.Array:
    """``xᵀ @ g`` (contraction over the capacity-sized row axis) with a
    capacity-stable operand grouping: fixed-extent ``[A, q] @ [q, B]``
    panel dots combined strictly sequentially in a scan.

    A plain ``x.T @ g`` is NOT bitwise zero-extension invariant once the
    contraction crosses the dot library's k-panel boundary (~512 rows on
    XLA CPU): growing N re-tiles the panels, regrouping the shared prefix
    — measured at [8, 896]·[896, 5] vs the same data zero-extended to
    1792 (the dW/head-gradient shape; :func:`rowsum`'s [1, N] shape is
    the one empirically stable case). Here every dot has the SAME static
    shape at any capacity — one executable, one grouping — and the
    cross-panel combine is loop-carried, which XLA never reassociates.
    Appending zero rows appends exact-zero panel products. This is the
    row-reduction primitive for every gradient contraction over a
    capacity-sized axis (``_dw_per_offset``, the classifier head's dW);
    the *per-scene* analogue with the same philosophy is
    ``kernels.segsum``."""
    n, a = x.shape
    npad = ((n + q - 1) // q) * q
    if npad != n:
        x = jnp.pad(x, ((0, npad - n), (0, 0)))
        g = jnp.pad(g, ((0, npad - n), (0, 0)))
    xc = x.reshape(npad // q, q, a)
    gc = g.reshape(npad // q, q, g.shape[1])

    def body(acc, xs):
        xq, gq = xs
        return acc + jnp.dot(xq.T, gq,
                             preferred_element_type=jnp.float32), None

    acc0 = jnp.zeros((a, g.shape[1]), jnp.float32)
    out, _ = jax.lax.scan(body, acc0, (xc, gc))
    return out


@jax.custom_vjp
def rowdot_matmul(x: jax.Array, w: jax.Array) -> jax.Array:
    """``x @ w`` whose weight gradient reduces over the capacity-sized row
    axis via :func:`chunked_rowdot` (autodiff's native ``xᵀ @ g`` would
    regroup between capacity buckets — its docstring). The forward and dx
    contract over the static channel axis only, so they need no help. Use
    for any dense layer applied per voxel row (the classifier head)."""
    return jnp.dot(x, w)


def _rowdot_matmul_fwd(x, w):
    return jnp.dot(x, w), (x, w)


def _rowdot_matmul_bwd(res, g):
    x, w = res
    return (jnp.dot(g, w.T).astype(x.dtype),
            chunked_rowdot(x, g).astype(w.dtype))


rowdot_matmul.defvjp(_rowdot_matmul_fwd, _rowdot_matmul_bwd)


# ---------------------------------------------------------------------------
# raw XLA implementations (reference-differentiable, no custom VJP)
# ---------------------------------------------------------------------------

def os_xla(features: jax.Array, m: jax.Array, weights: jax.Array,
           *, fuse: bool = False) -> jax.Array:
    """OS dataflow, pure-XLA. ``fuse=True`` materializes one [M, Kd, Cin]
    gather and a single MXU contraction (max utilization, Kd·Cin-deep);
    default scans offsets with an [M, Cin] working set (memory-safe).

    No custom VJP here — this is the autodiff oracle the gradient tests
    differentiate with plain ``jax.grad`` (tests/test_grad.py)."""
    mc = m.shape[0]
    if fuse:
        idx = jnp.clip(m, 0)
        g = features[idx] * (m >= 0)[..., None].astype(features.dtype)
        return jnp.einsum("mkc,kcd->md", g, weights,
                          preferred_element_type=jnp.float32).astype(features.dtype)

    def body(acc, xs):
        m_col, w_k = xs
        g = features[jnp.clip(m_col, 0)] * (m_col >= 0)[:, None].astype(features.dtype)
        return acc + jnp.dot(g, w_k, preferred_element_type=jnp.float32), None

    acc0 = jnp.zeros((mc, weights.shape[-1]), jnp.float32)
    acc, _ = jax.lax.scan(body, acc0, (m.T, weights))
    return acc.astype(features.dtype)


def ws_xla(features: jax.Array, m: jax.Array, weights: jax.Array,
           *, capacity: int) -> jax.Array:
    """WS dataflow, pure-XLA scan (compaction + GEMM + deterministic
    scatter merge). Same drop semantics as the fused kernel. No custom VJP
    (autodiff oracle; see :func:`os_xla`)."""
    mc = m.shape[0]
    rows = jnp.arange(mc, dtype=jnp.int32)

    def body(acc, xs):
        m_col, w_k = xs
        valid = m_col >= 0
        dest = jnp.where(valid, jnp.cumsum(valid) - 1, capacity)
        in_idx = jnp.zeros((capacity,), jnp.int32).at[dest].set(
            jnp.clip(m_col, 0), mode="drop")
        out_idx = jnp.full((capacity,), mc, jnp.int32).at[dest].set(rows, mode="drop")
        nvalid = valid.sum()
        g = features[in_idx] * (jnp.arange(capacity) < nvalid)[:, None].astype(features.dtype)
        part = jnp.dot(g, w_k, preferred_element_type=jnp.float32)  # [cap, Cout]
        # out_idx unique within an offset -> plain (non-colliding) scatter-add
        acc = acc.at[out_idx].add(part, mode="drop", unique_indices=True)
        return acc, None

    acc0 = jnp.zeros((mc, weights.shape[-1]), jnp.float32)
    acc, _ = jax.lax.scan(body, acc0, (m.T, weights))
    return acc.astype(features.dtype)


# ---------------------------------------------------------------------------
# shared backward machinery (kernel-map-transposed VJPs)
# ---------------------------------------------------------------------------

def ws_kept_map(m: jax.Array, capacity: int) -> jax.Array:
    """The kernel map WS *actually computed with*: per-offset valid pairs
    beyond ``capacity`` replaced by −1, replicating the compaction's
    ``mode="drop"`` ordering (first ``capacity`` valid rows per column
    survive). The VJP must differentiate the dropped function, not the
    lossless one."""
    valid = m >= 0
    return jnp.where(valid & (jnp.cumsum(valid, axis=0) <= capacity), m, -1)


def _grad_weights(weights: jax.Array) -> jax.Array:
    """Weights as the backward dataflow wants them: mirrored along the
    offset axis (column k of the transposed map corresponds to offset
    −δ_{mirror(k)}) and transposed in (Cin, Cout) — [Kd, Cout, Cin]."""
    return jnp.swapaxes(weights, 1, 2)[::-1]


def _dw_per_offset(features: jax.Array, m: jax.Array, g: jax.Array,
                   out_dtype) -> jax.Array:
    """dW[k] = Gₖᵀ @ g with Gₖ the offset's gathered (masked) features —
    one [M, Cin] gather + one chunked row contraction per offset in a
    scan; fp32 accumulation like the forward. Never materializes
    [M, Kd, Cin], and the contraction is :func:`chunked_rowdot` so weight
    gradients stay bitwise invariant across capacity buckets (a plain dot
    regroups its k-panels when M grows — its docstring)."""
    def body(carry, m_col):
        gk = features[jnp.clip(m_col, 0)] \
            * (m_col >= 0)[:, None].astype(features.dtype)
        return carry, chunked_rowdot(gk, g)

    _, dw = jax.lax.scan(body, 0, m.T)
    return dw.astype(out_dtype)


# ---------------------------------------------------------------------------
# output-stationary
# ---------------------------------------------------------------------------

def _os_primal(cfg, features, m, weights):
    fuse, backend, bm, bn, _ = cfg
    from repro.kernels import ops as kops
    use_pallas, _i = kops.resolve_backend(backend)
    if use_pallas:
        return kops.spconv_os_fused(features, m, weights, impl="pallas",
                                    bm=bm, bn=bn)
    return os_xla(features, m, weights, fuse=fuse)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _os_core(cfg, features, m, weights):
    return _os_primal(cfg, features, m, weights)


def _os_fwd(cfg, features, m, weights):
    return _os_primal(cfg, features, m, weights), (features, m, weights)


def _os_bwd(cfg, res, g):
    fuse, backend, _, _, self_t = cfg
    features, m, weights = res
    # dF_in: the OS dataflow itself, over the transposed map with mirrored
    # transposed weights — same backend, so Pallas forward ⇒ Pallas backward
    # (the implicit-GEMM gather kernel reads g instead of F_in). Tile sizes
    # re-auto (backward row count is N, not M). ``self_t`` (submanifold):
    # the map is its own transpose, skip the M·K³ mirror scatter.
    mt = m if self_t else transpose_kernel_map(m, n_in=features.shape[0])
    df = _os_primal((fuse, backend, 0, 0, self_t), g, mt,
                    _grad_weights(weights))
    dw = _dw_per_offset(features, m, g, weights.dtype)
    return df.astype(features.dtype), None, dw


_os_core.defvjp(_os_fwd, _os_bwd)


@partial(jax.jit, static_argnames=("fuse", "backend", "bm", "bn",
                                   "self_transpose"))
def output_stationary(
    features: jax.Array,   # [N_cap, Cin]
    m: jax.Array,          # int32 [M_cap, Kd]  (kernel-map column subset)
    weights: jax.Array,    # [Kd, Cin, Cout]
    *,
    fuse: bool = False,
    backend: str = "xla",
    bm: int = 0,
    bn: int = 0,
    self_transpose: bool = False,
) -> jax.Array:
    """OS dataflow (differentiable — module doc). XLA: :func:`os_xla`.
    Pallas: the implicit-GEMM kernel — gather fused in, no HBM
    intermediate, ``fuse`` is moot. The custom VJP computes dF_in as the
    OS pass over the transposed kernel map and dW as per-offset
    gathered-feature GEMMs.

    ``self_transpose``: caller asserts the map is its own transpose — a
    (mirror-closed column subset of a) submanifold map, the §5.4 identity —
    so the backward skips the mirror scatter and runs straight over ``m``.
    ``apply_spconv`` sets it from ``spec.submanifold``; bit-identical
    gradients either way (tests/test_grad.py)."""
    return _os_core((fuse, backend, bm, bn, self_transpose), features, m,
                    weights)


# ---------------------------------------------------------------------------
# weight-stationary
# ---------------------------------------------------------------------------

def _ws_primal(cfg, features, m, weights):
    capacity, backend, bm, bn, _ = cfg
    from repro.kernels import ops as kops
    use_pallas, _i = kops.resolve_backend(backend)
    if use_pallas:
        return kops.spconv_ws_fused(features, m, weights, capacity=capacity,
                                    impl="pallas", bm=bm, bn=bn)
    return ws_xla(features, m, weights, capacity=capacity)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ws_core(cfg, features, m, weights):
    return _ws_primal(cfg, features, m, weights)


def _ws_fwd(cfg, features, m, weights):
    return _ws_primal(cfg, features, m, weights), (features, m, weights)


def _ws_bwd(cfg, res, g):
    capacity, backend, _, _, self_t = cfg
    features, m, weights = res
    # Differentiate the function WS actually computed: drop overflow pairs
    # from the map first, then transpose. The backward dF is the WS
    # scatter-GEMM over the transposed map (Pallas: ws_scatter_gemm reads
    # g and merges into the input-row accumulator).
    mk = ws_kept_map(m, capacity)
    # ``self_t`` can only skip the mirror scatter when the capacity is
    # statically lossless (no drops possible ⇒ mk == m, symmetric); a
    # dropped map is NOT its own transpose even on submanifold layers
    # (the drop keeps *forward* column order).
    if self_t and capacity >= m.shape[0]:
        mt = mk
    else:
        mt = transpose_kernel_map(mk, n_in=features.shape[0])
    # every transposed column holds ≤ capacity valid pairs (it mirrors a
    # kept forward column), and ≤ min(M, N) by per-column injectivity — so
    # this bound is lossless and keeps the backward's compaction/GEMM
    # buffers at the tuned capacity, not M.
    bw_cap = min(capacity, m.shape[0], features.shape[0])
    df = _ws_primal((bw_cap, backend, 0, 0, self_t), g, mt,
                    _grad_weights(weights))
    dw = _dw_per_offset(features, mk, g, weights.dtype)
    return df.astype(features.dtype), None, dw


_ws_core.defvjp(_ws_fwd, _ws_bwd)


@partial(jax.jit, static_argnames=("capacity", "backend", "bm", "bn",
                                   "self_transpose"))
def weight_stationary(
    features: jax.Array,   # [N_cap, Cin]
    m: jax.Array,          # int32 [M_cap, Ks]
    weights: jax.Array,    # [Ks, Cin, Cout]
    *,
    capacity: int,
    backend: str = "xla",
    bm: int = 0,
    bn: int = 0,
    self_transpose: bool = False,
) -> jax.Array:
    """WS dataflow with static per-offset pair capacity (differentiable —
    module doc).

    Valid pairs beyond ``capacity`` are dropped (choose capacity from the
    tuner / column statistics; ``capacity = M_cap`` is always lossless).
    The per-offset compaction is the TPU replacement for the paper's
    filtering post-processing; the merge replaces atomicAdd (see module
    doc). Pallas: the fused compact+GEMM+merge kernel, same drop
    semantics. The custom VJP transposes the *kept* map, so gradients are
    exact for the dropped function too. ``self_transpose`` as in
    :func:`output_stationary` (skips the backward mirror scatter, only
    effective when the capacity is statically lossless)."""
    return _ws_core((capacity, backend, bm, bn, self_transpose), features, m,
                    weights)


def ws_overflow(kmap: KernelMap, cols: np.ndarray, capacity: int) -> jax.Array:
    """Diagnostic: True if any selected column exceeds the WS capacity."""
    return (kmap.column_counts()[cols] > capacity).any()


# ---------------------------------------------------------------------------
# hybrid dual-dataflow
# ---------------------------------------------------------------------------

def hybrid(
    features: jax.Array,
    kmap: KernelMap,
    weights: jax.Array,    # [K^3, Cin, Cout]
    *,
    K: int,
    stride: int,
    t: int,
    ws_capacity: int,
    fuse_dense: bool = False,
    backend: str = "xla",
    bm: int = 0,
    bn: int = 0,
    self_transpose: bool = False,
) -> jax.Array:
    """Adaptive hybrid dataflow: offsets with L1 < t via OS, rest via WS.

    t = 0 degenerates to full WS; t = L1NormMax+1 to full OS (paper §5.4).
    ``backend`` selects the kernel family for both halves (module doc).
    ``self_transpose`` propagates to both halves — valid because the
    l1_partition subsets of a submanifold map are mirror-closed, hence
    themselves self-transposed under positional reversal (module doc).
    """
    dense_idx, sparse_idx = l1_partition(K, stride, t)
    out = jnp.zeros((kmap.m.shape[0], weights.shape[-1]), features.dtype)
    if dense_idx.size:
        out = out + output_stationary(
            features, kmap.m[:, dense_idx], weights[dense_idx],
            fuse=fuse_dense, backend=backend, bm=bm, bn=bn,
            self_transpose=self_transpose)
    if sparse_idx.size:
        out = out + weight_stationary(
            features, kmap.m[:, sparse_idx], weights[sparse_idx],
            capacity=ws_capacity, backend=backend, bm=bm, bn=bn,
            self_transpose=self_transpose)
    return out


# ---------------------------------------------------------------------------
# analytic HBM traffic model (shared by benchmarks + cost-model tuner)
# ---------------------------------------------------------------------------

def hbm_bytes_model(M: int, Kd: int, Cin: int, Cout: int, itemsize: int = 4,
                    *, backend: str = "xla", dataflow: str = "os",
                    nnz: Optional[int] = None,
                    capacity: Optional[int] = None) -> dict:
    """Modeled HBM bytes for one layer's feature computation.

    Counts gather reads, gathered-intermediate write+re-read (XLA only —
    the fused Pallas kernels never materialize it), merge traffic (WS/XLA:
    Ks passes over the [M, Cout] accumulator; Pallas: output stays
    VMEM-resident), plus weights and output. ``nnz`` = valid kernel-map
    entries (defaults to dense M·Kd).
    """
    nnz = M * Kd if nnz is None else int(nnz)
    w_bytes = Kd * Cin * Cout * itemsize
    out_bytes = M * Cout * itemsize
    if dataflow == "os":
        if backend == "pallas":
            gather, intermediate = nnz * Cin * itemsize, 0
        else:
            gather = M * Kd * Cin * itemsize
            intermediate = 2 * M * Kd * Cin * itemsize
    else:  # ws
        cap = M if capacity is None else int(capacity)
        if backend == "pallas":
            gather, intermediate = nnz * Cin * itemsize, 0
        else:
            gather = Kd * cap * Cin * itemsize
            intermediate = Kd * (cap * Cin + 2 * M * Cout) * itemsize
    return {
        "total": gather + intermediate + w_bytes + out_bytes,
        "gather": gather,
        "intermediate": intermediate,
        "weights": w_bytes,
        "out": out_bytes,
    }
