"""Segmented-reduction engine: O(N) per-scene sums over batch-major rows.

Every per-scene statistic in the batched path (BN moments, scene pooling,
the masked-CE loss reduction) is a reduction over a *contiguous* row
segment: SparseTensor packs the scene index into the most-significant bits
of each packed word, so rows are batch-major-sorted and scene b's rows are
exactly ``[starts[b], starts[b] + counts[b])`` of the capacity-sized
buffer. Spira's thesis — exploit the structure instead of generic
scatter/reduce machinery — then says a per-scene reduction should cost one
pass over the N rows, not S capacity-wide passes (the ``dynamic_slice``
-per-scene + ``[cap, S]`` one-hot formulation this module replaces;
TorchSparse's batched locality-aware reduction makes the same argument on
GPU). This module is the single substrate for those reductions.

The canonical grouping (the bit-invariance contract)
----------------------------------------------------
The engine's guarantee — pinned by tests/test_session.py and
tests/test_grad.py through BN — is that a batch-of-B reduction is
*bitwise* identical to B single-scene reductions, and bitwise invariant
under zero-extension to a larger capacity bucket. ``core.dataflow.rowsum``
gets that from a dot's fixed k-panel blocking, but a dot's internal
operand grouping cannot be reproduced for a segment sitting at an
arbitrary row offset. The engine therefore *defines* the grouping, in
segment-relative terms, and every backend implements it exactly:

* rows of a segment are chunked by **relative** position ``rel // q``
  (``rel`` = row − segment start; ``q`` static, ``SegmentSpec.q``);
* within a chunk, fp32 accumulation is **strictly sequential** in row
  order, starting from +0.0;
* chunk partials combine **strictly sequentially** in chunk order,
  starting from +0.0; invalid rows/chunks are *skipped* (never "+ 0.0"-ed,
  so a −0.0 can never be laundered into +0.0 — and because every chain
  starts at +0.0, no partial is ever −0.0 either).

Each add is one IEEE fp32 add, so any two implementations of this
schedule agree bit-for-bit. The grouping depends only on each row's
position *relative to its segment's start*, which gives the two pinned
properties by construction:

* **alignment invariance** — a segment's sum is the same whether its rows
  sit at offset 0 (a single-scene run) or at ``starts[b]`` of a batched
  buffer: relative positions, and hence the add tree, are identical;
* **zero-extension invariance** — growing the buffer appends PAD rows
  with the sentinel id ``num_segments``, which belong to no segment and
  are skipped; real rows keep their relative positions.

Backends (``SegmentSpec.backend``, same contract as ``kernels.ops``):

* ``"xla"``   — a scatter-free chunk table (``searchsorted`` over the S+1
  chunk offsets, derived from (starts, counts) alone), ONE gather pass
  rearranging rows chunk-major, a q-step unrolled masked add chain (each
  step a vectorized [n_chunks, C] add — the fixed-length, shape-stable
  analogue of ``rowsum``'s fixed dot blocking: chain length never varies
  with capacity, and XLA does not reassociate explicit add chains), then
  a combine loop whose step j adds every segment's j-th chunk partial
  (the same per-segment sequential chain, vectorized over S).
* ``"pallas"`` — one sequential-grid pass over row tiles with VMEM
  accumulators ``acc``/``cur`` keyed by the precomputed scene-id column
  (SMEM); chunk boundaries detected from ``rel % q``. Off-TPU it runs in
  interpreter mode; tests/test_segsum.py pins fwd AND bwd bit parity with
  the XLA fallback.
* ``"auto"``  — pallas on TPU, xla elsewhere (``ops.resolve_backend``).

Gradients: :func:`segment_sum` and :func:`segment_gather` are exact
transposes of each other, and each carries a ``jax.custom_vjp`` that says
so — the backward of a segment sum is a segment gather (bit-exact, no
reduction at all) and the backward of a segment gather is THIS engine's
segment sum. Autodiff through BN/pooling/loss therefore never inserts an
XLA scatter-add or an elementwise reduce tree, and parameter gradients
inherit the invariances (tests/test_train_pointcloud.py pins them).

Input contract: ``sid`` is nondecreasing with ``counts[b]`` rows of value
``b`` starting at row ``starts[b]``; rows outside every segment (the PAD
tail) carry ``sid >= num_segments``. ``models.pointcloud.level_segments``
derives exactly this from the batch bits of each level's packed
coordinates.
"""
from __future__ import annotations

import dataclasses
import functools
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ---------------------------------------------------------------------------
# trace-time reduction counters (the acceptance counter: batched BN/pooling/
# loss route ONLY through here — models.pointcloud counts the retired sliced
# formulation separately, and tests/test_segsum.py asserts 0 of those and
# an S-independent number of these per traced step)
# ---------------------------------------------------------------------------

SEGMENT_CALLS = {"count": 0}


def reset_segment_calls() -> None:
    SEGMENT_CALLS["count"] = 0


def segment_call_count() -> int:
    """Segment reductions traced since the last reset (cf. the zdelta
    search counters — clear jit caches before comparing traces)."""
    return SEGMENT_CALLS["count"]


@dataclasses.dataclass(frozen=True)
class SegmentSpec:
    """Static segmented-reduction config (SpConvSpec-style: frozen, carried
    by the session, persisted by the tuner).

    ``backend`` is co-tuned on *step* time (fwd+bwd) by
    ``core.tuner.tune_segment_backend_measure`` — the train-mode tuning
    objective. ``q`` is the chunk length of the canonical grouping (module
    doc): it is part of the bit contract, so every reduction in one
    network must use one spec (the session guarantees this). ``tm`` is the
    Pallas row-tile (latency only, never numerics)."""

    backend: str = "auto"   # "auto" | "xla" | "pallas"
    q: int = 64
    tm: int = 128


# ---------------------------------------------------------------------------
# XLA fallback: chunk table + one gather pass + fixed-length add chain
# ---------------------------------------------------------------------------

def segment_sum_xla(x: jax.Array, sid: jax.Array, starts: jax.Array,
                    counts: jax.Array, *, num_segments: int,
                    q: int = 64) -> jax.Array:
    """Segment sums [S, C] (fp32) under the canonical grouping (module doc).

    One capacity-wide gather rearranges rows chunk-major; the chunk table
    (compact chunk enumeration ``Σ_b ceil(counts[b]/q)`` ≤ cap/q + S) is
    derived scatter-free from (starts, counts) alone — a ``searchsorted``
    over the S+1 chunk offsets per slot (XLA CPU lowers scatters
    element-sequentially, so the table must not write through one). No
    per-segment ``dynamic_slice``, no ``[cap, S]`` one-hot — S enters only
    through the [S, C] accumulator and S extra chunk slots."""
    cap, C = x.shape
    S = num_segments
    i32 = jnp.int32
    starts = starts.astype(i32)
    counts = counts.astype(i32)
    nch = -(-counts // q)                        # chunks per segment
    choff = jnp.concatenate([jnp.zeros((1,), i32),
                             jnp.cumsum(nch).astype(i32)])
    n2 = cap // q + S                            # static chunk-slot bound
    c = jnp.arange(n2, dtype=i32)
    # owning segment per chunk slot: duplicate offsets (empty segments)
    # resolve to the next nonempty owner via side="right"
    seg = jnp.clip(jnp.searchsorted(choff, c, side="right").astype(i32) - 1,
                   0, S - 1)
    j = c - choff[seg]                           # per-segment chunk index
    chunk_start = starts[seg] + j * q
    chunk_len = jnp.where(c < choff[S],
                          jnp.clip(counts[seg] - j * q, 0, q), 0)
    # ONE gather pass, chunk-major
    g = x[jnp.clip(chunk_start[:, None] + jnp.arange(q, dtype=i32)[None, :],
                   0, cap - 1)].astype(jnp.float32)       # [n2, q, C]
    # fixed-length (q, static) skip-guarded add chain — XLA preserves the
    # order of explicit adds; only the batch dim n2 varies with capacity
    p = jnp.zeros((n2, C), jnp.float32)
    for t in range(q):
        p = jnp.where((t < chunk_len)[:, None], p + g[:, t, :], p)

    # combine chunk partials: iteration j adds every segment's j-th
    # partial — ascending per-segment chunk order, i.e. exactly the
    # canonical sequential chain, vectorized over S per step and bounded
    # by the LARGEST segment's chunk count (dynamic; safe in a while_loop
    # because the engine's primal is never itself differentiated — the
    # custom VJPs route gradients around it)
    max_nch = nch.max() if S else jnp.zeros((), i32)

    def body(state):
        jj, acc = state
        rows = p[jnp.clip(choff[:-1] + jj, 0, n2 - 1)]
        return jj + 1, jnp.where((jj < nch)[:, None], acc + rows, acc)

    _, acc = jax.lax.while_loop(
        lambda state: state[0] < max_nch, body,
        (jnp.zeros((), i32), jnp.zeros((S, C), jnp.float32)))
    return acc


# ---------------------------------------------------------------------------
# Pallas kernel: one sequential pass, per-tile accumulators keyed by sid
# ---------------------------------------------------------------------------

def _segsum_kernel(sid_ref, starts_ref, x_ref, o_ref, acc_ref, cur_ref, *,
                   S, q, tm, n_tiles):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        cur_ref[...] = jnp.zeros_like(cur_ref)

    def row(r, carry):
        s = sid_ref[r, 0]

        @pl.when(s < S)
        def _accum():
            rel = i * tm + r - starts_ref[s, 0]
            boundary = (rel > 0) & (rel % q == 0)
            xr = x_ref[pl.ds(r, 1), :].astype(jnp.float32)
            cur = cur_ref[pl.ds(s, 1), :]
            acc = acc_ref[pl.ds(s, 1), :]
            # chunk boundary: retire the finished partial into acc and
            # start a fresh chain at +0.0 + x (the "+ 0.0" normalizes a
            # −0.0 row exactly as the fallback's zero-initialized chain)
            acc_ref[pl.ds(s, 1), :] = jnp.where(boundary, acc + cur, acc)
            cur_ref[pl.ds(s, 1), :] = jnp.where(boundary, xr + 0.0, cur + xr)

        return carry

    jax.lax.fori_loop(0, tm, row, 0)

    @pl.when(i == n_tiles - 1)
    def _flush():
        o_ref[...] = acc_ref[...] + cur_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("num_segments", "q", "tm", "interpret"))
def segment_sum_pallas(x: jax.Array, sid: jax.Array, starts: jax.Array, *,
                       num_segments: int, q: int = 64, tm: int = 128,
                       interpret: bool = False) -> jax.Array:
    """Pallas segment sum: sequential grid over row tiles, fp32 ``acc``/
    ``cur`` VMEM accumulators indexed by the SMEM scene-id column; chunk
    boundaries from the segment-relative position. Bit-identical to
    :func:`segment_sum_xla` (same canonical grouping — module doc).

    Production note: rows are resolved by a sequential in-tile loop of
    [1, C] VPU adds — O(N) with no S-wide passes, but unpipelined; a
    double-buffered multi-lane variant is a TPU-measurement follow-up
    (ROADMAP), irrelevant in interpreter mode."""
    cap, C = x.shape
    S = num_segments
    capp = ((cap + tm - 1) // tm) * tm
    if capp != cap:
        x = jnp.pad(x, ((0, capp - cap), (0, 0)))
        sid = jnp.pad(sid.astype(jnp.int32), (0, capp - cap),
                      constant_values=S)
    S_pad = max(8, ((S + 7) // 8) * 8)
    starts2 = jnp.zeros((S_pad, 1), jnp.int32).at[:S, 0].set(
        starts.astype(jnp.int32))
    n_tiles = capp // tm
    out = pl.pallas_call(
        functools.partial(_segsum_kernel, S=S, q=q, tm=tm, n_tiles=n_tiles),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((tm, 1), lambda i: (i, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((S_pad, 1), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((tm, C), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((S_pad, C), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((S_pad, C), jnp.float32),
        scratch_shapes=[pltpu.VMEM((S_pad, C), jnp.float32),
                        pltpu.VMEM((S_pad, C), jnp.float32)],
        interpret=interpret,
        name="segment_sum_pallas",
    )(sid.astype(jnp.int32)[:, None], starts2, x.astype(jnp.float32))
    return out[:S]


# ---------------------------------------------------------------------------
# public API: custom-VJP segment_sum / segment_gather (exact transposes)
# ---------------------------------------------------------------------------

def _segsum_impl(cfg, x, sid, starts, counts):
    S, q, tm, backend = cfg
    SEGMENT_CALLS["count"] += 1
    from .ops import resolve_backend
    use_pallas, interp = resolve_backend(backend)
    if use_pallas:
        return segment_sum_pallas(x, sid, starts, num_segments=S, q=q,
                                  tm=tm, interpret=interp)
    return segment_sum_xla(x, sid, starts, counts, num_segments=S, q=q)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _segsum_core(cfg, x, sid, starts, counts):
    return _segsum_impl(cfg, x, sid, starts, counts)


def _segsum_fwd(cfg, x, sid, starts, counts):
    return (_segsum_impl(cfg, x, sid, starts, counts),
            (sid, jnp.zeros((0,), x.dtype)))


def _segsum_bwd(cfg, res, g):
    # transpose of a segment sum = segment gather of the cotangent — one
    # elementwise pass, bit-exact at any alignment/capacity by nature
    S = cfg[0]
    sid, xdt = res
    dx = jnp.where((sid < S)[:, None],
                   g[jnp.clip(sid, 0, S - 1)], 0).astype(xdt.dtype)
    return dx, None, None, None


_segsum_core.defvjp(_segsum_fwd, _segsum_bwd)


def segment_sum(x: jax.Array, sid: jax.Array, starts: jax.Array,
                counts: jax.Array, *, num_segments: int,
                spec: SegmentSpec | None = None) -> jax.Array:
    """Per-segment column sums [num_segments, C] (fp32) of ``x`` [cap, C]
    under the canonical grouping — O(N), no S-wide passes; differentiable
    (backward = segment gather). Input contract in the module doc."""
    sp = spec or SegmentSpec()
    return _segsum_core((num_segments, sp.q, sp.tm, sp.backend),
                        x, sid, starts, counts)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _seggather_core(cfg, v, sid, starts, counts):
    S = cfg[0]
    return jnp.where((sid < S)[:, None], v[jnp.clip(sid, 0, S - 1)], 0)


def _seggather_fwd(cfg, v, sid, starts, counts):
    return _seggather_core(cfg, v, sid, starts, counts), (
        sid, starts, counts, jnp.zeros((0,), v.dtype))


def _seggather_bwd(cfg, res, g):
    # transpose of the per-scene broadcast = THIS engine's segment sum —
    # the one place autodiff would otherwise insert a scatter-add
    sid, starts, counts, vdt = res
    dv = _segsum_core(cfg, g, sid, starts, counts).astype(vdt.dtype)
    return dv, None, None, None


_seggather_core.defvjp(_seggather_fwd, _seggather_bwd)


def segment_gather(v: jax.Array, sid: jax.Array, starts: jax.Array,
                   counts: jax.Array, *, num_segments: int,
                   spec: SegmentSpec | None = None) -> jax.Array:
    """Broadcast per-segment rows ``v`` [num_segments, C] back onto the
    capacity-sized buffer (rows outside every segment get 0) — the
    replacement for the ``[cap, S]`` one-hot application matmul. Its VJP
    is :func:`segment_sum` with the same spec, so gradients of every
    per-scene statistic reduce through the engine, never a scatter-add."""
    sp = spec or SegmentSpec()
    return _seggather_core((num_segments, sp.q, sp.tm, sp.backend),
                           v, sid, starts, counts)


def segments_from_sizes(sizes, cap: int):
    """Host-side builder of a synthetic segmentation honoring the engine's
    input contract (module doc): contiguous segments of the given sizes
    packed from row 0, PAD tail carrying the sentinel id ``S``. Returns
    numpy ``(sid [cap], starts [S], counts [S])``. The single home of the
    contract's encoding for benchmarks and tests — real call sites derive
    the same triple from batch bits (``models.pointcloud.packed_segments``).
    """
    import numpy as np

    S = len(sizes)
    if sum(sizes) > cap:
        raise ValueError(f"segment sizes sum to {sum(sizes)} > cap {cap}")
    sid = np.full(cap, S, np.int32)
    starts = np.zeros(S, np.int32)
    pos = 0
    for b, sz in enumerate(sizes):
        starts[b] = pos
        sid[pos:pos + sz] = b
        pos += sz
    return sid, starts, np.asarray(sizes, np.int32)


def segment_moments(x: jax.Array, sid: jax.Array, starts: jax.Array,
                    counts: jax.Array, *, num_segments: int,
                    spec: SegmentSpec | None = None
                    ) -> tuple[jax.Array, jax.Array]:
    """(Σx, Σx²) per segment in ONE pass — the moments are reduced as a
    single [cap, 2C] segment sum over ``concat([x, x²])``, the same
    mean-free one-pass trick train-mode BN uses (E[x²] − mean²)."""
    C = x.shape[1]
    s = segment_sum(jnp.concatenate([x, x * x], axis=1), sid, starts,
                    counts, num_segments=num_segments, spec=spec)
    return s[:, :C], s[:, C:]
