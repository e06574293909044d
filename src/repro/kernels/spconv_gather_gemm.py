"""Fused implicit-GEMM output-stationary sparse convolution.

This is the true TorchSparse/Minuet-style dataflow: the kernel-map gather
happens *inside* the kernel, per tile, straight out of HBM-resident
``F_in`` — the caller never materializes the ``[M, Kd, Cin]`` gathered
intermediate that the unfused path (XLA gather + ``masked_group_gemm``)
writes to and re-reads from HBM.

  grid = (M/bm, Cout/bn, Kd)            — out tile revisited along Kd
  live, held [M/bm] SMEM (prefetched)   — per row tile, see below
  m block   (1, bm)        SMEM         — kernel-map column k of row tile i,
                                          from the [Kd, M/bm, 1, bm] view
  F_in      [N, 1, Cin_p]  HBM (ANY)    — gathered row-by-row by async copy
  w block   (1, Cin, bn)   VMEM
  out block (bm, bn)       VMEM         — fp32 scratch accumulator

TPU layout: Mosaic tiles the last two dims of every buffer by (8, 128), and
a DMA may only slice whole tiles there. ``F_in`` is therefore zero-padded
to ``Cin_p`` (a multiple of 128 lanes) and viewed as ``[N, 1, Cin_p]``, and
the VMEM staging buffer is ``(bm, 1, Cin_p)``: one gathered row is then an
index along an untiled leading dim on both ends of the copy. The matmul
reads back only the first ``Cin`` lanes, so the padding never enters the
arithmetic.

Per (tile, offset) the kernel walks the bm index scalars in SMEM, starts
one row DMA per *valid* entry and zeroes the staging row of every invalid
entry (m < 0) — the mask is applied in-register at gather time, never in
memory — then waits for all the copies before one MXU matmul accumulates
into fp32 scratch, flushed on the last offset. All copies of a tile share
one DMA semaphore, so they are in flight together rather than one by one.

Dead row tiles. A row tile whose map holds no valid entry at any offset is
*dead*; the wrapper finds them from the map alone (:func:`live_tiles`) and
prefetches two int32 ``[M/bm]`` arrays into SMEM before the grid starts
(``PrefetchScalarGridSpec``): ``live`` (1 for a live tile) and ``held``
(:func:`held_tiles`: the tile itself when live, else the last live tile
before it). On a dead tile's steps the kernel skips the DMA walk, the
waits and the matmul, and the index maps hold the map and weight blocks at
those of the last step before the tile (offset Kd-1 and the last Cout tile
of row tile ``held[i]``), so the pipeline copies nothing in; only the
output tile is written back. The ``k == 0`` zero init and the
``k == Kd-1`` flush still run, so a dead tile writes zeros — exactly the
``0 @ W`` sums it computed before — and every output row stays
bit-identical. The kernel maps of this package have dead tiles wherever
rows are padding: ``zdelta_search`` writes -1 to every output row past the
voxel count (the PAD tail of a capacity bucket), and
``kernel_map.transpose_kernel_map`` to every input row no output reads, so
the backward's dF_in pass skips them too. Nothing assumes the dead tiles
form a tail: a dead tile between live ones is skipped the same way.

HBM traffic vs the unfused path: the ``2·M·Kd·Cin`` intermediate bytes
(write + re-read) disappear, and gather reads drop from ``M·Kd·Cin`` to
``nnz·Cin`` (only valid kernel-map entries are fetched). See
``core.dataflow.hbm_bytes_model`` for the accounting used by benchmarks.

Alignment: bm must be a multiple of 8 (fp32 sublane) and divide M; bn ≤
Cout with Cout % bn == 0; ``kernels.ops.spconv_os_fused`` pads M and
picks tiles so arbitrary shapes work.

Backward engine: the OS custom VJP (``core.dataflow``) runs this same
kernel for dF_in — the operands become (cotangents g, the transposed
kernel map ``kernel_map.transpose_kernel_map``, mirrored Cout→Cin
weights), so training's backward is another implicit-GEMM gather with no
``[N, Kd, Cout]`` intermediate and no new kernel-map search.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def lane_rows(x: jax.Array) -> jax.Array:
    """``[N, C]`` → ``[N, 1, C_p]``, C zero-padded to a multiple of 128
    lanes: the HBM layout in which one row is a whole-tile DMA slice
    (module doc). Shared with ``ws_scatter_gemm``."""
    n, c = x.shape
    cp = -(-c // LANES) * LANES
    if cp != c:
        x = jnp.pad(x, ((0, 0), (0, cp - c)))
    return x.reshape(n, 1, cp)


def tile_columns(m: jax.Array, bm: int) -> jax.Array:
    """``[M, Kd]`` kernel map → ``[Kd, M/bm, 1, bm]``: the (1, bm) block of
    one offset column over one row tile has last two dims equal to the
    array's, which is the form an SMEM block may take."""
    M, Kd = m.shape
    return m.T.reshape(Kd, M // bm, 1, bm)


def row_copy(f_hbm, dst, idx, sem, n_in: int):
    """DMA descriptor for one gathered feature row ``F_in[idx]`` → ``dst``."""
    return pltpu.make_async_copy(f_hbm.at[jnp.clip(idx, 0, n_in - 1)], dst,
                                 sem)


def live_tiles(m: jax.Array, bm: int) -> jax.Array:
    """int32 ``[M/bm]``: 1 where the row tile holds a valid entry at some
    offset, 0 for a dead tile (all ``-1``). Read from the tiled view the
    kernel consumes, so XLA can fuse the reduction with its transpose."""
    return (tile_columns(m, bm).max(axis=(0, 2, 3)) >= 0).astype(jnp.int32)


def held_tiles(live: jax.Array) -> jax.Array:
    """int32 ``[M/bm]``: the row tile whose map block a step reads — the
    tile itself when live, else the last live tile before it (0 if none)."""
    idx = jnp.arange(live.shape[0], dtype=jnp.int32)
    return jax.lax.cummax(jnp.where(live != 0, idx, 0))


def _kernel(live_ref, held_ref, m_ref, f_hbm, w_ref, o_ref, acc_ref, g_ref,
            sem, *, n_k, n_in, bm, cin):
    i, k = pl.program_id(0), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def start(r, carry):
        idx = m_ref[0, r]

        @pl.when(idx >= 0)
        def _fetch():
            row_copy(f_hbm, g_ref.at[r], idx, sem, n_in).start()

        @pl.when(idx < 0)
        def _blank():
            g_ref[r] = jnp.zeros(g_ref.shape[1:], g_ref.dtype)

        return carry

    def wait(r, carry):
        idx = m_ref[0, r]

        @pl.when(idx >= 0)
        def _done():
            row_copy(f_hbm, g_ref.at[r], idx, sem, n_in).wait()

        return carry

    @pl.when(live_ref[i] != 0)
    def _accumulate():
        jax.lax.fori_loop(0, bm, start, 0)
        jax.lax.fori_loop(0, bm, wait, 0)
        g = g_ref[...].reshape(bm, g_ref.shape[-1])[:, :cin]
        acc_ref[...] += jnp.dot(g, w_ref[0],
                                preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def spconv_gather_gemm(
    features: jax.Array,  # [N, Cin] HBM-resident input features
    m: jax.Array,         # int32 [M, Kd] kernel-map column subset
    weights: jax.Array,   # [Kd, Cin, Cout]
    *,
    bm: int = 128,
    bn: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """out[i] = Σ_k 1[m[i,k] ≥ 0] · F_in[m[i,k]] @ W[k], gather fused in."""
    M, Kd = m.shape
    N, Cin = features.shape
    Cout = weights.shape[-1]
    assert M % bm == 0 and Cout % bn == 0, (M, bm, Cout, bn)
    f3 = lane_rows(features)
    live = live_tiles(m, bm)
    n_j = Cout // bn

    # A dead tile's steps hold the blocks of the last step before them
    # (offset Kd-1 and the last Cout tile of the held row tile), so the
    # pipeline copies no map or weight block for them.
    def m_block(i, j, k, live, held):
        return jnp.where(live[i] != 0, k, Kd - 1), held[i], 0, 0

    def w_block(i, j, k, live, held):
        on = live[i] != 0
        return jnp.where(on, k, Kd - 1), 0, jnp.where(on, j, n_j - 1)

    return pl.pallas_call(
        functools.partial(_kernel, n_k=Kd, n_in=N, bm=bm, cin=Cin),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(M // bm, n_j, Kd),
            in_specs=[
                pl.BlockSpec((None, None, 1, bm), m_block,
                             memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((1, Cin, bn), w_block),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, *_: (i, j)),
            scratch_shapes=[
                pltpu.VMEM((bm, bn), jnp.float32),
                pltpu.VMEM((bm,) + f3.shape[1:], features.dtype),
                pltpu.SemaphoreType.DMA,
            ]),
        out_shape=jax.ShapeDtypeStruct((M, Cout), features.dtype),
        interpret=interpret,
        name="spconv_gather_gemm",
    )(live, held_tiles(live), tile_columns(m, bm), f3, weights)
