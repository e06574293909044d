"""Fused weight-stationary sparse convolution: compact → GEMM → merge.

The XLA ``weight_stationary`` scans offsets, and per offset materializes a
``[capacity, Cin]`` gathered-feature buffer in HBM before its GEMM, then
scatter-adds into the accumulator. This kernel fuses all three stages per
output-row tile:

  host side (cheap int32 XLA, no feature bytes): valid pairs beyond
    ``capacity`` are dropped first (per offset, the first ``capacity``
    valid rows survive — exactly the XLA path's scatter-drop), then each
    (offset, row tile) is compacted: its valid pairs move to the front of
    the tile's ``bm`` slots in output-row order. ``in_idx`` holds their
    input rows, ``out_row`` their tile-local output rows and ``n`` their
    count. A kernel map has at most one pair per (output row, offset), so
    one tile's pairs of one offset always fit its ``bm`` slots.

  kernel: grid (M/bm, Cout/bn, Ks) — the (bm, bn) fp32 output block stays
    VMEM-resident while the offset axis is swept, and the offsets are
    visited in order, so every output row sums its contributions in the
    XLA scan's order. Per step it DMAs the ``n`` valid input rows from
    HBM-resident F_in into VMEM (an empty (tile, offset) skips the step),
    runs one MXU matmul against W[k] into the ``part`` scratch, and merges
    each product row into the output block at its ``out_row``.

  grid = (M/bm, Cout/bn, Ks)
  n / in_idx / out_row blocks  SMEM  — from [Ks, M/bm, 1, ·] views
  F_in       [N, 1, Cin_p]     HBM   — ``spconv_gather_gemm.lane_rows``
  w block    (1, Cin, bn)      VMEM
  out block  (bm, bn) fp32     VMEM

vs the XLA scan this removes the per-offset ``[capacity, Cin]`` HBM
intermediate and the ``Ks`` scatter passes over the ``[M, Cout]``
accumulator. VMEM holds one output tile, so the kernel's footprint does not
grow with M.

Accumulation is fp32 throughout (the output is fp32, cast by the caller),
matching the XLA path bit-for-bit on valid rows in interpret mode.

Backward engine: the WS custom VJP (``core.dataflow``) runs this same
kernel for dF_in over the transposed kernel map (capacity-drop mask
applied first, so gradients differentiate the dropped forward exactly) —
the fused compact+GEMM+merge sweep scatters cotangent rows into the
input-row accumulator the same way the forward scatters into output rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .spconv_gather_gemm import lane_rows, row_copy, tile_columns


def _kernel(n_ref, in_ref, row_ref, f_hbm, w_ref, o_ref, g_ref, part_ref,
            sem, *, n_in, cin):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    n = n_ref[0, 0]

    @pl.when(n > 0)
    def _offset():
        def start(r, carry):
            row_copy(f_hbm, g_ref.at[r], in_ref[0, r], sem, n_in).start()
            return carry

        def wait(r, carry):
            row_copy(f_hbm, g_ref.at[r], in_ref[0, r], sem, n_in).wait()
            return carry

        jax.lax.fori_loop(0, n, start, 0)
        jax.lax.fori_loop(0, n, wait, 0)
        # staging rows >= n hold stale data; their products are never merged
        g = g_ref[...].reshape(g_ref.shape[0], g_ref.shape[-1])[:, :cin]
        part_ref[...] = jnp.dot(g, w_ref[0],
                                preferred_element_type=jnp.float32)

        def merge(r, carry):
            row = row_ref[0, r]
            o_ref[pl.ds(row, 1), :] = (o_ref[pl.ds(row, 1), :]
                                       + part_ref[pl.ds(r, 1), :])
            return carry

        jax.lax.fori_loop(0, n, merge, 0)


@functools.partial(jax.jit, static_argnames=("capacity", "bm", "bn",
                                             "interpret"))
def ws_scatter_gemm(
    features: jax.Array,  # [N, Cin] HBM-resident input features
    m: jax.Array,         # int32 [M, Ks] kernel-map column subset
    weights: jax.Array,   # [Ks, Cin, Cout]
    *,
    capacity: int,
    bm: int = 128,
    bn: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """WS dataflow with static per-offset pair capacity, fully fused.

    Valid pairs beyond ``capacity`` are dropped (identical to the XLA
    path). M is padded to the ``bm`` row tile internally. Returns fp32
    ``[M, Cout]`` — cast at the call site.
    """
    M, Ks = m.shape
    N, Cin = features.shape
    Cout = weights.shape[-1]
    assert bm % 8 == 0 and Cout % bn == 0, (bm, Cout, bn)
    Mp = -(-M // bm) * bm
    n_tiles = Mp // bm

    # --- host-side compaction (int32 only; no feature movement) ---
    valid = m >= 0
    kept = jnp.where(valid & (jnp.cumsum(valid, axis=0) <= capacity), m, -1)
    kept = jnp.pad(kept, ((0, Mp - M), (0, 0)), constant_values=-1)
    t = tile_columns(kept, bm)                    # [Ks, M/bm, 1, bm]
    rows = jnp.arange(bm, dtype=jnp.int32)
    # valid rows first, each group in row order
    out_row = jnp.argsort(jnp.where(t >= 0, rows, bm + rows),
                          axis=-1).astype(jnp.int32)
    in_idx = jnp.take_along_axis(t, out_row, axis=-1)
    n = (t >= 0).sum(axis=-1, keepdims=True, dtype=jnp.int32)

    f3 = lane_rows(features)
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        functools.partial(_kernel, n_in=N, cin=Cin),
        grid=(n_tiles, Cout // bn, Ks),
        in_specs=[
            smem((None, None, 1, 1), lambda i, j, k: (k, i, 0, 0)),
            smem((None, None, 1, bm), lambda i, j, k: (k, i, 0, 0)),
            smem((None, None, 1, bm), lambda i, j, k: (k, i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, Cin, bn), lambda i, j, k: (k, 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Cout), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bm,) + f3.shape[1:], features.dtype),
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
    )(n, in_idx, out_row, f3, weights)
    return out[:M]
