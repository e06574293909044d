"""Jit'd public wrappers: Pallas on TPU, XLA fallback elsewhere.

Every op takes ``impl`` ∈ {"auto", "pallas", "xla"}:

  "auto"   — Pallas on TPU backends, XLA otherwise (CPU dry-runs / smoke
             tests never trace a TPU kernel; TPU runs get the fused path).
  "pallas" — always the Pallas kernel; on non-TPU hosts it runs through
             the interpreter (the CPU fallback the dataflow dispatch in
             core/dataflow.py relies on, so ``backend="pallas"`` specs
             stay runnable everywhere).
  "xla"    — always the jnp reference path.

``resolve_backend`` is the single source of that truth, and :func:`on_tpu`
the package's one platform check. The spconv entry
points also own tile selection and shape padding, so arbitrary (M, Cout)
work: M is padded to the row-tile with ``-1`` kernel-map rows (gather-
skipped, zero output, sliced off), and Cout falls back to a single
channel tile when 128 does not divide it.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from . import ref as _ref
from .masked_group_gemm import masked_group_gemm as _mgg_pallas
from .spconv_gather_gemm import live_tiles
from .spconv_gather_gemm import spconv_gather_gemm as _os_pallas
from .ws_scatter_gemm import ws_scatter_gemm as _ws_pallas
from .flash_attention import flash_attention as _fa_pallas


def on_tpu() -> bool:
    """Whether JAX's default backend is a TPU — the one platform check in
    the package. Every platform-dependent choice (Pallas compiled or
    interpreted, merge or sort downsample, which backends the tuner times)
    asks here, so a test steers them all by patching this function."""
    return jax.default_backend() == "tpu"


def resolve_backend(impl: str) -> Tuple[bool, bool]:
    """(use_pallas, interpret) for an ``impl``/``backend`` string."""
    if impl not in ("auto", "xla", "pallas"):
        raise ValueError(f"unknown backend {impl!r}; want auto|xla|pallas")
    tpu = on_tpu()
    if impl == "xla":
        return False, False
    if impl == "pallas":
        return True, not tpu
    return tpu, False


def _tiled_map(m: jax.Array, bm: int) -> Tuple[jax.Array, int]:
    """(``m`` padded with ``-1`` rows to whole row tiles, the row tile).
    bm 0 → auto: 128-row tiles."""
    bm = bm or 128
    pad = -m.shape[0] % bm
    if pad:
        m = jnp.pad(m, ((0, pad), (0, 0)), constant_values=-1)
    return m, bm


def _col_tile(Cout: int, bn: int) -> int:
    """0 → auto: 128 when it divides Cout, else one whole-Cout tile."""
    if bn:
        return bn
    return 128 if Cout % 128 == 0 else Cout


def spconv_os_fused(features: jax.Array, m: jax.Array, weights: jax.Array,
                    *, impl: str = "auto", bm: int = 0, bn: int = 0,
                    interpret: bool = False) -> jax.Array:
    """OS dataflow, implicit-GEMM: in-kernel gather from HBM F_in, no
    [M, Kd, Cin] intermediate. XLA fallback = gather + fused einsum."""
    use_pallas, interp = resolve_backend(impl)
    if not use_pallas:
        gathered = features[jnp.clip(m, 0)]
        return _ref.masked_group_gemm_ref(m, gathered, weights)
    M = m.shape[0]
    m, bm = _tiled_map(m, bm)
    bn = _col_tile(weights.shape[-1], bn)
    out = _os_pallas(features, m, weights, bm=bm, bn=bn,
                     interpret=interpret or interp)
    return out[:M] if m.shape[0] != M else out


def spconv_os_tiles(m: jax.Array, *, bm: int = 0) -> Tuple[int, jax.Array]:
    """(walked, live): the row tiles the OS kernel of
    :func:`spconv_os_fused` walks over ``m`` (static), and how many of
    them hold a valid entry (an int32 scalar); it skips the others."""
    m, bm = _tiled_map(m, bm)
    return m.shape[0] // bm, live_tiles(m, bm).sum()


def spconv_ws_fused(features: jax.Array, m: jax.Array, weights: jax.Array,
                    *, capacity: int, impl: str = "auto", bm: int = 0,
                    bn: int = 0, interpret: bool = False) -> jax.Array:
    """WS dataflow, fused compact+GEMM+merge. XLA fallback = the scan in
    core.dataflow.weight_stationary (imported lazily to avoid a cycle)."""
    use_pallas, interp = resolve_backend(impl)
    if not use_pallas:
        from repro.core.dataflow import weight_stationary
        return weight_stationary(features, m, weights, capacity=capacity)
    bn = _col_tile(weights.shape[-1], bn)
    out = _ws_pallas(features, m, weights, capacity=capacity,
                     bm=bm or 128, bn=bn, interpret=interpret or interp)
    return out.astype(features.dtype)


def output_stationary_fused(features: jax.Array, m: jax.Array,
                            weights: jax.Array, *, impl: str = "auto",
                            interpret: bool = False) -> jax.Array:
    """Unfused OS reference: XLA gather + (Pallas|XLA) masked grouped GEMM.

    Kept as the non-fused baseline — it still materializes the gathered
    [M, Kd, Cin] tensor in HBM; the fused path is :func:`spconv_os_fused`.
    """
    gathered = features[jnp.clip(m, 0)]                # [M, Kd, Cin]
    if resolve_backend(impl)[0]:
        mc, kd, cin = gathered.shape
        bm = 128 if mc % 128 == 0 else (8 if mc % 8 == 0 else 1)
        cout = weights.shape[-1]
        bn = 128 if cout % 128 == 0 else cout
        return _mgg_pallas(m, gathered, weights, bm=bm, bn=bn, interpret=interpret)
    return _ref.masked_group_gemm_ref(m, gathered, weights)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True,
              impl: str = "auto", interpret: bool = False) -> jax.Array:
    """(BH, S, D) attention; Pallas flash kernel on TPU, jnp reference off it."""
    if resolve_backend(impl)[0] and q.shape[1] % 128 == 0 and k.shape[1] % 128 == 0:
        return _fa_pallas(q, k, v, causal=causal, interpret=interpret)
    return _ref.flash_attention_ref(q, k, v, causal=causal)
