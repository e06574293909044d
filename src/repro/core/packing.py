"""Packed-native voxel coordinate codec (Spira §5.3).

Exploits the *Bounded Property*: voxel coordinates live in a finite grid
``(Rx/gx, Ry/gy, Rz/gz)``, so each component fits in a small bit budget and a
whole (batch, x, y, z) tuple packs into one int32 or int64. All voxel-indexing
operators in this engine work *natively* on packed values:

  * lexicographic order is preserved:  ``p > q  <=>  packed(p) > packed(q)``
  * offset addition is preserved (within bounds):
      ``packed(q) + packed_offset(d) == packed(q + d)``
  * stride-2^m rounding is a bitwise AND with a precomputed mask.

Packing happens once on the network's input coordinates; nothing downstream
unpacks (the *packed-native* property).

Guard-band contract
-------------------
Queries ``q + d`` may leave the grid. Packed addition then borrows/carries
across fields, producing a word whose canonical digits differ by ±1 in the
next field. To guarantee such words never *equal* a real packed coordinate
(false-positive match), real coordinates must keep every field value inside
``[guard, 2^b - guard)`` where ``guard >= max |d_component| = (K-1)/2 * s_p``.
``BitLayout.for_extent`` sizes fields for ``extent + 2*guard`` and the data
pipeline biases raw coordinates by ``+guard``. ``guard`` must be a power of
two >= the deepest stride so that packed-native stride rounding (bitmask AND)
commutes with the bias. Default guard = 16 (covers K<=9 at s_p<=8 and strides
up to 16).

64-bit packing uses jnp.int64 and therefore requires x64 (wrap call sites in
``jax.enable_x64(True)``); the 32-bit path is the default everywhere,
matching the paper's finding that 32-bit suffices for real workloads.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _batch_bits(batch: int) -> int:
    """Batch-field width for ``batch`` scenes (0 = batch-free layout) — the
    ONE sizing rule shared by ``BitLayout.for_extent`` and ``with_batch``."""
    return 0 if batch <= 1 else max(1, int(np.ceil(np.log2(int(batch)))))


@dataclasses.dataclass(frozen=True)
class BitLayout:
    """Bit allocation (batch, x, y, z), most-significant field first.

    Default mirrors the paper's evaluation split: 12/12/8 bits for x/y/z in a
    32-bit word; the batch field is prepended. ``bits_total <= 31`` uses int32
    (sign bit kept clear), otherwise int64 (``bits_total <= 63``).

    ``guard`` records the guard band the layout was sized for (module
    docstring); validation (``core.validate``) checks real coordinates
    against ``data_range`` = ``[guard, 2^b - guard)`` per field.

    Width is validated at *construction* — a layout that cannot fit an
    integer word fails here with the field split in hand, not later at the
    first ``.dtype`` lookup deep inside a plan build.
    """

    bx: int = 12
    by: int = 12
    bz: int = 8
    bb: int = 0  # batch bits (0 => single scene)
    guard: int = 16

    def __post_init__(self):
        if min(self.bx, self.by, self.bz) < 1 or self.bb < 0:
            raise ValueError(f"BitLayout needs bx/by/bz >= 1 and bb >= 0, "
                             f"got bx={self.bx} by={self.by} bz={self.bz} "
                             f"bb={self.bb}")
        if self.guard < 1 or self.guard & (self.guard - 1):
            raise ValueError(f"BitLayout guard must be a power of two >= 1, "
                             f"got {self.guard}")
        if self.bits_total > 63:
            raise ValueError(
                f"BitLayout too wide: bx={self.bx} + by={self.by} + "
                f"bz={self.bz} + bb={self.bb} = {self.bits_total} bits, but "
                f"64-bit packing keeps the sign bit clear (max 63). Shrink "
                f"the grid extents, lower the guard band (guard="
                f"{self.guard} adds ceil(log2(extent + 2*guard)) bits per "
                f"axis), or voxelize coarser.")

    @property
    def bits_total(self) -> int:
        return self.bb + self.bx + self.by + self.bz

    @property
    def dtype(self):
        if self.bits_total <= 31:
            return jnp.int32
        if self.bits_total <= 63:
            return jnp.int64
        raise ValueError(f"BitLayout too wide: {self.bits_total} bits")

    # Shifts: z is least significant.
    @property
    def shift_z(self) -> int:
        return 0

    @property
    def shift_y(self) -> int:
        return self.bz

    @property
    def shift_x(self) -> int:
        return self.bz + self.by

    @property
    def shift_b(self) -> int:
        return self.bz + self.by + self.bx

    def capacity(self) -> Tuple[int, int, int, int]:
        """(batch, x, y, z) max representable exclusive bounds."""
        return (1 << self.bb if self.bb else 1, 1 << self.bx, 1 << self.by, 1 << self.bz)

    def data_range(self) -> Tuple[Tuple[int, int], ...]:
        """Per-axis (lo, hi) *exclusive-hi* bounds real (guard-biased)
        coordinates must satisfy: ``[guard, 2^b - guard)`` for x, y, z —
        the guard-band contract (module docstring) that ``core.validate``
        enforces at the SparseTensor boundary."""
        g = self.guard
        return tuple((g, (1 << b) - g) for b in (self.bx, self.by, self.bz))

    @classmethod
    def for_extent(cls, ex: int, ey: int, ez: int, batch: int = 1,
                   guard: int = 16) -> "BitLayout":
        """Smallest layout covering a grid extent plus a ``guard`` band on
        each side (see module docstring for the guard contract).

        Raises at build time — with the per-axis bit budget in the message —
        when the extents need more than the 63 packable bits, instead of
        failing later at the first ``.dtype`` lookup."""
        assert guard >= 1 and guard & (guard - 1) == 0, \
            "guard must be a power of two"
        need = lambda n: max(1, int(np.ceil(np.log2(max(2, int(n) + 2 * guard)))))
        bits = {"x": need(ex), "y": need(ey), "z": need(ez)}
        bb = _batch_bits(batch)
        total = sum(bits.values()) + bb
        if total > 63:
            per_axis = ", ".join(
                f"{ax}: extent {e} + 2*{guard} guard -> {bits[ax]} bits"
                for ax, e in zip("xyz", (ex, ey, ez)))
            raise ValueError(
                f"BitLayout.for_extent({ex}, {ey}, {ez}, batch={batch}, "
                f"guard={guard}) needs {total} bits ({per_axis}"
                f"{f', batch -> {bb} bits' if bb else ''}) but packing "
                f"allows at most 63. Shrink the offending extents, reduce "
                f"the guard band, lower the batch size, or voxelize "
                f"coarser.")
        return cls(bx=bits["x"], by=bits["y"], bz=bits["z"], bb=bb,
                   guard=guard)

    def with_batch(self, batch: int) -> "BitLayout":
        """Same x/y/z fields, batch field sized for ``batch`` scenes.

        The batch field is the word's most-significant field and weight
        offsets never carry a batch component, so everything proved for
        single-scene packed words lifts to batched ones: sorted order is
        batch-major (per-scene segments stay contiguous and sorted),
        :func:`round_down` never clears batch bits (its run-structure lemma
        is batch-oblivious), and the guard band keeps offset queries from
        borrowing/carrying across the batch boundary (no cross-scene kernel-
        map matches). ``batch <= 1`` returns a batch-free layout."""
        return dataclasses.replace(self, bb=_batch_bits(batch))


# ---------------------------------------------------------------------------
# pack / unpack
# ---------------------------------------------------------------------------

def pack(coords: jax.Array, layout: BitLayout, batch: jax.Array | None = None) -> jax.Array:
    """Pack integer coordinates ``coords[..., 3]`` (x, y, z ≥ 0) into one word.

    ``batch`` (optional, same leading shape) goes in the most-significant
    field. Works natively under jit; the output is sorted-order compatible
    with lexicographic (batch, x, y, z) order.

    This function is a raw bit-field encoder and does NOT bounds-check: a
    negative or out-of-field component silently bleeds into the neighboring
    field (voxel aliasing). The (x, y, z in ``layout.data_range()``)
    contract is *enforced* at the data boundary —
    ``SparseTensor.from_point_cloud(validate=...)`` via ``core.validate`` —
    so everything downstream of a SparseTensor may assume it.
    """
    dt = layout.dtype
    x = coords[..., 0].astype(dt)
    y = coords[..., 1].astype(dt)
    z = coords[..., 2].astype(dt)
    out = (x << layout.shift_x) | (y << layout.shift_y) | (z << layout.shift_z)
    if batch is not None and layout.bb:
        out = out | (batch.astype(dt) << layout.shift_b)
    return out


def pack_offsets(offsets: jax.Array, layout: BitLayout) -> jax.Array:
    """Pack (possibly negative) weight offsets so that
    ``pack(q) + pack_offsets(d) == pack(q + d)`` — signedness rides on field
    arithmetic: a negative component contributes a borrow into the next field
    which cancels exactly when the sum per-field is within range."""
    dt = layout.dtype
    dx = offsets[..., 0].astype(dt)
    dy = offsets[..., 1].astype(dt)
    dz = offsets[..., 2].astype(dt)
    return (dx << layout.shift_x) + (dy << layout.shift_y) + (dz << layout.shift_z)


def unpack(packed: jax.Array, layout: BitLayout) -> Tuple[jax.Array, jax.Array]:
    """Inverse of :func:`pack`. Returns (coords[..., 3], batch)."""
    p = packed.astype(layout.dtype)
    mask = lambda b: (1 << b) - 1
    z = (p >> layout.shift_z) & mask(layout.bz)
    y = (p >> layout.shift_y) & mask(layout.by)
    x = (p >> layout.shift_x) & mask(layout.bx)
    b = (p >> layout.shift_b) & mask(layout.bb) if layout.bb else jnp.zeros_like(x)
    return jnp.stack([x, y, z], axis=-1).astype(jnp.int32), b.astype(jnp.int32)


# ---------------------------------------------------------------------------
# packed-native downsample rounding (Spira §5.3: bitwise mask)
# ---------------------------------------------------------------------------

def downsample_mask(layout: BitLayout, m: int) -> int:
    """Mask clearing the low ``m`` bits of each of the x/y/z fields: AND-ing a
    packed coordinate rounds every component down to a multiple of 2^m —
    the packed-native form of ``floor(v / 2^m) * 2^m`` (Eq. 1)."""
    full = (1 << layout.bits_total) - 1
    clear = ((1 << m) - 1) << layout.shift_z
    clear |= ((1 << m) - 1) << layout.shift_y
    clear |= ((1 << m) - 1) << layout.shift_x
    return full & ~clear


def round_down(packed: jax.Array, layout: BitLayout, m: int) -> jax.Array:
    """Apply :func:`downsample_mask`.

    **Not order-preserving on packed words.** Rounding floors each field
    independently, and the cleared bits sit in the *middle* of the word (low
    bits of the x and y fields), so a sorted input does not stay sorted:
    e.g. with m=1, packed (x=0, y=5, z=·) < (x=1, y=0, z=·) but rounds to
    (0, 4, ·) > (0, 0, ·). What *does* survive is run structure: restricted
    to inputs that agree on the cleared x-bits and cleared y-bits (the "run
    residue"), rounding is monotone — two such words first differ at an
    uncleared bit position, and flooring never reorders there. A sorted
    array therefore splits into 4^m interleaved sorted runs keyed by
    (x mod 2^m, y mod 2^m); ``voxel.downsample`` exploits exactly this to
    rebuild sortedness with a run merge instead of a fresh sort.

    Batch bits (``layout.bb > 0``) change nothing: they sit *above* x and
    are never cleared, so they behave like any other uncleared high bit —
    the run structure is still keyed by the cleared (x, y) residues alone,
    and each run is itself batch-major. Batched multi-scene coordinate
    streams therefore flow through the same merge pipeline unmodified.
    """
    if m == 0:
        return packed
    return packed & jnp.asarray(downsample_mask(layout, m), layout.dtype)


# ---------------------------------------------------------------------------
# offset enumeration Δ(K, s_p) with L1 norms and z-delta grouping
# ---------------------------------------------------------------------------

def offset_grid(K: int, stride: int = 1) -> np.ndarray:
    """All K³ weight offsets Δ(K, s_p), ordered so that each consecutive run
    of K offsets forms one *z-delta group*: identical (x, y), z ascending by
    ``stride``. Row-major (x, y, z) enumeration has exactly this property.
    Returns int32 [K^3, 3] (host-side; offsets are static per layer)."""
    half = (K - 1) // 2
    r = (np.arange(K) - half) * stride
    g = np.stack(np.meshgrid(r, r, r, indexing="ij"), axis=-1)  # (K,K,K,3) x,y,z
    return g.reshape(-1, 3).astype(np.int32)


def offset_l1(offsets: np.ndarray) -> np.ndarray:
    return np.abs(offsets).sum(axis=-1).astype(np.int32)
