"""Guarded-ingest suite: the voxel data contract enforced at the boundary.

Deterministic mirror of the hypothesis properties in test_property.py
(which skip when hypothesis is absent): pack/unpack round-trips at exact
field-boundary coordinates for int32 and int64 layouts, and out-of-range
input is REJECTED by validation rather than silently aliasing a neighbor
field — the failure mode ``core.validate`` exists to prevent.
"""
import contextlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (BitLayout, SparseTensor, ValidationError,
                        ValidationReport, pack, unpack, validate_point_cloud)


LAYOUT = BitLayout.for_extent(100, 80, 40, guard=16)   # int32-packed


def _ok_cloud(n=40, seed=0):
    rng = np.random.default_rng(seed)
    lo = [r[0] for r in LAYOUT.data_range()]
    hi = [r[1] for r in LAYOUT.data_range()]
    c = np.stack([rng.integers(lo[a], hi[a], n) for a in range(3)], axis=1)
    f = rng.normal(size=(n, 4)).astype(np.float32)
    return c.astype(np.int64), f


def _poisoned():
    """A cloud with one row per violation category (rows 0-4 bad)."""
    c, f = _ok_cloud()
    c = c.astype(np.float64)
    c[0] = [-3, 20, 20]                   # negative -> aliases on pack
    c[1] = [1 << LAYOUT.bx, 20, 20]       # past field width -> aliases
    c[2] = [LAYOUT.guard - 1, 20, 20]     # inside the guard band
    c[3] = [20.5, 20, 20]                 # fractional voxel coordinate
    f = f.copy()
    f[4, 0] = np.nan                      # non-finite feature row
    return c, f


# ---------------------------------------------------------------------------
# policies
# ---------------------------------------------------------------------------

def test_reject_raises_with_categorized_report():
    c, f = _poisoned()
    with pytest.raises(ValidationError) as ei:
        SparseTensor.from_point_cloud(c, f, LAYOUT)
    e = ei.value
    r = e.report
    assert (r.n_bad, r.n_aliased, r.n_out_of_guard, r.n_nonfinite,
            r.n_noninteger) == (5, 2, 1, 1, 1)
    # actionable: names the valid ranges and the remediation policies
    msg = str(e)
    assert "x∈[16," in msg and "clip" in msg and "drop" in msg


def test_clip_clamps_and_zeroes_then_serves():
    c, f = _poisoned()
    st = SparseTensor.from_point_cloud(c, f, LAYOUT, validate="clip")
    r = st.validation
    assert r.policy == "clip" and r.n_clipped == 5 and r.n_dropped == 0
    v, _ = st.coords()
    lo = np.array([rr[0] for rr in LAYOUT.data_range()])
    hi = np.array([rr[1] for rr in LAYOUT.data_range()])
    assert (v >= lo).all() and (v < hi).all()
    assert np.isfinite(np.asarray(st.features)).all()


def test_drop_removes_offending_rows():
    c, f = _poisoned()
    st = SparseTensor.from_point_cloud(c, f, LAYOUT, validate="drop")
    assert st.validation.n_dropped == 5
    assert int(st.count) == len(np.unique(
        np.asarray(pack(jnp.asarray(c[5:].astype(np.int64)), LAYOUT))))


def test_none_trusts_caller():
    c, f = _ok_cloud()
    cc, ff, r = validate_point_cloud(c, f, LAYOUT, policy="none")
    assert r.ok and r.n_points == len(c)
    with pytest.raises(ValueError, match="must be one of"):
        validate_point_cloud(c, f, LAYOUT, policy="bogus")


def test_clean_cloud_passes_all_policies():
    c, f = _ok_cloud()
    for pol in ("reject", "clip", "drop"):
        st = SparseTensor.from_point_cloud(c, f, LAYOUT, validate=pol)
        assert st.validation.ok, pol
        assert st.validation.n_clipped == 0 and st.validation.n_dropped == 0


def test_batched_scene_index_and_merged_report():
    good = _ok_cloud(seed=1)
    bad = _poisoned()
    with pytest.raises(ValidationError) as ei:
        SparseTensor.from_point_clouds([good, bad], LAYOUT)
    assert ei.value.scene_index == 1
    assert "scene 1" in str(ei.value)
    st = SparseTensor.from_point_clouds([good, bad], LAYOUT, validate="clip")
    r = st.validation
    assert r.n_points == len(good[0]) + len(bad[0]) and r.n_bad == 5
    # the report is host metadata: it survives padding but not jit
    assert st.pad_to(st.capacity * 2).validation is r


def test_report_summary_and_merge_arithmetic():
    a = ValidationReport(policy="clip", n_points=10, n_ok=8, n_aliased=2,
                         n_clipped=2)
    b = ValidationReport(policy="clip", n_points=5, n_ok=5)
    m = a.merged(b)
    assert (m.n_points, m.n_ok, m.n_bad, m.n_clipped) == (15, 13, 2, 2)
    assert "2/15" in m.summary()


# ---------------------------------------------------------------------------
# layout width validation (build-time, satellite: for_extent > 63 bits)
# ---------------------------------------------------------------------------

def test_for_extent_rejects_over_63_bits_naming_extents():
    with pytest.raises(ValueError) as ei:
        BitLayout.for_extent(10 ** 7, 10 ** 7, 10 ** 6, batch=32, guard=16)
    msg = str(ei.value)
    assert "63" in msg and "10000000" in msg and "guard" in msg


def test_direct_layout_width_and_guard_validation():
    with pytest.raises(ValueError, match="63"):
        BitLayout(bx=30, by=30, bz=8)
    with pytest.raises(ValueError, match="power of two"):
        BitLayout(bx=8, by=8, bz=8, guard=12)
    # exactly 63 bits is legal (sign bit stays clear)
    BitLayout(bx=21, by=21, bz=21, bb=0)


# ---------------------------------------------------------------------------
# boundary round-trips (deterministic mirror of the hypothesis property)
# ---------------------------------------------------------------------------

def _boundary_values(b: int, guard: int):
    vals = {0, 1, guard - 1, guard, guard + 1,
            (1 << b) - guard - 1, (1 << b) - guard, (1 << b) - 2,
            (1 << b) - 1}
    return sorted(v for v in vals if 0 <= v < (1 << b))


@pytest.mark.parametrize("layout", [
    BitLayout(bx=10, by=9, bz=8),              # 27 bits -> int32 words
    BitLayout(bx=22, by=21, bz=20),            # 63 bits -> int64 words
    BitLayout(bx=12, by=11, bz=10, bb=4),      # batched int64
], ids=["int32", "int64", "batched"])
def test_pack_unpack_roundtrip_at_field_boundaries(layout):
    """unpack(pack(c)) == c for every combination of per-axis boundary
    values (0, guard±1, max-in-field, max∓guard) — pack is exact on the
    whole field, not just the guarded interior."""
    bx = _boundary_values(layout.bx, layout.guard)
    by = _boundary_values(layout.by, layout.guard)
    bz = _boundary_values(layout.bz, layout.guard)
    c = np.array([(x, y, z) for x in bx for y in by for z in bz], np.int64)
    want_dtype = np.int32 if layout.bits_total <= 31 else np.int64
    # the 64-bit packing path needs x64 enabled (packing module doc)
    ctx = (jax.enable_x64(True) if layout.bits_total > 31
           else contextlib.nullcontext())
    with ctx:
        for sid in range(min(1 << layout.bb, 3)):
            b = (np.full(len(c), sid, np.int64) if layout.bb else None)
            p = np.asarray(pack(jnp.asarray(c), layout,
                                None if b is None else jnp.asarray(b)))
            assert p.dtype == want_dtype
            back, bid = unpack(jnp.asarray(p), layout)
            np.testing.assert_array_equal(np.asarray(back), c)
            np.testing.assert_array_equal(np.asarray(bid),
                                          b if b is not None else 0 * c[:, 0])


def test_out_of_range_is_rejected_not_wrapped():
    """PINNED: a coordinate one past the field width would alias a
    different voxel under raw pack() (the wraparound bug class); the
    guarded boundary must reject it instead."""
    layout = BitLayout(bx=8, by=8, bz=8)
    alias_src = np.array([[(1 << 8) + 3, 20, 20]], np.int64)
    # raw pack() really does corrupt: the out-of-field x round-trips to a
    # DIFFERENT in-range voxel (its low 8 bits) — the bug class we guard
    p_src = pack(jnp.asarray(alias_src), layout)
    back, _ = unpack(p_src, layout)
    np.testing.assert_array_equal(np.asarray(back),
                                  np.array([[3, 20, 20]], np.int64))
    f = np.zeros((1, 4), np.float32)
    with pytest.raises(ValidationError):
        SparseTensor.from_point_cloud(alias_src, f, layout)
    rep = None
    try:
        SparseTensor.from_point_cloud(alias_src, f, layout)
    except ValidationError as e:
        rep = e.report
    assert rep is not None and rep.n_aliased == 1
