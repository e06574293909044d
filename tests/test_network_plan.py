"""Network-wide voxel indexing + spconv layer integration + tuner."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    SpConvSpec, apply_spconv, init_spconv, build_network_plan,
    sequential_plan_fns, KernelMap, symmetrize_kernel_map, zdelta_offsets,
    zdelta_search, tune_threshold_cost_model, tune_threshold_measure,
)
from repro.core import reference
from repro.core.voxel import build_coord_set
from repro.data import scenes


def _specs():
    return (
        SpConvSpec("l0_sub", 4, 8, K=3, m_in=0, m_out=0, dataflow="os"),
        SpConvSpec("l1_down", 8, 16, K=3, m_in=0, m_out=1, dataflow="ws"),
        SpConvSpec("l2_sub", 16, 16, K=5, m_in=1, m_out=1, dataflow="hybrid", t=3),
        SpConvSpec("l3_down", 16, 32, K=3, m_in=1, m_out=2, dataflow="os"),
        SpConvSpec("l4_up", 32, 16, K=3, m_in=2, m_out=1, dataflow="os"),  # inverse conv
    )


def test_network_plan_all_engines_agree():
    sc = scenes.indoor_scene(11, room=(64, 48, 24))
    packed = scenes.pack_scene(sc)
    plans = {e: build_network_plan(packed, specs=_specs(), layout=sc.layout, engine=e)
             for e in ("zdelta", "bsearch", "hash")}
    for name in plans["zdelta"].kmaps:
        mz = np.asarray(plans["zdelta"].kmaps[name].m)
        np.testing.assert_array_equal(mz, np.asarray(plans["bsearch"].kmaps[name].m))
        np.testing.assert_array_equal(mz, np.asarray(plans["hash"].kmaps[name].m))


@pytest.mark.parametrize("engine", ["zdelta_pallas", "zdelta_pallas_window"])
def test_zdelta_pallas_engines_refused_on_tpu(monkeypatch, engine):
    """Their kernels do not compile for TPU: planning with them there must
    raise, not fall back to the interpreter."""
    from repro.kernels import ops
    sc = scenes.indoor_scene(12, room=(32, 32, 16))
    jax.clear_caches()
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    try:
        with pytest.raises(NotImplementedError, match="do not compile"):
            build_network_plan(scenes.pack_scene(sc), specs=_specs()[:1],
                               layout=sc.layout, engine=engine)
    finally:
        jax.clear_caches()


def test_network_plan_matches_brute_force_inverse_conv():
    """The l4_up inverse-conv map must match brute force with the fine-side
    offset stride."""
    sc = scenes.indoor_scene(12, room=(48, 40, 20))
    packed = scenes.pack_scene(sc)
    plan = build_network_plan(packed, specs=_specs(), layout=sc.layout)
    c1 = reference.downsample_reference(sc.coords, 1)
    c2 = reference.downsample_reference(sc.coords, 2)
    ref = reference.kernel_map_reference(c2, c1, 3, 2)  # inputs coarse, outputs fine
    got = np.asarray(plan.kmaps["l4_up"].m)
    np.testing.assert_array_equal(got[: len(c1)], ref)


def test_sequential_plan_matches_fused():
    sc = scenes.indoor_scene(13, room=(48, 40, 20))
    packed = scenes.pack_scene(sc)
    fused = build_network_plan(packed, specs=_specs(), layout=sc.layout)
    sort_fn, level_fns, map_fns = sequential_plan_fns(_specs(), sc.layout)
    coords = {0: sort_fn(packed)}
    for m, fn in level_fns.items():
        coords[m] = fn(coords[0])
    for s in _specs():
        km = map_fns[s.name](coords[s.m_in], coords[s.m_out])
        np.testing.assert_array_equal(np.asarray(km.m),
                                      np.asarray(fused.kmaps[s.name].m))


def test_spconv_layer_end_to_end_and_grad():
    sc = scenes.indoor_scene(14, room=(48, 40, 20))
    packed = scenes.pack_scene(sc)
    spec = SpConvSpec("l2_sub", 16, 16, K=5, m_in=1, m_out=1, dataflow="hybrid", t=3)
    plan = build_network_plan(packed, specs=(spec,), layout=sc.layout)
    kmap = plan.kmaps[spec.name]
    params = init_spconv(jax.random.key(0), spec)
    feats = jax.random.normal(jax.random.key(1), (packed.shape[0], 16))

    def loss(p):
        return (apply_spconv(p, spec, feats, kmap) ** 2).sum()

    g = jax.grad(loss)(params)
    assert np.isfinite(np.asarray(g["w"])).all()
    assert np.isfinite(float(loss(params)))


def test_symmetry_trick_matches_full_search():
    sc = scenes.indoor_scene(15, room=(48, 40, 20))
    packed = scenes.pack_scene(sc)
    cs = build_coord_set(jnp.asarray(packed))
    K = 3
    _, anchors, zstep = zdelta_offsets(K, 1, sc.layout)
    full = np.asarray(zdelta_search(cs, cs, anchors, zstep, K=K))
    half = full.copy()
    half[:, K ** 3 // 2 + 1:] = -1  # keep only first half + center
    sym = np.asarray(symmetrize_kernel_map(jnp.asarray(half), K=K))
    np.testing.assert_array_equal(sym, full)


def test_tuner_cost_model_prefers_hybrid_on_k5():
    sc = scenes.indoor_scene(16, room=(80, 64, 32))
    packed = scenes.pack_scene(sc)
    spec = SpConvSpec("l", 32, 32, K=5, m_in=0, m_out=0)
    plan = build_network_plan(packed, specs=(spec,), layout=sc.layout)
    r = tune_threshold_cost_model(plan.kmaps["l"], K=5, stride=1, cin=32, cout=32)
    # on surface scenes full-OS is never optimal for K=5 (many near-empty cols)
    assert r.t_best <= 6
    full_os = max(r.per_t)  # t = L1NormMax + 1
    assert r.per_t[r.t_best] <= r.per_t[full_os]  # at least as good as full OS


def test_tuner_measure_runs():
    sc = scenes.indoor_scene(17, room=(40, 32, 16))
    packed = scenes.pack_scene(sc)
    spec = SpConvSpec("l", 8, 8, K=3, m_in=0, m_out=0)
    plan = build_network_plan(packed, specs=(spec,), layout=sc.layout)
    kmap = plan.kmaps["l"]
    feats = jax.random.normal(jax.random.key(0), (packed.shape[0], 8))
    w = jax.random.normal(jax.random.key(1), (27, 8, 8)) * 0.1
    r = tune_threshold_measure(feats, kmap, w, K=3, stride=1,
                               ws_capacity=kmap.m.shape[0], repeats=1)
    assert r.t_best in r.per_t
