"""Pallas↔XLA dataflow parity: the fused implicit-GEMM kernels must match
the XLA dataflows bit-for-bit on valid rows (interpret mode on CPU).

Covers K ∈ {3, 5}, offset strides {1, 2}, dtypes {fp32, bf16}, WS
capacity overflow, zdelta window overflow fallback, the backend dispatch
through SpConvSpec/apply_spconv, and the joint tuner.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (KernelMap, SpConvSpec, apply_spconv, apply_tuning,
                        build_network_plan, hybrid, init_spconv,
                        output_stationary, plan_window, tune_layer_cost_model,
                        tune_layer_measure, weight_stationary, zdelta_offsets)
from repro.core.voxel import build_coord_set, downsample
from repro.data import scenes
from repro.kernels import ops
from repro.kernels.spconv_gather_gemm import spconv_gather_gemm
from repro.kernels.ws_scatter_gemm import ws_scatter_gemm
from repro.kernels.zdelta_window import zdelta_window_search


def _rand_map(rng, M, Kd, N, density=0.3):
    m = rng.integers(0, N, (M, Kd)).astype(np.int32)
    return jnp.asarray(np.where(rng.random((M, Kd)) < density, m, -1))


# ---------------------------------------------------------------------------
# kernel-level parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("K", [3, 5])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gather_gemm_bitmatch(K, dtype):
    rng = np.random.default_rng(0)
    M, N, Cin, Cout = 256, 300, 16, 32
    m = _rand_map(rng, M, K ** 3, N)
    f = jnp.asarray(rng.normal(size=(N, Cin)), dtype)
    w = jnp.asarray(rng.normal(size=(K ** 3, Cin, Cout)) / np.sqrt(Cin), dtype)
    got = spconv_gather_gemm(f, m, w, bm=128, bn=Cout, interpret=True)
    want = output_stationary(f, m, w)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("K", [3, 5])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("capacity", ["lossless", "overflow"])
def test_ws_scatter_bitmatch(K, dtype, capacity):
    rng = np.random.default_rng(1)
    M, N, Cin, Cout = 200, 220, 16, 32        # M deliberately not 128-tiled
    m = _rand_map(rng, M, K ** 3, N)
    cap = M if capacity == "lossless" else int(
        np.asarray((m >= 0).sum(0)).max()) // 2 or 1
    f = jnp.asarray(rng.normal(size=(N, Cin)), dtype)
    w = jnp.asarray(rng.normal(size=(K ** 3, Cin, Cout)) / np.sqrt(Cin), dtype)
    got = ws_scatter_gemm(f, m, w, capacity=cap, bm=64, bn=Cout,
                          interpret=True).astype(dtype)
    want = weight_stationary(f, m, w, capacity=cap)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_dispatch_pads_untiled_rows():
    """ops.spconv_os_fused must handle M % 128 != 0 via -1 row padding."""
    rng = np.random.default_rng(2)
    M, N, Cin, Cout = 200, 128, 8, 24
    m = _rand_map(rng, M, 27, N)
    f = jnp.asarray(rng.normal(size=(N, Cin)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(27, Cin, Cout)).astype(np.float32))
    got = ops.spconv_os_fused(f, m, w, impl="pallas")
    want = output_stationary(f, m, w)
    assert got.shape == (M, Cout)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _with_dead_tiles(m, dead):
    """``m`` with the rows of ``dead`` (a slice) set to -1."""
    m = np.array(m)
    m[dead] = -1
    return jnp.asarray(m)


# Row tiles with no valid entry at any offset are skipped; what they write
# (zeros) must match what the XLA dataflow computes for them, bit for bit.
@pytest.mark.parametrize("M,Cout", [(512, 256), (328, 24)])
@pytest.mark.parametrize("dead", [
    pytest.param(slice(200, None), id="pad_tail"),
    pytest.param(slice(128, 256), id="dead_between_live"),
    pytest.param(slice(None), id="all_dead"),
    pytest.param(slice(0, 0), id="none_dead"),
])
def test_gather_gemm_dead_tiles_bitmatch(M, Cout, dead):
    rng = np.random.default_rng(4)
    N, Cin = 300, 16
    m = _with_dead_tiles(_rand_map(rng, M, 27, N), dead)
    f = jnp.asarray(rng.normal(size=(N, Cin)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(27, Cin, Cout)).astype(np.float32))
    got = ops.spconv_os_fused(f, m, w, impl="pallas", bn=min(Cout, 128))
    want = output_stationary(f, m, w)
    assert got.shape == (M, Cout)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if dead == slice(None):
        assert not np.asarray(got).any()


def test_gather_gemm_backward_pad_tail_bitmatch():
    """dF_in runs the kernel over the transposed map, whose rows past the
    inputs any output reads are -1: Pallas and XLA agree bit for bit."""
    rng = np.random.default_rng(5)
    M, n_out, N, n_read, Cin, Cout = 256, 180, 512, 200, 8, 16
    m = np.full((M, 27), -1, np.int32)
    for k in range(27):               # per-column injective, PAD tail
        col = rng.permutation(n_read)[:n_out]
        m[:n_out, k] = np.where(rng.random(n_out) < 0.3, col, -1)
    m = jnp.asarray(m)
    f = jnp.asarray(rng.normal(size=(N, Cin)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(27, Cin, Cout)).astype(np.float32))
    ct = jnp.asarray(rng.normal(size=(M, Cout)).astype(np.float32))

    def df(backend):
        return jax.grad(lambda f: (output_stationary(
            f, m, w, backend=backend) * ct).sum())(f)

    got, want = df("pallas"), df("xla")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not np.asarray(got)[256:].any()      # two dead row tiles


# ---------------------------------------------------------------------------
# dataflow dispatch + hybrid parity on real kernel maps (strides 1 and 2)
# ---------------------------------------------------------------------------

def _scene_kmap(K, level):
    sc = scenes.indoor_scene(40 + K + level, room=(40, 32, 16))
    cs0 = build_coord_set(scenes.pack_scene(sc))
    cs = cs0 if level == 0 else downsample(cs0, sc.layout, level)
    stride = 1 << level
    _, anchors, zstep = zdelta_offsets(K, stride, sc.layout)
    from repro.core.zdelta import zdelta_search
    m = zdelta_search(cs, cs, anchors, zstep, K=K)
    return KernelMap(m=m, out_count=cs.count, in_count=cs.count), cs, stride, \
        (cs, cs, anchors, zstep)


@pytest.mark.parametrize("K,level", [(3, 0), (3, 1), (5, 0)])
def test_hybrid_backend_parity(K, level):
    kmap, cs, stride, _ = _scene_kmap(K, level)
    rng = np.random.default_rng(3)
    Cin, Cout = 8, 16
    f = jnp.asarray(rng.normal(size=(cs.capacity, Cin)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(K ** 3, Cin, Cout)).astype(np.float32))
    cap = int(np.asarray(kmap.column_counts()).max()) + 8
    t = 2 * stride
    a = hybrid(f, kmap, w, K=K, stride=stride, t=t, ws_capacity=cap,
               backend="xla")
    b = hybrid(f, kmap, w, K=K, stride=stride, t=t, ws_capacity=cap,
               backend="pallas")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_apply_spconv_backend_parity():
    sc = scenes.indoor_scene(44, room=(40, 32, 16))
    packed = scenes.pack_scene(sc)
    base = SpConvSpec("l", 8, 16, K=3, m_in=0, m_out=0, dataflow="hybrid", t=2)
    plan = build_network_plan(packed, specs=(base,), layout=sc.layout)
    params = init_spconv(jax.random.key(0), base)
    f = jax.random.normal(jax.random.key(1), (packed.shape[0], 8))
    outs = {}
    for backend in ("xla", "pallas"):
        spec = dataclasses.replace(base, backend=backend)
        outs[backend] = np.asarray(
            apply_spconv(params, spec, f, plan.kmaps["l"]))
    np.testing.assert_array_equal(outs["xla"], outs["pallas"])


def test_dense_spec_skips_mask_with_parity():
    """``spec.dense`` skips the post-bias row mask; when the plan's buffers
    are exact-sized (count == capacity — no PAD rows, the case the flag
    asserts) the output must be bit-identical to the masked path."""
    sc = scenes.indoor_scene(47, room=(40, 32, 16))
    packed = scenes.pack_scene(sc)          # exact-sized: no PAD tail
    base = SpConvSpec("l", 8, 16, K=3, m_in=0, m_out=0)
    plan = build_network_plan(packed, specs=(base,), layout=sc.layout)
    kmap = plan.kmaps["l"]
    assert int(kmap.out_count) == kmap.m.shape[0]   # level genuinely dense
    params = init_spconv(jax.random.key(3), base)
    f = jax.random.normal(jax.random.key(4), (packed.shape[0], 8))
    masked = apply_spconv(params, base, f, kmap)
    skipped = apply_spconv(params, dataclasses.replace(base, dense=True), f,
                           kmap)
    np.testing.assert_array_equal(np.asarray(masked), np.asarray(skipped))


# ---------------------------------------------------------------------------
# zdelta_pallas indexing engine
# ---------------------------------------------------------------------------

def _engine_specs(window=0):
    return (
        SpConvSpec("l0_sub", 4, 8, K=3, m_in=0, m_out=0, window=window),
        SpConvSpec("l1_down", 8, 16, K=3, m_in=0, m_out=1, dataflow="ws",
                   window=window),
        SpConvSpec("l2_sub", 16, 16, K=5, m_in=1, m_out=1, dataflow="hybrid",
                   t=3, window=window),
    )


def test_zdelta_pallas_engine_matches_zdelta():
    sc = scenes.indoor_scene(45, room=(48, 40, 24))
    packed = scenes.pack_scene(sc)
    ref = build_network_plan(packed, specs=_engine_specs(), layout=sc.layout,
                             engine="zdelta")
    got = build_network_plan(packed, specs=_engine_specs(), layout=sc.layout,
                             engine="zdelta_pallas")
    for name in ref.kmaps:
        np.testing.assert_array_equal(np.asarray(ref.kmaps[name].m),
                                      np.asarray(got.kmaps[name].m))


def test_zdelta_pallas_window_overflow_fallback():
    """A deliberately tiny window overflows; the per-tile XLA fallback must
    restore exact maps anyway."""
    sc = scenes.indoor_scene(46, room=(48, 40, 24))
    # pad capacity to a multiple of 128 so the engine picks 128-row tiles —
    # a 16-wide window then genuinely overflows
    raw = scenes.pack_scene(sc)
    cap = ((raw.shape[0] + 127) // 128) * 128
    packed = scenes.pack_scene(sc, capacity=cap)
    ref = build_network_plan(packed, specs=_engine_specs(), layout=sc.layout,
                             engine="zdelta")
    got = build_network_plan(packed, specs=_engine_specs(window=16),
                             layout=sc.layout, engine="zdelta_pallas")
    # confirm the tiny window actually overflows somewhere (else this test
    # exercises nothing)
    cs = build_coord_set(packed)
    _, anchors, zstep = zdelta_offsets(3, 1, sc.layout)
    _, ovf = zdelta_window_search(cs, cs, anchors, zstep, K=3, W=16, bm=128,
                                  interpret=True)
    assert int(np.asarray(ovf).sum()) > 0
    for name in ref.kmaps:
        np.testing.assert_array_equal(np.asarray(ref.kmaps[name].m),
                                      np.asarray(got.kmaps[name].m))


def test_plan_window_is_overflow_free():
    kmap, cs, stride, (ci, co, anchors, zstep) = _scene_kmap(3, 0)
    W = plan_window(ci, co, anchors, zstep, K=3)
    bm = next(b for b in (128, 64, 32, 16, 8, 4, 2, 1)
              if co.packed.shape[0] % b == 0)
    _, ovf = zdelta_window_search(ci, co, anchors, zstep, K=3,
                                  W=min(W, ci.packed.shape[0]), bm=bm,
                                  interpret=True)
    assert int(np.asarray(ovf).sum()) == 0


# ---------------------------------------------------------------------------
# joint tuner
# ---------------------------------------------------------------------------

def test_tune_layer_measure_and_apply():
    kmap, cs, stride, coords = _scene_kmap(3, 0)
    rng = np.random.default_rng(5)
    f = jnp.asarray(rng.normal(size=(cs.capacity, 8)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(27, 8, 8)).astype(np.float32)) * 0.1
    cap = int(np.asarray(kmap.column_counts()).max()) + 8
    r = tune_layer_measure(f, kmap, w, K=3, stride=1, ws_capacity=cap,
                           backends=("xla", "pallas"), repeats=1,
                           coords=coords)
    assert r.backend in ("xla", "pallas")
    assert (r.t_best, r.backend, r.bm, r.bn) in r.per_config
    assert r.window > 0
    spec = apply_tuning(
        SpConvSpec("l", 8, 8, K=3, dataflow="hybrid", ws_capacity=cap), r)
    assert (spec.t, spec.backend, spec.window) == (r.t_best, r.backend, r.window)
    # the tuned config computes the same function as the XLA reference
    got = hybrid(f, kmap, w, K=3, stride=1, t=spec.t, ws_capacity=cap,
                 backend=spec.backend, bm=spec.bm, bn=spec.bn)
    want = hybrid(f, kmap, w, K=3, stride=1, t=spec.t, ws_capacity=cap,
                  backend="xla")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_tune_layer_cost_model_prefers_fused_bytes():
    kmap, cs, stride, _ = _scene_kmap(5, 0)
    r = tune_layer_cost_model(kmap, K=5, stride=1, cin=32, cout=32)
    assert r.mode == "cost_model"
    # with byte costs in the model, the zero-intermediate pallas backend can
    # never lose at equal t
    xla_best = min(v for (t, b, *_), v in r.per_config.items() if b == "xla")
    pallas_best = min(v for (t, b, *_), v in r.per_config.items()
                     if b == "pallas")
    assert pallas_best <= xla_best
    assert r.backend == "pallas"
