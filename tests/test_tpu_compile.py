"""Compile rehearsals for the TPU v5e, without the chip.

Each test compiles a Pallas kernel of the main path at real widths, or one
whole MinkUNet-42 session step, for a *described* v5e chip
(``jax.experimental.topologies``): the TPU compiler refuses here what it
would refuse on the chip — block shapes it cannot tile, unaligned DMA
slices, more VMEM or HBM than the chip has. Nothing runs, so these say
nothing about results or speed.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every pytest
worker imports this file. Keep all such compiles in this one file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.kernel_map import l1_partition
from repro.core.packing import BitLayout
from repro.core.voxel import CoordSet
from repro.core.zdelta import zdelta_offsets
from repro.kernels import ops
from repro.kernels.segsum import segment_sum_pallas
from repro.kernels.spconv_gather_gemm import spconv_gather_gemm
from repro.kernels.ws_scatter_gemm import ws_scatter_gemm
from repro.kernels.zdelta_window import (zdelta_superwindow_search,
                                         zdelta_window_search)
from repro.models.pointcloud import minkunet42

# Capacity bucket of chip_smoke.py's serve batch: 4 outdoor scans of
# ~76k voxels each pad to 524288 rows.
BUCKET = 524288
LAYOUT = BitLayout.for_extent(1024, 1024, 40, guard=16).with_batch(4)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            t = topologies.get_topology_desc(platform="tpu",
                                             topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield t


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent compile cache off: a
    compile for a described chip is written to it but cannot be read back
    without the chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _col_tile(cout):
    return 128 if cout % 128 == 0 else cout


# (Cin, Cout) of minkunet42's layers; (32, 4) is the stem's dF_in in the
# backward, which runs the same kernel over the transposed map.
@pytest.mark.parametrize("cin,cout", [(4, 32), (32, 32), (256, 128),
                                      (160, 96), (128, 256), (32, 4)])
def test_spconv_gather_gemm_compiles(one_chip, cin, cout):
    s = lambda *a: _shape(one_chip, *a)
    _compile(lambda f, m, w: spconv_gather_gemm(f, m, w, bm=128,
                                                bn=_col_tile(cout)),
             s((BUCKET, cin), jnp.float32), s((BUCKET, 27), jnp.int32),
             s((27, cin, cout), jnp.float32))


# CenterPoint-Large's hybrid layers (K = 5, t = 3): the WS half runs over
# the offsets with L1 norm >= t. (16, 5) is the stem's dF_in.
@pytest.mark.parametrize("stride,cin,cout", [(1, 5, 16), (1, 16, 16),
                                             (2, 32, 32), (8, 64, 64),
                                             (1, 16, 5)])
def test_ws_scatter_gemm_compiles(one_chip, stride, cin, cout):
    s = lambda *a: _shape(one_chip, *a)
    _, cols = l1_partition(5, stride, 3)
    ks = len(cols)
    _compile(lambda f, m, w: ws_scatter_gemm(f, m, w, capacity=BUCKET,
                                             bm=128, bn=_col_tile(cout)),
             s((BUCKET, cin), jnp.float32), s((BUCKET, ks), jnp.int32),
             s((ks, cin, cout), jnp.float32))


def test_segment_sum_pallas_compiles(one_chip):
    """BN moments of the widest minkunet42 layer: [x, x²] is 2 × 256."""
    s = lambda *a: _shape(one_chip, *a)
    _compile(lambda x, sid, st: segment_sum_pallas(x, sid, st,
                                                   num_segments=4),
             s((BUCKET, 512), jnp.float32), s((BUCKET,), jnp.int32),
             s((4,), jnp.int32))


def _zdelta_args(sharding):
    _, anchors, zstep = zdelta_offsets(3, 1, LAYOUT)
    cs = lambda: CoordSet(packed=_shape(sharding, (BUCKET,), jnp.int32),
                          count=_shape(sharding, (), jnp.int32))
    return cs(), cs(), _shape(sharding, anchors.shape, jnp.int32), zstep


@pytest.mark.xfail(strict=True, raises=Exception, reason=(
    "The Pallas TPU lowering currently requires that the last two "
    "dimensions of your block shape are divisible by 8 and 128 "
    "respectively, or be equal to the respective dimensions of the overall "
    "array. (block (1, 128) over [n_tiles, 128]; with a 3-D view past that, "
    "Mosaic refuses the in-VMEM gathers: 'Only 2D gather is supported')"))
def test_zdelta_superwindow_search_compiles(one_chip):
    a, b, anchors, zstep = _zdelta_args(one_chip)
    _compile(lambda a, b, an: zdelta_superwindow_search(
        a, b, an, zstep, K=3, W=2048, bm=128), a, b, anchors)


@pytest.mark.xfail(strict=True, raises=Exception, reason=(
    "The Pallas TPU lowering currently requires that the last two "
    "dimensions of your block shape are divisible by 8 and 128 "
    "respectively, or be equal to the respective dimensions of the overall "
    "array. (block (1, 128) over [n_tiles, 128])"))
def test_zdelta_window_search_compiles(one_chip):
    a, b, anchors, zstep = _zdelta_args(one_chip)
    _compile(lambda a, b, an: zdelta_window_search(
        a, b, an, zstep, K=3, W=512, bm=128), a, b, anchors)


@pytest.fixture
def tpu_branch(monkeypatch):
    """Steer every platform decision onto its TPU branch. The jit caches
    are cleared on both sides so no trace of the other branch is reused."""
    jax.clear_caches()
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    yield
    jax.clear_caches()


def test_session_step_compiles(one_chip, tpu_branch):
    """One whole level-0 MinkUNet-42 serving step (plan + 42 Pallas convs
    + Pallas BN reductions) at the smoke's bucket fits one v5e."""
    from repro.serve import compile_network
    session = compile_network(minkunet42(), LAYOUT, batch=4)
    step = session._make_fn(0)
    params = jax.tree.map(
        lambda x: _shape(one_chip, x.shape, x.dtype), session.params)
    compiled = step.lower(params, _shape(one_chip, (BUCKET,), jnp.int32),
                          _shape(one_chip, (BUCKET, 4), jnp.float32)
                          ).compile()
    hlo = compiled.as_text()
    # one gather-GEMM and one segment-sum kernel per layer, none interpreted
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2 * 42
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 15 * 2**30
