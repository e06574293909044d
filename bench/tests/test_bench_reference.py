"""The plain reference against the program at tiny scenes on the CPU, and
its kernel maps against the repository's brute-force oracle."""
import numpy as np
import pytest

import jax

from bench import harness, reference, traffic
from bench.tests import tiny


def test_maps_match_the_brute_force_oracle():
    from repro.core.reference import downsample_reference, \
        kernel_map_reference
    scan = traffic.scan_batch(5, 1, "indoor", (32, 28, 16), 0.3)[0]
    net = reference.Net((reference.Layer("a", 1, 1, 3, 0, 0),
                         reference.Layer("d", 1, 1, 3, 0, 1),
                         reference.Layer("u", 1, 1, 3, 1, 0),
                         reference.Layer("b", 1, 1, 3, 1, 1)), 1, 1)
    hs = reference.build_scan(scan.coords, net)
    lv = {m: downsample_reference(scan.coords.astype(np.int64), m)
          for m in (0, 1)}
    for m in (0, 1):
        np.testing.assert_array_equal(hs.coords(m), lv[m])
    for a, b in ((0, 0), (0, 1), (1, 0), (1, 1)):
        want = kernel_map_reference(lv[a], lv[b], 3, 1 << min(a, b))
        np.testing.assert_array_equal(hs.maps[(a, b, 3)], want)


@pytest.mark.parametrize("config,extent,tol", [
    # a full-precision float32 forward on the CPU; minkunet42's deepest
    # level holds tens of voxels here, whose BN amplifies rounding
    ("sparse_resnet21", (32, 28, 16), 1e-4),
    ("minkunet42", (64, 48, 32), 1e-3),
])
def test_reference_matches_the_session(config, extent, tol):
    from repro.core.packing import BitLayout
    from repro.core.sparse_tensor import SparseTensor
    from bench.modes import common
    from repro.serve import compile_network
    cell = tiny.config_cell(f"bench/configs/{config}.json", seed=11)
    net = cell.net
    scan = traffic.scan_batch(11, 1, "indoor", extent, 0.3)[0]
    feats = traffic.scan_features(scan, net.in_channels)
    params = reference.init_params(reference.seed_key(11), net)
    session = compile_network(common.checked_program_net(cell),
                              BitLayout.for_extent(*extent), params=params)
    out = session(SparseTensor.from_point_cloud(scan.coords, feats,
                                                session.layout))
    n = int(out.count)
    vox, _ = out.coords()
    hs = reference.build_scan(scan.coords, net)
    ref = np.asarray(reference.forward(
        params, reference.device_inputs([hs], [feats], net), net=net))[0]
    gaps = reference.logit_gaps(vox, np.asarray(out.features)[:n], hs, ref,
                                net.out_level)
    assert gaps["logit_gap"] < tol


def test_voxel_gap_reads_a_wrong_class_where_logits_are_small():
    """One voxel's answer moved one class along, on a voxel whose logits
    lie a thousandth of the scan's largest: the scan-wide gap barely moves,
    the voxel's own gap reads 2/C of its range or more."""
    scan = traffic.scan_batch(5, 1, "indoor", (32, 28, 16), 0.3)[0]
    net = reference.Net((reference.Layer("a", 1, 1, 3, 0, 0),), 1, 19)
    hs = reference.build_scan(scan.coords, net)
    ref = np.random.default_rng(3).normal(size=(hs.count(0), 19))
    ref[7] *= 1e-3
    vox = hs.coords(0)
    sound = reference.logit_gaps(vox, ref, hs, ref, 0)
    assert sound["logit_gap"] == sound["logit_voxel_gap"] == 0.0
    bad = ref.copy()
    bad[7] = np.roll(bad[7], 1)
    gaps = reference.logit_gaps(vox, bad, hs, ref, 0)
    assert gaps["logit_gap"] < 2e-3
    assert gaps["logit_voxel_gap"] >= 2 / 19
    shuffled = np.random.default_rng(4).permutation(len(vox))
    assert reference.logit_gaps(vox[shuffled], bad[shuffled], hs, ref,
                                0) == gaps


def test_reference_gradient_matches_the_program():
    """Loss and gradients of a batch of two labelled scans: the program's
    fused plan-forward-loss graph differentiated by JAX, against the
    reference's."""
    from repro.core.packing import BitLayout
    from repro.models.pointcloud import NETWORKS
    from repro.train.pointcloud import (labeled_tensor,
                                        make_segmentation_loss_fn)
    cfg = harness.load_json(tiny.TESTS / "tiny_segnet.json")
    net = harness.load_module(tiny.TESTS / "tiny_segnet.py", "t_seg").net(cfg)
    scans = traffic.scan_batch(3, 2, "indoor", (32, 28, 16), 0.3,
                               labels=True, n_classes=net.n_classes)
    feats = [traffic.scan_features(s, 4) for s in scans]
    layout = BitLayout.for_extent(32, 28, 16).with_batch(2)
    st, lab = labeled_tensor([(s.coords, f, s.labels)
                              for s, f in zip(scans, feats)], layout)
    params = reference.init_params(reference.seed_key(3), net)
    loss_fn = make_segmentation_loss_fn(
        NETWORKS["tiny_segnet"](**cfg["program_args"]), layout)
    (loss, _), grad = jax.value_and_grad(loss_fn, has_aux=True)(
        params, st.packed, st.features, lab)
    hs = [reference.build_scan(s.coords, net) for s in scans]
    inp = reference.device_inputs(hs, feats, net, [s.labels for s in scans])
    rloss, rgrad = reference.loss_and_grad(params, inp, net=net)
    assert float(loss) == pytest.approx(float(rloss), rel=1e-5)
    gap, leaf = reference.leaf_norm_gap(jax.device_get(grad),
                                        jax.device_get(rgrad))
    assert gap < 1e-4, leaf


def test_reference_adamw_follows_its_equations():
    opt = reference.AdamW(lr=0.1, b1=0.9, b2=0.95, eps=1e-8,
                          weight_decay=0.0, grad_clip=1.0, warmup_steps=2,
                          total_steps=10, min_lr_ratio=0.1)
    assert opt.lr_at(0) == pytest.approx(0.05)
    assert opt.lr_at(1) == pytest.approx(0.1)
    assert opt.lr_at(10) == pytest.approx(0.01)
