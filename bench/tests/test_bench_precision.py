"""The matmul precision a configuration states reaches the program, and
what ``correct`` must catch in a cell stated at ``highest``: its control
(the reference in three bfloat16 passes), the reference in bfloat16, and
an answer moved to another class. On the CPU, at tiny cells."""
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference
from bench.modes import common
from bench.tests import tiny

ROOT = tiny.ROOT


def _cell(config: dict, name: str = "sparse_resnet21") -> harness.Cell:
    return tiny.config_cell(f"bench/configs/{name}.json", config, seed=3)


def _config(precision: str, name: str = "sparse_resnet21") -> dict:
    cfg = harness.load_json(ROOT / "bench" / "configs" / f"{name}.json")
    return dict(cfg, matmul_precision=precision)


def _serving_step_text(cell: harness.Cell) -> str:
    """The session's plan-and-forward program for a tiny bucket, as
    lowered (not compiled) under the process's current settings."""
    from repro.core.packing import BitLayout
    from repro.serve import compile_network
    session = compile_network(common.checked_program_net(cell),
                              BitLayout.for_extent(32, 28, 16))
    step = session._make_fn(0)
    return step.lower(session.params,
                      jax.ShapeDtypeStruct((1024,), jnp.int32),
                      jax.ShapeDtypeStruct((1024, 4), jnp.float32)).as_text()


def test_default_leaves_the_serving_step_as_it_is():
    before = jax.config.jax_default_matmul_precision
    plain = _serving_step_text(_cell(_config("default")))
    with harness.matmul_precision("default"):
        assert jax.config.jax_default_matmul_precision == before
        held = _serving_step_text(_cell(_config("default")))
    assert held == plain
    with harness.matmul_precision("highest"):
        highest = _serving_step_text(_cell(_config("highest")))
    assert highest != plain and "HIGHEST" in highest
    assert jax.config.jax_default_matmul_precision == before


def _root_at(tmp_path, monkeypatch, precision: str):
    from bench import peaks
    monkeypatch.setitem(peaks.PEAKS, "cpu", tiny.CPU_PEAKS)
    root = tiny.make_root(tmp_path, {"tiny.serve": tiny.SERVE})
    path = root / "bench" / "configs" / "sparse_resnet21.json"
    path.write_text(json.dumps(_config(precision)))
    return root


def test_highest_holds_through_the_run_and_its_worker_threads(
        tmp_path, capsys, monkeypatch):
    """The session is called from the engine's watchdog thread here, as
    the engine does with a dispatch timeout: the setting is the
    process's, not the calling thread's."""
    import functools
    import repro.serve
    from repro.serve.session import SpiraSession
    root = _root_at(tmp_path, monkeypatch, "highest")
    engine = repro.serve.PointCloudServeEngine
    monkeypatch.setattr(repro.serve, "PointCloudServeEngine",
                        functools.partial(engine, dispatch_timeout=600.0))
    seen = []
    real = SpiraSession.run_with_health

    def watched(self, st, **kw):
        seen.append((threading.current_thread() is threading.main_thread(),
                     jax.config.jax_default_matmul_precision))
        return real(self, st, **kw)

    monkeypatch.setattr(SpiraSession, "run_with_health", watched)
    before = jax.config.jax_default_matmul_precision
    rc, line, err = tiny.run_cell(root, "tiny.serve", capsys)
    assert rc == 0, err
    assert line["correct"] is True, line["checks"]
    assert seen and {p for _, p in seen} == {"highest"}
    assert not any(main for main, _ in seen)
    assert jax.config.jax_default_matmul_precision == before


@pytest.mark.parametrize("entry", ["run", "calibrate", "scopes"])
def test_high_is_refused_before_anything_is_built(tmp_path, capsys,
                                                  monkeypatch, entry):
    """Every way of driving a cell opens it through the harness, which
    applies the configuration's precision."""
    from bench import calibrate, scopes
    from bench.modes import serve
    root = _root_at(tmp_path, monkeypatch, "high")
    built = []
    monkeypatch.setattr(serve, "build", lambda *a, **k: built.append(a))
    drive = {
        "run": lambda: tiny.run_cell(root, "tiny.serve", capsys),
        "calibrate": lambda: calibrate.main(
            ["--workload", "tiny.serve", "--seeds", "3"], root=root,
            need_chip=False),
        "scopes": lambda: scopes.main(
            ["--workload", "tiny.serve", "--seed", "3", "--seconds", "0.5"],
            root=root, need_chip=False)}[entry]
    with pytest.raises(ValueError, match="high"):
        drive()
    assert built == []


def test_high_is_three_bfloat16_products():
    """The reference's ``high``: the error of the three-pass product lies
    far below one bfloat16 pass's and above none."""
    ka, kb = jax.random.split(jax.random.key(5))
    a = jax.random.normal(ka, (256, 96), jnp.float32)
    b = jax.random.normal(kb, (96, 64), jnp.float32)
    top = np.asarray(reference._dot(a, b, jax.lax.Precision.HIGHEST))
    high = np.asarray(reference._dot(a, b, jax.lax.Precision.HIGH))
    one = np.asarray(jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                             preferred_element_type=jnp.float32))
    e_high = np.abs(high - top).max() / np.abs(top).max()
    e_one = np.abs(one - top).max() / np.abs(top).max()
    assert 0.0 < e_high < e_one / 100


# A tiny outdoor MinkUNet-42 cell; at this size its deepest levels hold a
# few voxels each, whose standardisation amplifies rounding, so it takes
# limits of its own that hold the mechanism on the CPU (sound runs read
# 5.3e-4-6.0e-4 and 1.2e-6-2.0e-6, the three-pass control 0.014-0.020 and
# 4.0e-5-5.5e-5, the answer moved one class along 1.0).
KITTI = {"config": "minkunet42", "mode": "serve", "chips": 1,
         "traffic": {"kind": "outdoor", "extent": [96, 96, 24],
                     "overlap": 0.3, "pool": 3, "scans_per_item": 1,
                     "max_voxels": 3000, "in_flight": 1, "check_sample": 2},
         "limits": {"logit_voxel_gap": 4e-3, "logit_median_gap": 1e-5}}


@pytest.fixture(scope="module")
def kitti_root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("kitti"),
                          {"tiny.kitti": KITTI})


def test_high_control_and_wrong_class_fail_kitti(kitti_root, capsys):
    from bench import calibrate
    calibrate.main(["--workload", "tiny.kitti", "--seeds", "7",
                    "--control", "1"], root=kitti_root, need_chip=False)
    row = json.loads(capsys.readouterr().out.splitlines()[0])
    limits = KITTI["limits"]
    for k, limit in limits.items():
        assert row["program"][k]["value"] <= limit, k
    for name in ("control_high", "control_bf16", "fault_wrong_class"):
        assert any(row[name][k]["value"] > limit
                   for k, limit in limits.items()), (name, row[name])


def test_wrong_class_is_not_correct(kitti_root, capsys, monkeypatch):
    from bench import calibrate, peaks
    from repro.serve.session import SpiraSession
    monkeypatch.setitem(peaks.PEAKS, "cpu", tiny.CPU_PEAKS)
    real = SpiraSession.run_with_health

    def wrong(self, st, **kw):
        out, health = real(self, st, **kw)
        feats = calibrate.wrong_class(out.features)
        return type(out)(features=jnp.asarray(feats), packed=out.packed,
                         count=out.count, layout=out.layout), health

    monkeypatch.setattr(SpiraSession, "run_with_health", wrong)
    rc, line, err = tiny.run_cell(kitti_root, "tiny.kitti", capsys)
    assert rc == 0, err
    assert line["correct"] is False
    assert (line["checks"]["logit_voxel_gap"]["value"]
            > KITTI["limits"]["logit_voxel_gap"])


@pytest.mark.parametrize("control", ["high", "bf16"])
def test_control_in_the_programs_place_is_not_correct(kitti_root, capsys,
                                                      monkeypatch, control):
    """The reference in three bfloat16 passes (the control of a cell at
    ``highest``) and in bfloat16, put in the program's place for the
    scans a run checks, read ``correct`` false through the harness."""
    from bench import calibrate, peaks
    from bench.modes import serve
    monkeypatch.setitem(peaks.PEAKS, "cpu", tiny.CPU_PEAKS)
    real = serve.check_sample

    def as_control(cell, served, params, hosts, *a, **kw):
        if control == "high":
            _, dtype, prec = calibrate.control(cell)
        else:
            dtype, prec = jnp.bfloat16, common.precision(cell)
        assert cell.config["matmul_precision"] == "highest"
        low = calibrate.in_place(cell, served, params, hosts, dtype, prec)
        return real(cell, low, params, hosts, *a, **kw)

    monkeypatch.setattr(serve, "check_sample", as_control)
    rc, line, err = tiny.run_cell(kitti_root, "tiny.kitti", capsys)
    assert rc == 0, err
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())
