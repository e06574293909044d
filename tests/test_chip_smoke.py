"""chip_smoke.py's phases at a tiny size on the CPU, and its refusal to run
anywhere but on a TPU."""
import importlib.util
import pathlib

import numpy as np
import pytest

from repro.data import scenes as scenes_mod
from repro.models.pointcloud import tiny_segnet
from repro.serve import PointCloudRequest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _scenes(**kw):
    return scenes_mod.scene_batch(seed=3, kind="indoor", extent=(48, 40, 24),
                                  overlap=0.3, **kw)


def test_serve_phase_tiny(smoke):
    scenes = _scenes(batch=4)
    out = smoke.serve_phase(tiny_segnet(), scenes, batch=2)
    reqs = out["requests"]
    assert [r.outcome for r in reqs] == ["ok"] * 4
    assert [len(r.logits) for r in reqs] == [len(s.coords) for s in scenes]
    assert out["session"].compile_count == 1


def test_served_check_rejects_quarantined_request(smoke):
    sc = _scenes(batch=1)
    req = PointCloudRequest(coords=sc[0].coords, features=None,
                            outcome="quarantined", error="boom")
    with pytest.raises(RuntimeError, match="quarantined"):
        smoke._check_served([req], sc, 8)
    req = PointCloudRequest(coords=sc[0].coords, features=None, outcome="ok",
                            logits=np.full((len(sc[0].coords), 8), np.nan))
    with pytest.raises(RuntimeError, match="non-finite"):
        smoke._check_served([req], sc, 8)


def test_correctness_phase_tiny(smoke, monkeypatch):
    """Pallas (interpreted here) against XLA: bit-identical on the CPU; a
    tolerance below the measured difference fails the phase."""
    scenes = _scenes(batch=2)
    net = tiny_segnet(backend="pallas")
    res = smoke.correctness_phase(net, scenes, batch=2)
    assert res["max_abs"] == 0.0
    monkeypatch.setattr(smoke, "LOGIT_TOL", -1.0)
    with pytest.raises(RuntimeError, match="max rel diff"):
        smoke.correctness_phase(net, scenes, batch=2)


def test_train_phase_tiny(smoke):
    net = tiny_segnet()
    scenes = _scenes(batch=2, labels=True, n_classes=net.n_classes)
    losses = smoke.train_phase(net, scenes, steps=2)
    assert len(losses) == 2 and np.isfinite(losses).all()


def test_main_refuses_to_run_off_tpu(smoke, capsys):
    assert smoke.main() != 0
    assert '"ok"' not in capsys.readouterr().out
