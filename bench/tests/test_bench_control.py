"""What ``correct`` must catch, at tiny cells on the CPU: the control (the
reference in bfloat16 in the program's place), and a run of the harness
with the timed path broken underneath."""
import numpy as np
import pytest

import jax.numpy as jnp

from bench import harness
from bench.tests import tiny


@pytest.fixture
def root(tmp_path, monkeypatch):
    from bench import peaks
    monkeypatch.setitem(peaks.PEAKS, "cpu", tiny.CPU_PEAKS)
    return tiny.make_root(tmp_path)


def _calibrate(root, cell, capsys):
    import json
    from bench import calibrate
    calibrate.main(["--workload", cell, "--seeds", "7", "--control", "1"],
                   root=root, need_chip=False)
    out, _ = capsys.readouterr()
    return json.loads(out.splitlines()[0]), harness.load_json(
        root / "bench" / "workloads" / f"{cell}.json")["limits"]


def test_bf16_control_fails_serve(root, capsys):
    row, limits = _calibrate(root, "tiny.serve", capsys)
    assert set(limits) <= set(row["program"])
    for k, limit in limits.items():
        assert row["program"][k]["value"] <= limit, k
    for fault in ("control_bf16", "fault_altered_answer"):
        assert any(row[fault][k]["value"] > limit
                   for k, limit in limits.items()), (fault, row[fault])


def test_sound_train_run_is_correct(root, capsys):
    rc, line, err = tiny.run_cell(root, "tiny.train", capsys)
    assert rc == 0, err
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {"train_voxels_per_s", "setup_s"}


def test_altered_answer_is_not_correct(root, capsys, monkeypatch):
    from repro.serve.session import SpiraSession
    real = SpiraSession.run_with_health

    def altered(self, st, **kw):
        out, health = real(self, st, **kw)
        # the first voxel's answer replaced by the second's
        feats = out.features.at[0].set(out.features[1])
        return type(out)(features=feats, packed=out.packed,
                         count=out.count, layout=out.layout), health

    monkeypatch.setattr(SpiraSession, "run_with_health", altered)
    rc, line, err = tiny.run_cell(root, "tiny.serve", capsys)
    assert rc == 0, err
    assert line["correct"] is False


def test_unchanged_state_is_not_correct(root, capsys, monkeypatch):
    from repro.train.pointcloud import PointCloudTrainer
    real = PointCloudTrainer.step

    def frozen(self, st, labels):
        params, opt = self.session.params, self.opt_state
        out = real(self, st, labels)
        self.session.params, self.opt_state = params, opt
        return out

    monkeypatch.setattr(PointCloudTrainer, "step", frozen)
    rc, line, err = tiny.run_cell(root, "tiny.train", capsys)
    assert rc == 0, err
    assert line["correct"] is False
    assert line["checks"]["update_gap"]["value"] == pytest.approx(1.0)


def test_half_batch_left_out_is_not_correct(root, capsys, monkeypatch):
    from repro.train.pointcloud import PointCloudTrainer
    real = PointCloudTrainer.step

    def half(self, st, labels):
        # the second scan's rows carry the ignore label: the loss is the
        # mean over the first scan alone
        sid = (np.asarray(st.packed) >> st.layout.shift_b) & 1
        n = int(st.count)
        lab = np.asarray(labels).copy()
        lab[:n][sid[:n] == 1] = -1
        return real(self, st, jnp.asarray(lab))

    monkeypatch.setattr(PointCloudTrainer, "step", half)
    rc, line, err = tiny.run_cell(root, "tiny.train", capsys)
    assert rc == 0, err
    assert line["correct"] is False
