"""The benchmark's files: the contract of BENCHMARK.json, discovery by
name, and a cell added as files alone."""
import importlib
import json
import re

import numpy as np
import pytest

from bench import harness, reference, traffic
from bench.tests import tiny

ROOT = tiny.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return tiny.benchmark()


def test_contract_keys_and_names(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    assert bench["command"][1] == "bench/run.py"
    assert 1 <= bench["run_seconds"] <= 51
    names = [c["name"] for c in bench["configs"]]
    names += [w["name"] for w in bench["workloads"]]
    names += [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")


def test_every_cell_finds_its_files(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    layers = (ROOT / "PERF.md").read_text()
    for w in bench["workloads"]:
        _, entry, wl, centry, cfg = harness.find_cell(ROOT, w["name"])
        net = harness.reference_file(ROOT, centry, cfg).net(cfg)
        assert net.in_channels == cfg["in_channels"]
        assert (ROOT / "bench" / "modes" / f"{wl['mode']}.py").exists()
        assert callable(importlib.import_module(
            f"bench.modes.{wl['mode']}").run)
        got = [m["name"] for m in harness.reported(bench, w["name"], False)]
        assert "setup_s" in got and len(got) >= 2, got
        per = harness.reported(bench, w["name"], True)
        assert per, w["name"]
        for m in per:
            assert m["moves"] in got, (m["name"], m["moves"])
    for m in bench["per_layer"]:
        mod = harness.load_module(
            ROOT / "bench" / "metrics" / f"{m['name']}.py", "t_" + m["name"])
        assert callable(mod.read)
        assert m["moves"] in e2e
        assert f"| {m['layer']} |" in layers, m["layer"]
        for w in m.get("workloads", []):
            assert w in {x["name"] for x in bench["workloads"]}


def test_program_networks_are_the_configured_ones(bench):
    """Every configuration file, also one that no cell runs: the program's
    convs are the reference's layers, as many as the file states."""
    from bench.modes import common
    files = sorted((ROOT / "bench" / "configs").glob("*.json"))
    assert {c["file"] for c in bench["configs"]} <= {
        str(f.relative_to(ROOT)) for f in files}
    for f in files:
        cell = tiny.config_cell(str(f.relative_to(ROOT)))
        cfg = cell.config
        net = common.checked_program_net(cell)
        have = [(s.name, s.cin, s.cout, s.K, s.m_in, s.m_out)
                for s in net.conv_specs()]
        want = [(L.name, L.cin, L.cout, L.K, L.m_in, L.m_out)
                for L in cell.net.layers]
        assert have == want
        assert len(want) == cfg["convs"]
        widths = cfg["cs"] if "cs" in cfg else [b[1] for b in cfg["blocks"]]
        assert {L.cout for L in cell.net.layers} == set(widths)
        assert set(cfg["reduced"]) == set(cfg["source_values"])
        assert all(cfg[k] != v for k, v in cfg["source_values"].items())


def test_no_chip_no_result(bench, capsys):
    from bench import run
    cell = bench["workloads"][0]["name"]
    rc = run.main(["--workload", cell, "--seed", "1", "--seconds", "1"])
    out, err = capsys.readouterr()
    assert rc != 0 and out.strip() == ""
    assert "no TPU" in err


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_added_as_files_alone(tmp_path, capsys, monkeypatch, trace):
    from bench import peaks
    monkeypatch.setitem(peaks.PEAKS, "cpu", tiny.CPU_PEAKS)
    root = tiny.make_root(tmp_path, {"added.rooms": tiny.SERVE})
    rc, line, err = tiny.run_cell(root, "added.rooms", capsys, trace=trace)
    assert rc == 0, err
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert list(line)[-1] == "checks"
    bench = json.loads((root / "BENCHMARK.json").read_text())
    want = {m["name"] for m in harness.reported(bench, "added.rooms", trace)}
    assert set(line["metrics"]) <= want
    if trace:
        # the Pallas kernels do not run on the CPU: their readers find
        # nothing and their metrics are left out
        assert {"mfu.serve", "device_idle_share.serve",
                "host_pack_ms.serve"} <= set(line["metrics"])
        assert line["device"]["busy_s"] > 0
        assert line["breakdown"]["device_ops"]
        assert "metric os_gemm_roofline.serve found nothing to read" in err
    else:
        assert set(line["metrics"]) == want
    tail = err.strip().splitlines()[-2:]
    assert [l.split(":")[0] for l in tail] == ["check logit_gap",
                                               "check logit_median_gap"]


@pytest.mark.parametrize("kind,extent", [("indoor", (48, 40, 20)),
                                         ("outdoor", (96, 96, 24))])
def test_traffic_is_the_program_generator(kind, extent):
    from repro.data.scenes import scene_batch
    from repro.train.pointcloud import scene_features
    want = scene_batch(seed=2 ** 31 + 9, batch=3, kind=kind, extent=extent,
                       overlap=0.3, labels=True, n_classes=20)
    have = traffic.scan_batch(2 ** 31 + 9, 3, kind, extent, 0.3,
                              labels=True, n_classes=20)
    for w, h in zip(want, have):
        np.testing.assert_array_equal(w.coords, h.coords)
        np.testing.assert_array_equal(w.labels, h.labels)
        np.testing.assert_array_equal(scene_features(w, 4),
                                      traffic.scan_features(h, 4))


def test_seed_decides_inputs_and_weights():
    tr = dict(kind="indoor", extent=[32, 28, 16], overlap=0.3, pool=2)
    a = traffic.scan_pool(2 ** 33 + 1, tr)
    b = traffic.scan_pool(2 ** 33 + 1, tr)
    c = traffic.scan_pool(2 ** 33 + 2, tr)
    assert all(np.array_equal(x[0].coords, y[0].coords) for x, y in zip(a, b))
    assert not np.array_equal(a[0][0].coords, c[0][0].coords)
    net = reference.Net((reference.Layer("a", 4, 8, 3, 0, 0),), 4, 3)
    k1, k2 = reference.seed_key(2 ** 33 + 1), reference.seed_key(1)
    p1 = reference.init_params(k1, net)
    p2 = reference.init_params(k2, net)
    assert not np.allclose(p1["a"]["w"], p2["a"]["w"])
    np.testing.assert_array_equal(
        p1["a"]["w"], reference.init_params(reference.seed_key(2 ** 33 + 1),
                                            net)["a"]["w"])


def test_max_voxels_gives_every_seed_the_same_work():
    tr = dict(kind="outdoor", extent=[96, 96, 24], overlap=0.3, pool=3,
              scans_per_item=2, labels=True, n_classes=5)
    whole = traffic.scan_pool(2 ** 32 + 3, tr)
    cap = min(len(s.coords) for seed in (2 ** 32 + 3, 7)
              for item in traffic.scan_pool(seed, tr) for s in item) - 10
    for seed in (2 ** 32 + 3, 7):
        pool = traffic.scan_pool(seed, dict(tr, max_voxels=cap))
        assert all(len(s.coords) == len(s.labels) == cap
                   for item in pool for s in item)
    kept = traffic.scan_pool(2 ** 32 + 3, dict(tr, max_voxels=cap))
    for a, b in zip(whole[0], kept[0]):
        ka = {tuple(c) for c in a.coords}
        assert all(tuple(c) in ka for c in b.coords)
        assert (np.diff(b.coords[:, 0]) >= 0).all()


def test_cells_give_every_seed_the_same_levels():
    """``cells`` keeps the same number of voxels at its level, and with
    ``max_voxels`` the same number at level 0, on every seed: the serve
    engine's host work then meets no new shape."""
    tr = dict(kind="indoor", extent=[96, 80, 48], overlap=0.3, pool=3,
              scans_per_item=1, cells={"level": 3, "count": 500},
              max_voxels=25000)
    for seed in (2 ** 32 + 3, 7):
        for (scan,) in traffic.scan_pool(seed, tr):
            c = scan.coords.astype(np.int64)
            assert len(c) == 25000
            assert len(np.unique(c >> 3, axis=0)) == 500
            assert len(np.unique(c, axis=0)) == len(c)


def test_compile_inside_the_window_is_refused(tmp_path, capsys,
                                              monkeypatch):
    """Rooms of different sizes, with no ``max_voxels``: the program
    compiles its operations for each new size inside the window, and the
    run raises."""
    from bench import peaks
    monkeypatch.setitem(peaks.PEAKS, "cpu", tiny.CPU_PEAKS)
    tr = {k: v for k, v in tiny.SERVE["traffic"].items()
          if k != "max_voxels"}
    root = tiny.make_root(tmp_path, {"sizes.rooms": dict(tiny.SERVE,
                                                         traffic=tr)})
    with pytest.raises(RuntimeError, match="compiled inside the window"):
        tiny.run_cell(root, "sizes.rooms", capsys, seconds=2.5)
