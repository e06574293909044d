"""A configuration that brings its own network: the functions its
reference file defines in place of the shared ones (``harness.HOOKS``)
are the ones every part of a run takes. On the CPU, at tiny cells."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, opcount, reference, traffic
from bench.modes import common, serve
from bench.tests import tiny

# The cells of tiny_hooked. On the CPU, where both sides compute in full
# float32, sound runs read logit_voxel_gap 3.0e-6-1.2e-5 and
# logit_median_gap 2.2e-8-3.3e-8 (4 seeds); the bfloat16 control
# 0.094-0.41 and 8.0e-4-8.3e-4, an answer one class along 0.96-1.
SERVE = dict(tiny.SERVE, config="tiny_hooked",
             limits={"logit_voxel_gap": 1e-3, "logit_median_gap": 1e-5})
TRAIN = dict(tiny.TRAIN, config="tiny_hooked")


@pytest.fixture
def hooked(tmp_path, monkeypatch):
    """A checkout with the cells of tiny_hooked, and the list of the
    reference files the harness loads there, as modules."""
    from bench import peaks
    monkeypatch.setitem(peaks.PEAKS, "cpu", tiny.CPU_PEAKS)
    loaded = []
    real = harness.reference_file

    def keep(*args):
        loaded.append(real(*args))
        return loaded[-1]

    monkeypatch.setattr(harness, "reference_file", keep)
    root = tiny.make_root(tmp_path, {"hooked.serve": SERVE,
                                     "hooked.train": TRAIN})
    return root, loaded


def _append(root, source: str) -> None:
    """Redefine functions of tiny_hooked's reference file in ``root``."""
    path = root / "bench" / "configs" / "tiny_hooked.py"
    path.write_text(path.read_text() + source)


@pytest.mark.parametrize("cell,trace", [("hooked.serve", 0),
                                        ("hooked.serve", 1),
                                        ("hooked.train", 1)])
def test_configuration_added_as_files_alone(hooked, capsys, cell, trace):
    """Every function the file defines is called, and the run is correct;
    a traced serving run's ``mfu.serve`` reads the operations that the
    file's ``forward_flops`` counted."""
    root, loaded = hooked
    rc, line, err = tiny.run_cell(root, cell, capsys, trace=trace)
    assert rc == 0, err
    assert line["correct"] is True, line["checks"]
    (ref,) = loaded
    want = set(harness.HOOKS) - (set() if trace else {"forward_flops"})
    assert {name for name, _ in ref.CALLS} == want
    if cell == "hooked.serve" and trace:
        flops = sum(v for name, v in ref.CALLS if name == "forward_flops")
        rate = flops / line["device"]["window_s"]
        assert line["metrics"]["mfu.serve"]["value"] == pytest.approx(
            100.0 * rate / tiny.CPU_PEAKS["bf16_flops_per_s"], rel=1e-12)


WRONG_CLASS = '''

def forward_one(params, net, inp, precision):
    """The first voxel's answer moved one class along."""
    y = reference.forward_one(params, net, inp, precision)
    return y.at[0].set(jnp.roll(y[0], 1))
'''


def test_reference_is_the_configurations_forward_one(hooked, capsys):
    root, _ = hooked
    _append(root, WRONG_CLASS)
    rc, line, err = tiny.run_cell(root, "hooked.serve", capsys)
    assert rc == 0, err
    assert line["correct"] is False
    assert (line["checks"]["logit_voxel_gap"]["value"]
            > SERVE["limits"]["logit_voxel_gap"])


WIDER = '''

def program_net(cfg, net):
    """One conv of the program a channel wider than the reference's."""
    import dataclasses
    prog = common.program_net(cfg, net)
    return dataclasses.replace(prog, specs=tuple(
        dataclasses.replace(s, cout=s.cout + 1) if s.name == "enc0_c0"
        else s for s in prog.specs))
'''


def test_program_is_the_configurations_program_net(hooked, capsys,
                                                   monkeypatch):
    """The program runs whatever weights it is given, so the harness holds
    its convs to the reference's layers, before the window."""
    root, _ = hooked
    _append(root, WIDER)
    opened = []
    monkeypatch.setattr(common.Window, "__enter__",
                        lambda self: opened.append(self))
    with pytest.raises(ValueError, match="'enc0_c0'"):
        tiny.run_cell(root, "hooked.serve", capsys)
    assert opened == []


@pytest.mark.parametrize("config", ["sparse_resnet21", "minkunet42"])
def test_plain_configurations_take_the_shared_functions(config):
    """A reference file that defines only ``net``: every lookup gives the
    shared function itself, and the weights and the reference's logits
    through the modes are the shared functions' to the bit."""
    cell = tiny.config_cell(f"bench/configs/{config}.json", seed=2 ** 31 + 7)
    shared = {"init_params": reference.init_params,
              "device_inputs": reference.device_inputs,
              "forward_one": reference.forward_one,
              "program_net": common.program_net,
              "forward_flops": opcount.forward_flops}
    assert set(shared) == set(harness.HOOKS)
    for name, fn in shared.items():
        assert cell.hook(name) is fn, name
    params = jax.device_get(common.make_params(cell))
    want = jax.device_get(jax.jit(partial(
        reference.init_params, net=cell.net, dtype=jnp.float32))(
            reference.seed_key(cell.seed)))
    jax.tree.map(np.testing.assert_array_equal, params, want)
    scan = traffic.scan_batch(cell.seed, 1, "indoor", (32, 28, 16), 0.3)[0]
    hs = reference.build_scan(scan.coords, cell.net)
    feats = traffic.scan_features(scan, cell.net.in_channels)
    logits = reference.forward(
        want, reference.device_inputs([hs], [feats], cell.net),
        net=cell.net, precision=common.precision(cell))
    np.testing.assert_array_equal(
        serve.reference_logits(cell, hs, scan, params),
        np.asarray(logits)[0])
