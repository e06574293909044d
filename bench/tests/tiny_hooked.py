"""A small U-shaped network whose reference file defines every function
the harness looks up in a configuration's file (``bench.harness.HOOKS``).
Each records its call in :data:`CALLS` and gives the shared function's
result; ``device_inputs`` adds an array of its own, ``scale`` (ones),
which ``forward_one`` reads, as a point branch would read its corner rows
and weights."""
import jax.numpy as jnp

from bench import opcount, reference
from bench.modes import common
from bench.reference import Layer, Net

CALLS = []      # (hook, what it returned where that is a number)


def net(cfg: dict) -> Net:
    w, c = cfg["width"], cfg["in_channels"]
    return Net((Layer("stem", c, w, 3, 0, 0, skip_out=0),
                Layer("enc0_down", w, w, 2, 0, 1),
                Layer("enc0_c0", w, 2 * w, 3, 1, 1),
                Layer("dec0_up", 2 * w, w, 3, 1, 0),
                Layer("dec0_a", 2 * w, w, 3, 0, 0, skip_in=0)),
               c, cfg["num_classes"])


def init_params(key, net, dtype):
    CALLS.append(("init_params", None))
    return reference.init_params(key, net, dtype)


def device_inputs(scans, feats, net, labels=None):
    CALLS.append(("device_inputs", None))
    inp = reference.device_inputs(scans, feats, net, labels)
    return dict(inp, scale=jnp.ones((len(scans), 1), jnp.float32))


def forward_one(params, net, inp, precision):
    CALLS.append(("forward_one", None))
    return reference.forward_one(params, net, inp, precision) \
        * inp["scale"][0]


def program_net(cfg, net):
    CALLS.append(("program_net", None))
    return common.program_net(cfg, net)


def forward_flops(scan, net):
    flops = opcount.forward_flops(scan, net)
    CALLS.append(("forward_flops", flops))
    return flops
