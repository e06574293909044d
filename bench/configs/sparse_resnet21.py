"""SparseResNet21D (torchsparse) as the benchmark runs it, layer by layer.

Each stage ``(blocks, width, kernel, stride)`` opens with a conv of that
kernel, one level down when the stride is 2, then runs ``blocks - 1``
blocks of two 3^3 convs at its width. Every conv is followed by ReLU and
per-scan standardisation; the blocks' convs run in sequence (``residual``
is false: the program adds no identity). The logits are read at the last
level.
"""
from bench.reference import Layer, Net


def net(cfg: dict) -> Net:
    if cfg["residual"]:
        raise ValueError("the program's network adds no identity")
    layers, c, lvl = [], cfg["in_channels"], 0
    for s, (n, w, k, stride) in enumerate(cfg["blocks"]):
        if stride not in (1, 2):
            raise ValueError(f"stride {stride}: the program's convs halve "
                             "every axis or none")
        up = lvl + (stride == 2)
        layers.append(Layer(f"s{s}_conv", c, w, k, lvl, up))
        lvl, c = up, w
        for b in range(n - 1):
            layers += [Layer(f"s{s}_r{b}a", w, w, 3, lvl, lvl),
                       Layer(f"s{s}_r{b}b", w, w, 3, lvl, lvl)]
    return Net(tuple(layers), cfg["in_channels"], cfg["num_classes"])
