"""MinkUNet (SPVNAS, ``cs`` widths) as the benchmark runs it, layer by layer.

A stem of two convs at level 0; four encoder stages, each a strided conv
one level down at the incoming width, then ``blocks_per_stage`` blocks of
two convs to the stage's width ``cs[1..4]``; four decoder stages, each a
transposed conv one level up to ``cs[5..8]``, whose first block conv reads
that output concatenated with the output saved at its level (the stem's at
level 0, the last encoder conv's above), then the rest of the blocks.
Every conv is followed by ReLU and per-scan standardisation; the logits
are read at level 0. The blocks' convs run in sequence (``residual`` is
false: the program adds no identity).

Layer names follow the program's skip rule: convs named ``stem*`` and
``enc*_b`` save their output at their level, and ``dec*_a`` concatenates.
"""
from bench.reference import Layer, Net


def net(cfg: dict) -> Net:
    if cfg["residual"]:
        raise ValueError("the program's network adds no identity")
    cs = cfg["cs"]
    K, Kd, Ku = cfg["block_kernel"], cfg["down_kernel"], cfg["up_kernel"]
    n = 2 * cfg["blocks_per_stage"]
    layers = [Layer("stem0", cfg["in_channels"], cs[0], cfg["stem_kernel"],
                    0, 0),
              Layer("stem1", cs[0], cs[0], cfg["stem_kernel"], 0, 0,
                    skip_out=0)]
    c = cs[0]
    for s in range(4):
        w, lvl = cs[s + 1], s + 1
        layers.append(Layer(f"enc{s}_down", c, c, Kd, s, lvl))
        names = [f"enc{s}_c{i}" for i in range(n - 1)] + [f"enc{s}_b"]
        for i, name in enumerate(names):
            layers.append(Layer(name, c if i == 0 else w, w, K, lvl, lvl,
                                skip_out=lvl if i == n - 1 else None))
        c = w
    for s in range(4):
        w, lvl = cs[s + 5], 3 - s
        layers.append(Layer(f"dec{s}_up", c, w, Ku, lvl + 1, lvl))
        layers.append(Layer(f"dec{s}_a", w + cs[lvl], w, K, lvl, lvl,
                            skip_in=lvl))
        layers += [Layer(f"dec{s}_c{i}", w, w, K, lvl, lvl)
                   for i in range(1, n)]
        c = w
    return Net(tuple(layers), cfg["in_channels"], cfg["num_classes"])
