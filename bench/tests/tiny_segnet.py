"""A small all-submanifold network for training the harness on the CPU."""
from bench.reference import Layer, Net


def net(cfg: dict) -> Net:
    w, K = cfg["width"], cfg["K"]
    layers = [Layer("stem", cfg["in_channels"], w, K, 0, 0)]
    layers += [Layer(f"sub{i}", w, w, K, 0, 0) for i in range(cfg["depth"] - 1)]
    return Net(tuple(layers), cfg["in_channels"], cfg["n_classes"])
