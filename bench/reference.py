"""Plain float32 reference of the benchmark networks, and the weights.

It computes in float32 at the matmul precision that the configuration
states (``matmul_precision``, :func:`precision`): ``default`` is what JAX
gives a float32 product when nothing is asked for, which on a TPU is one
bfloat16 pass with float32 sums, and on the CPU full float32; ``highest``
is full float32 everywhere; ``high`` is three bfloat16 passes, written out
(:func:`_dot`), which only a control asks for.

Everything here is written from the networks' mathematics, with none of
the engine's machinery: no packed words, no z-delta search, no capacity
buckets shared with the program, no custom gradients. It decides
``correct`` for every cell, so it imports nothing of the program.

* Levels: level ``m`` of a scan is the set ``floor(v / 2^m) * 2^m`` of its
  voxels ``v`` (:func:`build_scan`), found by sorting integer keys on the
  host.
* Kernel maps: output voxel ``q`` reads input voxel ``q + d`` through
  weight offset ``d``, for ``d`` on the ``K^3`` grid of spacing
  ``2^min(m_in, m_out)`` (:func:`offsets`) in row-major (x, y, z) order —
  the order of the weights' first axis. Found by binary search over the
  sorted keys.
* A layer: gather, one matmul per offset, bias, rows outside the level
  zeroed; then ReLU and per-scan standardisation over the scan's voxels
  (mean, and variance about the mean), as the networks' BN without an
  affine.
* Skips: a layer may concatenate, on the channel axis after its input,
  the output saved at its input level by an earlier layer.
* Training: masked mean cross-entropy over every labelled voxel of the
  batch, ``jax.grad``, and AdamW with global-norm clipping, written from
  the optimizer's equations.
* A network that is more than such a stack: its configuration's reference
  file defines its own ``init_params``, ``device_inputs`` or
  ``forward_one`` (``bench.harness.Cell.hook``), and :func:`forward`,
  :func:`loss_fn` and :func:`train_steps` run the ``forward_one`` they
  are given.

The device arrays are padded per level to a power of two (rows beyond the
scan's voxels have no neighbours and are masked), so that every seed of a
cell reuses the same compiled programs.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

EPS_BN = 1e-5
_SHIFT = 64          # keeps every queried coordinate non-negative
_BITS = 21           # bits per axis in a host key


@dataclasses.dataclass(frozen=True)
class Layer:
    name: str
    cin: int
    cout: int
    K: int
    m_in: int
    m_out: int
    skip_in: Optional[int] = None    # concatenate the output saved here
    skip_out: Optional[int] = None   # save this layer's output here


@dataclasses.dataclass(frozen=True)
class Net:
    layers: Tuple[Layer, ...]
    in_channels: int
    n_classes: int

    @property
    def levels(self) -> Tuple[int, ...]:
        return tuple(sorted({L.m_in for L in self.layers}
                            | {L.m_out for L in self.layers}))

    @property
    def out_level(self) -> int:
        return self.layers[-1].m_out

    def map_keys(self) -> Tuple[Tuple[int, int, int], ...]:
        """Distinct (m_in, m_out, K) kernel maps the layers read."""
        return tuple(sorted({(L.m_in, L.m_out, L.K) for L in self.layers}))


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def init_params(key: jax.Array, net: Net, dtype=jnp.float32) -> dict:
    """Random weights: ``w[k]`` of each layer N(0, 1/(cin K^3)), biases
    N(0, 0.1^2), the classifier N(0, 1/C). Jit it to make them on the
    device in one call."""
    params = {}
    for i, L in enumerate(net.layers):
        kw, kb = jax.random.split(jax.random.fold_in(key, i))
        fan_in = L.cin * L.K ** 3
        params[L.name] = {
            "w": jax.random.normal(kw, (L.K ** 3, L.cin, L.cout), dtype)
            / math.sqrt(fan_in),
            "b": 0.1 * jax.random.normal(kb, (L.cout,), dtype)}
    c = net.layers[-1].cout
    params["head"] = jax.random.normal(
        jax.random.fold_in(key, len(net.layers)), (c, net.n_classes),
        dtype) / math.sqrt(c)
    return params


PRECISIONS = {"default": jax.lax.Precision.DEFAULT,
              "high": jax.lax.Precision.HIGH,
              "highest": jax.lax.Precision.HIGHEST}


def precision(name: str) -> jax.lax.Precision:
    """The matmul precision a configuration names."""
    if name not in PRECISIONS:
        raise ValueError(f"matmul_precision {name!r}: one of "
                         f"{sorted(PRECISIONS)}")
    return PRECISIONS[name]


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative whole-number seed."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


# ---------------------------------------------------------------------------
# host: levels and kernel maps of one scan
# ---------------------------------------------------------------------------

def _encode(c: np.ndarray) -> np.ndarray:
    c = c.astype(np.int64) + _SHIFT
    return (c[:, 0] << (2 * _BITS)) | (c[:, 1] << _BITS) | c[:, 2]


def _decode(k: np.ndarray) -> np.ndarray:
    m = (1 << _BITS) - 1
    return np.stack([k >> (2 * _BITS), (k >> _BITS) & m, k & m],
                    axis=1) - _SHIFT


def offsets(K: int, stride: int) -> np.ndarray:
    """The ``K^3`` offsets, ``(i - (K - 1) // 2) * stride`` for ``i < K``
    on each axis: centred for odd ``K``, ``{0, stride}`` for ``K = 2``."""
    r = range(-((K - 1) // 2), K - (K - 1) // 2)
    return np.array(list(itertools.product(r, r, r)), np.int64) * stride


def neighbour_map(out_keys: np.ndarray, in_keys: np.ndarray, K: int,
                  stride: int) -> np.ndarray:
    """``nbr[i, k]`` = row of ``in_keys`` at ``out[i] + d_k``, else -1."""
    d = offsets(K, stride)
    dk = (d[:, 0] << (2 * _BITS)) + (d[:, 1] << _BITS) + d[:, 2]
    q = out_keys[:, None] + dk[None, :]
    idx = np.minimum(np.searchsorted(in_keys, q), max(len(in_keys) - 1, 0))
    hit = in_keys[idx] == q if len(in_keys) else np.zeros(q.shape, bool)
    return np.where(hit, idx, -1).astype(np.int32)


@dataclasses.dataclass
class HostScan:
    """One scan's levels (sorted keys), kernel maps, and the order that
    takes the scan's own voxel rows to key order."""
    keys: Dict[int, np.ndarray]
    maps: Dict[Tuple[int, int, int], np.ndarray]
    order: np.ndarray

    def coords(self, level: int) -> np.ndarray:
        return _decode(self.keys[level])

    def count(self, level: int) -> int:
        return len(self.keys[level])


def build_scan(coords: np.ndarray, net: Net) -> HostScan:
    """Levels and maps of one scan of unique voxels at level 0."""
    c = np.asarray(coords, np.int64)
    k0 = _encode(c)
    order = np.argsort(k0, kind="stable")
    if len(k0) > 1 and not (np.diff(k0[order]) > 0).all():
        raise ValueError("the reference takes a scan of unique voxels")
    keys = {m: np.unique(_encode((c >> m) << m)) for m in net.levels}
    maps = {(a, b, K): neighbour_map(keys[b], keys[a], K, 1 << min(a, b))
            for a, b, K in net.map_keys()}
    return HostScan(keys, maps, order)


def pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def device_inputs(scans: Sequence[HostScan], feats: Sequence[np.ndarray],
                  net: Net, labels: Optional[Sequence[np.ndarray]] = None
                  ) -> dict:
    """Stack scans into padded device arrays: one leading scan axis, each
    level padded to the next power of two of its largest scan."""
    caps = {m: pow2(max(s.count(m) for s in scans)) for m in net.levels}
    B = len(scans)
    maps = {}
    for (a, b, K) in net.map_keys():
        arr = np.full((B, caps[b], K ** 3), -1, np.int32)
        for i, s in enumerate(scans):
            arr[i, :s.count(b)] = s.maps[(a, b, K)]
        maps[f"{a}_{b}_{K}"] = arr
    if net.layers[0].m_in != 0:
        raise ValueError("the networks read their input at level 0")
    x = np.zeros((B, caps[0], net.in_channels), np.float32)
    for i, (s, f) in enumerate(zip(scans, feats)):
        x[i, :s.count(0)] = np.asarray(f)[s.order]
    counts = {str(m): np.array([s.count(m) for s in scans], np.int32)
              for m in net.levels}
    out = {"x": x, "maps": maps, "counts": counts}
    if labels is not None:
        if net.out_level != 0:
            raise ValueError("per-voxel labels need logits at level 0")
        lab = np.full((B, caps[0]), -1, np.int32)
        for i, (s, l) in enumerate(zip(scans, labels)):
            lab[i, :s.count(0)] = np.asarray(l)[s.order]
        out["labels"] = lab
    return jax.tree.map(jnp.asarray, out)


# ---------------------------------------------------------------------------
# device: the forward pass, the loss, the optimizer
# ---------------------------------------------------------------------------

def _dot(a, b, precision):
    """``a @ b`` with float32 sums at ``precision``. ``HIGH`` on float32
    is written out as the three bfloat16 products a TPU computes for it
    (each operand split into a bfloat16 high part and a bfloat16 rest; the
    two rests' product left out), so that it means the same on every
    platform."""
    if precision == jax.lax.Precision.HIGH and a.dtype == jnp.float32:
        def split(v):
            hi = v.astype(jnp.bfloat16)
            return hi, (v - hi.astype(v.dtype)).astype(jnp.bfloat16)

        (ah, al), (bh, bl) = split(a), split(b)
        d = partial(jnp.dot, precision=jax.lax.Precision.DEFAULT,
                    preferred_element_type=jnp.float32)
        return d(ah, bh) + (d(ah, bl) + d(al, bh))
    return jnp.dot(a, b, precision=precision,
                   preferred_element_type=jnp.float32)


def _conv(x, nbr, w, b, count, precision):
    def body(acc, col_w):
        col, wk = col_w
        g = jnp.where((col >= 0)[:, None], x[jnp.maximum(col, 0)], 0)
        return acc + _dot(g, wk, precision), None

    acc0 = jnp.zeros((nbr.shape[0], w.shape[-1]), jnp.float32)
    acc, _ = jax.lax.scan(jax.checkpoint(body), acc0, (nbr.T, w))
    y = acc.astype(x.dtype) + b
    return jnp.where((jnp.arange(nbr.shape[0]) < count)[:, None], y, 0)


def _relu_bn(y, count):
    y = jax.nn.relu(y)
    valid = (jnp.arange(y.shape[0]) < count)[:, None]
    n = jnp.maximum(count, 1).astype(y.dtype)
    mean = jnp.where(valid, y, 0).sum(0) / n
    var = jnp.where(valid, jnp.square(y - mean), 0).sum(0) / n
    return jnp.where(valid, (y - mean) * jax.lax.rsqrt(var + EPS_BN), 0)


def forward_one(params, net: Net, inp, precision):
    """Logits of one scan, rows in key order of the output level. ``inp``
    holds the scan's arrays of :func:`device_inputs` (``x``, ``maps``,
    ``counts``), its floating ones in the computing type."""
    x, maps, counts = inp["x"], inp["maps"], inp["counts"]
    skips = {}
    for L in net.layers:
        if L.skip_in is not None:
            x = jnp.concatenate([x, skips[L.skip_in]], axis=-1)
        p = params[L.name]
        y = _conv(x, maps[f"{L.m_in}_{L.m_out}_{L.K}"], p["w"], p["b"],
                  counts[str(L.m_out)], precision)
        x = _relu_bn(y, counts[str(L.m_out)])
        if L.skip_out is not None:
            skips[L.skip_out] = x
    return _dot(x, params["head"], precision).astype(x.dtype)


def _cast(tree, dtype):
    return jax.tree.map(lambda a: a.astype(dtype)
                        if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)


@partial(jax.jit, static_argnames=("net", "dtype", "precision",
                                   "forward_one"))
def forward(params, inp, *, net: Net, dtype=jnp.float32,
            precision=jax.lax.Precision.HIGHEST, forward_one=forward_one):
    """Logits ``[B, cap_out, n_classes]`` of a stack of scans: each scan's
    arrays of ``inp`` but its labels, through ``forward_one`` (a
    configuration's own, or :func:`forward_one`)."""
    p = _cast(params, dtype)
    scans = _cast({k: v for k, v in inp.items() if k != "labels"}, dtype)
    f = lambda one: forward_one(p, net, one, precision)
    return jax.vmap(f)(scans).astype(jnp.float32)


def loss_fn(params, inp, net: Net, dtype, precision, forward_one):
    """Masked mean cross-entropy over every labelled voxel of the batch."""
    logits = forward(params, inp, net=net, dtype=dtype, precision=precision,
                     forward_one=forward_one)
    lab = inp["labels"]
    valid = lab >= 0
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.take_along_axis(logp, jnp.maximum(lab, 0)[..., None],
                              axis=-1)[..., 0]
    return jnp.where(valid, ce, 0).sum() / jnp.maximum(valid.sum(), 1)


@dataclasses.dataclass(frozen=True)
class AdamW:
    lr: float
    b1: float
    b2: float
    eps: float
    weight_decay: float
    grad_clip: float
    warmup_steps: int
    total_steps: int
    min_lr_ratio: float

    def lr_at(self, t: int) -> float:
        """Learning rate of the step that starts with ``t`` steps done:
        linear warm-up, then cosine decay to ``min_lr_ratio``."""
        warm = min(1.0, (t + 1) / max(self.warmup_steps, 1))
        prog = min(max((t - self.warmup_steps)
                       / max(self.total_steps - self.warmup_steps, 1), 0.0),
                   1.0)
        cos = 0.5 * (1 + math.cos(math.pi * prog))
        return self.lr * warm * (self.min_lr_ratio
                                 + (1 - self.min_lr_ratio) * cos)


@partial(jax.jit, static_argnames=("net", "dtype", "precision",
                                   "forward_one"))
def loss_and_grad(params, inp, *, net: Net, dtype=jnp.float32,
                  precision=jax.lax.Precision.HIGHEST,
                  forward_one=forward_one):
    return jax.value_and_grad(loss_fn)(params, inp, net, dtype, precision,
                                       forward_one)


def train_steps(params, inputs: Sequence[dict], net: Net, opt: AdamW, *,
                dtype=jnp.float32, precision=jax.lax.Precision.HIGHEST,
                forward_one=forward_one) -> dict:
    """Run ``len(inputs)`` AdamW steps from ``params`` (float32 state).
    Returns each step's loss, the first step's clipped gradient and the
    final parameters, all on the host."""
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    losses, first_grad = [], None
    for t, inp in enumerate(inputs):
        loss, g = loss_and_grad(p, inp, net=net, dtype=dtype,
                                precision=precision, forward_one=forward_one)
        g = jax.tree.map(lambda a: a.astype(jnp.float32), g)
        gnorm = math.sqrt(sum(float(jnp.sum(jnp.square(a)))
                              for a in jax.tree.leaves(g)))
        scale = min(1.0, opt.grad_clip / max(gnorm, 1e-9))
        g = jax.tree.map(lambda a: a * scale, g)
        if first_grad is None:
            first_grad = jax.device_get(g)
        lr, k = opt.lr_at(t), t + 1
        bc1, bc2 = 1 - opt.b1 ** k, 1 - opt.b2 ** k
        m = jax.tree.map(lambda a, b: opt.b1 * a + (1 - opt.b1) * b, m, g)
        v = jax.tree.map(lambda a, b: opt.b2 * a + (1 - opt.b2) * b * b, v, g)
        p = jax.tree.map(
            lambda a, mm, vv: a - lr * ((mm / bc1) / (jnp.sqrt(vv / bc2)
                                                      + opt.eps)
                                        + opt.weight_decay * a), p, m, v)
        losses.append(float(loss))
    return {"losses": losses, "first_grad": first_grad,
            "params": jax.device_get(p)}


# ---------------------------------------------------------------------------
# comparisons
# ---------------------------------------------------------------------------

def logit_gaps(served_voxels: np.ndarray, served: np.ndarray,
               scan: HostScan, ref: np.ndarray, level: int
               ) -> Dict[str, float]:
    """|served - reference| over the scan's largest reference logit: the
    largest (``logit_gap``), the root mean square (``logit_rms_gap``) and
    the median (``logit_median_gap``) over every logit of the scan; and
    the largest over voxels of |served - reference| over the range (largest
    less smallest) of the voxel's own reference logits
    (``logit_voxel_gap``), which an answer moved one class along reads at
    2/C or more of, for C classes, however small the voxel's logits lie
    beside the scan's largest. The served rows are matched to the
    reference's by their voxel; a missing, extra or repeated voxel reads
    infinity."""
    bad = {k: math.inf for k in GAPS}
    keys = scan.keys[level]
    if served.shape[0] != len(keys) or served_voxels.shape[0] != len(keys):
        return bad
    sk = _encode(np.asarray(served_voxels, np.int64))
    order = np.argsort(sk, kind="stable")
    if not np.array_equal(sk[order], keys):
        return bad
    want = np.asarray(ref[:len(keys)], np.float64)
    have = np.asarray(served, np.float64)[order]
    if not np.isfinite(have).all():
        return bad
    diff = np.abs(have - want)
    d = diff / max(np.abs(want).max(), 1e-30)
    spread = np.ptp(want, axis=1)
    return {"logit_gap": float(d.max()),
            "logit_rms_gap": float(np.sqrt(np.mean(np.square(d)))),
            "logit_median_gap": float(np.median(d)),
            "logit_voxel_gap": float(
                (diff.max(axis=1) / np.maximum(spread, 1e-30)).max())}


GAPS = ("logit_gap", "logit_rms_gap", "logit_median_gap",
        "logit_voxel_gap")


def leaf_norm_gaps(have: dict, want: dict, skip: Sequence[str] = ()
                   ) -> Dict[str, float]:
    """Each leaf's ``|‖have‖ - ‖want‖| / max(‖want‖, median leaf
    ‖want‖)``, over the leaves not in ``skip``."""
    hv = _flat_norms(have)
    wv = _flat_norms(want)
    names = [n for n in wv if n not in skip]
    med = float(np.median([wv[n] for n in names]))
    return {n: (abs(hv[n] - wv[n]) / max(wv[n], med, 1e-30)
                if math.isfinite(hv[n]) else math.inf) for n in names}


def leaf_norm_gap(have: dict, want: dict, skip: Sequence[str] = ()
                  ) -> Tuple[float, str]:
    """The worst leaf of :func:`leaf_norm_gaps`; returns (gap, leaf)."""
    worst, leaf = 0.0, ""
    for n, g in leaf_norm_gaps(have, want, skip).items():
        if g >= worst:
            worst, leaf = g, n
    return worst, leaf


def _flat_norms(tree: dict) -> Dict[str, float]:
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        out[name] = float(np.linalg.norm(np.asarray(leaf, np.float64)))
    return out


def still_leaves(grad: dict, rel: float = 1e-3) -> List[str]:
    """Leaves whose reference gradient norm is under ``rel`` of the median
    leaf's: under Adam they move by round-off alone, so they are left out
    of the comparison of parameter changes."""
    norms = _flat_norms(grad)
    med = float(np.median(list(norms.values())))
    return [n for n, v in norms.items() if v < rel * med]


def tree_sub(a: dict, b: dict) -> dict:
    return jax.tree.map(lambda x, y: np.asarray(x, np.float64)
                        - np.asarray(y, np.float64), a, b)
