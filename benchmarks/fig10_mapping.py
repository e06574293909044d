"""Paper Fig. 10: mapping performance (pre-processing + search) across
engines, scene sizes and kernel sizes.

Engines: Spira z-delta (no pre-processing) vs Simple BSearch (packed, no
pre-processing) vs hash table (build = pre-processing + probe lookups,
TorchSparse-style), plus the PR-2 engines: the §5.4 symmetry half-search
(⌈K²/2⌉+1 anchor groups instead of K²) and the superwindow Pallas kernel
(one window DMA per output tile; interpreter off-TPU, so its wall time is
algorithmic cost only — the DMA counter is the device claim). Reports wall
time and the hardware-independent work counters.
"""
import jax
import jax.numpy as jnp

from repro.core import (offset_grid, pack_offsets, simple_bsearch,
                        symmetry_anchor_count, zdelta_offsets, zdelta_search,
                        zdelta_search_symmetric)
from repro.core import hashmap
from repro.kernels import ops
from repro.kernels.zdelta_window import zdelta_superwindow_search
from .common import emit, prep, scene_set, timeit, us

# interpreter-mode pallas rows are slow off-TPU: smallest scene only
PALLAS_SCENES = 1


def run(K: int = 3):
    rows = []
    for si, (name, sc) in enumerate(scene_set()):
        cs, _ = prep(sc)
        n = int(cs.count)
        g_sym = symmetry_anchor_count(K)
        _, anchors, zstep = zdelta_offsets(K, 1, sc.layout)
        offs = pack_offsets(jnp.asarray(offset_grid(K, 1)), sc.layout)

        zd = jax.jit(lambda c: zdelta_search(c, c, anchors, zstep, K=K))
        zs = jax.jit(lambda c: zdelta_search_symmetric(c, c, anchors, zstep,
                                                       K=K))
        bs = jax.jit(lambda c: simple_bsearch(c, c, offs, K=K))
        ts = hashmap.table_size_for(cs.capacity)

        def hash_full(c):
            tk, tv = hashmap.build_table(c, table_size=ts)
            return hashmap.hash_kernel_map(tk, tv, c, offs, K=K)

        def hash_build(c):
            return hashmap.build_table(c, table_size=ts)

        hf = jax.jit(hash_full)
        hb = jax.jit(hash_build)

        t_z = timeit(zd, cs)
        t_s = timeit(zs, cs)
        t_b = timeit(bs, cs)
        t_h = timeit(hf, cs)
        t_hb = timeit(hb, cs)
        rows.append((f"fig10/{name}/K{K}/zdelta", us(t_z),
                     f"n={n};searches={n * K * K};speedup_vs_bsearch={t_b / t_z:.2f}"))
        rows.append((f"fig10/{name}/K{K}/zdelta_sym", us(t_s),
                     f"n={n};searches={n * g_sym};speedup_vs_full={t_z / t_s:.2f}"))
        rows.append((f"fig10/{name}/K{K}/bsearch", us(t_b),
                     f"n={n};searches={n * K ** 3}"))
        rows.append((f"fig10/{name}/K{K}/hash", us(t_h),
                     f"n={n};preproc_frac={t_hb / t_h:.2f}"))
        if si < PALLAS_SCENES:
            cap = ((cs.capacity + 127) // 128) * 128   # full 128-row tiles
            csp, _ = prep(sc, capacity=cap)
            interpret = not ops.on_tpu()
            sw = jax.jit(lambda c: zdelta_superwindow_search(
                c, c, anchors, zstep, K=K, W=min(4096, cap),
                interpret=interpret)[0])
            t_w = timeit(sw, csp, repeats=3, warmup=1)
            n_tiles = cap // 128
            rows.append((f"fig10/{name}/K{K}/zdelta_superwindow", us(t_w),
                         f"n={n};dmas={n_tiles};dmas_pergroup_kernel="
                         f"{n_tiles * K * K}"))
    emit(rows)
    return rows


if __name__ == "__main__":
    run(3)
    run(5)
