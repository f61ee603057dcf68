"""Backend kind ``tiered-disk``: PQ codes and graph in device memory steer
the walk; the final beam is reranked from full-precision vectors read back
from a block store on disk (``repro.serving.TieredBackend`` over a
``BlockSlowTier``, with its cache and pinned set sized by the config)."""
from __future__ import annotations

import time

from bench import index_cache
from bench.backends import exact
from bench.data import sub_seed


def open_backend(cfg: dict, base, seed: int, config_file, log):
    import jax.numpy as jnp

    from repro import serving
    from repro.core.types import GraphIndex
    from repro.index import TieredIndex, build_tiered_index
    from repro.index import open_or_build_slow_tier
    from repro.pq import PqCodebook

    key = index_cache.cache_key(config_file, seed)
    arrays = index_cache.load(key)
    t0 = time.perf_counter()
    built = arrays is None
    if built:
        arrays = exact.graph_arrays(base, cfg, seed, log)
        graph = GraphIndex(**arrays)
        tiered = build_tiered_index(base, graph, m_pq=int(cfg["pq"]["m"]),
                                    seed=sub_seed(seed, "pq"))
        arrays = dict(arrays, centroids=tiered.codebook.centroids,
                      codes=tiered.codes)
        index_cache.save(key, arrays)
    graph = GraphIndex(**{k: jnp.asarray(arrays[k]) for k in
                          ("adj", "entry", "alpha", "lid", "mu", "sigma")})
    index = TieredIndex(graph=graph,
                        codebook=PqCodebook(
                            centroids=jnp.asarray(arrays["centroids"])),
                        codes=jnp.asarray(arrays["codes"]), vectors=base)
    st = cfg["slow_tier"]
    slow = open_or_build_slow_tier(
        index_cache.entry_dir(key) / "store.blocks", index,
        cache_nodes=int(st["cache_nodes"]), pin_nodes=int(st["pin_nodes"]),
        log=log)
    build_s = time.perf_counter() - t0
    backend = serving.TieredBackend(index, slow_tier=slow,
                                    step_kernel="auto")
    return exact.Served(backend=backend, built=built, build_s=build_s,
                        slow_tier=slow)
