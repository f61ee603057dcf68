"""Backend kind ``sharded``: the base set split into row shards over a mesh
of chips (``mesh`` in the config, one shard per chip), served by the
program's staged ``repro.serving.DistributedBackend``.

Each shard holds its own sub-graph (built on the chip that owns it by
``repro.distributed.build_sharded_arrays``), its PQ codes, its full
vectors and its entry candidates (each query's walk on the shard starts
from the nearest); the probe grants every (query, shard) its own budget
under the same ``AdaptiveBeamBudget`` the engine holds (``bench.run.budget``), the
continue program reranks on each shard and merges the shards' top k.  A
served id is ``shard * rows_per_shard + local id``, the row of the base set.

The built arrays (not the vectors, which the seed makes anew) are cached
per seed through :mod:`bench.index_cache`.  Of every batch the backend
finishes, the per (query, shard) hops and budgets are recorded with the
time it finished (a few KB a batch; the warm-up's passes add at most a few
dozen), so the per-shard readers can take the window's batches
(:func:`window_results`).
"""
from __future__ import annotations

import functools
import time

from bench import index_cache
from bench.backends import exact
from bench.data import sub_seed

CACHED = ("adj", "codes", "centroids", "entries")
# (perf_counter at finish, {"shard_hops", "budget"}) of every batch since
# open_backend; "shard_hops" is None where the program has no such counter.
RECORD: list = []


def counters(res) -> dict:
    """What the per-shard readers read of a ``BatchResult``."""
    return {"shard_hops": res.extras.get("shard_hops"),
            "budget": res.astats.budget}


def window_results(win) -> list:
    """The recorded counters of the batches finished inside ``win``."""
    return [rec for t, rec in RECORD if win.t_first <= t <= win.t_last]


@functools.lru_cache(maxsize=None)
def _recorded_class():
    from repro.serving import DistributedBackend

    class Recorded(DistributedBackend):
        def finish(self, *a, **kw):
            res = super().finish(*a, **kw)
            RECORD.append((time.perf_counter(), counters(res)))
            return res

    return Recorded


def make_mesh(cfg: dict):
    import jax

    from repro import compat

    axes = cfg["mesh"]
    shape = tuple(int(v) for v in axes.values())
    n_shards = 1
    for v in shape:
        n_shards *= v
    return compat.make_mesh(shape, tuple(axes),
                            devices=jax.devices()[:n_shards])


def open_backend(cfg: dict, base, seed: int, config_file, log):
    import numpy as np

    from repro.distributed import build_sharded_arrays
    from repro.distributed.sharded_search import place_sharded_arrays

    from bench import run

    RECORD.clear()
    mesh = make_mesh(cfg)
    key = index_cache.cache_key(config_file, seed)
    cached = index_cache.load(key)
    t0 = time.perf_counter()
    built = cached is None
    if built:
        log(f"building {mesh.devices.size} shards concurrently")
        arrays, _ = build_sharded_arrays(
            base, mesh, build_cfg=exact.build_config(cfg, seed),
            m_pq=int(cfg["pq"]["m"]), alpha=float(cfg["build"]["alpha"]),
            pq_iters=int(cfg["pq"]["iters"]), seed=sub_seed(seed, "pq"))
        index_cache.save(key, {k: np.asarray(arrays[k]) for k in CACHED})
    else:
        arrays = place_sharded_arrays(mesh, vectors=base, **cached)
    arrays["adj"].block_until_ready()
    build_s = time.perf_counter() - t0
    s = cfg["search"]
    backend = _recorded_class()(
        mesh, arrays, beam_width=int(s["l_search"]),
        max_hops=int(s["probe_hops"]) + int(s["hop_factor"])
        * int(s["l_search"]),
        k=int(cfg["k"]), query_chunk=int(s["query_chunk"]), use_pq=True,
        beam_budget=run.budget(cfg), merge="hierarchical",
        step_kernel="auto")
    return exact.Served(backend=backend, built=built, build_s=build_s)
