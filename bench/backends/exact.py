"""Backend kind ``exact``: vectors and graph in device memory, exact
float32 distances steer the walk (``repro.serving.ExactBackend``)."""
from __future__ import annotations

import dataclasses
import time

from bench import index_cache
from bench.data import sub_seed


@dataclasses.dataclass
class Served:
    backend: object
    built: bool
    build_s: float
    slow_tier: object = None

    def close(self) -> None:
        close = getattr(self.backend, "close", None)
        if close is not None:
            close()


def build_config(cfg: dict, seed: int):
    from repro.core import build

    b = cfg["build"]
    return build.BuildConfig(
        degree=int(b["degree"]), beam_width=int(b["l_build"]),
        iters=int(b["iters"]), lid_k=int(b["lid_k"]),
        alpha_min=float(b["alpha_min"]), alpha_max=float(b["alpha_max"]),
        batch=int(b["batch"]), max_hops=int(b["max_hops"]),
        reverse_cap=int(b["reverse_cap"]), seed=sub_seed(seed, "build"))


def graph_arrays(base, cfg: dict, seed: int, log) -> dict:
    from repro.core import build

    t0 = time.perf_counter()
    graph = build.build_mcgi(
        base, build_config(cfg, seed),
        progress=lambda m: log(f"build +{time.perf_counter() - t0:.1f}s {m}"))
    graph.adj.block_until_ready()
    return {"adj": graph.adj, "entry": graph.entry, "alpha": graph.alpha,
            "lid": graph.lid, "mu": graph.mu, "sigma": graph.sigma}


def open_backend(cfg: dict, base, seed: int, config_file, log) -> Served:
    import jax.numpy as jnp

    from repro import serving

    key = index_cache.cache_key(config_file, seed)
    arrays = index_cache.load(key)
    t0 = time.perf_counter()
    built = arrays is None
    if built:
        arrays = graph_arrays(base, cfg, seed, log)
        index_cache.save(key, arrays)
    build_s = time.perf_counter() - t0
    backend = serving.ExactBackend(base, jnp.asarray(arrays["adj"]),
                                   jnp.asarray(arrays["entry"]),
                                   step_kernel="auto")
    return Served(backend=backend, built=built, build_s=build_s)
