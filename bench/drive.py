"""What every traffic driver shares: the window's record, host spans, the
compile counter and the warm-up of the continue program's lane family.

A driver is ``bench/drivers/<mode>.py``, found by the ``mode`` of a traffic
mix; it has ``warm(engine, pool, traffic, seed, counter, log)`` and
``run(engine, pool, traffic, seed, seconds) -> Window``.

The only things put around the program are host spans
(``jax.profiler.TraceAnnotation``) and, in the open loop, a thin engine
proxy and dispatcher that stamp when each dispatch began and ended and
which requests it carried.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import functools
import os
import threading

import numpy as np

ENGINE_STAGES = ("_dispatch", "_walk_prefetch", "_schedule", "_prefetch",
                 "_gather")
WARM_THREADS = max(1, min(8, os.cpu_count() or 1))


class CompileCounter:
    """Counts programs JAX obtains (compiled or read from the persistent
    cache) — each is a shape the warm-up did not reach."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self._lock = threading.Lock()

    def install(self) -> "CompileCounter":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == self.EVENT:
            with self._lock:
                self.count += 1


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def annotate_engine(engine) -> None:
    """Wrap the engine's pipeline stages in host spans (``engine.*``), so a
    device gap can be laid to the stage the host was in."""
    for stage in ENGINE_STAGES:
        fn = getattr(engine, stage, None)
        if fn is None:
            continue

        @functools.wraps(fn)
        def wrapped(*a, _fn=fn, _name="engine." + stage.lstrip("_"), **kw):
            with span(_name):
                return _fn(*a, **kw)

        setattr(engine, stage, wrapped)


@dataclasses.dataclass
class Window:
    """What a window served: one row per answered query, plus timings."""

    qidx: np.ndarray            # (A,) pool rows answered
    ids: np.ndarray             # (A, k)
    d2: np.ndarray              # (A, k)
    attempted: int
    not_ok: int                 # requests not answered ``ok``
    t_first: float              # first dispatch / first due time
    t_last: float               # last completion
    hops: np.ndarray | None = None
    evals: np.ndarray | None = None
    latencies_s: np.ndarray | None = None
    lateness_s: np.ndarray | None = None
    dispatches: list = dataclasses.field(default_factory=list)
    queue_waits_s: np.ndarray | None = None
    never_done: int = 0
    open_lanes: np.ndarray | None = None   # open lanes seen at each submit

    @property
    def seconds(self) -> float:
        return self.t_last - self.t_first


def warm_lane_family(engine, batch: np.ndarray, quantum: int) -> None:
    """Run the continue program at every bucket lane count a batch of this
    size can produce (``quantum``, ``2 * quantum``, .., the batch), the way
    the pipeline slices a probe state for one bucket.

    Each lane count brings programs of its own (~16 at 256-query batches,
    1,025 in all), and a checkout's first run compiles every one of them:
    one after another that took 700 s and more on a v5e host.  XLA releases
    the GIL while it compiles, so the lane counts are warmed from
    ``WARM_THREADS`` threads.
    """
    import jax
    import jax.numpy as jnp

    backend, cfg = engine.backend, engine.budget_cfg
    ctxs = backend.admit(batch)
    state, budgets, hop_limits, _ = backend.probe(ctxs, cfg)
    cont = backend.continue_fn(cfg)
    b = batch.shape[0]

    def one(lanes: int) -> None:
        sel = jnp.asarray(np.arange(lanes) % b)
        sub = jax.tree_util.tree_map(lambda a: a[sel], state)
        out = cont(sub, ctxs[sel], budgets[sel], hop_limits[sel])
        for h in out:
            np.asarray(h)

    with concurrent.futures.ThreadPoolExecutor(WARM_THREADS) as pool:
        list(pool.map(one, range(quantum, b + 1, quantum)))
