"""Driver of ``"mode": "open"`` mixes: an open loop through the front door.

Single-query requests, pool rows drawn with replacement from the seed, are
due at the times of the mix's ``arrivals`` and go through
``FrontDoor.submit`` under the mix's QoS ``class``, with the wall clock and
the thread dispatcher.  Each request is timed from its due time, so a
stalled generator shows in the latency, and how late the generator ran is
kept.  A thin engine proxy and dispatcher stamp when each dispatch began
and ended and which requests it carried.
"""
from __future__ import annotations

import time

import numpy as np

from bench import loadgen
from bench.data import sub_seed
from bench.drive import Window, span, warm_lane_family


class _Stamped:
    __slots__ = ("flight", "t_begin", "t_finished")

    def __init__(self, flight, t_begin: float):
        self.flight, self.t_begin = flight, t_begin
        self.t_finished = None


class EngineProxy:
    """Stands in for a ``SearchEngine`` at the front door and stamps, on
    the door's clock (``time.monotonic``), when each dispatch's ``begin``
    was called and when its ``finish_from`` returned."""

    def __init__(self, engine):
        self.engine = engine

    def begin(self, queries, **kw):
        t = time.monotonic()
        return _Stamped(self.engine.begin(queries, **kw), t)

    def finish_from(self, s: _Stamped):
        res = self.engine.finish_from(s.flight)
        s.t_finished = time.monotonic()
        return res

    @property
    def supports_partial(self) -> bool:
        return self.engine.supports_partial

    def partial_result(self, s: _Stamped):
        return self.engine.partial_result(s.flight)

    def close(self) -> None:
        self.engine.close()


def stamping_dispatcher(workers: int, log: list):
    """The production thread dispatcher, noting for each dispatch its begin
    stamp, its real lane count and the futures of the requests it carries."""
    from repro.serving import server as sv

    class Stamping(sv.ThreadDispatcher):
        def submit(self, disp, finish, on_done):
            log.append((disp.flight, int(disp.n_real),
                        [r.future for r in disp.requests]))
            super().submit(disp, finish, on_done)

    return Stamping(workers=workers)


def qos_class(traffic: dict):
    from repro.serving import server as sv

    c = traffic["class"]
    return sv.QoSClass(c["name"], deadline_s=float(c["deadline_s"]),
                       batch_window_s=float(c["batch_window_s"]),
                       max_lanes=int(c["max_lanes"]),
                       lane_quantum=int(c["lane_quantum"]))


def warm(engine, pool: np.ndarray, traffic: dict, seed: int, counter,
         log) -> None:
    """Warm the front door's dispatch shape and its lane family, then send a
    short burst of requests until one compiles nothing new."""
    rng = np.random.default_rng(sub_seed(seed, "warm"))
    lanes = int(traffic["class"]["lane_quantum"])
    first = pool[rng.integers(0, pool.shape[0], lanes)]
    before = counter.count
    engine.search(first)
    warm_lane_family(engine, first, engine.pad_quantum)
    log(f"lane family: {counter.count - before} programs obtained")
    for p in range(4):
        before = counter.count
        for _ in range(3):
            engine.search(pool[rng.integers(0, pool.shape[0], lanes)])
        new = counter.count - before
        log(f"warm pass {p + 1}: {new} programs obtained")
        if new == 0:
            return


def run(engine, pool: np.ndarray, traffic: dict, seed: int, seconds: float,
        rate: float | None = None, drain_s: float = 60.0) -> Window:
    from repro.serving import server as sv

    rng = np.random.default_rng(sub_seed(seed, "traffic"))
    arrivals = dict(traffic["arrivals"])
    if rate is not None:
        arrivals["rate"] = rate
    offsets = loadgen.arrival_offsets(arrivals, seconds, rng)
    rows = rng.integers(0, pool.shape[0], offsets.size)
    cls = qos_class(traffic)
    proxy = EngineProxy(engine)
    dlog: list = []
    door = sv.FrontDoor(
        {cls.name: proxy}, [cls], max_queue=int(traffic["max_queue"]),
        dispatcher=stamping_dispatcher(int(traffic["dispatch_workers"]),
                                       dlog))
    futs, due, late = [], np.empty(offsets.size), np.empty(offsets.size)
    with span("bench.window"):
        t_start = time.monotonic() + 0.01
        for i, (off, row) in enumerate(zip(offsets, rows)):
            due[i] = t_start + off
            wait = due[i] - time.monotonic()
            if wait > 0:
                with span("bench.sleep"):
                    time.sleep(wait)
            with span("bench.submit"):
                late[i] = time.monotonic() - due[i]
                futs.append(door.submit(pool[row], cls=cls.name))
        with span("bench.drain"):
            try:
                door.close(wait=True, timeout=cls.deadline_s + drain_s)
            except TimeoutError:
                pass
    results = [f.result(timeout=0) if f.done() else None for f in futs]
    t_close = time.monotonic()
    never = sum(r is None for r in results)
    ok = [i for i, r in enumerate(results)
          if r is not None and r.ids is not None]
    not_ok = sum(r is None or r.status != sv.OK for r in results)
    lat = np.array([
        t_close - due[i] if r is None else
        (r.t_done - due[i] if r.status == sv.OK
         else max(r.t_done - due[i], cls.deadline_s))
        for i, r in enumerate(results)])
    fut_row = {id(f): i for i, f in enumerate(futs)}
    waits = np.array([st.t_begin - due[fut_row[id(f)]]
                      for st, _, fs in dlog for f in fs])
    done_t = [r.t_done for r in results if r is not None]
    # Requests open (admitted, not complete) as each one was submitted.
    sent = due + late
    open_lanes = np.arange(sent.size) - np.searchsorted(
        np.sort(done_t), sent, side="right")
    k = int(engine.k)
    return Window(
        qidx=rows[ok],
        ids=(np.stack([results[i].ids for i in ok]) if ok
             else np.empty((0, k), np.int64)),
        d2=(np.stack([results[i].d2 for i in ok]) if ok
            else np.empty((0, k), np.float32)),
        attempted=int(offsets.size), not_ok=int(not_ok),
        t_first=float(t_start), t_last=float(max(done_t, default=t_start)),
        hops=np.array([results[i].hops for i in ok
                       if results[i].hops is not None]),
        latencies_s=lat, lateness_s=late,
        dispatches=[(st.t_begin, st.t_finished, n) for st, n, _ in dlog],
        queue_waits_s=waits, never_done=int(never), open_lanes=open_lanes)
