"""The async serving front door, tested entirely under the virtual clock.

Every test drives :class:`repro.serving.server.VirtualClock` /
:class:`VirtualDispatcher` — no ``time.sleep`` anywhere, every interleaving
(randomized arrivals, class mixes, coalescing boundaries, deadline expiry
mid-continue, overload shed, drain on shutdown) replayable bit-exactly.
The core property: for every *admitted* request the served lane is
bit-identical to a direct engine call on the same queries — admission,
coalescing and scheduling never change the math (the front-door extension
of the pipeline's result-transparency invariant).

Engines are the shared parity fixtures (``tests/_backend_fixtures.py``,
pinned LID center so per-lane results are dispatch-composition-independent);
the admission/lifecycle mechanics run against a deterministic fake engine
so queue/deadline/shed behaviour is tested without device math in the way.
"""
import dataclasses
import functools
import math
import threading

import numpy as np

from repro.core import search
from repro.serving import server
from repro.serving.engine import BatchResult, SearchEngine, TieredBackend
from tests._backend_fixtures import BUDGET, built, engine
from tests._hypothesis_compat import given, settings, st


@functools.lru_cache(maxsize=1)
def ref_rows():
    """Per-lane reference results over the fixture queries: under the pinned
    center, row i of the all-queries batch == any dispatch containing lane i
    (pinned by the parity matrix; relied on here)."""
    _x, q, _gt, _idx, _t = built()
    res = engine("exact").search(q)
    return q, np.asarray(res.ids), np.asarray(res.d2)


class FakeEngine:
    """Deterministic engine-shaped object for admission mechanics: results
    derived from the batch bytes, injectable finish failure, close counting.
    No partial support — in-flight deadline hedges fall through to timeout.
    """

    supports_partial = False

    def __init__(self, k: int = 4, fail_finish: bool = False):
        self.k = k
        self.fail_finish = fail_finish
        self.close_calls = 0
        self.finishes = 0

    def begin(self, batch):
        return {"batch": np.asarray(batch, np.float64)}

    def finish_from(self, flight):
        if self.fail_finish:
            raise RuntimeError("injected finish failure")
        self.finishes += 1
        b = flight["batch"]
        base = np.round(b[:, :1] * 1000.0).astype(np.int64)
        ids = base + np.arange(self.k)[None, :]
        d2 = ids.astype(np.float64) / 7.0
        stats = search.SearchStats(
            hops=np.full(b.shape[0], 7.0),
            dist_evals=np.full(b.shape[0], 70.0))
        return BatchResult(ids=ids, d2=d2, stats=stats)

    def close(self):
        self.close_calls += 1


def fake_door(*, deadline_s=100.0, batch_window_s=0.0, max_lanes=4,
              max_queue=256, service_time=0.0, probe_time=0.0,
              eng=None, lane_quantum=1):
    clock = server.VirtualClock()
    eng = FakeEngine() if eng is None else eng
    door = server.FrontDoor(
        {"a": eng},
        [server.QoSClass("a", deadline_s=deadline_s,
                         batch_window_s=batch_window_s, max_lanes=max_lanes,
                         lane_quantum=lane_quantum)],
        max_queue=max_queue, clock=clock,
        dispatcher=server.VirtualDispatcher(
            clock, service_time=service_time, probe_time=probe_time))
    return door, clock, eng


# ------------------------------------------------------------ virtual clock


def test_virtual_clock_orders_by_time_then_submission():
    clock = server.VirtualClock()
    fired = []
    clock.call_at(2.0, fired.append, "late")
    clock.call_at(1.0, fired.append, "first-at-1")
    clock.call_at(1.0, fired.append, "second-at-1")
    t = clock.call_at(1.5, fired.append, "cancelled")
    t.cancel()
    assert clock.pending() == 3
    ran = clock.advance(1.2)
    assert ran == 2 and fired == ["first-at-1", "second-at-1"]
    assert clock.now() == 1.2          # advances to the horizon
    clock.advance(1.0)
    assert fired == ["first-at-1", "second-at-1", "late"]
    # inf never fires but still hands back a cancelable handle.
    t_inf = clock.call_at(math.inf, fired.append, "never")
    clock.advance(1e9)
    assert fired[-1] == "late" and not t_inf.cancelled


def test_virtual_clock_callbacks_see_their_own_fire_time():
    clock = server.VirtualClock()
    seen = []
    clock.call_at(1.0, lambda: (seen.append(clock.now()),
                                clock.call_later(0.5, seen.append, "chain")))
    clock.advance(2.0)
    # The chained event lands at 1.5 (relative to its scheduler's fire
    # time), inside the same advance.
    assert seen == [1.0, "chain"]


# ------------------------------------- bit-identity of admitted results


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n=st.integers(1, 14),
       max_lanes=st.sampled_from([1, 2, 3, 5]),
       window=st.sampled_from([0.0, 0.01, 0.05]),
       two_classes=st.sampled_from([False, True]))
def test_served_results_bit_identical_to_direct(seed, n, max_lanes, window,
                                                two_classes):
    """Randomized arrivals / class mixes / coalescing boundaries: every
    admitted request's served lane is bit-identical to the direct engine
    result for that query."""
    q, ref_ids, ref_d2 = ref_rows()
    rng = np.random.default_rng(seed)
    eng = engine("exact")
    clock = server.VirtualClock()
    classes = [server.QoSClass("a", deadline_s=1e6, batch_window_s=window,
                               max_lanes=max_lanes)]
    engines = {"a": eng}
    if two_classes:
        classes.append(server.QoSClass("b", deadline_s=1e6,
                                       batch_window_s=window,
                                       max_lanes=max_lanes))
        engines["b"] = eng
    door = server.FrontDoor(engines, classes, clock=clock,
                            dispatcher=server.VirtualDispatcher(clock))
    rows = rng.integers(0, q.shape[0], size=n)
    names = [c.name for c in classes]
    futs = []
    for r in rows:
        futs.append(door.submit(q[r], cls=names[rng.integers(len(names))]))
        clock.advance(float(rng.choice([0.0, 0.002, 0.02])))
    clock.advance(1.0)
    for r, f in zip(rows, futs):
        res = f.result(timeout=0)
        assert res.status == server.OK, res
        np.testing.assert_array_equal(res.ids, ref_ids[r])
        np.testing.assert_array_equal(res.d2, ref_d2[r])
    stats = door.stats()
    assert stats["admitted"] == n and stats["ok"] == n
    assert stats["open_lanes"] == 0 and stats["queued_lanes"] == 0


def test_lane_quantum_padding_is_result_transparent():
    """lane_quantum pads dispatches to a lane grid; padded rows are dropped
    and the real lanes stay bit-identical (pinned center)."""
    q, ref_ids, ref_d2 = ref_rows()
    eng = engine("exact")
    clock = server.VirtualClock()
    door = server.FrontDoor(
        {"a": eng},
        [server.QoSClass("a", deadline_s=1e6, batch_window_s=0.01,
                         max_lanes=8, lane_quantum=4)],
        clock=clock, dispatcher=server.VirtualDispatcher(clock))
    futs = [door.submit(q[i]) for i in range(6)]     # 6 lanes -> pad to 8
    clock.advance(0.02)
    for i, f in enumerate(futs):
        res = f.result(timeout=0)
        assert res.status == server.OK
        np.testing.assert_array_equal(res.ids, ref_ids[i])
        np.testing.assert_array_equal(res.d2, ref_d2[i])
    assert door.stats()["dispatches"] == 1


# --------------------------------------------- deadlines, hedges, partials


def test_deadline_hedge_partial_matches_engine_partial():
    """A deadline expiring mid-flight serves the best-so-far partial —
    bit-identical to ``engine.partial_result`` of an identical dispatch —
    and the late full result never overwrites it."""
    q, _ids, _d2 = ref_rows()
    eng = engine("exact")
    assert eng.supports_partial
    clock = server.VirtualClock()
    door = server.FrontDoor(
        {"a": eng}, [server.QoSClass("a", deadline_s=1.0, max_lanes=3)],
        clock=clock,
        dispatcher=server.VirtualDispatcher(clock, service_time=10.0,
                                            probe_time=0.001))
    futs = [door.submit(q[i]) for i in range(3)]     # flush at max_lanes
    ref = eng.partial_result(eng.begin(np.stack([q[0], q[1], q[2]])))
    clock.advance(1.0)                               # deadlines fire
    for i, f in enumerate(futs):
        res = f.result(timeout=0)
        assert res.status == server.PARTIAL
        np.testing.assert_array_equal(res.ids, np.asarray(ref.ids)[i])
        np.testing.assert_array_equal(res.d2, np.asarray(ref.d2)[i])
        assert res.extras.get("partial") is True
    clock.advance(20.0)                              # full result lands late
    assert all(f.result(timeout=0).status == server.PARTIAL for f in futs)
    stats = door.stats()
    assert stats["partial"] == 3 and stats["open_lanes"] == 0


def test_deadline_in_queue_times_out_and_frees_slot():
    door, clock, _ = fake_door(deadline_s=0.5, batch_window_s=10.0,
                               max_lanes=8)
    futs = [door.submit(np.float64([i, 0.0])) for i in range(2)]
    assert door.stats()["queued_lanes"] == 2
    clock.advance(0.5)
    assert [f.result(timeout=0).status for f in futs] == [server.TIMEOUT] * 2
    stats = door.stats()
    assert stats["queued_lanes"] == 0 and stats["open_lanes"] == 0
    # The queue slot is free again: a later submit (with a per-request
    # deadline outlasting the batch window) is served normally.
    f = door.submit(np.float64([5.0, 0.0]), deadline_s=20.0)
    clock.advance(10.0)
    assert f.result(timeout=0).status == server.OK


def test_wedged_dispatch_without_probe_times_out():
    """Total wedge (service and probe never arrive): every in-flight lane
    completes as timeout at its deadline — no future is ever left hanging."""
    door, clock, _ = fake_door(deadline_s=1.0, max_lanes=2,
                               service_time=math.inf, probe_time=math.inf)
    futs = [door.submit(np.float64([i, 0.0])) for i in range(4)]
    clock.advance(1.0)
    assert all(f.result(timeout=0).status == server.TIMEOUT for f in futs)
    assert door.stats()["open_lanes"] == 0


def test_overload_sheds_at_bound_and_hedges_reopen_admission():
    """A wedged backend fills the open-lane bound: later submits shed
    (an explicit response), the bound is never exceeded, and once deadline
    hedges complete the stuck lanes admission reopens."""
    door, clock, _ = fake_door(deadline_s=1.0, max_lanes=2, max_queue=6,
                               service_time=math.inf, probe_time=math.inf)
    futs = [door.submit(np.float64([i, 0.0])) for i in range(15)]
    stats = door.stats()
    assert stats["shed"] == 9 and stats["max_open_lanes"] == 6
    shed_notes = [f.result(timeout=0) for f in futs if f.done()]
    assert len(shed_notes) == 9
    assert all("queue full" in r.note for r in shed_notes)
    clock.advance(1.0)                    # hedges complete the stuck lanes
    assert all(f.done() for f in futs)
    stats = door.stats()
    assert stats["timeout"] == 6 and stats["open_lanes"] == 0
    f = door.submit(np.float64([99.0, 0.0]))   # admission reopened
    assert not f.done() or f.result(timeout=0).status != server.SHED
    clock.advance(2.0)
    assert f.result(timeout=0).status == server.TIMEOUT  # still wedged
    assert door.stats()["max_open_lanes"] <= 6


def test_dispatch_error_surfaces_as_error_status():
    door, clock, _ = fake_door(eng=FakeEngine(fail_finish=True), max_lanes=2)
    futs = [door.submit(np.float64([i, 0.0])) for i in range(2)]
    clock.advance(0.1)
    for f in futs:
        res = f.result(timeout=0)
        assert res.status == server.ERROR
        assert "injected finish failure" in res.note
    assert door.stats()["error"] == 2
    assert door.stats()["open_lanes"] == 0


class PartialFailEngine(FakeEngine):
    """Offers deadline hedges whose partial computation raises."""

    supports_partial = True

    def partial_result(self, flight):
        raise RuntimeError("injected partial failure")


def test_failed_partial_is_counted_and_named():
    """A deadline hedge that raises is never swallowed: the lane times out
    with the exception in its note and stats() counts it."""
    door, clock, _ = fake_door(eng=PartialFailEngine(), deadline_s=1.0,
                               max_lanes=2, service_time=math.inf)
    futs = [door.submit(np.float64([i, 0.0])) for i in range(2)]
    clock.advance(1.0)
    for f in futs:
        res = f.result(timeout=0)
        assert res.status == server.TIMEOUT
        assert "injected partial failure" in res.note
    assert door.stats()["partial_errors"] == 1      # one hedge per dispatch


def test_serve_launcher_exit_check_on_failing_backend():
    """The serving launcher's exit check: a failing backend's error
    statuses and failed hedges fail the run, a healthy run and shed/timeout
    outcomes do not, and so does any out-of-filter result."""
    from repro.launch.serve import run_failures

    failing, clock, _ = fake_door(eng=FakeEngine(fail_finish=True),
                                  max_lanes=2)
    for i in range(3):
        failing.submit(np.float64([i, 0.0]))
    server.drain_virtual(failing, clock)
    reasons = run_failures(failing.stats())
    assert len(reasons) == 1 and "3 front-door request(s)" in reasons[0]

    hedged, clock, _ = fake_door(eng=PartialFailEngine(), deadline_s=1.0,
                                 max_lanes=2, service_time=math.inf)
    hedged.submit(np.float64([0.0, 0.0]))
    hedged.submit(np.float64([1.0, 0.0]))
    clock.advance(1.0)
    assert run_failures(hedged.stats()) == ["1 deadline hedge(s) raised"]

    healthy, clock, _ = fake_door(max_lanes=2, max_queue=2,
                                  service_time=math.inf, probe_time=math.inf,
                                  deadline_s=1.0)
    for i in range(4):                    # two admitted (timeout), two shed
        healthy.submit(np.float64([i, 0.0]))
    clock.advance(1.0)
    st = healthy.stats()
    assert st["shed"] == 2 and st["timeout"] == 2
    assert run_failures(st) == []
    assert run_failures(st, out_of_filter=0) == []
    assert run_failures(out_of_filter=3) == ["3 result(s) outside their filter"]


# ----------------------------------------------------- shutdown / lifecycle


def test_drain_serves_pending_and_closes_shared_engine_once():
    """close(): pending lanes are flushed and served, later submits shed,
    an engine shared by two classes closes exactly once, and close is
    idempotent."""
    eng = FakeEngine()
    clock = server.VirtualClock()
    door = server.FrontDoor(
        {"a": eng, "b": eng},
        [server.QoSClass("a", deadline_s=100.0, batch_window_s=50.0,
                         max_lanes=8),
         server.QoSClass("b", deadline_s=100.0, batch_window_s=50.0,
                         max_lanes=8)],
        clock=clock, dispatcher=server.VirtualDispatcher(clock))
    futs = [door.submit(np.float64([i, 0.0]), cls="a") for i in range(3)]
    futs += [door.submit(np.float64([9.0, 0.0]), cls="b")]
    assert not any(f.done() for f in futs)      # parked behind the window
    server.drain_virtual(door, clock)
    assert door.drained
    assert all(f.result(timeout=0).status == server.OK for f in futs)
    assert eng.close_calls == 1                 # shared engine: exactly once
    shed = door.submit(np.float64([0.0, 0.0]), cls="a")
    res = shed.result(timeout=0)
    assert res.status == server.SHED and "closing" in res.note
    door.close(wait=False)                      # idempotent
    assert eng.close_calls == 1
    stats = door.stats()
    assert stats["ok"] == 4 and stats["shed"] == 1
    assert stats["admitted"] == stats["ok"]


def test_drain_completes_wedged_lanes_via_deadlines():
    """Shutdown with a wedged backend: drain completes every admitted lane
    through its deadline timer, then tears down."""
    door, clock, eng = fake_door(deadline_s=2.0, max_lanes=2,
                                 service_time=math.inf, probe_time=math.inf)
    futs = [door.submit(np.float64([i, 0.0])) for i in range(4)]
    server.drain_virtual(door, clock)
    assert door.drained
    assert all(f.result(timeout=0).status == server.TIMEOUT for f in futs)
    assert eng.close_calls == 1


def test_engine_close_idempotent_and_safe_with_inflight_stream():
    """SearchEngine.close() concurrent with an in-flight ``search_batches``
    stream over a fresh disk tier: the stream completes bit-identically
    (reads degrade to synchronous after close) and double-close is a no-op.
    Synchronised with events only — no sleeps."""
    from tests._backend_fixtures import built_disk_tier

    from repro.index import BlockSlowTier, BlockStore

    _x, q, _gt, _idx, tiered = built()
    tier = BlockSlowTier(BlockStore(built_disk_tier().store.path),
                         cache_nodes=256)
    eng = SearchEngine(TieredBackend(tiered, slow_tier=tier), BUDGET, k=10)
    batches = [q[:8], q[8:20], q[20:32]]
    ref = [eng.search(b) for b in batches]

    first_done = threading.Event()
    closed = threading.Event()
    out = []

    def stream():
        yield batches[0]
        first_done.set()
        assert closed.wait(60), "close() never signalled"
        yield batches[1]
        yield batches[2]

    t = threading.Thread(
        target=lambda: out.extend(eng.search_batches(stream())))
    t.start()
    assert first_done.wait(60)
    eng.close()            # concurrent with the in-flight stream
    eng.close()            # idempotent
    closed.set()
    t.join(timeout=120)
    assert not t.is_alive()
    assert len(out) == 3
    for got, want in zip(out, ref):
        np.testing.assert_array_equal(got.ids, want.ids)
        np.testing.assert_array_equal(got.d2, want.d2)


# ------------------------------------------------ determinism / QoS classes


def _replay_run(seed: int):
    """One randomized front-door scenario; returns a serializable trace."""
    q, _ids, _d2 = ref_rows()
    rng = np.random.default_rng(seed)
    eng = engine("exact")
    clock = server.VirtualClock()
    door = server.FrontDoor(
        {"a": eng, "b": eng},
        [server.QoSClass("a", deadline_s=0.25, batch_window_s=0.02,
                         max_lanes=3),
         server.QoSClass("b", deadline_s=5.0, batch_window_s=0.1,
                         max_lanes=5)],
        max_queue=8, clock=clock,
        dispatcher=server.VirtualDispatcher(clock, service_time=0.3,
                                            probe_time=0.01))
    futs = []
    for _ in range(12):
        r = int(rng.integers(0, q.shape[0]))
        cls = "a" if rng.random() < 0.5 else "b"
        futs.append(door.submit(q[r], cls=cls))
        clock.advance(float(rng.choice([0.0, 0.01, 0.15])))
    clock.advance(30.0)
    trace = []
    for f in futs:
        res = f.result(timeout=0)
        trace.append((res.status, res.qos, round(res.latency, 9),
                      None if res.ids is None else res.ids.tobytes()))
    return trace, door.stats()


def test_identical_runs_replay_bit_exactly():
    """The whole interleaving — statuses, latencies, result bytes, counters
    — replays bit-exactly under the virtual clock."""
    t1, s1 = _replay_run(1234)
    t2, s2 = _replay_run(1234)
    assert t1 == t2 and s1 == s2
    statuses = {s for s, _, _, _ in t1}
    assert server.OK in statuses       # the mix actually exercises serving


def test_per_class_budget_laws_diverge_over_shared_backend():
    """Two QoS classes with their own (lam, l_min) engines over one shared
    backend: the thorough class is granted strictly more budget for the
    same queries — the per-class I/O split the front door exists for."""
    q, _ids, _d2 = ref_rows()
    eng_i = engine("exact")                      # BUDGET: l_min=8
    eng_b = SearchEngine(eng_i.backend,
                         dataclasses.replace(BUDGET, l_min=BUDGET.l_max),
                         k=10)
    clock = server.VirtualClock()
    door = server.FrontDoor(
        {"interactive": eng_i, "batch": eng_b},
        [server.QoSClass("interactive", deadline_s=1e6, max_lanes=8),
         server.QoSClass("batch", deadline_s=1e6, max_lanes=8)],
        clock=clock, dispatcher=server.VirtualDispatcher(clock))
    fi = [door.submit(q[i], cls="interactive") for i in range(8)]
    fb = [door.submit(q[i], cls="batch") for i in range(8)]
    clock.advance(1.0)
    bud_i = [f.result(timeout=0).budget for f in fi]
    bud_b = [f.result(timeout=0).budget for f in fb]
    assert all(b is not None for b in bud_i + bud_b)
    assert np.mean(bud_b) > np.mean(bud_i)
    assert max(bud_i) <= BUDGET.l_max and min(bud_b) == BUDGET.l_max


def test_calibrate_budget_law_per_class():
    """Per-class law fitting: each class meets its own target, a looser
    target fits a higher lam (more I/O savings), and ``class_budget_cfgs``
    deploys one budget config per class."""
    from repro.core import calibrate

    def make_eval(cfg):
        # Synthetic monotone recall response: decreasing in lam, increasing
        # in the floor (the direction the real law has).
        def eval_recall(c):
            return min(1.0, 1.0 - 0.5 * c.lam + 0.001 * c.l_min)
        return eval_recall

    results = calibrate.calibrate_budget_law_per_class(
        make_eval, BUDGET, {"interactive": 0.7, "batch": 0.95}, joint=False)
    assert set(results) == {"interactive", "batch"}
    assert all(r.achieved for r in results.values())
    assert results["interactive"].lam > results["batch"].lam
    cfgs = calibrate.class_budget_cfgs(results, BUDGET)
    assert set(cfgs) == {"interactive", "batch"}
    for name, cfg in cfgs.items():
        assert cfg.lam == results[name].lam
        assert cfg.l_max == BUDGET.l_max
