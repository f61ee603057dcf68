"""The serving engine's own host spans, read from a ``--trace 1`` run.

The program runs every stage of a batch inside a ``jax.profiler``
``TraceAnnotation`` named ``engine.<stage>[.<sub-stage>]`` that carries the
batch's sequence number as its ``batch`` stat (the launch span also carries
``buckets``, ``lanes`` and ``padded_lanes``).  :func:`load` parses the
``.xplane.pb`` that :mod:`bench.run` writes under :data:`TRACE_DIR` once,
keeps

* the device's busy intervals (the TPU ``XLA Ops`` line),
* the host ``engine.*`` events that carry a ``batch`` stat — the
  benchmark's own stat-less wrappers of the same names are left out, so no
  stage is counted twice —
* JAX's compile events (``lower_sharding_computation``,
  ``backend_compile_and_load``),

inside the ``bench.window`` span, and reduces them to

* ``per_batch_ms`` — per span name, the mean over batches of the host ms
  the batch spent in it;
* ``padded_pct`` — 100 × (Σ ``padded_lanes`` ÷ Σ ``lanes`` − 1) over the
  launch spans; ``buckets_per_batch`` — the mean ``buckets``;
* ``idle_split`` — every device idle gap cut at span boundaries, each
  piece given to the innermost (shortest) span or compile event covering
  it, else to ``outside_engine``; the pieces sum to the window's idle time.

The reduction is printed once on standard error as one table.  A trace
without such spans (a program that does not emit them) reduces to
``None``, and the readers then report nothing.
"""
from __future__ import annotations

import functools
import os
import re
import sys
import time

from bench import spec
from bench import trace as trace_mod

TRACE_DIR = spec.BENCH_DIR / "cache" / "trace"
ENGINE_PREFIX = "engine."
BATCH_STAT = "batch"
COMPILE_EVENTS = ("lower_sharding_computation", "backend_compile_and_load")
OUTSIDE = "outside_engine"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def _clip_merge(intervals, lo: float, hi: float) -> list:
    return trace_mod.merged([(max(s, lo), min(e, hi)) for s, e in intervals
                             if e > lo and s < hi])


def idle_split(busy, spans, lo: float, hi: float) -> dict:
    """{name: idle ns} over the gaps of ``busy`` (merged (start, end)) in
    [lo, hi].  Each gap is cut at the boundaries of ``spans`` ((name, start,
    end)); each piece goes to the shortest span covering it, or to
    :data:`OUTSIDE`."""
    out: dict = {}
    edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
    spans = sorted(spans, key=lambda t: t[1])
    nxt, active = 0, []
    for g_s, g_e in zip(edges[0::2], edges[1::2]):
        if g_e <= g_s:
            continue
        while nxt < len(spans) and spans[nxt][1] < g_e:
            active.append(spans[nxt])
            nxt += 1
        active = [sp for sp in active if sp[2] > g_s]
        cuts = sorted({g_s, g_e} | {x for _, s, e in active for x in (s, e)
                                    if g_s < x < g_e})
        for a, b in zip(cuts, cuts[1:]):
            cover = [sp for sp in active if sp[1] <= a and sp[2] >= b]
            name = (min(cover, key=lambda sp: sp[2] - sp[1])[0] if cover
                    else OUTSIDE)
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def reduce_events(device_busy: dict, host_events: list) -> dict | None:
    """The reduction on plain data (tested without a chip).

    ``device_busy``: {device name: [(start_ns, end_ns)]};
    ``host_events``: [(name, start_ns, end_ns, stats dict)] in the same
    timebase, holding the ``bench.window`` span.  ``None`` when no
    ``engine.*`` event in the window carries a ``batch`` stat.
    """
    windows = [(s, e) for n, s, e, _ in host_events
               if n == trace_mod.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {trace_mod.WINDOW_SPAN!r} host span")
    lo, hi = windows[0]
    engine = [(n, s, e, st) for n, s, e, st in host_events
              if n.startswith(ENGINE_PREFIX) and BATCH_STAT in st
              and lo <= s < hi]
    if not engine:
        return None
    compiles = [(n, s, e) for n, s, e, _ in host_events
                if n in COMPILE_EVENTS and e > lo and s < hi]

    per_batch: dict = {}
    for n, s, e, st in engine:
        b = per_batch.setdefault(n, {})
        b[st[BATCH_STAT]] = b.get(st[BATCH_STAT], 0.0) + (e - s)
    launches = [st for n, _, _, st in engine
                if n == "engine.schedule.launch"]
    lanes = sum(st.get("lanes", 0) for st in launches)
    padded = sum(st.get("padded_lanes", 0) for st in launches)

    spans = [(n, s, e) for n, s, e, _ in engine] + compiles
    split: dict = {}
    idle = 0.0
    devices = sorted(device_busy)
    for dev in devices:
        busy = _clip_merge(device_busy[dev], lo, hi)
        idle += (hi - lo) - sum(e - s for s, e in busy)
        for name, ns in idle_split(busy, spans, lo, hi).items():
            split[name] = split.get(name, 0.0) + ns
    n_dev = max(1, len(devices))
    return {
        "window_s": (hi - lo) * 1e-9,
        "idle_s": idle * 1e-9 / n_dev,
        "batches": len({st[BATCH_STAT] for _, _, _, st in engine}),
        "per_batch_ms": {n: sum(b.values()) / len(b) * 1e-6
                         for n, b in sorted(per_batch.items())},
        "padded_pct": (100.0 * (padded / lanes - 1.0) if lanes else None),
        "buckets_per_batch": (sum(st.get("buckets", 0) for st in launches)
                              / len(launches) if launches else None),
        "compile_events": len(compiles),
        "idle_split": {n: v * 1e-9 / n_dev for n, v in
                       sorted(split.items(), key=lambda kv: -kv[1])},
    }


def read_xplane(path) -> tuple[dict, list]:
    """(device_busy, host_events) from an ``.xplane.pb`` file: only the
    events :func:`reduce_events` reads."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    busy: dict = {}
    host: list = []
    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            ivs = busy.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == trace_mod.OP_LINE:
                    for ev in line.events:
                        s = float(ev.start_ns)
                        ivs.append((s, s + float(ev.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name
                    if not (name.startswith(ENGINE_PREFIX)
                            or name in COMPILE_EVENTS
                            or name == trace_mod.WINDOW_SPAN):
                        continue
                    s = float(ev.start_ns)
                    stats = (dict(ev.stats) if name.startswith(ENGINE_PREFIX)
                             else {})
                    host.append((name, s, s + float(ev.duration_ns), stats))
    return busy, host


def table(r: dict, parse_s: float) -> str:
    """The reduction as one plain-text table."""
    rows = [f"engine spans: {r['batches']} batches, window "
            f"{r['window_s']:.3f}s, device idle {r['idle_s']:.3f}s, "
            f"{r['compile_events']} compile events, buckets per batch "
            f"{r['buckets_per_batch']}, padded lanes {r['padded_pct']}%, "
            f"xplane parsed in {parse_s:.2f}s",
            f"{'span':<28}{'idle s':>10}{'idle %':>8}{'host ms/batch':>15}"]
    names = list(r["idle_split"]) + [n for n in r["per_batch_ms"]
                                     if n not in r["idle_split"]]
    for n in names:
        idle = r["idle_split"].get(n, 0.0)
        ms = r["per_batch_ms"].get(n)
        rows.append(f"{n:<28}{idle:>10.4f}"
                    f"{100 * idle / max(r['idle_s'], 1e-12):>8.1f}"
                    f"{'' if ms is None else f'{ms:.3f}':>15}")
    return "\n".join(rows)


@functools.lru_cache(maxsize=1)
def _load(path: str, mtime_ns: int) -> dict | None:
    t0 = time.perf_counter()
    busy, host = read_xplane(path)
    r = reduce_events(busy, host)
    if r is not None:
        print(table(r, time.perf_counter() - t0), file=sys.stderr,
              flush=True)
    return r


def load(trace_dir=TRACE_DIR) -> dict | None:
    """The reduction of the newest trace under ``trace_dir`` (parsed once
    per file), or ``None`` when there is no trace or no engine span."""
    try:
        path = trace_mod.Capture(trace_dir).xplane()
    except FileNotFoundError:
        return None
    return _load(str(path), os.stat(path).st_mtime_ns)


def mean_ms(rec: dict, name: str) -> float | None:
    """The reader of a ``<stage>_ms.stream`` metric: mean host ms per batch
    in span ``name`` over a traced stream window."""
    if rec["mode"] != "stream":
        return None
    r = load()
    return None if r is None else r["per_batch_ms"].get(name)
