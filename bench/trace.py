"""Profiler capture of the measured window, and its reduction to numbers.

:class:`Capture` wraps ``jax.profiler`` around the window of a ``--trace 1``
run.  :func:`reduce` reads the ``.xplane.pb`` it wrote with nothing but JAX
and returns:

* ``window_s`` — the length of the benchmark's ``bench.window`` host span;
* ``busy_s`` — the union of the device's op intervals inside it, averaged
  over the devices traced;
* ``kernel_s`` — summed device time per kernel name (``beam_step``,
  ``pq_scan``, ``topk``): ops whose instruction name is the kernel's;
* ``device_ops`` — the ten op kinds (instruction names without their
  numeric suffix: ``beam_step``, ``fusion``, ``while``) with the most
  device self time, i.e. time not covered by ops nested inside them (a
  ``while`` op spans the ops of its body);
* ``idle_gaps`` — device idle time inside the window, summed by the
  innermost benchmark host span (``bench.*`` / ``engine.*``,
  ``TraceAnnotation``) that covers most of each gap; ``host.unattributed``
  where none does.
"""
from __future__ import annotations

import glob
import os
import pathlib
import re
import shutil

KERNELS = ("beam_step", "pq_scan", "topk")
WINDOW_SPAN = "bench.window"
SPAN_PREFIXES = ("bench.", "engine.")
OP_LINE = "XLA Ops"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


class Capture:
    """``with Capture(dir):`` traces everything inside to ``dir``."""

    def __init__(self, out_dir: pathlib.Path):
        self.out_dir = pathlib.Path(out_dir)

    def __enter__(self):
        import jax

        shutil.rmtree(self.out_dir, ignore_errors=True)
        self.out_dir.mkdir(parents=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # host spans only: TraceAnnotation
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(self.out_dir), profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax

        jax.profiler.stop_trace()

    def xplane(self) -> pathlib.Path:
        found = glob.glob(os.path.join(self.out_dir, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {self.out_dir}")
        return pathlib.Path(sorted(found)[-1])


def union_length(intervals) -> float:
    """Total length covered by (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def merged(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def op_kind(name: str) -> str:
    """The op kind of a device event: the event name is the HLO text
    (``%beam_step.1 = (...) custom-call(...)``); ``beam_step.1`` and
    ``beam_step.6`` are one kind."""
    if name.startswith("%"):
        name = name[1:]
    name = name.split(" = ", 1)[0].split(" ", 1)[0]
    return re.sub(r"(\.\d+)+$", "", name) or name


def self_times(ops) -> list:
    """Per op, its duration less that of the ops nested directly inside
    it on the same line; ``ops`` are (name, start, end)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [e - s for _, s, e in ops]
    stack: list = []
    for i in order:
        _, s, e = ops[i]
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][2]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


def gaps_by_span(busy, spans, lo: float, hi: float) -> dict:
    """{span name: idle seconds} over the idle gaps of ``busy`` in
    [lo, hi]; each gap goes to the span covering most of it (the shorter
    span on a tie), or to ``host.unattributed``."""
    out: dict = {}
    edges = [lo] + [x for s, e in merged(busy) for x in (s, e)] + [hi]
    spans = sorted(spans, key=lambda t: t[1])
    nxt, active = 0, []
    for g_s, g_e in zip(edges[0::2], edges[1::2]):
        if g_e <= g_s:
            continue
        while nxt < len(spans) and spans[nxt][1] < g_e:
            active.append(spans[nxt])
            nxt += 1
        active = [sp for sp in active if sp[2] > g_s]
        best, best_cov, best_len = "host.unattributed", 0.0, float("inf")
        for name, s, e in active:
            cov = min(e, g_e) - max(s, g_s)
            if cov <= 0:
                continue
            if cov > best_cov or (cov == best_cov and e - s < best_len):
                best, best_cov, best_len = name, cov, e - s
        out[best] = out.get(best, 0.0) + (g_e - g_s) * 1e-9
    return out


def reduce_events(device_ops: dict, host_spans: list) -> dict:
    """The reduction proper, on plain data (tested without a chip).

    ``device_ops``: {device name: [(op name, start_ns, end_ns)]};
    ``host_spans``: [(span name, start_ns, end_ns)] in the same timebase.
    """
    windows = [(s, e) for n, s, e in host_spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN!r} host span in the trace")
    lo, hi = windows[0]
    spans = [(n, s, e) for n, s, e in host_spans
             if n != WINDOW_SPAN and n.startswith(SPAN_PREFIXES)]
    busy_total, kernel_ns, op_ns, gaps = 0.0, {}, {}, {}
    devices = sorted(device_ops)
    for dev in devices:
        ops = [(n, s, e) for n, s, e in device_ops[dev] if e > lo and s < hi]
        busy = _clip([(s, e) for _, s, e in ops], lo, hi)
        busy_total += union_length(busy)
        for (name, s, e), own in zip(ops, self_times(ops)):
            dur = min(e, hi) - max(s, lo)
            kind = op_kind(name)
            op_ns[kind] = op_ns.get(kind, 0.0) + own * dur / max(e - s, 1e-9)
            if kind in KERNELS:
                kernel_ns[kind] = kernel_ns.get(kind, 0.0) + dur
        for name, sec in gaps_by_span(busy, spans, lo, hi).items():
            gaps[name] = gaps.get(name, 0.0) + sec
    n_dev = max(1, len(devices))
    top_ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_total * 1e-9 / n_dev,
        "devices": len(devices),
        "kernel_s": {k: v * 1e-9 / n_dev for k, v in kernel_ns.items()},
        "device_ops": [[k, v * 1e-9 / n_dev] for k, v in top_ops],
        "idle_gaps": [[k, v / n_dev] for k, v in top_gaps],
    }


def read_xplane(path) -> tuple[dict, list]:
    """(device_ops, host_spans) from an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    device_ops: dict = {}
    host_spans: list = []
    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            ops = device_ops.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != OP_LINE:
                    continue
                for ev in line.events:
                    s = float(ev.start_ns)
                    ops.append((ev.name, s, s + float(ev.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIXES):
                        s = float(ev.start_ns)
                        host_spans.append(
                            (ev.name, s, s + float(ev.duration_ns)))
    return device_ops, host_spans


def reduce(path) -> dict:
    device_ops, host_spans = read_xplane(path)
    if not device_ops:
        raise ValueError(f"{path}: no TPU device plane in the trace")
    return reduce_events(device_ops, host_spans)
