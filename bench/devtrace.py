"""Reduction from a profiler trace to device busy time, op and module time.

``load(path)`` reads an ``.xplane.pb`` into plain event records; ``reduce``
turns the records of one traced window into the numbers the per-layer
metrics read.  Tests run ``reduce`` on a small recorded trace
(``tests/bench/data``), so every PR computes these numbers the same way.

An event record is ``[plane, line, name, start_ns, dur_ns]``.  Device
planes are named ``/device:TPU:<i>``; on each, the ``XLA Ops`` line holds one
event per executed operation and the ``XLA Modules`` line one per program
run (named after the jitted function).  Host planes hold the benchmark's
``TraceAnnotation`` spans and the runtime's own.
"""

from __future__ import annotations

import collections
import re

DEVICE = re.compile(r"^/device:TPU:\d+\b")
OPS, MODULES = "XLA Ops", "XLA Modules"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute")
WINDOW = "bench_window"


def load(path: str) -> list[list]:
    """Event records of the device planes and of the host threads."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        dev = bool(DEVICE.match(plane.name))
        if not (dev or plane.name.startswith("/host:")):
            continue
        for line in plane.lines:
            if dev and line.name not in (OPS, MODULES):
                continue
            for ev in line.events:
                out.append([plane.name, line.name, ev.name,
                            int(ev.start_ns), int(ev.duration_ns)])
    return out


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(s, e, w0, w1):
    return max(s, w0), min(e, w1)


def _attribute(gaps, host, short_ns: int = 10_000):
    """Idle seconds by the innermost host span open at each gap's middle.

    Gaps under ``short_ns`` are the launch gaps between consecutive ops and
    are counted together."""
    idle = collections.Counter()
    marks = []
    for i, (s, e, n) in enumerate(host):
        marks += [(s, 0, i), (e, 2, i)]
    for gs, ge in gaps:
        if ge - gs < short_ns:
            idle["between ops (< 10 us)"] += (ge - gs) * 1e-9
        else:
            marks.append(((gs + ge) // 2, 1, ge - gs))
    active = {}
    for t, kind, x in sorted(marks):
        if kind == 0:
            active[x] = host[x]
        elif kind == 2:
            active.pop(x, None)
        else:
            inner = min(active.values(), key=lambda h: h[1] - h[0],
                        default=None)
            idle[inner[2] if inner else "no host span"] += x * 1e-9
    return idle


def op_name(name: str) -> str:
    """An HLO op event's name up to its result's shape, without layouts:
    ``%fusion.49 = f32[28311600,5]``."""
    return name.split("{")[0].strip()


def window_of(events) -> tuple[int, int]:
    """The span of the host ``bench_window`` annotation(s)."""
    spans = [(s, s + d) for p, l, n, s, d in events
             if not DEVICE.match(p) and n == WINDOW]
    if not spans:
        raise ValueError("trace holds no bench_window span")
    return min(s for s, _ in spans), max(e for _, e in spans)


def reduce(events, window=None, top: int = 10) -> dict:
    """Busy and idle time, per-op and per-module device time, collectives.

    Times are seconds, averaged over the device planes that ran anything in
    the window.  ``idle_gaps`` attributes each gap between device ops to
    the innermost host span open at its middle.
    """
    w0, w1 = window if window is not None else window_of(events)
    per_dev = collections.defaultdict(list)
    ops, modules = collections.Counter(), collections.Counter()
    coll = 0.0
    for p, l, n, s, d in events:
        if not DEVICE.match(p):
            continue
        a, b = _clip(s, s + d, w0, w1)
        if b <= a:
            continue
        if l == MODULES:
            modules[n.split("(")[0]] += (b - a) * 1e-9
            continue
        per_dev[p].append((a, b))
        ops[op_name(n)] += (b - a) * 1e-9
        if COLLECTIVE.search(n):
            coll += (b - a) * 1e-9
    if not per_dev:
        raise ValueError("no device op ran inside the traced window")
    n_dev = len(per_dev)
    busy, gaps = 0.0, []
    for ivs in per_dev.values():
        merged = _union(ivs)
        busy += sum(e - s for s, e in merged) * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    idle = _attribute(gaps, [(s, s + d, n) for p, l, n, s, d in events
                             if not DEVICE.match(p) and n != WINDOW])
    scale = 1.0 / n_dev
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy * scale,
        "devices": n_dev,
        "collective_s": coll * scale,
        "modules": {k: v * scale for k, v in modules.items()},
        "device_ops": [[k, v * scale] for k, v in ops.most_common(top)],
        "idle_gaps": [[k, v * scale] for k, v in idle.most_common(top)],
    }
