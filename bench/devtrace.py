"""Reduction of a profiler trace to device metrics.

Two steps, so that every PR computes the same numbers the same way:

1. :func:`read_xplane` turns the ``.xplane.pb`` the JAX profiler writes
   into a small plain record: for every device, its operations as
   ``[name, start_ns, duration_ns]``; and the benchmark's own host spans
   (names starting ``bench.``), on the same clock.
2. :func:`reduce` takes that record to the window's device busy time,
   the time of named kernels, the operations that took most time, and
   the idle time by the innermost host span that was open during it.

The record of a short window on the chip is kept in ``bench/tests`` and
checked there.
"""
from __future__ import annotations

import bisect
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_SUFFIX = re.compile(r"[.:]\d+$")


def read_xplane(path: str) -> dict:
    """``{"devices": {plane: [[op, start_ns, dur_ns], ...]},
    "spans": [[name, start_ns, dur_ns], ...]}`` from one trace file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: dict[str, list] = {}
    spans: list = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend([e.name, e.start_ns, e.duration_ns]
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend([e.name, e.start_ns, e.duration_ns]
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "spans": spans}


def _union(intervals: list[tuple[float, float]]) -> list[list[float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _segments(spans) -> tuple[list[float], list[str]]:
    """The window cut at every span boundary: ``(starts, names)``, where
    ``names[k]`` is the shortest benchmark span (other than the window)
    open from ``starts[k]`` to ``starts[k + 1]``, or "other"."""
    cuts = sorted({t for _, s, d in spans for t in (s, s + d)})
    starts, names = [], []
    events = sorted((s, d, name) for name, s, d in spans
                    if name != WINDOW_SPAN)
    active: list[tuple[float, float, str]] = []
    k = 0
    for t in cuts:
        while k < len(events) and events[k][0] <= t:
            s, d, name = events[k]
            active.append((d, s + d, name))
            k += 1
        active = [a for a in active if a[1] > t]
        starts.append(t)
        names.append(min(active)[2] if active else "other")
    return starts, names


def op_family(name: str) -> str:
    """An operation's instruction name without the numeric suffix XLA
    gives each instance. The TPU trace names an operation by its HLO
    text: ``%fusion.12 = f32[64]{0} fusion(...)`` -> ``fusion``."""
    return _SUFFIX.sub("", name.split(" = ", 1)[0].lstrip("%"))


def _self_times(ops) -> list[float]:
    """Each operation's duration less that of the operations nested in
    it on the same line (a ``while`` holds its body's operations)."""
    order = sorted(range(len(ops)), key=lambda k: (ops[k][0], -ops[k][1]))
    own = [e - s for s, e in ops]
    stack: list[int] = []
    for k in order:
        s, e = ops[k]
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(k)
    return own


def reduce(record: dict, top: int = 10) -> dict:
    """Device metrics of the window that the ``bench.window`` span marks.

    Returns ``window_s``, ``n_devices``, and averaged over the devices:
    ``busy_s``, the time of the union of the operations; ``op_s``, each
    operation family's self time (nested operations taken out);
    ``device_ops`` and ``idle_gaps``, the
    ``top`` families and the idle time by host span, as lists of
    ``[name, seconds]``, most first."""
    windows = [(s, s + d) for n, s, d in record["spans"]
               if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found"
                         f" {len(windows)}")
    w0, w1 = windows[0]
    spans = [sp for sp in record["spans"]
             if sp[1] < w1 and sp[1] + sp[2] > w0]
    devices = record["devices"]
    if not devices:
        raise ValueError("the trace holds no device")
    seg_starts, seg_names = _segments(spans)
    busy = 0.0
    by_op: dict[str, float] = {}
    idle: dict[str, float] = {}
    for ops in devices.values():
        clipped, names = [], []
        for name, s, d in ops:
            s, e = max(s, w0), min(s + d, w1)
            if e > s:
                clipped.append((s, e))
                names.append(name)
        for name, t in zip(names, _self_times(clipped)):
            fam = op_family(name)
            by_op[fam] = by_op.get(fam, 0.0) + t
        merged = _union(clipped)
        busy += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge > gs:
                k = bisect.bisect_right(seg_starts, 0.5 * (gs + ge)) - 1
                who = seg_names[k] if k >= 0 else "other"
                idle[who] = idle.get(who, 0.0) + (ge - gs)
    nd = len(devices)

    def ranked(d: dict) -> list:
        return [[k, v / nd / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"window_s": (w1 - w0) / 1e9, "busy_s": busy / nd / 1e9,
            "n_devices": nd,
            "op_s": {k: v / nd / 1e9 for k, v in by_op.items()},
            "device_ops": ranked(by_op), "idle_gaps": ranked(idle)}


def op_seconds(reduced: dict, prefixes: tuple[str, ...]) -> float:
    """Device time of the operation families whose names start with one
    of ``prefixes`` (a kernel's events carry its name)."""
    return sum(v for k, v in reduced["op_s"].items()
               if k.startswith(prefixes))
