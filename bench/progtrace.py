"""The program's own spans and counters in a traced run.

:mod:`devtrace` reduces the benchmark's spans (``bench.``). The program
opens spans of its own (``mgk.``, ``repro/obs.py``) on the same clock,
tags the lowrank XMV's device operations with the named scope
``xmv_lowrank``, and returns what its counters gained in each build
(``GramDriver.health["counters"]``). This module reads those:

1. :func:`read_xplane`: like ``devtrace.read_xplane``, with the spans of
   both prefixes and, per device operation, whether its HLO instruction
   runs in the scope ``xmv_lowrank`` (the trace keeps the compiled
   modules): ``[start_ns, duration_ns, scoped]``.
2. :func:`reduce`: the window's device busy time, each span's self time
   (the time it was the innermost span open), the device idle time by
   innermost span, each gap split where spans begin and end, and the
   device self time of the scoped operations.
3. :func:`of_run` and :func:`counters`: the reduction of a run's traced
   window and the counters its builds gained; None where the run left
   no trace or the program kept no counters (a program without them).
"""
from __future__ import annotations

import bisect
import functools
import glob
import os
import re

import devtrace

PREFIXES = ("bench.", "mgk.")
SCOPE = "xmv_lowrank"
_IN_SCOPE = re.compile(rf"(?:^|[/(]){SCOPE}(?:[/)]|$)")
METADATA_PLANE = "/host:metadata"


def read_xplane(path: str) -> dict:
    """``{"devices": {plane: [[start_ns, dur_ns, scoped], ...]},
    "spans": [[name, start_ns, dur_ns], ...]}`` from one trace file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    scoped = scoped_instructions(path)
    devices: dict[str, list] = {}
    spans: list = []
    for plane in pd.planes:
        if devtrace.DEVICE_PLANE.match(plane.name):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == devtrace.OPS_LINE:
                    ops.extend([e.start_ns, e.duration_ns,
                                int(instruction(e.name) in scoped)]
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend([e.name, e.start_ns, e.duration_ns]
                             for e in line.events
                             if e.name.startswith(PREFIXES))
    return {"devices": devices, "spans": spans}


def instruction(op: str) -> str:
    """The HLO instruction an operation event runs: the trace names it
    by its HLO text, ``%fusion.12 = f32[64]{0} fusion(...)``."""
    return op.split(" = ", 1)[0].lstrip("%")


def scoped_instructions(path: str) -> set[str]:
    """Names of the HLO instructions whose op name holds the scope
    ``xmv_lowrank`` (``.../vmap(xmv_lowrank)/dot_general``), from the
    compiled modules the profiler keeps in the trace's metadata plane.
    The device's operation events carry no op name of their own.

    Protobuf fields read: XSpace.planes 1; XPlane.name 2,
    .event_metadata 4 (map entry value 2); XEventMetadata.stats 5;
    XStat.bytes_value 6 (an HloProto); HloProto.hlo_module 1;
    HloModuleProto.computations 3; HloComputationProto.instructions 2;
    HloInstructionProto.name 1, .metadata 7; OpMetadata.op_name 2."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: set[str] = set()
    for plane in _values(space, 1):
        if bytes(next(_values(plane, 2), b"")) != METADATA_PLANE.encode():
            continue
        for entry in _values(plane, 4):
            for meta in _values(entry, 2):
                for stat in _values(meta, 5):
                    for proto in _values(stat, 6):
                        out |= _scoped_in_module(proto)
    return out


def _scoped_in_module(hlo_proto) -> set[str]:
    out = set()
    for module in _values(hlo_proto, 1):
        for comp in _values(module, 3):
            for inst in _values(comp, 2):
                for meta in _values(inst, 7):
                    op_name = bytes(next(_values(meta, 2), b"")).decode()
                    if _IN_SCOPE.search(op_name):
                        out.add(bytes(next(_values(inst, 1))).decode())
    return out


def _values(msg, field: int):
    """The length-delimited values of ``field`` in a serialized protobuf
    message, in order; other fields are skipped."""
    i, end = 0, len(msg)
    while i < end:
        tag, i = _varint(msg, i)
        wire = tag & 7
        if wire == 0:
            _, i = _varint(msg, i)
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        elif wire == 2:
            n, i = _varint(msg, i)
            if tag >> 3 == field:
                yield msg[i:i + n]
            i += n
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")


def _varint(msg, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        b = msg[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def reduce(record: dict) -> dict:
    """``window_s``, ``busy_s`` and ``scope_s`` (device time, averaged
    over the devices), ``span_s`` (each span name's self time in the
    window) and ``idle`` (device idle time by innermost span, averaged
    over the devices), in seconds."""
    (w0, w1), = [(s, s + d) for n, s, d in record["spans"]
                 if n == devtrace.WINDOW_SPAN]
    spans = [sp for sp in record["spans"]
             if sp[1] < w1 and sp[1] + sp[2] > w0]
    starts, names = devtrace._segments(spans)
    span_s: dict[str, float] = {}
    for name, t in _split(w0, w1, starts, names):
        span_s[name] = span_s.get(name, 0.0) + t
    busy = scope = 0.0
    idle: dict[str, float] = {}
    for ops in record["devices"].values():
        clipped, flags = [], []
        for s, d, scoped in ops:
            s, e = max(s, w0), min(s + d, w1)
            if e > s:
                clipped.append((s, e))
                flags.append(scoped)
        scope += sum(t for t, f in zip(devtrace._self_times(clipped), flags)
                     if f)
        merged = devtrace._union(clipped)
        busy += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for gs, ge in zip(edges[::2], edges[1::2]):
            for name, t in _split(gs, ge, starts, names):
                idle[name] = idle.get(name, 0.0) + t
    nd = len(record["devices"]) or 1
    return {"window_s": (w1 - w0) / 1e9, "busy_s": busy / nd / 1e9,
            "scope_s": scope / nd / 1e9,
            "span_s": {k: v / 1e9 for k, v in span_s.items()},
            "idle": {k: v / nd / 1e9 for k, v in idle.items()}}


def _split(t0, t1, starts, names):
    """``(name, duration)`` of each piece of ``[t0, t1]`` between two
    span boundaries, named by the innermost span open there (the
    segments of ``devtrace._segments``)."""
    k = bisect.bisect_right(starts, t0) - 1
    while t0 < t1:
        end = min(t1, starts[k + 1]) if k + 1 < len(starts) else t1
        yield (names[k] if k >= 0 else "other"), end - t0
        t0, k = end, k + 1


@functools.cache
def _reduce_file(path: str) -> dict:
    return reduce(read_xplane(path))


def of_run(run) -> dict | None:
    """The reduction of ``run``'s traced window: the harness writes the
    trace beside the window's stores, under ``trace/``."""
    if not run.window.builds:
        return None
    work_dir = os.path.dirname(run.window.builds[0][0])
    paths = glob.glob(os.path.join(work_dir, "trace", "**", "*.xplane.pb"),
                      recursive=True)
    return _reduce_file(paths[0]) if len(paths) == 1 else None


def counters(run) -> dict | None:
    """What the program's counters gained over the window's builds."""
    per_build = [h["counters"] for _, h in run.window.builds
                 if "counters" in h]
    if not per_build:
        return None
    total: dict[str, int] = {}
    for c in per_build:
        for k, v in c.items():
            total[k] = total.get(k, 0) + v
    return total


def self_ms_per_block(run, names: tuple[str, ...]) -> float | None:
    """Self time of the spans ``names`` per block of the window, in ms;
    None where none of them was open in the window."""
    r = of_run(run)
    if r is None or not any(n in r["span_s"] for n in names):
        return None
    return 1000.0 * sum(r["span_s"].get(n, 0.0) for n in names) \
        / run.window.blocks
