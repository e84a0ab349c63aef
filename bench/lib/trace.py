"""From a profiler trace to the numbers the per-layer metrics read.

:func:`load` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a
small normalised dict, and the functions below reduce that dict.  The
normalised form is what the tests' recorded trace holds:

    {"window": [start_ns, end_ns],
     "device": [[line, name, start_ns, duration_ns], ...],
     "host":   [[name, start_ns, duration_ns], ...]}

``device`` holds the events of the first device's plane, ``host`` the
benchmark's own host spans (names starting with ``bench.``).
"""
from __future__ import annotations

import glob
import os
import re
from typing import Iterable

# the first chip's plane, and its line that holds one event per executed
# operation (asynchronous copies are on a line of their own)
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
_KIND = re.compile(r"\s([a-z][\w.-]*)\(")


def op_name(hlo: str) -> str:
    """``"<instruction> (<opcode>)"`` from the HLO text a TPU trace names an
    operation by; a custom call (a Pallas kernel) keeps its whole text,
    which holds the kernel's name."""
    head, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo
    kind = _KIND.search(rest)
    kind = kind.group(1) if kind else "?"
    if kind == "custom-call":
        return hlo
    return f"{head.lstrip('%')} ({kind})"


def load(trace_dir: str) -> dict:
    """Read the newest ``.xplane.pb`` under ``trace_dir``: the benchmark's
    host spans, and the first chip's events that overlap the window span
    (one clock: the profiler puts device events on the host's)."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    host = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
            for plane in data.planes if not plane.name.startswith("/device:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(SPAN_PREFIX)]
    lo, hi = span_window(host)
    device = []
    dev_planes = sorted((p for p in data.planes
                         if p.name.startswith(DEVICE_PLANE)),
                        key=lambda p: p.name)
    for plane in dev_planes[:1]:
        for line in plane.lines:
            for ev in line.events:
                s, d = int(ev.start_ns), int(ev.duration_ns)
                if s < hi and s + d > lo:
                    device.append([line.name, op_name(ev.name), s, d])
    return {"window": [lo, hi], "device": device, "host": host}


def span_window(spans: Iterable, name: str = "bench.window") -> tuple:
    """(start, end) of the host span that marks the measured window."""
    for n, s, d in spans:
        if n == name:
            return s, s + d
    raise ValueError(f"no {name!r} span in the trace")


def union(intervals: Iterable, lo: int, hi: int) -> list:
    """Merged, clipped ``[start, end]`` intervals."""
    spans = sorted((max(s, lo), min(e, hi)) for s, e in intervals
                   if min(e, hi) > max(s, lo))
    out = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def covered_ns(intervals: Iterable, lo: int, hi: int) -> int:
    return sum(e - s for s, e in union(intervals, lo, hi))


def ops(trace: dict, line: str = OPS_LINE) -> list:
    return [(name, s, d) for ln, name, s, d in trace["device"] if ln == line]


def busy_ns(trace: dict) -> int:
    lo, hi = trace["window"]
    return covered_ns(((s, s + d) for _, s, d in ops(trace)), lo, hi)


def window_ns(trace: dict) -> int:
    lo, hi = trace["window"]
    return hi - lo


def idle_share(trace: dict) -> float:
    return 1.0 - busy_ns(trace) / window_ns(trace)


def top_ops(trace: dict, n: int = 10) -> list:
    """``[[name, seconds], ...]``: the operations with the most device time
    in the window, summed over their events."""
    lo, hi = trace["window"]
    tot: dict = {}
    for name, s, d in ops(trace):
        clipped = min(s + d, hi) - max(s, lo)
        if clipped > 0:
            tot[name] = tot.get(name, 0) + clipped
    ranked = sorted(tot.items(), key=lambda kv: (-kv[1], kv[0]))[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def gaps(trace: dict) -> list:
    """``(start, end)`` of each stretch of the window with no operation on
    the device."""
    lo, hi = trace["window"]
    busy = union(((s, s + d) for _, s, d in ops(trace)), lo, hi)
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def span_at(trace: dict, t: int, skip: tuple = ("bench.window",)) -> str:
    """The innermost benchmark span open at ``t`` (the one that started
    last), or ``"none"``."""
    best = None
    for name, s, d in trace["host"]:
        if name in skip or not s <= t < s + d:
            continue
        if best is None or s > best[1]:
            best = (name, s)
    return best[0] if best else "none"


def idle_gaps(trace: dict, n: int = 10) -> list:
    """``[[span, seconds], ...]``: device idle time summed by the host span
    that was open in the middle of each gap, largest first."""
    tot: dict = {}
    for s, e in gaps(trace):
        name = span_at(trace, (s + e) // 2)
        tot[name] = tot.get(name, 0) + (e - s)
    ranked = sorted(tot.items(), key=lambda kv: (-kv[1], kv[0]))[:n]
    return [[name, ns / 1e9] for name, ns in ranked]
