"""The program's own spans and the device's name stacks, from the traced
window's profiler trace, and the numbers the per-layer metrics build on
them.

:func:`lib.trace.load` keeps the benchmark's spans and the device's op
events.  This module reads two more things from the same ``.xplane.pb``
and adds them to that dict under two keys:

    "program":      [[name, start_ns, duration_ns, {stat: value}], ...]
                    host spans of the program (names start with ``repro.``)
    "device_scope": [[start_ns, duration_ns, name_stack], ...]
                    the first chip's "XLA Ops" events that overlap the window

Both lie on the one clock of the trace, so each stretch of device idle
time can be put down to the program span open then, and each instant of
device busy time to the name stack of the innermost operation running then
(a ``while`` loop's event holds the events of its body: an instant counts
once, for the operation that started last).  A trace without program
spans (a program that has none) or without name stacks yields ``None``,
never 0.
"""
from __future__ import annotations

import functools
import glob
import heapq
import os
import sys
from typing import Iterable, Optional

from bench.lib import trace as TR
from bench.lib.cell import OUT_DIR

# where a traced run writes its trace
TRACE_DIR = OUT_DIR / "trace"
PREFIX = "repro."
# the stat of an "XLA Ops" event's metadata that holds the operation's
# name stack (JAX's name scopes, ``transpose(...)`` around the backward),
# as ``jit(step)/transpose(jvp())/dot_general:``
NAME_STACK_STAT = "tf_op"
# the device line with one event per program run, named
# ``jit_<function>(<program id>)``
MODULES_LINE = "XLA Modules"
BUNDLE_SPANS = ("repro.bundle_fetch", "repro.bundle_offload",
                "repro.bundle_prefetch")
STEP_SPAN = "repro.train_step"
# the jitted step every strategy calls: the name stack of its operations
# starts with this
STEP_PROGRAM = "jit(step)"
FUSED_UPDATES = ("fused_adamw_kernel", "fused_sgdm_kernel",
                 "fused_adagrad_kernel")


@functools.lru_cache(maxsize=None)
def _xspace():
    """The message class of the profiler's ``XSpace``, built from the part
    of its schema (``tsl/profiler/protobuf/xplane.proto``) that the
    reduction reads; the fields it leaves out are skipped when parsing.
    ``jax.profiler.ProfileData`` gives an event's own stats but not those
    of its metadata, which is where an operation's name stack is."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)

    F = descriptor_pb2.FieldDescriptorProto
    i64, u64, f64, text = (F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_DOUBLE,
                           F.TYPE_STRING)
    value = ("double_value", "uint64_value", "int64_value", "str_value",
             "ref_value")
    schema = {
        "XSpace": [("planes", 1, "XPlane")],
        "XPlane": [("name", 2, text), ("lines", 3, "XLine"),
                   ("event_metadata", 4, "EventMetadataEntry"),
                   ("stat_metadata", 5, "StatMetadataEntry")],
        "EventMetadataEntry": [("key", 1, i64),
                               ("value", 2, "XEventMetadata")],
        "StatMetadataEntry": [("key", 1, i64),
                              ("value", 2, "XStatMetadata")],
        "XLine": [("name", 2, text), ("timestamp_ns", 3, i64),
                  ("events", 4, "XEvent")],
        "XEvent": [("metadata_id", 1, i64), ("offset_ps", 2, i64),
                   ("duration_ps", 3, i64), ("stats", 4, "XStat")],
        "XStat": [("metadata_id", 1, i64), ("double_value", 2, f64),
                  ("uint64_value", 3, u64), ("int64_value", 4, i64),
                  ("str_value", 5, text), ("ref_value", 7, u64)],
        "XEventMetadata": [("name", 2, text), ("stats", 5, "XStat")],
        "XStatMetadata": [("name", 2, text)],
    }
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")
    for name, fields in schema.items():
        msg = fd.message_type.add(name=name)
        if name == "XStat":
            msg.oneof_decl.add(name="value")
        for fname, number, kind in fields:
            f = msg.field.add(name=fname, number=number)
            if isinstance(kind, str):
                f.type, f.type_name = F.TYPE_MESSAGE, f".bench_xplane.{kind}"
                f.label = (F.LABEL_OPTIONAL if fname == "value"
                           else F.LABEL_REPEATED)
            else:
                f.type, f.label = kind, F.LABEL_OPTIONAL
            if name == "XStat" and fname in value:
                f.oneof_index = 0
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def _stats(stats, names: dict) -> dict:
    out = {}
    for st in stats:
        which = st.WhichOneof("value")
        v = getattr(st, which) if which else 0
        out[names.get(st.metadata_id, "")] = \
            names.get(v, "") if which == "ref_value" else v
    return out


def _program_name(module: str) -> str:
    """``jit(step)`` from the module name ``jit_step(<id>)`` the trace
    gives a program: the form a name stack starts with."""
    head = module.split("(")[0]
    return f"jit({head[4:]})" if head.startswith("jit_") else head


def read_xplane(path: str) -> dict:
    """``{"window", "program", "device_scope"}`` from one ``.xplane.pb``:
    the benchmark's window span (``None`` where it has none), the
    program's host spans, and the first chip's operations that overlap the
    window, each with its name stack; an operation that JAX gave none (a
    loop, a copy the compiler added) gets its program's name alone."""
    with open(path, "rb") as f:
        space = _xspace().FromString(f.read())
    chips = sorted(p.name for p in space.planes
                   if p.name.startswith(TR.DEVICE_PLANE))
    window, program, ops, programs = None, [], [], {}
    for plane in space.planes:
        device = plane.name.startswith(TR.DEVICE_PLANE)
        if device and plane.name != chips[0]:
            continue
        names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        for line in plane.lines:
            for ev in line.events:
                md = meta[ev.metadata_id]
                s = int(line.timestamp_ns + ev.offset_ps / 1000)
                d = int(ev.duration_ps / 1000)
                if not device:
                    if md.name == "bench.window":
                        window = [s, s + d]
                    elif md.name.startswith(PREFIX):
                        program.append([md.name, s, d,
                                        _stats(ev.stats, names)])
                elif line.name == MODULES_LINE:
                    ident = md.name.rsplit("(", 1)[-1].rstrip(")")
                    programs[ident] = _program_name(md.name)
                elif line.name == TR.OPS_LINE:
                    st = _stats(md.stats, names)
                    ops.append([s, d, str(st.get(NAME_STACK_STAT, "")),
                                str(st.get("program_id", ""))])
    scope = []
    if window is not None:
        lo, hi = window
        scope = [[s, d, stack.rstrip(":") or programs.get(ident, "")]
                 for s, d, stack, ident in ops if s < hi and s + d > lo]
    return {"window": window, "program": program, "device_scope": scope}


def _xplane_of(window: list) -> Optional[dict]:
    """What :func:`read_xplane` reads from the newest trace under the
    benchmark's trace directory whose window span is ``window``: the one
    this run just wrote."""
    paths = sorted(glob.glob(os.path.join(TRACE_DIR, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime,
                   reverse=True)
    for path in paths:
        got = read_xplane(path)
        if got["window"] == list(window):
            return got
    return None


def extended(facts: dict) -> Optional[dict]:
    """The traced window's trace with ``program`` and ``device_scope``
    added (read from its ``.xplane.pb`` once, then kept in the dict), or
    ``None`` where there is no trace or it holds no program span.  The
    first read prints the breakdown that no metric carries."""
    tr = facts.get("trace")
    if tr is None:
        return None
    if "program" not in tr:
        got = _xplane_of(tr["window"])
        if got is None:
            return None
        tr["program"], tr["device_scope"] = got["program"], \
            got["device_scope"]
        report(tr)
    return tr if tr["program"] else None


# ------------------------------------------------------------ host spans

def overlap_ns(a: Iterable, b: Iterable) -> int:
    """Length of the intersection of two sorted lists of disjoint
    ``(start, end)`` intervals."""
    a, b = list(a), list(b)
    i = j = tot = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        tot += max(0, e - s)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def subtract(intervals: Iterable, cuts: list) -> list:
    """The parts of ``(start, end)`` intervals that lie outside the sorted,
    disjoint ``cuts``."""
    out = []
    for s, e in intervals:
        for a, b in cuts:
            if b <= s or a >= e:
                continue
            if a > s:
                out.append((s, a))
            s = max(s, b)
            if s >= e:
                break
        if s < e:
            out.append((s, e))
    return out


def _spans(tr: dict, names: Iterable) -> list:
    names = set(names)
    lo, hi = tr["window"]
    return TR.union(((s, s + d) for n, s, d, _ in tr["program"]
                     if n in names), lo, hi)


def idle_inside_ns(tr: dict, names: Iterable) -> int:
    """Device idle time of the window that lies inside the union of the
    program's spans named ``names``."""
    return overlap_ns(TR.gaps(tr), _spans(tr, names))


def bundle_idle_ns(tr: dict) -> int:
    return idle_inside_ns(tr, BUNDLE_SPANS)


def step_host_idle_ns(tr: dict) -> int:
    """Idle time inside the program's steps and outside its bundle
    transfers."""
    return idle_inside_ns(tr, (STEP_SPAN,) + BUNDLE_SPANS) \
        - bundle_idle_ns(tr)


def span_at(tr: dict, t: int, skip: tuple = ("bench.window",)) -> str:
    """The innermost span open at ``t``, the benchmark's or the
    program's (the one that started last), or ``"none"``."""
    best = None
    spans = [h[:3] for h in tr["host"]] + [p[:3] for p in tr["program"]]
    for name, s, d in spans:
        if name in skip or not s <= t < s + d:
            continue
        if best is None or s > best[1]:
            best = (name, s)
    return best[0] if best else "none"


def idle_gaps(tr: dict, n: int = 12, gaps: Optional[list] = None) -> list:
    """``[[span, seconds], ...]``: device idle time (or ``gaps``) summed by
    the innermost span, the benchmark's or the program's, open mid-gap."""
    tot: dict = {}
    for s, e in TR.gaps(tr) if gaps is None else gaps:
        name = span_at(tr, (s + e) // 2)
        tot[name] = tot.get(name, 0) + (e - s)
    ranked = sorted(tot.items(), key=lambda kv: (-kv[1], kv[0]))[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


# ---------------------------------------------------------- device time

def innermost(events: Iterable, lo: int, hi: int):
    """``(start, end, name_stack)`` pieces of ``[lo, hi]``: each instant
    that some event covers goes to the innermost event open then, the one
    that started last (of two that started together, the shorter)."""
    evs = sorted((s, s + d, stack) for s, d, stack in events)
    cuts = sorted({t for s, e, _ in evs for t in (s, e)})
    heap, k = [], 0
    for a, b in zip(cuts, cuts[1:]):
        while k < len(evs) and evs[k][0] <= a:
            s, e, stack = evs[k]
            heapq.heappush(heap, (-s, e, k, stack))
            k += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        a, b = max(a, lo), min(b, hi)
        if heap and b > a:
            yield a, b, heap[0][3]


def kind(stack: str) -> str:
    """``update``, ``backward``, ``forward`` (the rest of the step
    program) or ``outside`` (an operation of another program)."""
    parts = stack.split("/")
    if not parts[0].startswith(STEP_PROGRAM):
        return "outside"
    if "update" in parts or any(k in parts for k in FUSED_UPDATES):
        return "update"
    if "transpose(" in stack:
        return "backward"
    return "forward"


def device_split(facts: dict) -> Optional[dict]:
    """Device busy time of the window in ns, and its parts by
    :func:`kind`; ``None`` without program spans or name stacks."""
    tr = extended(facts)
    if tr is None or not any("/" in st for _, _, st in tr["device_scope"]):
        return None
    lo, hi = tr["window"]
    out = dict.fromkeys(("busy", "forward", "backward", "update",
                         "outside"), 0)
    for a, b, stack in innermost(tr["device_scope"], lo, hi):
        out["busy"] += b - a
        out[kind(stack)] += b - a
    return out if out["busy"] else None


def report(tr: dict, out=sys.stderr) -> None:
    """Print what the program's spans and scopes show that no metric
    carries: idle time by innermost span, the window's idle time split
    into the bundle transfers, the rest of the step and the rest (by the
    benchmark's span open there), and busy time split by kind."""
    if not tr["program"]:
        print("program: no repro. spans in the trace", file=out)
        return
    win = TR.window_ns(tr)
    bundle, host = bundle_idle_ns(tr), step_host_idle_ns(tr)
    rest = subtract(TR.gaps(tr), _spans(tr, (STEP_SPAN,) + BUNDLE_SPANS))
    print(f"program: idle gaps by innermost span {idle_gaps(tr)}", file=out)
    print(f"program: idle % of window: bundle transfer {100 * bundle / win}"
          f", rest of the step {100 * host / win}, outside the step "
          f"{[[n, 1e11 * t / win] for n, t in idle_gaps(tr, gaps=rest)]}",
          file=out)
    split = device_split({"trace": tr})
    if split is not None:
        parts = ", ".join(f"{k} {100 * split[k] / split['busy']}"
                          for k in ("forward", "backward", "update",
                                    "outside"))
        print(f"program: busy % by kind: {parts}", file=out)
