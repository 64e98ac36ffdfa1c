"""From a profiler trace to numbers: device busy time, kernel time, idle gaps.

Two stages, so that the second can be checked on a small recorded trace:

1. :func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and
   keeps what the reduction needs, as plain lists (:func:`from_json`
   reads them back from a JSON file):

   - ``ops``: device operations of each device plane, ``[start_ns, end_ns,
     name, text]``.  On a TPU an op event is named by its HLO instruction
     (``%vmap_jit_abft_eb_pallas__.1 = (f32[...]) custom-call(...)``):
     ``name`` is the instruction's own name (``vmap_jit_abft_eb_pallas__.1``)
     and ``text`` the rest.  A Pallas call's instruction is named after the
     jit that encloses it, so a kernel, whose body is always called
     ``_kernel``, is known by that jit's name;
   - ``modules``: executions of compiled programs on each device plane,
     ``[start_ns, end_ns, name]``;
   - ``spans``: the host spans the benchmark opened with
     ``jax.profiler.TraceAnnotation`` (names starting ``bench/``),
     ``[start_ns, end_ns, name]``.

   Device and host times share the profiler's clock.

2. The functions below reduce those lists over a time range ``[lo, hi)``.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
from typing import Iterable

#: host spans the benchmark writes start with this
SPAN_PREFIX = "bench/"
#: device lines that hold one event per executed op / program
OPS_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)


def find_xspace(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def split_hlo(text: str) -> tuple:
    """``%name = rest`` -> (name, rest); other event names pass whole."""
    head, sep, rest = text.partition(" = ")
    if sep and head.startswith("%") and " " not in head:
        return head[1:], rest
    return text, ""


def load(path: str) -> dict:
    """Read an xplane file into ``{"ops": {plane: [...]}, "modules":
    {plane: [...]}, "spans": [...]}`` (times in ns)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, modules, spans = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") or plane.name.startswith(
                "/device:GPU:"):
            o, m = ops.setdefault(plane.name, []), modules.setdefault(
                plane.name, [])
            for line in plane.lines:
                if line.name in OPS_LINES:
                    for e in line.events:
                        o.append([int(e.start_ns), int(e.end_ns),
                                  *split_hlo(e.name)])
                elif line.name in MODULE_LINES:
                    for e in line.events:
                        m.append([int(e.start_ns), int(e.end_ns), e.name])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append([int(e.start_ns), int(e.end_ns),
                                      e.name])
    for v in list(ops.values()) + list(modules.values()):
        v.sort()
    spans.sort()
    return {"ops": ops, "modules": modules, "spans": spans}


def from_json(path: str) -> dict:
    with gzip.open(path, "rt") if path.endswith(".gz") else open(path) as f:
        return json.load(f)


# ------------------------------ reduction ---------------------------------

def clip(intervals: Iterable, lo: float, hi: float) -> list:
    """[(start, end)] cut to [lo, hi); empty ones dropped."""
    out = []
    for iv in intervals:
        s, e = max(iv[0], lo), min(iv[1], hi)
        if e > s:
            out.append((s, e))
    return out


def union(intervals: Iterable) -> list:
    """Merge [(start, end)] into disjoint sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(ops: list, lo: float, hi: float) -> float:
    """Length of the union of the op intervals inside [lo, hi)."""
    return float(sum(e - s for s, e in union(clip(ops, lo, hi))))


def span_range(tr: dict, name: str) -> tuple:
    """(start, end) of the one host span called ``name``."""
    hits = [s for s in tr["spans"] if s[2] == name]
    if len(hits) != 1:
        raise ValueError(f"expected one span {name!r}, found {len(hits)}")
    return hits[0][0], hits[0][1]


def matching(ops: list, needle: str) -> list:
    """Ops whose own name contains ``needle`` (not their operands')."""
    return [o for o in ops if needle in o[2]]


def op_time_ns(ops: list, needle: str, lo: float, hi: float) -> float:
    """Device time of the ops under ``needle`` inside [lo, hi): the union
    of their intervals, so nested or overlapping events count once."""
    return busy_ns(matching(ops, needle), lo, hi)


def module_time_ns(modules: list, needle: str, lo: float, hi: float) -> float:
    return busy_ns([m for m in modules if needle in m[2]], lo, hi)


def top_ops(ops: list, lo: float, hi: float, n: int = 10) -> list:
    """[[name, seconds]] of the n device op names that took most time."""
    tot = {}
    for o in ops:
        s, e = max(o[0], lo), min(o[1], hi)
        if e > s:
            key = label(o)
            tot[key] = tot.get(key, 0) + (e - s)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in best]


def label(op: list, width: int = 120) -> str:
    """A readable name for an op: its name and the start of its
    instruction, without layouts (``fusion.3 = s32[26000] fusion(...``)."""
    text = re.sub(r"\{[^{}]*\}", "", op[3])
    return f"{op[2]} = {text}"[:width] if text else op[2]


def gaps(ops: list, lo: float, hi: float) -> list:
    """[(start, end)] of the device's idle intervals inside [lo, hi)."""
    out, t = [], lo
    for s, e in union(clip(ops, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def idle_gaps(tr: dict, ops: list, lo: float, hi: float, n: int = 10,
              exclude=()) -> list:
    """[[host span, seconds]]: the device's idle time inside [lo, hi) by
    what the host was doing, the n largest.  Each idle nanosecond goes to
    the innermost benchmark span open at that moment (``host:other``
    where none is), leaving out the spans named in ``exclude``."""
    spans = sorted(s for s in tr["spans"] if s[2] not in exclude)
    starts = [s[0] for s in spans]
    longest = max((s[1] - s[0] for s in spans), default=0)
    tot = {}
    for gs, ge in gaps(ops, lo, hi):
        first = bisect.bisect_left(starts, gs - longest)
        near = [s for s in spans[first:bisect.bisect_left(starts, ge)]
                if s[1] > gs]
        cuts = sorted({gs, ge} | {t for a, b, _ in near for t in (a, b)
                                  if gs < t < ge})
        for a, b in zip(cuts, cuts[1:]):
            inner = [s for s in near if s[0] <= a and s[1] >= b]
            name = (min(inner, key=lambda s: s[1] - s[0])[2] if inner
                    else "host:other")
            tot[name] = tot.get(name, 0) + (b - a)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in best]
