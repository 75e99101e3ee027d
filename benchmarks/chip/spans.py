"""The program's own spans (`repro.*`) in a run's profile, on the clock of
the device's operations, and what the per-layer readers compute from them.

    python3 benchmarks/chip/spans.py .bench_trace/<workload>

prints, for the traced window of the newest profile there, each span
name's count, mean, self time and union, and the device's idle time split
by stage (`idle_by_stage`), as one JSON object.

A trace here is a plain dict (`build`): the window, the host threads'
spans line by line as (name, start_ns, end_ns, stats), and device 0's
busy intervals. `load` reads it once per profile file, so the readers of
one run share one read.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import sys
from collections import defaultdict

from common import SPAN_PREFIX as HARNESS, WINDOW_SPAN as WINDOW, merge

PREFIX = "repro."
# opened on one thread and closed on another: the profiler records such a
# span on the closing thread's line, where it does not nest with the rest
CROSS_THREAD = frozenset({"repro.serve.queue_wait"})
# spans that mark the thread doing the work of a scoring pass or a step
WORK = frozenset({"repro.serve.pass", "repro.train.step"})

_LOADED: dict = {}


def _nest(line):
    """Each span of one thread's line with its self time and its parent,
    (name, start, end) of the innermost span enclosing it: spans on one
    thread nest, so a stack over the spans in order of start finds it."""
    out = []
    stack: list[list] = []            # [end, record]
    for name, s, e, stats in sorted(line, key=lambda r: (r[1], -r[2])):
        rec = [name, s, e, stats, e - s, None]
        if name in CROSS_THREAD or not name.startswith(PREFIX):
            out.append(rec)
            continue
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            parent = stack[-1][1]
            rec[5] = (parent[0], parent[1], parent[2])
            parent[4] -= min(e, stack[-1][0]) - s
        stack.append([min(e, stack[-1][0]) if stack else e, rec])
        out.append(rec)
    return [tuple(r) for r in out]


def build(window, lines, busy=None) -> dict:
    """A trace from plain data: `window` (lo_ns, hi_ns) or None, `lines` a
    list of host lines each a list of (name, start_ns, end_ns, stats),
    `busy` device 0's op intervals (None: no device in the profile)."""
    nested = [_nest(line) for line in lines]
    by_name = defaultdict(list)
    for i, line in enumerate(nested):
        for name, s, e, stats, own, parent in line:
            by_name[name].append((s, e, own, parent, i, stats))
    return {"window": window, "lines": nested, "by_name": dict(by_name),
            "busy": None if busy is None else merge(busy)}


def _read(path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    lines, windows, devices = [], [], {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name] = [(ev.start_ns, ev.end_ns)
                                           for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                kept = []
                for ev in line.events:
                    name = ev.name
                    if name == WINDOW:
                        windows.append((ev.start_ns, ev.end_ns))
                    elif name.startswith((PREFIX, HARNESS)):
                        kept.append((name, ev.start_ns, ev.end_ns,
                                     dict(ev.stats)))
                if kept:
                    lines.append(kept)
    busy = devices[min(devices)] if devices else None
    return build(windows[-1] if windows else None, lines, busy)


def load(trace_dir: str) -> dict | None:
    """The trace of the newest profile under `trace_dir`, or None."""
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        return None
    key = (files[-1], os.path.getmtime(files[-1]))
    if key not in _LOADED:
        _LOADED.clear()
        _LOADED[key] = _read(files[-1])
    return _LOADED[key]


def ending_in_window(trace: dict, name: str) -> list:
    """(start, end, self, parent, line, stats) of each `name` span that
    ends inside the window."""
    if trace["window"] is None:
        return []
    lo, hi = trace["window"]
    return [r for r in trace["by_name"].get(name, ()) if lo < r[1] <= hi]


def mean_s(trace: dict, name: str, own: bool = False) -> float | None:
    """Mean seconds of the `name` spans ending inside the window; with
    `own`, of their self time."""
    rows = ending_in_window(trace, name)
    if not rows:
        return None
    return sum(r[2] if own else r[1] - r[0] for r in rows) / len(rows) / 1e9


def self_s(trace: dict, name: str) -> float:
    """Summed self time (duration less the time of spans nested in it on
    its thread) of the `name` spans ending inside the window, seconds."""
    return sum(r[2] for r in ending_in_window(trace, name)) / 1e9


def child_s(trace: dict, parent: str, child: str) -> float:
    """Summed seconds of the `child` spans directly inside a `parent` span
    that ends inside the window."""
    if trace["window"] is None:
        return 0.0
    lo, hi = trace["window"]
    return sum(e - s for s, e, _, par, *_ in trace["by_name"].get(child, ())
               if par is not None and par[0] == parent
               and lo < par[2] <= hi) / 1e9


def union_s(trace: dict, name: str) -> float:
    """Seconds of the window covered by at least one `name` span."""
    if trace["window"] is None:
        return 0.0
    lo, hi = trace["window"]
    ivs = merge((max(s, lo), min(e, hi))
                 for s, e, *_ in trace["by_name"].get(name, ()))
    return sum(e - s for s, e in ivs) / 1e9


def window_s(trace: dict) -> float | None:
    if trace["window"] is None:
        return None
    lo, hi = trace["window"]
    return (hi - lo) / 1e9


def _flat(line) -> list[tuple[float, float, str]]:
    """One thread's line as disjoint (start, end, innermost span) pieces."""
    pieces, stack = [], []            # stack of [end, name]
    cursor = None
    spans = sorted(((s, e, n) for n, s, e, *_ in line
                    if n.startswith(PREFIX) and n not in CROSS_THREAD),
                   key=lambda r: (r[0], -r[1]))
    for s, e, name in spans:
        while stack and stack[-1][0] <= s:
            end, top = stack.pop()
            if end > cursor:
                pieces.append((cursor, end, top))
            cursor = max(cursor, end)
        if stack and s > cursor:
            pieces.append((cursor, s, stack[-1][1]))
        stack.append([min(e, stack[-1][0]) if stack else e, name])
        cursor = s
    while stack:
        end, top = stack.pop()
        if end > cursor:
            pieces.append((cursor, end, top))
        cursor = max(cursor, end)
    return pieces


def _at(pieces, starts, t):
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and pieces[i][1] > t:
        return pieces[i][2]
    return None


def idle_by_stage(trace: dict) -> dict | None:
    """Device 0's idle seconds inside the window, by stage. Each idle
    instant goes to the innermost `repro.*` span open on a thread that
    does the work (the lines holding `repro.serve.pass` or
    `repro.train.step`); where none is open there, to a `repro.*` span
    open on another thread (a connection thread's decode); else to
    "waiting". Cross-thread queue-wait spans take no part. None without a
    device or a window in the trace."""
    if trace["busy"] is None or trace["window"] is None:
        return None
    lo, hi = trace["window"]
    edges = [lo] + [t for s, e in trace["busy"]
                    for t in (max(lo, min(s, hi)), max(lo, min(e, hi)))] \
        + [hi]
    idle = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
    work, other = [], defaultdict(list)
    for line in trace["lines"]:
        if any(r[0] in WORK for r in line):
            work.append(_flat(line))
        else:
            for name, s, e, *_ in line:
                if name.startswith(PREFIX) and name not in CROSS_THREAD:
                    other[name].append((s, e))
    layers = [(p, [x[0] for x in p]) for p in work]
    for name in sorted(other):
        p = [(s, e, name) for s, e in merge(other[name])]
        layers.append((p, [x[0] for x in p]))
    cuts = sorted({t for p, _ in layers for s, e, _ in p
                   for t in (s, e) if lo < t < hi}
                  | {t for iv in idle for t in iv})
    idle_starts = [s for s, _ in idle]
    out: dict = defaultdict(float)
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        i = bisect.bisect_right(idle_starts, mid) - 1
        if i < 0 or idle[i][1] <= mid:
            continue
        stage = next((n for p, st in layers
                      if (n := _at(p, st, mid)) is not None), None)
        key = stage[len(PREFIX):] if stage else "waiting"
        out[key] += (b - a) / 1e9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


# ----------------------------------------------------------------------------
# what the per-layer readers return: None where the span is absent
# ----------------------------------------------------------------------------
def mean_ms(trace_dir: str, name: str, own: bool = False) -> float | None:
    """Mean milliseconds of the `name` spans of a run's traced window; with
    `own`, of their self time."""
    trace = load(trace_dir)
    m = mean_s(trace, name, own) if trace else None
    return None if m is None else 1e3 * m


def union_pct(trace_dir: str, name: str) -> float | None:
    """Share of a run's traced window covered by `name` spans, percent."""
    trace = load(trace_dir)
    if not trace or not ending_in_window(trace, name):
        return None
    return 100.0 * union_s(trace, name) / window_s(trace)


def outside_ms(trace_dir: str, parent: str, child: str) -> float | None:
    """Mean milliseconds of a `parent` span spent outside its `child`
    spans, over the traced window's `parent` spans."""
    trace = load(trace_dir)
    rows = ending_in_window(trace, parent) if trace else []
    if not rows:
        return None
    total = sum(e - s for s, e, *_ in rows) / 1e9
    return 1e3 * (total - child_s(trace, parent, child)) / len(rows)


def summary(trace: dict) -> dict:
    names = sorted(n for n in trace["by_name"] if n.startswith(PREFIX))
    spans = {}
    for n in names:
        rows = ending_in_window(trace, n)
        if rows:
            spans[n] = {"count": len(rows),
                        "mean_ms": 1e3 * mean_s(trace, n),
                        "self_s": self_s(trace, n),
                        "union_s": union_s(trace, n)}
    busy = trace["busy"]
    return {"window_s": window_s(trace), "spans": spans,
            "device_busy_intervals": None if busy is None else len(busy),
            "idle_by_stage": idle_by_stage(trace)}


if __name__ == "__main__":
    trace = load(sys.argv[1])
    if trace is None:
        raise SystemExit(f"no profile under {sys.argv[1]}")
    print(json.dumps(summary(trace), indent=1))
