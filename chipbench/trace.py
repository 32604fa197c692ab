"""Reduction of a profiler trace to device busy time, idle share and
the breakdown the result line carries.

A trace is first flattened to plain data (:func:`flatten`): for each
plane, its lines, each a list of ``(name, start_ns, duration_ns)``.  The
reduction (:func:`reduce`) works on that form only, so the tests can
feed it a small recorded trace.

- The window runs from the start of the first request span
  (:data:`REQUEST_SPAN`, written by the benchmark around every request
  of the traced window) to the end of the last.
- Device busy time is the union of the intervals of the operations on
  a device plane's ``XLA Ops`` line (all its lines where it has none),
  clipped to the window, averaged over the devices that ran anything.
- Each idle gap on the first such device is put down to the innermost
  host span, on the thread that holds the request spans, that covers
  the gap's middle.
"""

from __future__ import annotations

import bisect
import glob
import os

REQUEST_SPAN = "chipbench.request"
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"


def flatten(profile) -> list[dict]:
    """``jax.profiler.ProfileData`` → ``[{"name", "lines": {name:
    [(event, start_ns, duration_ns), ...]}}]``.  A device op's name is
    cut to its HLO instruction name (``%while.27 = (...)`` → ``%while.27``).
    """
    out = []
    for plane in profile.planes:
        lines = {}
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name.split(" = ", 1)[0], float(e.start_ns),
                 float(e.duration_ns))
                for e in line.events)
        out.append({"name": plane.name, "lines": lines})
    return out


def load(trace_dir: str) -> list[dict]:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return flatten(ProfileData.from_file(paths[-1]))


def _is_device(plane: dict) -> bool:
    name = plane["name"]
    return name.startswith("/device:") and "CPU" not in name


def _union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _op_events(plane: dict) -> list[tuple[str, float, float]]:
    lines = plane["lines"]
    if _OPS_LINE in lines:
        return lines[_OPS_LINE]
    return [e for evs in lines.values() for e in evs]


def _host_line(planes: list[dict]) -> list[tuple[str, float, float]]:
    """The host thread that holds the request spans."""
    for plane in planes:
        for events in plane["lines"].values():
            if any(name == REQUEST_SPAN for name, _, _ in events):
                return events
    return []


def _module_of(plane: dict):
    """Map an op's start to the program (XLA module) it ran in."""
    mods = sorted((s, s + d, n) for n, s, d in
                  plane["lines"].get(_MODULES_LINE, []))

    def find(t: float) -> str | None:
        lo, hi = 0, len(mods)
        while lo < hi:
            mid = (lo + hi) // 2
            if mods[mid][0] <= t:
                lo = mid + 1
            else:
                hi = mid
        if lo and mods[lo - 1][0] <= t <= mods[lo - 1][1]:
            return mods[lo - 1][2]
        return None

    return find


class _Spans:
    """Innermost covering span of one thread's properly nested spans."""

    def __init__(self, events):
        self.ev = sorted(((s, s + d, n) for n, s, d in events),
                         key=lambda e: (e[0], -e[1]))
        self.starts = [e[0] for e in self.ev]
        self.parent, stack = [], []
        for i, (s, _, _) in enumerate(self.ev):
            while stack and self.ev[stack[-1]][1] <= s:
                stack.pop()
            self.parent.append(stack[-1] if stack else -1)
            stack.append(i)

    def at(self, t: float) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0 and self.ev[i][1] < t:
            i = self.parent[i]
        if i < 0:
            return "between requests"
        name = self.ev[i][2]
        return "host, inside a request (no finer span)" \
            if name == REQUEST_SPAN else name


def reduce(planes: list[dict], top: int = 10) -> dict | None:
    """Busy and idle time of the traced window; None when the trace has
    no request span or no device operation."""
    host = _host_line(planes)
    spans = [(s, s + d) for name, s, d in host if name == REQUEST_SPAN]
    devices = [p for p in planes if _is_device(p) and _op_events(p)]
    if not spans or not devices:
        return None
    lo, hi = min(a for a, _ in spans), max(b for _, b in spans)
    busy, first = [], None
    for plane in devices:
        merged = _union(((s, s + d) for _, s, d in _op_events(plane)),
                        lo, hi)
        if merged and first is None:
            first = (plane, merged)
        if merged:
            busy.append(sum(b - a for a, b in merged))
    if first is None:
        return None
    plane, merged = first
    module = _module_of(plane)
    ops: dict[str, float] = {}
    for name, s, d in _op_events(plane):
        if lo <= s <= hi:
            mod = module(s)
            key = f"{mod}:{name}" if mod else name
            ops[key] = ops.get(key, 0.0) + d
    gaps: dict[str, float] = {}
    spans_at = _Spans(host).at
    edges = [lo] + [x for ab in merged for x in ab] + [hi]
    for a, b in zip(edges[::2], edges[1::2]):
        if b > a:
            what = spans_at((a + b) / 2)
            gaps[what] = gaps.get(what, 0.0) + (b - a)
    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": sum(busy) / len(busy) * ns,
        "n_devices": len(busy),
        "device_ops": [[k, v * ns] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v * ns] for k, v in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:top]],
    }
