"""Stage-level reduction of a profiler trace: device seconds per named
program, host seconds per program span, and the device's idle time
under each span.

It reads :func:`chipbench.trace.flatten`'s form over the window and on
the device that :func:`chipbench.trace.reduce` reads: from the start of
the first request span to the end of the last, on the first device
plane that ran an operation in it.

- ``programs``: device seconds per program base name (the XLA module
  name without its ``(<id>)``), the union of that program's ``XLA
  Modules`` intervals clipped to the window.  A ``%while`` and the
  fusions inside it count once, which a sum of op durations does not.
- ``spans``: seconds per ``pfdnn.*`` span name (the program's own
  spans, :mod:`repro.core.spans`) on the thread that holds the request
  spans, summed over that name's events and clipped to the requests.
- ``idle_by_stage``: every idle instant of the device goes to the
  innermost ``pfdnn.*`` span over it; the Python tracer's frames are not
  spans of the program.  Idle time inside a request but under no such
  span reads :data:`NO_SPAN`, idle time outside every request
  :data:`BETWEEN`.
"""

from __future__ import annotations

from chipbench import trace

SPAN_PREFIX = "pfdnn."
NO_SPAN = "no program span"
BETWEEN = "between requests"
_NS = 1e-9


def program_name(module: str) -> str:
    """``jit_pfdnn_dp_lanes(123)`` → ``jit_pfdnn_dp_lanes``."""
    return module.split("(", 1)[0]


def span_seconds(planes: list[dict]) -> dict[str, float]:
    """Seconds per ``pfdnn.*`` span name inside the request spans (no
    device needed: the host thread alone)."""
    host = trace._host_line(planes)
    reqs = [(s, s + d) for n, s, d in host if n == trace.REQUEST_SPAN]
    out: dict[str, float] = {}
    for name, s, d in host:
        if name.startswith(SPAN_PREFIX):
            inside = sum(max(0.0, min(s + d, b) - max(s, a))
                         for a, b in reqs)
            out[name] = out.get(name, 0.0) + inside * _NS
    return out


def _program_seconds(plane: dict, lo: float, hi: float) -> dict:
    by_name: dict[str, list] = {}
    for name, s, d in plane["lines"].get(trace._MODULES_LINE, []):
        by_name.setdefault(program_name(name), []).append((s, s + d))
    return {name: sum(b - a for a, b in trace._union(iv, lo, hi)) * _NS
            for name, iv in by_name.items()}


def _innermost(events) -> list[tuple[float, float, str]]:
    """The time line cut where properly nested spans start or end, each
    piece with the innermost span over it, in order (time under no span
    is left out)."""
    out: list[tuple[float, float, str]] = []
    stack: list[tuple[float, str]] = []       # (end, name), outermost first
    t = 0.0
    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] <= s:
            end, inner = stack.pop()
            if end > t:
                out.append((t, end, inner))
            t = max(t, end)
        if stack and s > t:
            out.append((t, s, stack[-1][1]))
        t = s
        stack.append((e, name))
    while stack:
        end, inner = stack.pop()
        if end > t:
            out.append((t, end, inner))
        t = max(t, end)
    return out


def _idle_by_stage(host, idle: list[tuple[float, float]]) -> dict:
    pieces = _innermost(
        (s, s + d, NO_SPAN if n == trace.REQUEST_SPAN else n)
        for n, s, d in host
        if n == trace.REQUEST_SPAN or n.startswith(SPAN_PREFIX))
    out: dict[str, float] = {}
    j = 0
    for a, b in idle:                  # both lists in time order
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        covered, k = 0.0, j
        while k < len(pieces) and pieces[k][0] < b:
            pa, pb, name = pieces[k]
            part = min(b, pb) - max(a, pa)
            out[name] = out.get(name, 0.0) + part * _NS
            covered += part
            k += 1
        if b - a > covered:
            out[BETWEEN] = out.get(BETWEEN, 0.0) + (b - a - covered) * _NS
    return out


def reduce(planes: list[dict]) -> dict | None:
    """``programs``, ``spans`` and ``idle_by_stage`` of the traced
    window; None when the trace has no request span or no device
    operation in it."""
    host = trace._host_line(planes)
    reqs = [(s, s + d) for n, s, d in host if n == trace.REQUEST_SPAN]
    if not reqs:
        return None
    lo, hi = min(a for a, _ in reqs), max(b for _, b in reqs)
    for plane in planes:
        if not trace._is_device(plane):
            continue
        merged = trace._union(((s, s + d) for _, s, d in
                               trace._op_events(plane)), lo, hi)
        if merged:
            break
    else:
        return None
    edges = [lo] + [x for ab in merged for x in ab] + [hi]
    idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    return {"programs": _program_seconds(plane, lo, hi),
            "spans": span_seconds(planes),
            "idle_by_stage": _idle_by_stage(host, idle)}
