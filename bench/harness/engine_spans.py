"""The serving engine's ``engine.*`` spans, from the program's own tracer,
placed on the traced window's clock, and the device's idle time split among
them.

``DynamicEngine.serve`` with a tracer sends each span of its host loop to
the tracer's JSONL as a ``complete`` event (``serving/engine.py``).  Those
events run on the tracer's clock; the window's idle gaps (``xplane.Summary``)
run on the profiler's, from the window's start.  The window opens just
before the serve is called, and ``engine.serve`` starts its ``setup_us``
arg after that call, so the call is the window's start: the first
microseconds of the window, before the call, go uncounted.
"""
from __future__ import annotations

import collections

PREFIX = "engine."


def spans(run) -> list | None:
    """(name, start_s, end_s, args) of the run's ``engine.*`` spans,
    seconds from the window's start; None when the run's tracer holds
    none (an untraced run, or a program that sends no such spans)."""
    obs = run.serve.get("obs") if run.kind == "serve" else None
    tracer = getattr(obs, "tracer", None)
    if tracer is None:
        return None
    ev = [e for e in getattr(tracer, "events", ())
          if e.get("ph") == "X" and e["name"].startswith(PREFIX)]
    serve = [e for e in ev if e["name"] == PREFIX + "serve"]
    if not serve:
        return None
    args = serve[0].get("args", {})
    t_call = serve[0]["ts"] - args.get("setup_us", 0.0)      # microseconds
    return [(e["name"], (e["ts"] - t_call) * 1e-6,
             (e["ts"] + e["dur"] - t_call) * 1e-6, e.get("args", {}))
            for e in ev]


def innermost(nested) -> list:
    """Nested (start, end, name) spans as non-overlapping (start, end, name)
    pieces in time order, each piece owned by the innermost span over it."""
    out, stack, t = [], [], None       # stack of [end, name]

    def pop():
        nonlocal t
        end, name = stack.pop()
        if end > t:
            out.append((t, end, name))
            t = end

    for a, b, n in sorted(nested, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][0] <= a:
            pop()
        if stack and a > t:
            out.append((t, a, stack[-1][1]))
        t = a
        stack.append([b, n])
    while stack:
        pop()
    return out


def cover(gaps, pieces) -> dict:
    """Seconds of the (start, end) gaps that each label of the
    non-overlapping (start, end, label) pieces covers; both in time order."""
    out, j = collections.Counter(), 0
    for a, b in gaps:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            lo, hi = max(a, pieces[k][0]), min(b, pieces[k][1])
            if hi > lo:
                out[pieces[k][2]] += hi - lo
            k += 1
    return dict(out)


def idle_by_span(run) -> dict | None:
    """Device-idle seconds of the traced window under each ``engine.*``
    span name, each stretch of a gap going to the innermost span over it;
    None without a device plane (the CPU) or without engine spans."""
    if run.trace is None or not run.trace.devices:
        return None
    found = spans(run)
    if not found:
        return None
    pieces = innermost([(a, b, n) for n, a, b, _ in found])
    return cover([(a, b) for a, b, _ in run.trace.gaps], pieces)
