"""From a profiler trace (``.xplane.pb``) to the numbers the metrics read.

The window is the bench's own ``bench.window`` span on the host.  Device
work is the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane: ops nest
(a ``while`` spans its body), so busy time is the union of their intervals
and an op's own time excludes the ops nested inside it; an op is named by
the head of its HLO text, and a fusion also by the computation it calls.
Idle gaps are the stretches of the window in which no op ran, each labelled
by the innermost ``bench.*`` host span around it.
"""
from __future__ import annotations

import collections
import dataclasses
import re
from pathlib import Path

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                  # mean over the device planes used
    devices: int
    op_self_s: dict                # op name -> own seconds, summed over runs
    op_count: dict
    idle_by_span: dict             # host span label -> idle seconds
    gaps: list                     # (start_s, end_s, label), window-relative
    op_calls: dict = dataclasses.field(default_factory=dict)
                                   # op name -> computation it calls

    def kernel_s(self, match) -> float | None:
        """Own seconds of the ops whose name ``match`` accepts; None when
        the trace has none."""
        hit = [s for n, s in self.op_self_s.items() if match(n)]
        return sum(hit) if hit else None

    def kernel_calls(self, match) -> int:
        return sum(c for n, c in self.op_count.items() if match(n))

    def breakdown(self, k: int = 10) -> dict:
        ops = sorted(self.op_self_s.items(), key=lambda kv: -kv[1])[:k]
        idle = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:k]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in idle]}


def op_name(event_name: str) -> str:
    """'%fusion.12 = bf16[...] fusion(...)' -> 'fusion.12'."""
    head = event_name.split(" = ", 1)[0]
    return head.lstrip("%")


def op_calls(event_name: str) -> str:
    """'%fusion.9 = f32[8] fusion(%p), kind=kCustom, calls=%all-reduce-scatter'
    -> 'all-reduce-scatter'; '' for an op that calls no computation."""
    m = re.search(r"\bcalls=%?([^,\s]+)", event_name)
    return m.group(1) if m else ""


def _merge(iv):
    out = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _self_times(events):
    """Own time of nested intervals: (name, start, end) sorted by start."""
    own = collections.Counter()
    count = collections.Counter()
    stack = []          # [name, end, child_time]

    def pop():
        n, end, start, child = stack.pop()
        own[n] += (end - start) - child
        if stack:
            stack[-1][3] += end - start

    for n, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= a:
            pop()
        stack.append([n, b, a, 0.0])
        count[n] += 1
    while stack:
        pop()
    return own, count


def summarize(path: Path) -> Summary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    spans, window = [], None
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == WINDOW:
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                elif e.name.startswith("bench."):
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name[len("bench."):]))
    if window is None:
        raise ValueError(f"no {WINDOW} span in {path}")
    w0, w1 = window
    busy, own, count, planes = [], collections.Counter(), \
        collections.Counter(), 0
    calls = {}
    merged_all = []
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        evs = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for e in line.events:
                a, b = max(e.start_ns, w0), min(e.start_ns + e.duration_ns, w1)
                if b > a:
                    n = op_name(e.name)
                    evs.append((n, a, b))
                    if n not in calls:
                        calls[n] = op_calls(e.name)
        if not evs:
            continue
        planes += 1
        o, c = _self_times(evs)
        own.update(o)
        count.update(c)
        merged = _merge([(a, b) for _, a, b in evs])
        busy.append(sum(b - a for a, b in merged))
        merged_all.append(merged)
    gaps, idle = [], collections.Counter()
    if merged_all:
        # gaps of the first device used; one-chip cells have one
        edges = [w0] + [x for iv in merged_all[0] for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = 0.5 * (a + b)
            inside = [s for s in spans if s[0] <= mid < s[1]]
            label = (min(inside, key=lambda s: s[1] - s[0])[2]
                     if inside else "outside bench spans")
            idle[label] += (b - a) * 1e-9
            gaps.append(((a - w0) * 1e-9, (b - w0) * 1e-9, label))
    return Summary(
        window_s=(w1 - w0) * 1e-9,
        busy_s=(sum(busy) / len(busy) * 1e-9) if busy else 0.0,
        devices=planes,
        op_self_s={n: t * 1e-9 for n, t in own.items()},
        op_count=dict(count),
        idle_by_span=dict(idle),
        gaps=gaps,
        op_calls={n: c for n, c in calls.items() if c},
    )
