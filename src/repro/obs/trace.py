"""Phase tracing: a lightweight host-side span tracer with JSONL export.

Records where a token's latency actually goes — the phases of a request's
life in the serving engines (admission -> chunk-prefill -> decode/verify ->
retire), the sweep engine's candidate lifecycle, and train-loop steps — as
Chrome-trace-flavored events on a single monotonic clock:

    {"name": "step", "ph": "X", "ts": <us since tracer start>,
     "dur": <us>, "args": {"phase": "decode", ...}}

``ph`` is "X" (complete span, has ``dur``) or "i" (instant event).  One
JSON object per line (:meth:`Tracer.dump` / ``path=``), so logs stream and
cheap tools (jq, pandas) read them without a closing bracket.

Every :meth:`Tracer.span` is also written into the profiler's own trace
through :func:`annotate`, so while ``jax.profiler`` records, host phases sit
on the same clock as the device's ``XLA Ops`` line and the idle gaps between
ops can be read against what the host was doing.  With no profiler running
an annotation costs about a microsecond.
"""
from __future__ import annotations

import contextlib
import json
import time
from typing import Any, Dict, Iterator, List, Optional

from jax.profiler import TraceAnnotation


def annotate(name: str, **args: Any) -> TraceAnnotation:
    """A span on the profiler's trace, for use as a context manager; ``args``
    (ints, floats, strings) come back as the event's stats.  Its
    ``set_metadata(**args)`` adds args while it is open."""
    return TraceAnnotation(name, **args)


class Tracer:
    """Monotonic-clock span/event recorder.

    ``path`` streams events as JSONL while recording; without it events
    accumulate in ``self.events`` (bounded by ``max_events``) for a later
    :meth:`dump`.  ``t0`` is the ``time.monotonic()`` value at which the
    events' ``ts`` is 0.
    """

    def __init__(self, path: Optional[str] = None, max_events: int = 200_000):
        self.t0 = time.monotonic()
        self.events: List[Dict[str, Any]] = []
        self.max_events = max_events
        self.dropped = 0
        self._file = open(path, "w") if path else None

    # ------------------------------------------------------------------
    def now_us(self) -> float:
        return (time.monotonic() - self.t0) * 1e6

    def _emit(self, ev: Dict[str, Any]) -> None:
        if self._file is not None:
            self._file.write(json.dumps(ev) + "\n")
        if len(self.events) >= self.max_events:
            self.dropped += 1
            return
        self.events.append(ev)

    def event(self, name: str, **args: Any) -> None:
        """Instant event (admission granted, slot retired, candidate pruned)."""
        self._emit({"name": name, "ph": "i", "ts": self.now_us(),
                    **({"args": args} if args else {})})

    @contextlib.contextmanager
    def span(self, name: str, **args: Any) -> Iterator[None]:
        """Complete span around a host-side phase, recorded here and as an
        annotation of the same name and args on the profiler's trace."""
        ts = self.now_us()
        try:
            with annotate(name, **args):
                yield
        finally:
            self._emit({"name": name, "ph": "X", "ts": ts,
                        "dur": self.now_us() - ts,
                        **({"args": args} if args else {})})

    def complete(self, name: str, t_start: float, t_end: float,
                 **args: Any) -> None:
        """Record an already-timed span from two ``time.monotonic()`` stamps.

        The non-contextmanager spelling for hot loops (the dynamic engine's
        per-step path): the caller times the region itself — usually with
        stamps it already takes for other bookkeeping — and this just emits,
        skipping the generator-contextmanager machinery of :meth:`span`.
        It writes nothing to the profiler's trace (the region is over).
        """
        self._emit({"name": name, "ph": "X",
                    "ts": (t_start - self.t0) * 1e6,
                    "dur": (t_end - t_start) * 1e6,
                    **({"args": args} if args else {})})

    # ------------------------------------------------------------------
    def dump(self, path: str) -> int:
        """Write accumulated events as JSONL; returns the event count."""
        with open(path, "w") as f:
            for ev in self.events:
                f.write(json.dumps(ev) + "\n")
        return len(self.events)

    def close(self) -> None:
        if self._file is not None:
            self._file.close()
            self._file = None

    def __enter__(self) -> "Tracer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def load_jsonl(path: str) -> List[Dict[str, Any]]:
    """Read a trace file back (schema check in tests, ad-hoc analysis)."""
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out
