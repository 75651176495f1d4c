"""What one run records, and the result line it prints."""
from __future__ import annotations

import contextlib
import dataclasses
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Optional

from harness.cells import ROOT

TRACE_DIR = ROOT / ".bench_trace"


@dataclasses.dataclass
class Check:
    """One number compared with the reference, beside its limit."""

    name: str
    value: float
    limit: float
    detail: str = ""

    def __post_init__(self):
        self.value = float(self.value)

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclasses.dataclass
class Run:
    """Everything a per-layer metric's reader may read."""

    kind: str
    cell: Any
    window_s: float
    attempted: int
    failed: int
    memory_peak_bytes: Optional[int] = None
    step_stamps: list = dataclasses.field(default_factory=list)
    tokens: int = 0                 # trained or served tokens in the window
    e2e: dict = dataclasses.field(default_factory=dict)
    checks: list = dataclasses.field(default_factory=list)
    serve: dict = dataclasses.field(default_factory=dict)
    trace: Any = None               # xplane.Summary of the traced window
    compile_events: dict = dataclasses.field(default_factory=dict)
    peaks: dict = dataclasses.field(default_factory=dict)
    extra: dict = dataclasses.field(default_factory=dict)


class Timer:
    """Set-up runs from process start to the first timed step."""

    def __init__(self, t_process: float):
        self.t_process = t_process
        self.t_window = None
        self.t_stop = None
        self.window_compiles = None
        self._compiles_at_start = 0

    def window_start(self, now: Optional[float] = None) -> None:
        from harness import compiles

        self._compiles_at_start = compiles.count()
        self.t_window = time.perf_counter() if now is None else now

    def window_stop(self, now: Optional[float] = None) -> None:
        from harness import compiles

        self.t_stop = time.perf_counter() if now is None else now
        self.window_compiles = compiles.count() - self._compiles_at_start

    @property
    def setup_s(self) -> float:
        return self.t_window - self.t_process

    @property
    def window_s(self) -> float:
        return self.t_stop - self.t_window

    @staticmethod
    def memory_peak() -> Optional[int]:
        import jax

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.local_devices()]
        peaks = [p for p in peaks if p is not None]
        return max(peaks) if peaks else None


class NoTracer:
    """Stands in for ``BenchTracer`` when the run is not traced."""

    @staticmethod
    def window():
        return contextlib.nullcontext()

    @staticmethod
    def span(name: str):
        return contextlib.nullcontext()


class BenchTracer:
    """The bench's own host spans, written into the profiler's trace, and
    the profiler around the window."""

    def __init__(self, path: Path = TRACE_DIR):
        self.path = path

    @contextlib.contextmanager
    def window(self):
        import jax

        shutil.rmtree(self.path, ignore_errors=True)
        jax.profiler.start_trace(str(self.path))
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                yield
        finally:
            jax.profiler.stop_trace()

    @staticmethod
    def span(name: str):
        import jax

        return jax.profiler.TraceAnnotation("bench." + name)

    def xplane(self) -> Path:
        found = sorted(self.path.glob("plugins/profile/*/*.xplane.pb"))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {self.path}")
        return found[-1]


def emit(result: dict, checks: list) -> dict:
    """Print the compared numbers beside their limits as the last lines of
    stderr, and the result as the last line of stdout, with the checks
    under a key of their own that comes last."""
    for c in checks:
        state = "ok" if c.ok else "FAIL"
        print(f"[check] {c.name} {c.value!r} limit {c.limit!r} {state}"
              + (f" ({c.detail})" if c.detail else ""), file=sys.stderr)
    result = dict(result)
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return result
