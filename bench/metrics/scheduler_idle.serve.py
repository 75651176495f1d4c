"""Share of the traced window in which the device idled while the serving
engine's host loop admitted a request, built a step's control block and
page tables, dispatched the step or did the bookkeeping after it: idle time
whose innermost ``engine.*`` span is ``engine.admit``, ``engine.prepare``,
``engine.dispatch`` or ``engine.bookkeep``; layer: serving scheduler
(serving/engine.py DynamicEngine.serve).  Idle gaps from the device trace,
spans from the engine's tracer (harness/engine_spans.py).  Idle time in
``engine.sync`` (the device's results on their way back) and in
``engine.wait_arrival`` (no request to run) is not counted."""
from harness import engine_spans

PHASES = ("engine.admit", "engine.prepare", "engine.dispatch",
          "engine.bookkeep")


def read(run):
    if run.kind != "serve":
        return None
    idle = engine_spans.idle_by_span(run)
    if idle is None:
        return None
    return 100.0 * sum(idle.get(n, 0.0) for n in PHASES) / run.trace.window_s
