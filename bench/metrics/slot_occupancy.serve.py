"""Mean share of the engine's decode slots that hold a request at a step,
counting the one being admitted: the ``live`` arg of the ``engine.step``
spans of the engine's tracer over the cell's ``n_slots``; layer: serving
scheduler (serving/engine.py DynamicEngine.serve)."""
import statistics

from harness import engine_spans


def read(run):
    found = engine_spans.spans(run)
    live = [args["live"] for n, _, _, args in found or ()
            if n == "engine.step" and "live" in args]
    if not live:
        return None
    return 100.0 * statistics.fmean(live) / run.cell.traffic["n_slots"]
