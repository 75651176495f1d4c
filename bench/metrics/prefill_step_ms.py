"""Median host time of the serving engine's steps that carry a prompt
chunk (and decode the live slots besides), from the ``engine.step`` spans
of the engine's tracer whose ``phase`` is ``prefill`` or ``chunk_prefill``;
layer: serving scheduler (serving/engine.py DynamicEngine.serve)."""
import statistics

from harness import engine_spans

PREFILL = ("prefill", "chunk_prefill")


def read(run):
    found = engine_spans.spans(run)
    durs = [b - a for n, a, b, args in found or ()
            if n == "engine.step" and args.get("phase") in PREFILL]
    if not durs:
        return None
    return 1e3 * statistics.median(durs)
