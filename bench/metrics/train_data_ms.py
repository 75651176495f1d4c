"""Median host time to make one step's batch in the window (the program's
synthetic pipeline on the host, then the put on the device), made while the
chip runs the steps dispatched ahead: it moves the rate once it outlasts
the device step; layer: data pipeline (data/pipeline.py)."""
import statistics


def read(run):
    data = run.extra.get("data_s") if run.kind == "train" else None
    if not data:
        return None
    return 1e3 * statistics.median(data)
