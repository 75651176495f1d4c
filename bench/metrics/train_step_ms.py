"""Median host time between successive train steps' losses read back in
the window, with steps dispatched ahead: the pace of the step when the chip
is what holds it; layer: training loop (launch/steps.py train step)."""
import statistics


def read(run):
    if run.kind != "train" or not run.step_stamps:
        return None
    return 1e3 * statistics.median(b - a for a, b in run.step_stamps)
