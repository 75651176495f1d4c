"""Median host time of one vmapped sweep step over all candidates in the
window (dispatch to the candidates' losses read back); layer: sweep engine
(core/tuning.py make_batched_step, batched_train)."""
import statistics


def read(run):
    if run.kind != "sweep" or not run.step_stamps:
        return None
    return 1e3 * statistics.median(b - a for a, b in run.step_stamps)
