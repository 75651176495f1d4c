"""95th percentile of the wait from a request's due time to its admission,
from the engine tracer's admission events; layer: serving scheduler.

The tracer's clock and the serve's differ by a constant: the last step span
ends when the last token is stamped, which fixes it."""
import numpy as np


def read(run):
    obs = run.serve.get("obs") if run.kind == "serve" else None
    if obs is None or obs.tracer is None:
        return None
    ev = obs.tracer.events
    steps = [e for e in ev if e["name"] == "step" and e.get("ph") == "X"]
    adm = [e for e in ev if e["name"] == "admission"]
    last_tok = max(t[-1] for t in run.serve["token_times"] if t)
    if not steps or not adm:
        return None
    offset = (steps[-1]["ts"] + steps[-1]["dur"]) * 1e-6 - last_tok
    arr = run.serve["arrivals"]
    waits = [e["ts"] * 1e-6 - offset - arr[e["args"]["req"]] for e in adm]
    return float(np.percentile(waits, 95))
