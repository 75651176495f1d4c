"""Latency arithmetic of a serving run.

TTFT is counted from the time a request was due, not from when the engine
took it; throughput is completed output tokens over the time from the first
arrival to the last completion.  Percentiles are ``np.percentile``'s linear
interpolation over every sample.
"""
from __future__ import annotations

import numpy as np


def pct(samples, q: float) -> float:
    return float(np.percentile(np.asarray(samples, np.float64), q))


def latency(token_times, arrivals, lengths) -> dict:
    """token_times: per request, seconds from the serve's start at which
    each of its tokens was stamped; arrivals: due times on the same clock."""
    ttft, itl = [], []
    for r, ts in enumerate(token_times):
        if not ts:
            continue
        ttft.append(ts[0] - arrivals[r])
        itl.extend(np.diff(ts))
    last = max(ts[-1] for ts in token_times if ts)
    first = float(np.min(arrivals))
    n_tok = int(np.asarray(lengths).sum())
    return {
        "tokens": n_tok,
        "makespan_s": last - first,
        "tokens_per_s": n_tok / (last - first),
        "ttft_p50_s": pct(ttft, 50), "ttft_p95_s": pct(ttft, 95),
        "itl_p50_s": pct(itl, 50), "itl_p95_s": pct(itl, 95),
        "requests": len(ttft),
    }
