"""Median wall time of one DynamicEngine step, from the engine's own
serve_step_seconds histogram; layer: serving scheduler."""


def read(run):
    obs = run.serve.get("obs") if run.kind == "serve" else None
    if obs is None or "serve_step_seconds" not in obs.metrics:
        return None
    (p50,) = obs.metrics.histogram("serve_step_seconds").percentiles((50,))
    return 1e3 * p50
