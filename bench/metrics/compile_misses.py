"""Programs compiled anew in this process (persistent-cache misses; 0 when
every program came from the cache); layer: entry points
(launch/compile_cache.py)."""


def read(run):
    return float(run.compile_events.get("misses", 0))
