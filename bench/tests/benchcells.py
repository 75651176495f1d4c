"""Cells cut to a size the CPU runs in seconds (the cell's own files at
tiny widths), and one run of the harness past its look for a chip."""
from __future__ import annotations

import copy

TINY = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            vocab_size=256)
TINY_BASE = dict(base_hidden_size=64, base_intermediate_size=128,
                 base_num_attention_heads=4, base_num_key_value_heads=2,
                 base_head_dim=16)


# cells whose files stand under bench/ but which BENCHMARK.json does not
# list yet: (configuration, traffic mix, a listed cell reporting the same
# metrics)
DEFERRED = {
    "sweep.smollm-360m-proxy.n16": ("smollm-360m-proxy", "sweep-n16",
                                    "train.smollm-360m.s2048"),
}


def resolve(workload: str):
    """The cell ``workload``, listed in BENCHMARK.json or deferred."""
    from harness import cells

    if workload not in DEFERRED:
        return cells.resolve(workload)
    config, traffic, like = DEFERRED[workload]
    cell = cells.resolve(like)
    cell.name = workload
    cell.config = cells.load_json(cells.BENCH / "configs" / f"{config}.json")
    cell.traffic = cells.load_json(cells.BENCH / "traffic" / f"{traffic}.json")
    cell.chips = cell.traffic["mesh"][0] * cell.traffic["mesh"][1]
    return cell


def tiny_cell(workload: str):
    """The cell as BENCHMARK.json (or DEFERRED) names it, at test size."""
    from harness import cells

    cell = resolve(workload)
    cell.limits = cells.limits(workload)
    cell.config = copy.deepcopy(cell.config)
    cell.config.update(TINY)
    cell.config["mup"].update(TINY_BASE)
    t = cell.traffic
    if t["kind"] == "train":
        t.update(seq_len=64, batch=8, use_pallas=False, remat="none")
    elif t["kind"] == "sweep":
        t.update(seq_len=64, use_pallas=False, remat="none", candidates=4)
    else:
        t.update(n_slots=4, page_size=8, prefill_chunk=16, max_prompt_len=64,
                 gen_len=8, check_requests=4,
                 prompt={"dist": "lognormal", "median": 24, "sigma": 0.5,
                         "min": 4, "max": 64},
                 arrivals={"process": "poisson", "rate_per_s": 40.0})
    return cell


def run_tiny(cell, seed: int = 2**31 + 77, seconds: float = 0.5,
             trace: int = 0, control: bool = False):
    """One run of the harness past its look for a chip; the result line."""
    import time
    import types

    import jax

    import run as run_py

    args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=trace,
                                 control=control)
    return run_py.execute(cell, args, time.perf_counter(),
                          jax.devices()[:cell.chips])
