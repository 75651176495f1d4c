"""Benchmark harness entry: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines.  Benches whose ``run()``
returns a metrics dict additionally get it written to
``experiments/BENCH_<name>.json`` (``perf_`` prefix stripped — e.g.
perf_serve -> BENCH_serve.json) for machine consumption.  Run with:
    PYTHONPATH=src python -m benchmarks.run [--only NAME] [--quick]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from repro.launch import compile_cache

    compile_cache.enable()
    from benchmarks import (
        fig1_transformer_lr_stability,
        fig3_mlp_lr_stability,
        fig4_hp_stability,
        fig5_coord_check,
        fig7_wider_is_better,
        perf_serve,
        perf_sweep,
        perf_traffic,
        roofline,
        table4_mutransfer_vs_direct,
    )

    benches = {
        "fig3": fig3_mlp_lr_stability,
        "fig1": fig1_transformer_lr_stability,
        "fig4": fig4_hp_stability,
        "fig5": fig5_coord_check,
        "fig7": fig7_wider_is_better,
        "table4": table4_mutransfer_vs_direct,
        "perf_sweep": perf_sweep,
        "perf_serve": perf_serve,
        "perf_traffic": perf_traffic,
        "roofline": roofline,
    }
    # a bench may fold its dict into another bench's file under a sub-key
    # (perf_traffic -> BENCH_serve.json["traffic"]), so one file carries a
    # whole subsystem's numbers; the owner bench preserves those sub-keys
    # when it rewrites the file (--only runs must not drop them)
    merge_keys: dict = {}
    for mod in benches.values():
        t, k = getattr(mod, "MERGE_INTO", (None, None))
        if k is not None:
            merge_keys.setdefault(t, set()).add(k)

    failures = 0
    print("name,us_per_call,derived")
    for name, mod in benches.items():
        if args.only and args.only != name:
            continue
        try:
            result = mod.run()
            if isinstance(result, dict):
                os.makedirs("experiments", exist_ok=True)
                short = name[5:] if name.startswith("perf_") else name
                target, key = getattr(mod, "MERGE_INTO", (short, None))
                path = f"experiments/BENCH_{target}.json"
                old = {}
                if os.path.exists(path):
                    with open(path) as f:
                        old = json.load(f)
                if key is not None:
                    old[key] = result
                    result = old
                else:
                    for k in merge_keys.get(target, ()):
                        if k in old and k not in result:
                            result[k] = old[k]
                with open(path, "w") as f:
                    json.dump(result, f, indent=2)
                # repo-root mirrors (ROOT_SUMMARY = {filename: key|None}):
                # headline summaries live next to README for quick diffing,
                # while experiments/ keeps the canonical per-bench files
                for fname, key in getattr(mod, "ROOT_SUMMARY", {}).items():
                    data = result if key is None else result.get(key)
                    if data is not None:
                        with open(fname, "w") as f:
                            json.dump(data, f, indent=2)
        except Exception:
            failures += 1
            print(f"{name},0,FAILED", flush=True)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
