"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name from
``BENCHMARK.json``.  Set-up (weights on the device from the seed, the cell's
own shapes compiled, the checked first steps) is timed from process start;
then the window runs for ``--seconds``.  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics from a profiler
trace of the window.  Either way the run ends by comparing what the window's
program produced with the plain reference, and prints each compared number
beside its limit.  The last line of stdout is one JSON object.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.  ``--control reference`` puts the reference, in a lower
precision, in the program's place; ``--fault`` plants one of
``harness/faults.py``'s faults underneath (each must come out not correct);
``--rates`` replays a serving cell's requests at each of several arrival
rates and prints their latencies instead of a result (the knee sweep).
"""
from __future__ import annotations

import os
import time

T_PROCESS = time.perf_counter()


def _process_age() -> float:
    """Seconds since this process started, from /proc where there is one."""
    try:
        with open("/proc/self/stat") as f:
            start = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return max(up - start / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError):
        return 0.0


T_PROCESS -= _process_age()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))


def parse(argv=None):
    from harness import faults

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("reference",), default=None,
                    help="the reference in a lower precision in the "
                         "program's place (readings for the limits)")
    ap.add_argument("--fault", choices=sorted(faults.FAULTS), default=None,
                    help="plant a fault underneath the harness (readings "
                         "for the limits)")
    ap.add_argument("--rates", default=None,
                    help="comma-separated arrival rates: a knee sweep")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from harness import cells

    cell = cells.resolve(args.workload)
    cell.limits = cells.limits(args.workload)
    # the persistent compile cache lives in this checkout at a fixed path,
    # whatever the environment names, so two checkouts share no programs
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from repro.launch import compile_cache

    cache_dir = compile_cache.enable()
    from harness import compiles  # noqa: F401  (listens from here on)
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} x {dev.platform}", file=sys.stderr)
        return 2
    print(f"[bench] {cell.name} seed {args.seed}: {len(devices)} x "
          f"{dev.device_kind}, jax {jax.__version__}, compile cache "
          f"{cache_dir}", file=sys.stderr, flush=True)
    if args.rates:
        from harness import run_serve

        rates = [float(r) for r in args.rates.split(",")]
        for lat in run_serve.knee(cell, args.seed, args.seconds, rates,
                                  devices[:cell.chips]):
            print(json.dumps(lat), flush=True)
        return 0
    execute(cell, args, T_PROCESS, devices[:cell.chips])
    return 0


def execute(cell, args, t_process, devices):
    """Set-up, window and check of one run; prints the result line and
    returns it."""
    from harness import cells, faults, record
    from harness import run_serve, run_sweep, run_train
    from repro.kernels import ops
    from repro.launch import compile_cache

    runners = {"train": run_train, "serve": run_serve, "sweep": run_sweep}
    mod = runners[cell.traffic["kind"]]
    timer = record.Timer(t_process)
    tracer = record.BenchTracer() if args.trace else record.NoTracer()
    ops.RESOLVED.clear()
    with faults.planted(getattr(args, "fault", None)):
        run = mod.run(cell, args.seed, args.seconds, bool(args.trace), timer,
                      tracer, devices, control=args.control)
    run.compile_events = dict(compile_cache.EVENTS)
    run.extra["window_compiles"] = timer.window_compiles
    dev = devices[0]
    run.peaks = cells.peaks(dev.device_kind) if dev.platform == "tpu" else {}
    print(f"[bench] ops resolved: {dict(ops.RESOLVED)}; setup "
          f"{timer.setup_s!r} s; window {timer.window_s!r} s; compiles in "
          f"window {timer.window_compiles}; compile cache "
          f"{run.compile_events}; peak device memory "
          f"{run.memory_peak_bytes} bytes; {_sizes(cell)}",
          file=sys.stderr, flush=True)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    out = {"correct": all(c.ok for c in run.checks) and run.failed == 0,
           "attempted": run.attempted, "failed": run.failed}
    if args.trace:
        summary = _xplane(tracer)
        run.trace = summary
        out["metrics"] = cells.read_per_layer(cell, run)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["device"] = device
        out["breakdown"] = summary.breakdown()
    else:
        metrics = {}
        for m in cell.end_to_end:
            v = timer.setup_s if m["name"] == "setup_s" else \
                run.e2e.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        out["metrics"] = metrics
        out["device"] = device
    return record.emit(out, run.checks)


def _sizes(cell) -> str:
    c, t = cell.config, cell.traffic
    keys = ("mesh", "fsdp", "batch", "seq_len", "candidates", "n_slots",
            "max_prompt_len", "gen_len", "n_pages", "prefill_chunk", "remat")
    return (f"sizes: {c['name']} L{c['num_hidden_layers']} "
            f"d{c['hidden_size']} {c['dtype']}; "
            + ", ".join(f"{k} {t[k]}" for k in keys if k in t))


def _xplane(tracer):
    import shutil

    from harness import xplane

    path = tracer.xplane()
    summary = xplane.summarize(path)
    shutil.rmtree(tracer.path, ignore_errors=True)
    return summary


if __name__ == "__main__":
    sys.exit(main())
