"""Record the small four-chip trace that ``test_bench_trace.py`` reads.

On a host with four TPU v5e chips, from the root of the checkout:

    python3 bench/tests/record_trace.py train_fsdp4_2layers.xplane.pb
    xz -9e train_fsdp4_2layers.xplane.pb   # kept compressed in tests/data/

It builds the program as the cell ``train.smollm2-1.7b.fsdp4`` does, at 2 of
its 24 layers (every width as published), and traces two train steps on the
cell's (4, 1) ZeRO-3 mesh, each inside a ``bench.train_step`` span, inside
the ``bench.window`` span.
"""
from __future__ import annotations

import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent), str(HERE.parents[1] / "src")]

CELL, LAYERS, STEPS = "train.smollm2-1.7b.fsdp4", 2, 2


def main(out: str) -> None:
    import jax

    from benchcells import resolve
    from harness import record, run_train
    from repro.distributed.sharding import shardings

    cell = resolve(CELL)
    cell.config = dict(cell.config, num_hidden_layers=LAYERS)
    o = run_train._build(cell, 1, jax.devices()[:cell.chips])
    params, opt_state, step = o["params"], o["opt_state"], o["step"]
    tracer = record.BenchTracer(HERE / ".record_trace")
    with shardings(o["mesh"], o["rules"]):
        batch, _ = o["feed"](0)
        params, opt_state, m = step(params, opt_state, batch)
        float(m["loss"])
        with tracer.window():
            for t in range(1, 1 + STEPS):
                batch, _ = o["feed"](t)
                with tracer.span("train_step"):
                    params, opt_state, m = step(params, opt_state, batch)
                    float(m["loss"])
    shutil.copy(tracer.xplane(), out)
    shutil.rmtree(tracer.path, ignore_errors=True)
    print(f"wrote {out} ({Path(out).stat().st_size} bytes)")


if __name__ == "__main__":
    main(sys.argv[1])
