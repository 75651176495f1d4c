"""The four-chip training cell at test size on four virtual CPU devices.

Run by ``test_bench_fsdp.py`` in a process of its own, since the device
count is fixed when JAX starts:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python bench/tests/fourchips.py

Prints one JSON object: the program's parameter shardings as the harness
builds them, a run past the look for a chip with its checked steps compared
with the sharded reference, the same run with each fault a sharded training
cell can have planted underneath, and the sharded reference against the same
reference on one device.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent), str(HERE.parents[1] / "src")]

FSDP4 = "train.smollm2-1.7b.fsdp4"


def tiny_fsdp4():
    """The cell at test size, in float32 so that the program and the
    reference agree to float32 rounding."""
    from benchcells import tiny_cell

    cell = tiny_cell(FSDP4)
    cell.config["dtype"] = "float32"
    return cell


def shardings(cell, devices):
    """{leaf path: (shape, mesh axes of its spec)} of the program's
    parameters as the harness places them."""
    import jax

    from harness import run_train

    o = run_train._build(cell, 5, devices)
    return {jax.tree_util.keystr(p): [list(x.shape), str(x.sharding.spec)]
            for p, x in jax.tree_util.tree_flatten_with_path(o["params"])[0]}


def references(cell, devices):
    """The reference's numbers on all four devices and on one."""
    import numpy as np

    from harness import run_train

    rows = np.random.default_rng(11).integers(
        0, cell.config["vocab_size"],
        size=(2, cell.traffic["batch"], cell.traffic["seq_len"] + 1))
    out = {}
    for n in (len(devices), 1):
        losses, g0, dn = run_train.reference_steps(cell, 7, rows, devices[:n])
        out[n] = {"losses": list(map(float, losses)),
                  "grad_norms": list(map(float, g0)),
                  "change_norms": list(map(float, dn))}
    return out


def main():
    import jax

    from benchcells import run_tiny
    from harness import faults

    devices = jax.devices()
    assert len(devices) == 4, devices
    cell = tiny_fsdp4()
    res = {"mesh": cell.traffic["mesh"], "fsdp": cell.traffic["fsdp"],
           "shardings": shardings(cell, devices)}
    out = run_tiny(tiny_fsdp4())
    res.update(correct=out["correct"], checks=out["checks"],
               count=out["device"]["count"])
    res["faults"] = {}
    for fault in ("frozen_state", "half_batch", "no_exchange"):
        with faults.planted(fault):
            out = run_tiny(tiny_fsdp4())
        res["faults"][fault] = {"correct": out["correct"],
                                "checks": out["checks"]}
    res["references"] = references(tiny_fsdp4(), devices)
    print(json.dumps(res))


if __name__ == "__main__":
    main()
