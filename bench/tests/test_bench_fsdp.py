"""The four-chip training cell: its mesh is the cell's chips, the program's
state is sharded over them, and its checked steps match the reference run
sharded over the same chips, at test size on four virtual CPU devices (in a
process of its own: ``fourchips.py``)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from harness import cells

HERE = Path(__file__).resolve().parent


@pytest.fixture(scope="module")
def four():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, str(HERE / "fourchips.py")], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_every_large_leaf_is_sharded(four):
    assert four["mesh"] == [4, 1] and four["fsdp"] and four["count"] == 4
    large = {k: v for k, v in four["shardings"].items()
             if np.prod(v[0]) >= 1024}
    assert len(large) == 7          # embed, wq, wk, wv, wo, mlp wi and wo
    for path, (shape, spec) in large.items():
        assert "data" in spec, (path, shape, spec)


def test_program_matches_the_sharded_reference(four):
    # float32 on both sides: the gaps are float32 rounding, amplified by
    # AdamW's division by sqrt(nu) in the change
    assert four["correct"]
    gaps = {k: v["value"] for k, v in four["checks"].items()}
    assert gaps["loss_gap"] < 1e-5
    assert gaps["grad_norm_gap"] < 1e-5
    assert gaps["change_norm_gap"] < 1e-4


@pytest.mark.parametrize("fault", ["frozen_state", "half_batch",
                                   "no_exchange"])
def test_each_fault_of_a_sharded_step_is_caught(four, fault):
    out = four["faults"][fault]
    assert not out["correct"], out["checks"]


def test_sharded_reference_equals_one_device_reference(four):
    on4, on1 = four["references"]["4"], four["references"]["1"]
    for k in ("losses", "grad_norms", "change_norms"):
        np.testing.assert_allclose(on4[k], on1[k], rtol=1e-5, atol=1e-7)


def _root(tmp_path, mesh, chips):
    bench = {"configs": [{"name": "c", "file": "c.json"}],
             "workloads": [{"name": "w", "config": "c", "traffic": "t",
                            "chips": chips}],
             "end_to_end": [], "per_layer": []}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "c.json").write_text("{}")
    (tmp_path / "bench" / "traffic").mkdir(parents=True)
    (tmp_path / "bench" / "traffic" / "t.json").write_text(
        json.dumps({"kind": "train", "mesh": mesh, "fsdp": True}))
    return tmp_path


@pytest.mark.parametrize("mesh, chips", [([2, 1], 4), ([1, 1], 4),
                                         ([2, 2], 1), ([4, 1], 1)])
def test_a_mesh_that_is_not_the_cells_chips_is_refused(tmp_path, mesh, chips):
    with pytest.raises(ValueError, match="mesh"):
        cells.resolve("w", root=_root(tmp_path, mesh, chips))


@pytest.mark.parametrize("mesh", [[4, 1], [2, 2], [1, 4]])
def test_a_mesh_of_the_cells_chips_resolves(tmp_path, mesh):
    cell = cells.resolve("w", root=_root(tmp_path, mesh, 4))
    assert cell.chips == 4 and cell.traffic["mesh"] == mesh
