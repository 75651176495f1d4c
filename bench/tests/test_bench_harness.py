"""The harness finds cells, configurations, traffic, limits and metrics by
name, and refuses to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from harness import cells

ROOT = Path(__file__).resolve().parents[2]
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("w", [w["name"] for w in BENCHMARK["workloads"]])
def test_every_cell_resolves_by_name(w):
    cell = cells.resolve(w)
    assert cell.config["name"] in {c["name"] for c in BENCHMARK["configs"]}
    assert cell.traffic["kind"] in ("train", "sweep", "serve")
    assert cell.chips in (1, 4)
    assert cells.limits(w)
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert m["moves"] in names


@pytest.mark.parametrize("m", [m["name"] for m in BENCHMARK["per_layer"]])
def test_every_metric_has_a_reader(m):
    assert callable(cells.metric_reader(m))


def test_configs_match_their_files():
    for c in BENCHMARK["configs"]:
        f = cells.load_json(ROOT / c["file"])
        assert f["name"] == c["name"] and f["source"] == c["source"]
        assert f["reduced"] == c["reduced"]


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        cells.resolve("no.such.cell")


def _run(cwd, *extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         BENCHMARK["workloads"][0]["name"], "--seed", "3", "--seconds", "1",
         "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cpu_device_exits_nonzero_without_a_result():
    p = _run(ROOT)
    assert p.returncode == 2, p.stderr[-2000:]
    assert "needs 1 TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_benchmark_alone_exits_nonzero_without_a_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
