"""The comparison that decides ``correct`` for serving cells, driven
through the rest of a run at test size on the CPU: sound runs pass, a token
altered where it is produced fails, and the control runs."""
import pytest

from benchcells import run_tiny, tiny_cell
from harness import cells, faults

SERVE = [w["name"] for w in cells.benchmark()["workloads"]
         if cells.resolve(w["name"]).traffic["kind"] == "serve"]


def _checks(out):
    return {k: v["value"] for k, v in out["checks"].items()}


@pytest.mark.parametrize("w", SERVE)
def test_serve_sound_run_is_correct(w):
    out = run_tiny(tiny_cell(w), seconds=0.5)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in cells.resolve(w).end_to_end}


def test_serve_altered_token_is_caught():
    with faults.planted("altered_token"):
        out = run_tiny(tiny_cell(SERVE[0]), seconds=0.5)
    assert not out["correct"]
    assert _checks(out)["served_logit_gap"] > 1.0


def test_serve_traced_run_reports_per_layer_metrics():
    out = run_tiny(tiny_cell(SERVE[0]), seconds=0.5, trace=1)
    assert out["correct"]
    assert "engine_step_ms" in out["metrics"]
    assert "queue_wait_p95_s" in out["metrics"]
    assert "compile_misses" in out["metrics"]
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("control", ["reference"])
def test_serving_control_runs_and_compares(control):
    out = run_tiny(tiny_cell(SERVE[0]), seconds=0.5, control=control)
    assert out["checks"]["served_logit_gap"]["value"] >= 0.0
    assert out["attempted"] >= 1

