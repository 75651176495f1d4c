"""The comparison that decides ``correct`` for training cells (train and
sweep), driven through the rest of a run at test size on the CPU: sound runs
pass, each fault a training cell can have, planted in the program underneath
the harness, fails it, and the control reads above the sound run."""
import pytest

from benchcells import run_tiny, tiny_cell
from harness import faults

TRAIN = "train.smollm-360m.s2048"
SWEEP = "sweep.smollm-360m-proxy.n16"


def _checks(out):
    return {k: v["value"] for k, v in out["checks"].items()}


@pytest.mark.parametrize("w", [TRAIN, SWEEP])
def test_training_sound_run_is_correct(w):
    out = run_tiny(tiny_cell(w))
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("w", [TRAIN, SWEEP])
def test_training_state_left_unchanged_is_caught(w):
    with faults.planted("frozen_state"):
        out = run_tiny(tiny_cell(w))
    assert not out["correct"]
    assert _checks(out)["change_norm_gap"] == pytest.approx(1.0)


@pytest.mark.parametrize("w", [TRAIN, SWEEP])
def test_training_half_batch_is_caught(w):
    with faults.planted("half_batch"):
        out = run_tiny(tiny_cell(w))
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("w", [TRAIN, SWEEP])
def test_training_control_reads_above_the_sound_run(w):
    # the reference with float8 matmul operands in the program's place
    sound = _checks(run_tiny(tiny_cell(w), seed=5))
    ctl = _checks(run_tiny(tiny_cell(w), seed=5, control="reference"))
    assert ctl["change_norm_gap"] > 3 * sound["change_norm_gap"]
