"""The reduction from a profiler trace to the per-layer numbers, on a trace
recorded on a TPU v5 lite: three train steps of a 4-layer smollm-360m at
batch 2 x 2048, each inside a ``bench.train_step`` span."""
from pathlib import Path

import pytest

from harness import xplane

TRACE = Path(__file__).parent / "data" / "train_4layers.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return xplane.summarize(TRACE)


def test_window_and_busy(summary):
    assert summary.devices == 1
    assert summary.window_s == pytest.approx(0.19367587, rel=1e-9)
    assert summary.busy_s == pytest.approx(0.186726666, rel=1e-9)
    # ops on the line never overlap here, so own times add up to busy time
    assert sum(summary.op_self_s.values()) == pytest.approx(summary.busy_s)


def test_flash_kernels_found_by_name(summary):
    is_flash = lambda n: n.startswith("_attention_jit")  # noqa: E731
    # forward, dq and dkv kernels, 4 layers, 3 steps
    assert summary.kernel_calls(is_flash) == 36
    assert summary.kernel_s(is_flash) == pytest.approx(0.094324043, rel=1e-6)
    assert summary.kernel_s(lambda n: n == "no such kernel") is None


def test_idle_gaps_labelled_by_bench_spans(summary):
    idle = summary.idle_by_span
    assert idle["train_step"] == pytest.approx(0.0069492, rel=1e-4)
    total = sum(b - a for a, b, _ in summary.gaps)
    assert total == pytest.approx(summary.window_s - summary.busy_s)
    bd = summary.breakdown()
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) <= 10
    assert bd["device_ops"][0][0] == "_attention_jit.32"
    assert [s for _, s in bd["device_ops"]] == sorted(
        (s for _, s in bd["device_ops"]), reverse=True)


def test_self_times_of_nested_ops():
    # a while loop [0, 10) holding two ops; a lone op after it
    own, count = xplane._self_times(
        [("while", 0, 10), ("a", 1, 3), ("b", 4, 8), ("c", 12, 13)])
    assert own == {"while": 4, "a": 2, "b": 4, "c": 1}
    assert count == {"while": 1, "a": 1, "b": 1, "c": 1}


def test_merge_and_op_name():
    assert xplane._merge([(5, 7), (0, 2), (1, 3), (3, 4)]) == [[0, 4], [5, 7]]
    assert xplane.op_name("%fusion.12 = bf16[2]{0} fusion(%p)") == "fusion.12"
    assert xplane.op_name("while.3") == "while.3"
