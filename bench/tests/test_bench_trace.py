"""The reduction from a profiler trace to the per-layer numbers, on a trace
recorded on a TPU v5 lite: three train steps of a 4-layer smollm-360m at
batch 2 x 2048, each inside a ``bench.train_step`` span; and two train
steps of the four-chip cell's step at 2 of its 24 layers, recorded on a
TPU v5e-4 host by ``record_trace.py`` (kept compressed)."""
import lzma
import types
from pathlib import Path

import pytest

from harness import cells, xplane

TRACE = Path(__file__).parent / "data" / "train_4layers.xplane.pb"
FSDP4_TRACE = (Path(__file__).parent / "data"
               / "train_fsdp4_2layers.xplane.pb.xz")


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


def test_collectives_share_of_each_chip():
    # four planes' own times added: two chips each 2 s in gathers and 1 s in
    # reductions, two others 1 s and 0.5 s, over a 10 s window
    four = xplane.Summary(
        window_s=10.0, busy_s=9.0, devices=4,
        op_self_s={"all-gather-start.3": 2.0, "all-gather-done.3": 4.0,
                   "reduce-scatter.7": 1.0, "all-reduce-done.1": 2.0,
                   "all-gather-fusion.2": 0.0, "fusion.12": 20.0,
                   "_attention_jit.3": 5.0, "copy-start.4": 1.0},
        op_count={}, idle_by_span={}, gaps=[],
        op_calls={"fusion.12": "fused_computation.3"})
    run = types.SimpleNamespace(kind="train", trace=four)
    read = cells.metric_reader("collectives.train")
    assert read(run) == pytest.approx(100.0 * 9.0 / 4 / 10.0)
    # a trace with no collective, a run with no trace, a serving run: nothing
    alone = xplane.Summary(window_s=10.0, busy_s=9.0, devices=1,
                           op_self_s={"fusion.12": 9.0}, op_count={},
                           idle_by_span={}, gaps=[])
    assert read(types.SimpleNamespace(kind="train", trace=alone)) is None
    assert read(types.SimpleNamespace(kind="train", trace=None)) is None
    assert read(types.SimpleNamespace(kind="serve", trace=four)) is None


# op names and called computations as the TPU compiler gives them in the
# four-chip cell's step (compiled for v5e:2x2), and in the one-chip trace
@pytest.mark.parametrize("name, calls, collective", [
    ("all-gather.57", "", True),
    ("all-reduce.17", "", True),
    ("collective-permute-start", "", True),
    ("collective-permute-done.17", "", True),
    ("async-collective-start", "fused_computation.7", True),
    ("async-collective-done.3", "fused_computation.9", True),
    ("fusion.568", "async_collective_fusion.568.clone", True),
    ("fusion.9", "all-reduce-scatter", True),
    ("fusion.493", "fused_computation.92.clone.clone", False),
    ("maximum_bitcast_fusion.49", "fused_computation.314", False),
    ("copy-start.27", "", False),
    ("slice-start.10", "async_computation.10", False),
    ("_attention_jit.3", "", False),
])
def test_collective_names(name, calls, collective):
    reader = cells._module(cells.BENCH / "metrics" / "collectives.train.py")
    assert reader.is_collective(name, calls) is collective


def test_op_calls_reads_the_called_computation():
    assert xplane.op_calls(
        "%fusion.9 = f32[12320,2048]{1,0:T(8,128)} fusion(%fusion.1), "
        "kind=kCustom, calls=%all-reduce-scatter, metadata={op_name=\"x\"}"
    ) == "all-reduce-scatter"
    assert xplane.op_calls("%all-reduce.17 = f32[8]{0} all-reduce(%p), "
                           "to_apply=%region_9.23") == ""
    s = xplane.summarize(TRACE)
    assert s.op_calls and all(s.op_calls.values())


@pytest.fixture(scope="module")
def fsdp4(tmp_path_factory):
    path = tmp_path_factory.mktemp("fsdp4") / "train.xplane.pb"
    path.write_bytes(lzma.decompress(FSDP4_TRACE.read_bytes()))
    return xplane.summarize(path)


def test_collectives_on_a_four_chip_trace(fsdp4):
    assert fsdp4.devices == 4
    read = cells.metric_reader("collectives.train")
    share = read(types.SimpleNamespace(kind="train", trace=fsdp4))
    assert share == pytest.approx(17.5148, abs=1e-3)
    # most of it is the fusions that call an async collective (the FSDP
    # gathers); the gradient's reduction is the fusion that calls
    # all-reduce-scatter
    reader = cells._module(cells.BENCH / "metrics" / "collectives.train.py")
    by_calls = {}
    for name, s in fsdp4.op_self_s.items():
        calls = fsdp4.op_calls.get(name, "")
        if reader.is_collective(name, calls):
            kind = calls.split(".")[0] if calls.startswith(
                ("async_collective", "all-reduce")) else name.split(".")[0]
            by_calls[kind] = by_calls.get(kind, 0.0) + s
    assert max(by_calls, key=by_calls.get) == "async_collective_fusion"
    assert {"all-gather", "all-reduce-scatter",
            "collective-permute-start"} <= set(by_calls)


def test_four_chip_trace_is_busy_on_every_chip(fsdp4):
    # the window's busy time is the mean over the four planes
    assert 0.8 < fsdp4.busy_s / fsdp4.window_s <= 1.0
