"""FLOP and byte counts of the model and the kernels, against numbers
worked out by hand for one shape."""
import importlib.util
from pathlib import Path

import pytest

from harness import cells, flops

METRICS = Path(__file__).resolve().parents[1] / "metrics"
LLAMA = cells.family(cells.resolve("train.smollm-360m.s2048"))


def _metric(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


SMALL = {"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 2,
         "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 4,
         "vocab_size": 10}


def test_matmul_params_by_hand():
    # per layer: q 8x8, o 8x8, k 8x4, v 8x4, gate/up/down 3 x 8x16
    per_layer = 64 + 64 + 32 + 32 + 3 * 128
    assert LLAMA.matmul_params(SMALL) == 2 * per_layer + 80


def test_matmul_params_smollm_360m():
    c = cells.load_json(cells.BENCH / "configs" / "smollm-360m.json")
    # 32 x (960*960*2 + 960*320*2 + 3*960*2560) + 960*49152 (tied readout)
    assert LLAMA.matmul_params(c) == 361_758_720


def test_train_flops_by_hand():
    # 3 x (S x 2N + attention): positions 0..3 attend 1..4 keys, 4*q_dim*L
    # FLOPs per key: 4 * 8 * 2 = 64
    N = LLAMA.matmul_params(SMALL)
    want = 3 * (4 * 2 * N + 64 * (1 + 2 + 3 + 4))
    assert flops.train_flops_per_sequence(LLAMA, SMALL, 4) == pytest.approx(want)


def test_serve_flops_by_hand():
    # prompt 3 tokens (keys 1+2+3), 3 outputs: decode at positions 3, 4
    # (keys 4 + 5)
    N = LLAMA.matmul_params(SMALL)
    want = 3 * 2 * N + 64 * 6 + 2 * 2 * N + 64 * 9
    assert flops.serve_flops(LLAMA, SMALL, [3], [3]) == pytest.approx(want)


def test_flash_attention_work_by_hand():
    m = _metric("flash_attention_roofline.train")
    # B=1, S=4, H=2, hd=8: forward 2*B*H*hd*S^2 = 512, backward twice that
    assert m.flops(1, 4, 2, 8) == 1536
    # q = 64, kv = 32 elements; fwd q+k+v+o, bwd q+k+v+o+do read, dq dk dv
    assert m.bytes_moved(1, 4, 2, 1, 8) == 2 * ((64 + 64 + 64) + (64 * 3 + 64)
                                                + (64 + 64))
    assert m.is_kernel("_attention_jit.31")
    assert not m.is_kernel("jvp_jit__softmax_xent_jit__.1")


def test_decode_attention_work_by_hand():
    m = _metric("decode_attention_roofline.serve")
    # prompt 3, 3 outputs -> decode at positions 3 and 4, contexts 4 and 5;
    # pages of 4 -> 1 page, then 2 pages
    fl, by = m.work(SMALL, 4, [3], [3])
    assert fl == 4 * 2 * 4 * (4 + 5) * 2
    assert by == 2 * (4 + 8) * 1 * 4 * 2 * 2
    assert m.is_kernel("_decode_attention_jit.7")
    assert not m.is_kernel("_decode_attention_multi_jit.2")


def test_peaks_table():
    p = cells.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        cells.peaks("TPU v9 imaginary")


def _train_run(chips, summary=None):
    import types

    c = cells.load_json(cells.BENCH / "configs" / "smollm2-1.7b.json")
    cell = cells.Cell(name="t", config=c, traffic={"seq_len": 2048,
                                                   "batch": 32},
                      chips=chips, end_to_end=[], per_layer=[])
    return types.SimpleNamespace(kind="train", cell=cell, tokens=10 * 32 * 2048,
                                 window_s=40.0, attempted=10, trace=summary,
                                 peaks=cells.peaks("TPU v5 lite"))


def test_mfu_is_a_share_of_each_chips_peak():
    read = cells.metric_reader("mfu.train")
    one, four = read(_train_run(1)), read(_train_run(4))
    assert four == pytest.approx(one / 4, rel=1e-12)
    # 10 steps of 32 x 2048 tokens of SmolLM2-1.7B over 40 s on one chip
    N = LLAMA.matmul_params(_train_run(1).cell.config)
    assert N == 24 * (2048 * 2048 * 4 + 3 * 2048 * 8192) + 2048 * 49152
    want = 320 * flops.train_flops_per_sequence(LLAMA, _train_run(1).cell.config,
                                                2048)
    assert one == pytest.approx(100 * want / (40.0 * 197e12))


def test_flash_roofline_is_per_chip_whatever_the_planes():
    from harness import xplane

    def summary(planes):
        # the same kernels' work split evenly over the planes: each plane's
        # own time is the whole's over their number, and the summary adds
        # the planes' own times
        per_plane = 12.0 / planes
        return xplane.Summary(
            window_s=40.0, busy_s=30.0, devices=planes,
            op_self_s={"_attention_jit.3": per_plane * planes},
            op_count={"_attention_jit.3": 10 * planes}, idle_by_span={},
            gaps=[])

    read = cells.metric_reader("flash_attention_roofline.train")
    one = read(_train_run(1, summary(1)))
    assert 0 < one < 100
    assert read(_train_run(4, summary(4))) == pytest.approx(one, rel=1e-12)
