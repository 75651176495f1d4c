"""Model FLOPs of the window's training steps over the window times the
chip's bf16 peak (bench/peaks.json); layer: model step (models/).  The
float32 sweep is held to the bf16 peak too."""
from harness import flops


def read(run):
    if run.kind not in ("train", "sweep") or not run.peaks:
        return None
    t = run.cell.traffic
    rows = run.tokens / t["seq_len"]
    work = rows * flops.train_flops_per_sequence(run.cell.config, t["seq_len"])
    return 100.0 * work / (run.window_s * run.peaks["bf16_flops"])
