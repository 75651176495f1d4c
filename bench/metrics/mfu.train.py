"""Model FLOPs of the window's training steps over the window times the
cell's chips times one chip's bf16 peak (bench/peaks.json): the share of
each chip's peak; layer: model step (models/).  The float32 sweep is held
to the bf16 peak too."""
from harness import cells, flops


def read(run):
    if run.kind not in ("train", "sweep") or not run.peaks:
        return None
    c, t = run.cell.config, run.cell.traffic
    rows = run.tokens / t["seq_len"]
    work = rows * flops.train_flops_per_sequence(cells.family(run.cell), c,
                                                 t["seq_len"])
    return 100.0 * work / (run.window_s * run.cell.chips
                           * run.peaks["bf16_flops"])
