"""Model FLOPs of the prompt and output tokens processed in the window over
the window times the cell's chips times one chip's bf16 peak; layer: model
step (models/)."""
from harness import cells, flops


def read(run):
    if run.kind != "serve" or not run.peaks:
        return None
    s = run.serve
    work = flops.serve_flops(cells.family(run.cell), run.cell.config,
                             s["lens"], s["lengths"])
    return 100.0 * work / (run.window_s * run.cell.chips
                           * run.peaks["bf16_flops"])
