"""The paged decode kernel's share of its roofline while serving: the
least time its algorithmic work needs at the chip's peaks over the device
time of its calls in the trace; layer: kernels (kernels/decode_attention.py,
called through ops._decode_attention_jit; the multi-token kernel that
chunked prefill uses is not counted here).

Work is counted from the live contexts: a token generated at position p
reads the k and v pages that hold positions 0..p (whole pages, since the
kernel reads pages) of every layer and spends 4 * H * hd FLOPs per
position.  Decode forwards of a request with prompt length P and G output
tokens sit at positions P .. P + G - 2."""
import math


def is_kernel(name: str) -> bool:
    return name.startswith("_decode_attention_jit")


def work(c, page, prompt_lens, out_lens, itemsize=2):
    H, K, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                c["head_dim"])
    L = c["num_hidden_layers"]
    fl = by = 0.0
    for P, G in zip(prompt_lens, out_lens):
        for pos in range(int(P), int(P) + max(int(G) - 1, 0)):
            ctx = pos + 1
            fl += 4.0 * H * hd * ctx * L
            by += 2.0 * math.ceil(ctx / page) * page * K * hd * itemsize * L
    return fl, by


def read(run):
    if run.kind != "serve" or run.trace is None or not run.peaks:
        return None
    t_kernel = run.trace.kernel_s(is_kernel)
    if not t_kernel:
        return None
    s, t = run.serve, run.cell.traffic
    fl, by = work(run.cell.config, t["page_size"], s["lens"], s["lengths"])
    least = max(fl / run.peaks["bf16_flops"], by / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t_kernel
