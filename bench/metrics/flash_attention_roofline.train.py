"""Flash attention's share of its roofline in training: the least time its
algorithmic work needs at the chip's peaks over the device time of its
forward and backward kernels in the trace; layer: kernels
(kernels/flash_attention.py, called through ops._attention_jit).

Work is counted from the shapes of causal attention, not from the kernels:
forward q.k and p.v over the causal half (2 * B * H * hd * S**2 FLOPs), and
backward twice that (dq, dk, dv and dp; recomputing p is not counted).
Bytes are q, k, v and o read or written once forward, and q, k, v, o, do
read and dq, dk, dv written once backward, in the activation type."""


def is_kernel(name: str) -> bool:
    return name.startswith("_attention_jit")


def flops(B, S, H, hd):
    fwd = 2.0 * B * H * hd * S * S
    return 3.0 * fwd


def bytes_moved(B, S, H, K, hd, itemsize=2):
    q, kv = B * S * H * hd, B * S * K * hd
    fwd = (q + 2 * kv + q) * itemsize
    bwd = (q + 2 * kv + q + q) * itemsize + (q + 2 * kv) * itemsize
    return float(fwd + bwd)


def read(run):
    if run.kind != "train" or run.trace is None or not run.peaks:
        return None
    t_kernel = run.trace.kernel_s(is_kernel)
    if not t_kernel:
        return None
    c, t = run.cell.config, run.cell.traffic
    B, S = t["batch"], t["seq_len"]
    H, K, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                c["head_dim"])
    calls = run.attempted * c["num_hidden_layers"]
    t_flops = calls * flops(B, S, H, hd) / run.peaks["bf16_flops"]
    t_bytes = calls * bytes_moved(B, S, H, K, hd) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * max(t_flops, t_bytes) / t_kernel
