"""Model FLOPs counted from the configuration's shapes, not from the
compiled program: a later change to the program is read against the same
work.

A family (``cells.family``) counts its matmul parameters and one token's
attention; this adds them up.  A token at position p (counted from 0)
attends to p + 1 positions.
"""
from __future__ import annotations


def forward_flops(fam, c: dict, context: int) -> float:
    return 2.0 * fam.matmul_params(c) + fam.attention_flops(c, context)


def train_flops_per_sequence(fam, c: dict, seq_len: int) -> float:
    """Forward and backward (3x forward) of one causal training row."""
    mean_context = (seq_len + 1) / 2.0
    return 3.0 * seq_len * forward_flops(fam, c, mean_context)


def serve_flops(fam, c: dict, prompt_lens, out_lens) -> float:
    """Prefill of every prompt and one forward per generated token after
    the first (which the prefill's last position gives)."""
    n_mm = fam.matmul_params(c)
    total = 0.0
    for p, g in zip(prompt_lens, out_lens):
        p, g = int(p), int(g)
        total += p * 2.0 * n_mm + fam.attention_flops(c, 1) * p * (p + 1) / 2
        n = max(g - 1, 0)       # decode forwards at positions p .. p + n - 1
        total += n * 2.0 * n_mm
        total += fam.attention_flops(c, 1) * (n * (p + 1) + n * (n - 1) / 2)
    return total
