"""Model FLOPs counted from the configuration's shapes, not from the
compiled program: a later change to the program is read against the same
work.

Matmul parameters count the readout (tied to the embedding) and not the
embedding lookup.  A token at position p (counted from 0) attends to p + 1
positions; each of its layers spends 2 FLOPs per multiply-add on q.k and
on p.v.
"""
from __future__ import annotations


def matmul_params(c: dict) -> int:
    d, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    qd = c["num_attention_heads"] * c["head_dim"]
    kvd = c["num_key_value_heads"] * c["head_dim"]
    per_layer = d * qd * 2 + d * kvd * 2 + 3 * d * f
    return c["num_hidden_layers"] * per_layer + d * v


def attention_flops(c: dict, context: int) -> float:
    """Forward FLOPs of one token's attention over ``context`` positions."""
    qd = c["num_attention_heads"] * c["head_dim"]
    return 4.0 * qd * context * c["num_hidden_layers"]


def forward_flops(c: dict, context: int) -> float:
    return 2.0 * matmul_params(c) + attention_flops(c, context)


def train_flops_per_sequence(c: dict, seq_len: int) -> float:
    """Forward and backward (3x forward) of one causal training row."""
    mean_context = (seq_len + 1) / 2.0
    return 3.0 * seq_len * forward_flops(c, mean_context)


def serve_flops(c: dict, prompt_lens, out_lens) -> float:
    """Prefill of every prompt and one forward per generated token after
    the first (which the prefill's last position gives)."""
    total = 0.0
    for p, g in zip(prompt_lens, out_lens):
        p, g = int(p), int(g)
        total += p * 2.0 * matmul_params(c) + attention_flops(c, 1) * p * (p + 1) / 2
        n = max(g - 1, 0)       # decode forwards at positions p .. p + n - 1
        total += n * 2.0 * matmul_params(c)
        total += attention_flops(c, 1) * (n * (p + 1) + n * (n - 1) / 2)
    return total
