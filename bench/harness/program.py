"""The system under test, built from a configuration file.

The configuration file holds the published sizes; this maps them onto the
program's ``ModelConfig`` (every size set from the file, none left to the
program's defaults), so the program runs the configuration as the file
states it.
"""
from __future__ import annotations


def model_config(c: dict, **overrides):
    """The program's ``ModelConfig`` for configuration file ``c``."""
    from repro.configs import get_config

    m = c["mup"]
    if c["hidden_act"] != "silu":
        raise ValueError(f"unsupported hidden_act {c['hidden_act']!r}")
    cfg = get_config(c["repo_arch"]).replace(
        name=c["name"],
        n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        d_head=c["head_dim"],
        d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"],
        pattern=("attn",),
        tail=(),
        act="silu_glu",
        norm_eps=c["rms_norm_eps"],
        rope_theta=c["rope_theta"],
        tie_embeddings=c["tie_word_embeddings"],
        max_seq_len=c["max_position_embeddings"],
        parametrization=m["parametrization"],
        base_d_model=m["base_hidden_size"],
        base_n_heads=m["base_num_attention_heads"],
        base_n_kv_heads=m["base_num_key_value_heads"],
        base_d_head=m["base_head_dim"],
        base_d_ff=m["base_intermediate_size"],
        sigma=m["sigma"],
        alpha_output=m["alpha_output"],
        alpha_attn=m["alpha_attn"],
        alpha_embed=m["alpha_embed"],
        dtype=c["dtype"],
        eos_token_id=-1,
    )
    return cfg.replace(**overrides) if overrides else cfg

