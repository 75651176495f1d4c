"""The Llama family: RMSNorm, rotary attention with grouped kv heads,
SiLU-gated MLP, tied readout.

A configuration file names its family (``"family": "llama"``) and
``harness.cells.family`` finds this file by that name.  A family gives the
harness three things:

- ``program_config(c, **overrides)``: the system under test, built from the
  configuration file (every size set from the file, none left to the
  program's defaults), so the program runs the configuration as the file
  states it;
- the plain reference: ``Spec``, ``init``, ``INIT_ORDER``, ``lr_factors``,
  ``hidden``, ``readout`` and ``loss``, in float32 ``jax.numpy`` with every
  matmul through ``reference.mm``.  It imports nothing of the system under
  test;
- ``matmul_params`` and ``attention_flops``: model FLOPs counted from the
  configuration's shapes (``harness.flops`` builds on them).

The reference follows muP's Table-8 rules with a base shape, written from
the published equations.  Where the system makes a choice the equations
leave open, the reference states it and makes the same one:

- norm gains are stored as ``g`` and applied as ``(1 + g)``, zero-initialised;
- rotary embedding rotates the two halves of each head, ``theta ** (-2i/d)``;
- query head ``h`` reads kv head ``h // (H / K)``;
- the gated MLP's input projection holds ``[gate | up]`` side by side;
- weights are drawn leaf by leaf with ``fold_in(key, i)``, ``i`` the leaf's
  index in the sorted parameter tree; query weights start at zero (App. D.2);
- muP Table 8: embedding std ``sigma``, hidden weights ``sigma / sqrt(fan_in)``
  with Adam learning rate divided by ``fan_in / base_fan_in``, readout
  multiplier ``alpha_output * base_d / d``, attention scale
  ``alpha_attn * sqrt(base_head_dim) / head_dim``.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from harness.reference import HP, mm


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def program_config(c: dict, **overrides):
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


# ---------------------------------------------------------------------------
# model FLOPs from the shapes
# ---------------------------------------------------------------------------

def matmul_params(c: dict) -> int:
    """Matmul parameters: the readout (tied to the embedding) counts, the
    embedding lookup does not."""
    d, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    qd = c["num_attention_heads"] * c["head_dim"]
    kvd = c["num_key_value_heads"] * c["head_dim"]
    per_layer = d * qd * 2 + d * kvd * 2 + 3 * d * f
    return c["num_hidden_layers"] * per_layer + d * v


def attention_flops(c: dict, context: int) -> float:
    """Forward FLOPs of one token's attention over ``context`` positions:
    2 FLOPs per multiply-add on q.k and on p.v, in every layer."""
    qd = c["num_attention_heads"] * c["head_dim"]
    return 4.0 * qd * context * c["num_hidden_layers"]


# ---------------------------------------------------------------------------
# the reference: parameters, their shapes, init std and Adam learning rate
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Spec:
    """Sizes and muP base shape of one configuration."""

    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    eps: float
    rope_theta: float
    base_d: int
    base_heads: int
    base_kv_heads: int
    base_head_dim: int
    base_ffn: int

    @classmethod
    def from_config(cls, c: dict) -> "Spec":
        m = c["mup"]
        return cls(
            layers=c["num_hidden_layers"], d=c["hidden_size"],
            heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
            head_dim=c["head_dim"], ffn=c["intermediate_size"],
            vocab=c["vocab_size"], eps=c["rms_norm_eps"],
            rope_theta=c["rope_theta"], base_d=m["base_hidden_size"],
            base_heads=m["base_num_attention_heads"],
            base_kv_heads=m["base_num_key_value_heads"],
            base_head_dim=m["base_head_dim"],
            base_ffn=m["base_intermediate_size"],
        )


def leaf_rules(s: Spec, sigma: float) -> dict:
    """{path: (shape, init std or None for zeros, Adam lr factor)}."""
    L, D, H, K, hd, F = s.layers, s.d, s.heads, s.kv_heads, s.head_dim, s.ffn
    qd, base_qd = H * hd, s.base_heads * s.base_head_dim
    hidden = lambda fan, base: (sigma / math.sqrt(fan), base / fan)
    wk_std, wk_lr = hidden(D, s.base_d)
    wo_std, wo_lr = hidden(qd, base_qd)
    wi_std, wi_lr = hidden(D, s.base_d)
    mo_std, mo_lr = hidden(F, s.base_ffn)
    return {
        ("embed",): ((s.vocab, D), sigma, 1.0),
        ("final_norm",): ((D,), None, 1.0),
        ("layers", "wk"): ((L, D, K, hd), wk_std, wk_lr),
        ("layers", "wo"): ((L, H, hd, D), wo_std, wo_lr),
        ("layers", "wq"): ((L, D, H, hd), None, s.base_d / D),
        ("layers", "wv"): ((L, D, K, hd), wk_std, wk_lr),
        ("layers", "ln1"): ((L, D), None, 1.0),
        ("layers", "ln2"): ((L, D), None, 1.0),
        ("layers", "mlp_wi"): ((L, D, 2 * F), wi_std, wi_lr),
        ("layers", "mlp_wo"): ((L, F, D), mo_std, mo_lr),
    }


# the order in which the system's sorted parameter tree lists these leaves
INIT_ORDER = (
    ("embed",), ("final_norm",), ("layers", "wk"), ("layers", "wo"),
    ("layers", "wq"), ("layers", "wv"), ("layers", "ln1"), ("layers", "ln2"),
    ("layers", "mlp_wi"), ("layers", "mlp_wo"),
)


def init(key, s: Spec, sigma: float = 1.0, dtype=jnp.float32) -> dict:
    """Parameters from ``key``: normal draws times std, rounded to ``dtype``
    (the type they are served in) and held as float32."""
    rules = leaf_rules(s, sigma)
    out: dict = {"layers": {}}
    for i, path in enumerate(INIT_ORDER):
        shape, std, _ = rules[path]
        if std is None:
            w = jnp.zeros(shape, jnp.float32)
        else:
            k = jax.random.fold_in(key, i)
            w = (std * jax.random.normal(k, shape)).astype(dtype)
            w = w.astype(jnp.float32)
        if len(path) == 1:
            out[path[0]] = w
        else:
            out["layers"][path[1]] = w
    return out


def lr_factors(s: Spec) -> dict:
    rules = leaf_rules(s, 1.0)
    out: dict = {"layers": {}}
    for path in INIT_ORDER:
        f = rules[path][2]
        if len(path) == 1:
            out[path[0]] = f
        else:
            out["layers"][path[1]] = f
    return out


# ---------------------------------------------------------------------------
# the reference's forward and loss
# ---------------------------------------------------------------------------

def rmsnorm(x, g, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + g)


def rope(x, pos, theta):
    """x (B, S, N, hd), pos (B, S): rotate the halves of each head."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[..., None, None] * inv
    c, s_ = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s_, x2 * c + x1 * s_], axis=-1)


def layer(x, w, pos, s: Spec, hp: HP):
    """One decoder layer on x (B, S, D); causal attention over the row."""
    B, S, _ = x.shape
    G = s.heads // s.kv_heads
    h = rmsnorm(x, w["ln1"], s.eps)
    q = rope(mm("bsd,dhk->bshk", h, w["wq"]), pos, s.rope_theta)
    k = rope(mm("bsd,dhk->bshk", h, w["wk"]), pos, s.rope_theta)
    v = mm("bsd,dhk->bshk", h, w["wv"])
    scale = hp.alpha_attn * math.sqrt(s.base_head_dim) / s.head_dim
    qg = q.reshape(B, S, s.kv_heads, G, s.head_dim)
    logits = mm("bqkgd,btkd->bkgqt", qg, k) * scale
    causal = pos[:, :, None] >= pos[:, None, :]          # (B, q, t)
    logits = jnp.where(causal[:, None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    o = mm("bkgqt,btkd->bqkgd", p, v).reshape(B, S, s.heads, s.head_dim)
    x = x + mm("bshk,hkd->bsd", o, w["wo"])
    h2 = rmsnorm(x, w["ln2"], s.eps)
    gate, up = jnp.split(mm("bsd,df->bsf", h2, w["mlp_wi"]), 2, axis=-1)
    return x + mm("bsf,fd->bsd", jax.nn.silu(gate) * up, w["mlp_wo"])


def hidden(params, tokens, s: Spec, hp: HP, remat: bool = False):
    """Final-normed hidden states (B, S, D), layer by layer."""
    B, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x = params["embed"][tokens] * hp.alpha_embed
    body = lambda x, w: (layer(x, w, pos, s, hp), None)
    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["layers"])
    return rmsnorm(x, params["final_norm"], s.eps)


def readout(params, h, s: Spec, hp: HP):
    mult = hp.alpha_output * s.base_d / s.d
    return mm("...d,vd->...v", h, params["embed"]) * mult


def loss(params, tokens, labels, s: Spec, hp: HP, chunk: int = 512):
    """Mean next-token cross-entropy; each layer is checkpointed and the
    readout runs in row chunks so the (B, S, V) logits never live whole."""
    h = hidden(params, tokens, s, hp, remat=True)
    B, S, D = h.shape
    c = min(chunk, S)
    hs = h.reshape(B, S // c, c, D).swapaxes(0, 1)
    ls = labels.reshape(B, S // c, c).swapaxes(0, 1)

    @jax.checkpoint
    def piece(args):
        hh, ll = args
        lg = readout(params, hh, s, hp)
        lse = jax.nn.logsumexp(lg, axis=-1)
        pick = jnp.take_along_axis(lg, ll[..., None], axis=-1)[..., 0]
        return jnp.sum(lse - pick)

    return jnp.sum(jax.lax.map(piece, (hs, ls))) / (B * S)
