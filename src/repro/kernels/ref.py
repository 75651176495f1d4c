"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

Deliberately written as straight-line jnp (no tiling, no online softmax) so
they are independently-auditable references for tests/test_kernels.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -2.3819763e38


def attention_ref(
    q: jax.Array,          # (B, S, H, d)
    k: jax.Array,          # (B, T, K, d)
    v: jax.Array,          # (B, T, K, d)
    *,
    scale: float,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
) -> jax.Array:
    B, S, H, d = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    qg = q.reshape(B, S, K, G, d).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    logits = jnp.einsum("bskgh,btkh->bkgst", qg, kf) * scale
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)
    q_idx = jnp.arange(S)[:, None]
    k_idx = jnp.arange(T)[None, :]
    mask = jnp.ones((S, T), bool)
    if causal:
        mask &= k_idx <= q_idx
    if window:
        mask &= (q_idx - k_idx) < window
    logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", p, vf)
    return out.reshape(B, S, H, d).astype(q.dtype)


def attention_policy_ref(
    q: jax.Array,          # (B, S, H, d)
    k: jax.Array,          # (B, T, K, d)
    v: jax.Array,          # (B, T, K, d)
    *,
    scale: float,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    policy=None,
) -> jax.Array:
    """attention_ref with the q·kᵀ and p·v matmuls routed through the
    mixed-precision policy (repro.quant.quant_matmul) — the CPU/ref-impl
    realization of the same dtype choices the Pallas kernels make per tile.

    Differentiable: quant_matmul is a straight-through custom_vjp whose
    backward matmuls run under the same policy, so ref-impl training on CPU
    exercises genuinely quantized forward *and* backward matmuls (coord
    checks and loss-parity tests measure the real policy, not f32).
    """
    from repro.quant.core import quant_matmul

    B, S, H, d = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    kf = jnp.repeat(k, G, axis=2)                       # (B, T, H, d)
    vf = jnp.repeat(v, G, axis=2)
    qt = q.transpose(0, 2, 1, 3).astype(jnp.float32)    # (B, H, S, d)
    kt = kf.transpose(0, 2, 3, 1).astype(jnp.float32)   # (B, H, d, T)
    vt = vf.transpose(0, 2, 1, 3).astype(jnp.float32)   # (B, H, T, d)
    mm = jax.vmap(jax.vmap(lambda a, b: quant_matmul(a, b, policy)))
    logits = mm(qt, kt) * scale                         # (B, H, S, T)
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)
    q_idx = jnp.arange(S)[:, None]
    k_idx = jnp.arange(T)[None, :]
    mask = jnp.ones((S, T), bool)
    if causal:
        mask &= k_idx <= q_idx
    if window:
        mask &= (q_idx - k_idx) < window
    logits = jnp.where(mask[None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = mm(p, vt)                                     # (B, H, S, d)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def _gather_kv(k_pages, v_pages, tab, k_scale, v_scale):
    """Gather kv-head-major (N, K, P, d) pages to f32 (B, C, P, K, d) bands,
    dequantizing int8 pools with their per-page-per-head scales when
    given."""
    k = k_pages[tab].astype(jnp.float32)                # (B, C, K, P, d)
    v = v_pages[tab].astype(jnp.float32)
    if k_scale is not None:
        k = k * k_scale[tab][..., None, None]
        v = v * v_scale[tab][..., None, None]
    return k.swapaxes(2, 3), v.swapaxes(2, 3)


def decode_attention_ref(
    q: jax.Array,            # (B, H, d) — one query per decode slot
    k_pages: jax.Array,      # (N, K, P, d) — paged KV pool
    v_pages: jax.Array,      # (N, K, P, d)
    pos_pages: jax.Array,    # (N, P) int32 token positions; -1 = empty
    page_table: jax.Array,   # (B, C) int32 page ids per slot
    q_pos: jax.Array,        # (B,) int32 query positions; -1 = inactive slot
    *,
    scale,
    window: int = 0,
    softcap: float = 0.0,
    k_scale=None,            # (N, K) f32 per-page-per-head scales (int8 pools)
    v_scale=None,
) -> jax.Array:
    """Single-query attention over a paged KV cache (the flash-decode oracle).

    Gathers each slot's pages into a contiguous (C*P) band and masks by the
    *stored* token positions: an entry is visible iff pos >= 0, pos <= q_pos
    and (windowed) q_pos - pos < window.  Fully-masked rows (inactive slots,
    q_pos = -1) return exact zeros — same contract as the Pallas kernel,
    whose running denominator stays 0 for such rows.

    With ``k_scale``/``v_scale`` the pools hold int8 blocks: entries are
    dequantized after the gather with the same f32 math the kernel uses
    in-VMEM (``int8 · per-page-per-head scale``), so kernel-vs-ref stays in
    the tight tolerance tier even on quantized pools.
    """
    B, H, d = q.shape
    N, K, P, _ = k_pages.shape
    C = page_table.shape[1]
    G = H // K
    tab = jnp.clip(page_table, 0, N - 1)
    k, v = _gather_kv(k_pages, v_pages, tab, k_scale, v_scale)
    k = k.reshape(B, C * P, K, d)
    v = v.reshape(B, C * P, K, d)
    pos = pos_pages[tab].reshape(B, C * P)
    mask = (pos >= 0) & (pos <= q_pos[:, None])
    if window:
        mask &= (q_pos[:, None] - pos) < window
    qg = q.reshape(B, K, G, d).astype(jnp.float32)
    logits = jnp.einsum("bkgd,btkd->bkgt", qg, k) * scale
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)
    logits = jnp.where(mask[:, None, None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    # all-masked rows: NEG_INF is finite so softmax is uniform, not NaN —
    # zero it so inactive slots contribute exact 0s (kernel contract)
    p = jnp.where(mask[:, None, None, :], p, 0.0)
    out = jnp.einsum("bkgt,btkd->bkgd", p, v)
    return out.reshape(B, H, d).astype(q.dtype)


def decode_attention_multi_ref(
    q: jax.Array,            # (B, T, H, d) — T queries per decode slot
    k_pages: jax.Array,      # (N, K, P, d) — paged KV pool
    v_pages: jax.Array,      # (N, K, P, d)
    pos_pages: jax.Array,    # (N, P) int32 token positions; -1 = empty
    page_table: jax.Array,   # (B, C) int32 page ids per slot
    q_pos: jax.Array,        # (B, T) int32 per-query positions; -1 = masked
    *,
    scale,
    window: int = 0,
    softcap: float = 0.0,
    k_scale=None,            # (N, K) f32 per-page-per-head scales (int8 pools)
    v_scale=None,
) -> jax.Array:
    """Multi-query paged attention (the speculative verify/catch-up oracle).

    Same visibility contract as decode_attention_ref, applied per query row:
    entry visible to query t iff pos >= 0, pos <= q_pos[:, t] and (windowed)
    q_pos[:, t] - pos < window.  Rows with q_pos = -1 (inactive slots, or
    leading context positions before the start of a short prompt) return
    exact zeros.  Causality *within* the new chunk is handled by the same
    rule, because the engine writes the chunk into the pages before
    attending: a chunk entry at position p is visible only to chunk queries
    at positions >= p.
    """
    B, T, H, d = q.shape
    N, K, P, _ = k_pages.shape
    C = page_table.shape[1]
    G = H // K
    tab = jnp.clip(page_table, 0, N - 1)
    k, v = _gather_kv(k_pages, v_pages, tab, k_scale, v_scale)
    k = k.reshape(B, C * P, K, d)
    v = v.reshape(B, C * P, K, d)
    pos = pos_pages[tab].reshape(B, C * P)
    mask = (pos[:, None, :] >= 0) & (pos[:, None, :] <= q_pos[:, :, None])
    if window:
        mask &= (q_pos[:, :, None] - pos[:, None, :]) < window
    qg = q.reshape(B, T, K, G, d).astype(jnp.float32)
    logits = jnp.einsum("btkgd,bskd->bkgts", qg, k) * scale
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(mask[:, None, None], p, 0.0)
    out = jnp.einsum("bkgts,bskd->btkgd", p, v)
    return out.reshape(B, T, H, d).astype(q.dtype)


def rmsnorm_ref(x: jax.Array, gain: jax.Array, eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps) * (1.0 + gain.astype(jnp.float32))
    return y.astype(x.dtype)


def softmax_cross_entropy_ref(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Per-position CE, f32: logsumexp(logits) - logits[label].

    Negative (masked) labels are clamped to 0 — callers zero those positions
    out themselves (the ops/model contract).  Deliberately materializes the
    straight-line log-softmax math the chunked kernel avoids.
    """
    x = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(x, axis=-1)
    safe = jnp.clip(labels, 0, logits.shape[-1] - 1)
    picked = jnp.take_along_axis(x, safe[..., None], axis=-1)[..., 0]
    return lse - picked
