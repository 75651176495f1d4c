"""Per-page-per-head scale management for the int8 paged KV cache.

Pool layout (see ``repro.serving.kv_cache``): k/v leaves are
``(..., n_pages, kv_heads, page_size, head_dim)``; the quantized pools
add f32 scale leaves ``(..., n_pages, kv_heads)`` — one scale per page
per kv head, shared by every token and head-dim lane in that page.  That
granularity is what clears the ~2x byte budget: per-page scales cost
``4·K`` bytes against ``2·K·hd·P`` of int8 payload, where per-token
scales would cost ``4·K·P`` and eat the win at small head dims.

Scale lifecycle (enforced by kv_cache, stated here because quant owns
the invariant): a page's scale only *grows* while the page is live
(scatter-max on write; existing bytes are requantized when it grows),
and is zeroed when the allocator invalidates the page.  Evicted/shared
pages carry their scales with them — the scale pool is indexed by the
same physical page id as the payload, so page-table indirection moves
both or neither.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.quant.core import INT8_MAX, _EPS


def abs_scale(x: jax.Array) -> jax.Array:
    """Per-page-per-head absmax/127 scales for a ``(..., K, P, hd)`` pool.

    Reduces the page (token) and head-dim axes, returning ``(..., K)``.
    """
    xf = jnp.abs(x.astype(jnp.float32))
    return jnp.max(xf, axis=(-2, -1)) / INT8_MAX


def pack_kv(
    k: jax.Array, v: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Quantize k/v pools ``(..., N, K, P, hd)`` to int8 + per-page scales.

    Returns ``(k_q, v_q, k_scale, v_scale)`` with scales ``(..., N, K)``.
    """
    k_scale = abs_scale(k)
    v_scale = abs_scale(v)
    k_q = quantize_with(k, k_scale)
    v_q = quantize_with(v, v_scale)
    return k_q, v_q, k_scale, v_scale


def quantize_with(x: jax.Array, scale: jax.Array) -> jax.Array:
    """Round ``(..., K, P, hd)`` values to int8 using ``(..., K)`` scales."""
    s = jnp.maximum(scale, _EPS)[..., None, None]
    q = jnp.round(x.astype(jnp.float32) / s)
    return jnp.clip(q, -INT8_MAX, INT8_MAX).astype(jnp.int8)


def unpack_kv(
    k_q: jax.Array, v_q: jax.Array, k_scale: jax.Array, v_scale: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Dequantize int8 pools back to f32 (the ref-oracle view)."""
    k = k_q.astype(jnp.float32) * k_scale[..., None, None]
    v = v_q.astype(jnp.float32) * v_scale[..., None, None]
    return k, v
