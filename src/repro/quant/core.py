"""Quantize/dequantize primitives and policy-routed matmuls.

Two matmul entry points with one semantics:

  - :func:`kernel_dot` — a plain function usable *inside* Pallas kernel
    bodies (and in interpret mode).  Per-row scales on the left operand,
    per-column scales on the right, both computed dynamically at the tile.
    No custom_vjp: the flash-attention factory already owns the backward
    pass and routes each backward tile matmul through ``kernel_dot`` too.
  - :func:`quant_matmul` — a straight-through ``custom_vjp`` wrapper for
    plain-jnp call sites (readout/CE logit matmul, ref-impl attention).
    Forward runs the policy's quantized dot; backward runs the *same
    policy* on dX = g·Wᵀ and dW = Xᵀ·g (FP8-LM style), with the
    round-to-nearest treated as identity (straight-through estimator).

Scales are dynamic per call — nothing is stored, so there is no scale
state to manage at this layer (the KV cache, which *does* persist bytes,
owns its scales in :mod:`repro.quant.kv`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

INT8_MAX = 127.0
_EPS = 1e-12


def quantize_int8(x: jax.Array, axis=-1) -> tuple[jax.Array, jax.Array]:
    """Symmetric int8 quantization with absmax/127 scales along ``axis``.

    Returns ``(q, scale)`` with ``q`` int8 and ``scale`` f32 keeping the
    reduced axis as size 1, so ``q * scale`` broadcasts back.
    """
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=axis, keepdims=True) / INT8_MAX
    q = jnp.round(xf / jnp.maximum(scale, _EPS))
    q = jnp.clip(q, -INT8_MAX, INT8_MAX).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    """Inverse of :func:`quantize_int8`: ``q * scale`` in f32."""
    return q.astype(jnp.float32) * scale


def kernel_dot(a: jax.Array, b: jax.Array, policy=None) -> jax.Array:
    """Policy-routed 2-D matmul ``a @ b`` with f32 output.

    ``"none"`` → f32 dot; ``"bf16"`` → bf16 operands, f32 accumulate;
    ``"int8"`` → per-row (a) / per-column (b) dynamic scales, int32
    accumulate, f32 rescale.  Safe inside Pallas kernel bodies.
    """
    mode = getattr(policy, "matmul", "none") if policy is not None else "none"
    if mode == "bf16":
        a, b = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
        if jax.default_backend() == "cpu":
            # XLA's CPU backend has no bf16 x bf16 -> f32 dot; products of
            # bf16 values are exact in f32, so upcasting gives the same sum
            a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return jax.lax.dot(a, b, preferred_element_type=jnp.float32)
    if mode == "int8":
        qa, sa = quantize_int8(a, axis=1)  # (m, k) -> scales (m, 1)
        qb, sb = quantize_int8(b, axis=0)  # (k, n) -> scales (1, n)
        acc = jax.lax.dot(qa, qb, preferred_element_type=jnp.int32)
        return acc.astype(jnp.float32) * sa * sb
    return jax.lax.dot(
        a.astype(jnp.float32), b.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )


@functools.lru_cache(maxsize=None)
def _quant_matmul_fn(policy):
    """Straight-through scaled matmul for a fixed policy (2-D operands)."""

    @jax.custom_vjp
    def matmul(x, w):
        return kernel_dot(x, w, policy)

    def fwd(x, w):
        return kernel_dot(x, w, policy), (x, w)

    def bwd(res, g):
        x, w = res
        dx = kernel_dot(g, w.T, policy)
        dw = kernel_dot(x.T, g, policy)
        return dx.astype(x.dtype), dw.astype(w.dtype)

    matmul.defvjp(fwd, bwd)
    return matmul


def quant_matmul(x: jax.Array, w: jax.Array, policy=None) -> jax.Array:
    """Policy-routed matmul ``x @ w`` with straight-through gradients.

    ``x`` may have leading batch dims (collapsed to rows); ``w`` is 2-D.
    With no active policy this is a plain f32 matmul (still f32 output).
    """
    lead = x.shape[:-1]
    x2 = x.reshape((-1, x.shape[-1]))
    out = _quant_matmul_fn(policy if policy is not None else None)(x2, w)
    return out.reshape(lead + (w.shape[-1],))
