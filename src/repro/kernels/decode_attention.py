"""Flash-decode Pallas TPU kernel: single-query attention over a paged KV cache.

The serving engine's decode step is one query token per slot attending over
that slot's pages of the shared block pool.  The kernel never materializes a
contiguous per-slot KV view — pages are fetched straight from the pool via a
*scalar-prefetched page table* (pltpu.PrefetchScalarGridSpec): the BlockSpec
index map for the K/V/pos pools reads ``table[b, j]`` to pick the physical
page for logical page j of slot b, so the gather happens in the DMA engine,
not as an HBM->HBM copy.

Design (TPU-native, mirrors kernels/flash_attention.py):
  - grid (B, K, C): slots x kv-heads x logical pages.  The last grid dim is
    iterated sequentially on TPU, so the per-page online-softmax running
    state (m, l, acc) lives in VMEM scratch across it — this *is* the
    split-KV loop of flash-decode, with grid-sequential accumulation
    replacing the CUDA two-pass reduce.
  - the pool is kv-head-major, (N, K, P, d): one (page, kv head) block is
    a whole (P, d) tile, the block shape the TPU compiler accepts.
  - GQA in-kernel: q is laid out (B, K, G, d); each program handles all G
    query heads of one kv head, so the MXU sees a (G x d) @ (d x P) matmul
    and K/V pages are fetched once per group, not once per query head.
  - page-level skipping: pages beyond the slot's live page count
    (q_pos // P, ring-clamped for windowed layers) are never computed
    (pl.when); masking *within* a live page is by the stored per-token
    positions, so ring-buffer wraparound and half-filled pages need no
    special cases.
  - sliding window + gemma2 softcap folded in as compile-time constants.
  - fully-masked rows (inactive slots, q_pos = -1) produce exact zeros: the
    running denominator stays 0 and the finalize divide is guarded.

Validated against kernels/ref.py::decode_attention_ref in interpret mode
(tests/test_decode_attention.py: GQA/MQA x window x softcap sweep).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import NEG_INF


def _decode_kernel(
    tab_ref,      # scalar-prefetch: (B, C) int32 page table
    qpos_ref,     # scalar-prefetch: (B,) int32 query positions (-1 inactive)
    q_ref,        # (1, 1, G, d)
    k_ref,        # (1, 1, P, d) — page picked by the index map via tab_ref
    v_ref,        # (1, 1, P, d)
    pos_ref,      # (1, 1, P) int32 stored token positions of the page
    *rest,        # [ks_ref, vs_ref (1, 1, 1, 1) — int8 pools only,] o_ref, scratch
    scale: float, window: int, softcap: float,
    page: int, n_pages_per_slot: int, kv_quant: bool = False,
):
    if kv_quant:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        (o_ref, acc_ref, m_ref, l_ref), ks_ref, vs_ref = rest, None, None
    b = pl.program_id(0)
    j = pl.program_id(2)
    qp = qpos_ref[b]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # live logical pages: the slot has written pages 0..qp//page; windowed
    # layers clamp to the ring length (every ring slot live once warm).
    n_live = jnp.minimum(n_pages_per_slot, qp // page + 1)
    needed = jnp.logical_and(qp >= 0, j < n_live)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                # (G, d)
        k = k_ref[0, 0].astype(jnp.float32)                # (P, d)
        v = v_ref[0, 0].astype(jnp.float32)                # (P, d)
        if kv_quant:
            # in-kernel dequant: int8 page · per-page-per-head f32 scale —
            # the same math the ref oracle applies after its gather
            k = k * ks_ref[0, 0]
            v = v * vs_ref[0, 0]
        pos = pos_ref[0]                                   # (1, P)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        mask = jnp.logical_and(pos >= 0, pos <= qp)
        if window:
            mask = jnp.logical_and(mask, (qp - pos) < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                 # (G, 1)
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # explicit where: when every entry is masked m_new stays NEG_INF and
        # exp(s - m_new) would be exp(0) = 1 — the mask keeps p at exact 0.
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(j == n_pages_per_slot - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def _decode_multi_kernel(
    tab_ref,      # scalar-prefetch: (B, C) int32 page table
    qmax_ref,     # scalar-prefetch: (B,) int32 latest query position per slot
    q_ref,        # (1, 1, T*G, d) — row t*G + g is query t, head g
    qrow_ref,     # (1, T*G, 1) int32 position of each query row
    k_ref,        # (1, 1, P, d) — page picked by the index map via tab_ref
    v_ref,        # (1, 1, P, d)
    pos_ref,      # (1, 1, P) int32 stored token positions of the page
    *rest,        # [ks_ref, vs_ref (1, 1, 1, 1) — int8 pools only,] o_ref, scratch
    scale: float, window: int, softcap: float,
    page: int, n_pages_per_slot: int, kv_quant: bool = False,
):
    """Multi-query (T > 1) variant of _decode_kernel for speculative verify.

    Identical grid and page streaming; the online-softmax state carries
    T*G rows instead of G, and the per-page visibility mask is applied
    per query row from its own position tag (so the chunk's internal
    causality comes for free — chunk entries carry their positions in the
    page pool by the time the kernel runs).
    """
    if kv_quant:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        (o_ref, acc_ref, m_ref, l_ref), ks_ref, vs_ref = rest, None, None
    b = pl.program_id(0)
    j = pl.program_id(2)
    qp_max = qmax_ref[b]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # live pages are bounded by the *latest* query in the chunk; earlier
    # queries see a subset via their own position mask.
    n_live = jnp.minimum(n_pages_per_slot, qp_max // page + 1)
    needed = jnp.logical_and(qp_max >= 0, j < n_live)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                # (T*G, d)
        k = k_ref[0, 0].astype(jnp.float32)                # (P, d)
        v = v_ref[0, 0].astype(jnp.float32)                # (P, d)
        if kv_quant:
            k = k * ks_ref[0, 0]
            v = v * vs_ref[0, 0]
        pos = pos_ref[0]                                   # (1, P)
        qrow = qrow_ref[0]                                 # (T*G, 1)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        mask = jnp.logical_and(pos >= 0, pos <= qrow)      # (T*G, P)
        if window:
            mask = jnp.logical_and(mask, (qrow - pos) < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                # (T*G, 1)
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(j == n_pages_per_slot - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def _page_specs(P, d, kv_quant):
    """BlockSpecs of one (page, kv-head) fetch: k and v (1, 1, P, d) tiles,
    positions as a (1, 1, P) row, int8 scales as (1, 1, 1, 1) — every
    block's last two dims whole, as the TPU compiler requires.  The page is
    picked by the scalar-prefetched table: ``tab[b, j]``."""
    def page_map(b, kh, j, tab, _):
        return (tab[b, j], kh, 0, 0)

    specs = [
        pl.BlockSpec((1, 1, P, d), page_map),
        pl.BlockSpec((1, 1, P, d), page_map),
        pl.BlockSpec((1, 1, P), lambda b, kh, j, tab, _: (tab[b, j], 0, 0)),
    ]
    if kv_quant:
        specs += [pl.BlockSpec((1, 1, 1, 1), page_map)] * 2
    return specs


def _page_args(k_pages, v_pages, pos_pages, k_scale, v_scale):
    N, _, P, _ = k_pages.shape
    args = [k_pages, v_pages, pos_pages.reshape(N, 1, P)]
    if k_scale is not None:
        args += [
            k_scale.astype(jnp.float32).reshape(*k_scale.shape, 1, 1),
            v_scale.astype(jnp.float32).reshape(*v_scale.shape, 1, 1),
        ]
    return args


def flash_decode(
    q: jax.Array,            # (B, H, d) — one query per slot
    k_pages: jax.Array,      # (N, K, P, d) paged pool, kv-head-major
    v_pages: jax.Array,      # (N, K, P, d)
    pos_pages: jax.Array,    # (N, P) int32; -1 = empty
    page_table: jax.Array,   # (B, C) int32 page ids
    q_pos: jax.Array,        # (B,) int32; -1 = inactive slot -> zeros out
    *,
    scale: float,
    window: int = 0,
    softcap: float = 0.0,
    k_scale: jax.Array | None = None,   # (N, K) f32 — int8 pools
    v_scale: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Paged single-query flash attention; returns (B, H, d).

    With ``k_scale``/``v_scale``, ``k_pages``/``v_pages`` hold int8 blocks
    and each page is dequantized in-kernel (VMEM, right after the DMA the
    page table routed) by its per-page-per-head scale — the scales ride the
    same ``tab[b, j]`` index maps as the pages, so quantization is invisible
    to the allocator and page tables.

    Inference-only (no custom_vjp — nothing backprops through serving).
    Use kernels.ops.decode_attention for the dispatching wrapper.
    """
    B, H, d = q.shape
    N, K, P, _ = k_pages.shape
    C = page_table.shape[1]
    assert H % K == 0, (H, K)
    G = H // K
    qg = q.reshape(B, K, G, d)
    tab = jnp.clip(page_table, 0, N - 1).astype(jnp.int32)
    qp = q_pos.astype(jnp.int32)
    kv_quant = k_scale is not None

    kernel = functools.partial(
        _decode_kernel,
        scale=scale, window=window, softcap=softcap,
        page=P, n_pages_per_slot=C, kv_quant=kv_quant,
    )
    q_spec = pl.BlockSpec((1, 1, G, d), lambda b, kh, j, tab, qp: (b, kh, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, K, C),
        in_specs=[q_spec, *_page_specs(P, d, kv_quant)],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((G, d), jnp.float32),   # acc
            pltpu.VMEM((G, 1), jnp.float32),   # m (running max)
            pltpu.VMEM((G, 1), jnp.float32),   # l (running denom)
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, d), q.dtype),
        interpret=interpret,
    )(tab, qp, qg, *_page_args(k_pages, v_pages, pos_pages, k_scale, v_scale))
    return out.reshape(B, H, d)


def flash_decode_multi(
    q: jax.Array,            # (B, T, H, d) — T queries per slot
    k_pages: jax.Array,      # (N, K, P, d) paged pool, kv-head-major
    v_pages: jax.Array,      # (N, K, P, d)
    pos_pages: jax.Array,    # (N, P) int32; -1 = empty
    page_table: jax.Array,   # (B, C) int32 page ids
    q_pos: jax.Array,        # (B, T) int32; -1 rows -> zeros out
    *,
    scale: float,
    window: int = 0,
    softcap: float = 0.0,
    k_scale: jax.Array | None = None,   # (N, K) f32 — int8 pools
    v_scale: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Paged multi-query flash attention (speculative verify / drafter
    catch-up); returns (B, T, H, d).

    The T-token chunk must already be written into the pages (the engine
    writes before attending), so per-row position masking gives both the
    history visibility and the chunk's internal causality.  Scales, when
    given, dequantize int8 pages in-kernel exactly as in flash_decode.
    """
    B, T, H, d = q.shape
    N, K, P, _ = k_pages.shape
    C = page_table.shape[1]
    assert H % K == 0, (H, K)
    G = H // K
    # (B, K, T*G, d): all T queries of one kv head in a single program so
    # K/V pages stream once per (slot, kv head), same as the T=1 kernel.
    # The row flattening happens here, not in the kernel, so the kernel
    # never reshapes across its sublane dim.
    qg = q.reshape(B, T, K, G, d).transpose(0, 2, 1, 3, 4).reshape(
        B, K, T * G, d
    )
    tab = jnp.clip(page_table, 0, N - 1).astype(jnp.int32)
    qp = q_pos.astype(jnp.int32)
    qrow = jnp.repeat(qp, G, axis=1)[..., None]            # (B, T*G, 1)
    kv_quant = k_scale is not None

    kernel = functools.partial(
        _decode_multi_kernel,
        scale=scale, window=window, softcap=softcap,
        page=P, n_pages_per_slot=C, kv_quant=kv_quant,
    )
    q_spec = pl.BlockSpec(
        (1, 1, T * G, d), lambda b, kh, j, tab, qm: (b, kh, 0, 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, K, C),
        in_specs=[
            q_spec,
            pl.BlockSpec((1, T * G, 1), lambda b, kh, j, tab, qm: (b, 0, 0)),
            *_page_specs(P, d, kv_quant),
        ],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((T * G, d), jnp.float32),   # acc
            pltpu.VMEM((T * G, 1), jnp.float32),   # m (running max)
            pltpu.VMEM((T * G, 1), jnp.float32),   # l (running denom)
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, T * G, d), q.dtype),
        interpret=interpret,
    )(
        tab, jnp.max(qp, axis=1), qg, qrow,
        *_page_args(k_pages, v_pages, pos_pages, k_scale, v_scale),
    )
    return out.reshape(B, K, T, G, d).transpose(0, 2, 1, 3, 4).reshape(
        B, T, H, d
    )
