"""Flash attention Pallas TPU kernel — forward and recomputation backward.

TPU-native design (not a CUDA port):
  - HBM -> VMEM tiling via BlockSpec: q tile (bq, d_head), k/v tiles
    (bk, d_head); the MXU sees (bq x d) @ (d x bk) and (bq x bk) @ (bk x d)
    matmuls.  Tiles come from the shapes (``choose_tiles``): the largest
    power-of-two multiple of 128 that divides the sequence, up to a cap
    that fits VMEM; with a sliding window, bk no wider than the window
    needs.  Each grid step costs a fixed fraction of a microsecond whatever
    it computes, so wide tiles mean few steps.
  - online softmax with running (m, l, acc) carried in VMEM scratch across
    the kv grid dimension (TPU grids iterate the last dim sequentially, so
    scratch accumulation is well-defined — this replaces the CUDA warp-level
    reduction structure with grid-sequential accumulation).
  - causal + sliding-window masking by block skipping (pl.when) plus an
    intra-block iota mask; fully-masked tile pairs are never computed, and
    in the dk/dv kernel never fetched either: its q/do/lse/delta index maps
    clamp an invisible q tile's block index to the nearest visible one, and
    Pallas skips the copy of a block index that does not change between
    grid steps.  The forward and dq kernels keep plain k/v maps, and every
    computed causal or windowed tile pair builds its mask: clamped k/v maps
    there, and a mask-free path for wholly visible pairs, measured no
    faster on a v5e.
  - gemma2 attention-logit softcap and muP 1/d scaling folded in (scale is
    an argument — Definition 4.1 is a compile-time constant here).
  - GQA: the kv-head block index is derived from the q-head grid index.

Backward (Dao et al. 2022 style, recomputation-based):
  - the forward additionally emits the per-row logsumexp ``lse = m + log l``
    (shape (B, H, S, 1)); softmax probabilities are *recomputed* blockwise in
    the backward kernels as ``p = exp(logits - lse)`` instead of stashing
    the (S, T) matrix — O(S) residual memory instead of O(S^2).
  - dq kernel: grid (B, H, nq, nk) — for each q tile, accumulate
    ``dq += ds @ k`` over kv tiles in VMEM scratch.
  - dk/dv kernel: grid (B, K, nk, G, nq) — for each kv tile, accumulate
    ``dv += p^T @ do`` and ``dk += ds^T @ q`` over the (group, q-tile)
    inner dims, summing the G query heads of a GQA group in-kernel so the
    dk/dv written to HBM are already per kv head.
  - ``delta = rowsum(do * o)`` (the softmax-jacobian correction) is a cheap
    elementwise reduce done in plain jnp between the two kernels.
  - softcap backward: the tanh derivative is computed from the *pre-mask*
    logits so masked positions contribute exactly 0 (never NaN via
    0 * inf).

``flash_attention`` is differentiable: it carries a ``jax.custom_vjp``
whose forward saves (q, k, v, o, lse) and whose backward runs the two
Pallas kernels above.  Validated — values and gradients — against
kernels/ref.py (pure jnp oracle) in interpret=True mode on CPU across
shape/dtype sweeps (tests/test_kernels.py, tests/test_kernel_grads.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.quant.core import kernel_dot

NEG_INF = -2.3819763e38


def _block_visible(q_start, k_start, bq, bk, causal: bool, window: int):
    """Whether any (q, k) pair in the tile pair is visible (trace-time expr)."""
    needed = True
    if causal:
        needed = k_start <= q_start + bq - 1
    if window:
        in_window = (k_start + bk - 1) >= (q_start - window + 1)
        needed = jnp.logical_and(needed, in_window) if causal else in_window
    return needed


def _tile_mask(q_start, k_start, bq, bk, causal: bool, window: int):
    """(bq, bk) bool visibility mask for one tile pair, causal or windowed
    (T is a multiple of bk, so no key lies past the sequence; attention
    that is neither needs no mask)."""
    q_idx = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_idx = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    dist = q_idx - k_idx
    mask = dist >= 0 if causal else dist < window
    if causal and window:
        mask &= dist < window
    return mask


def _q_block(ki, qi, *, bq, bk, nq, causal, window):
    """The q/do/lse/delta block index dk/dv grid step (ki, qi) fetches: qi
    clamped to the q tiles that see k tile ki, so a step with nothing
    visible names a block Pallas already holds, and no copy is made."""
    if causal:
        qi = jnp.maximum(qi, jnp.minimum((ki * bk) // bq, nq - 1))
    if window:
        qi = jnp.minimum(qi, (ki * bk + bk + window - 2) // bq)
    return qi


def tile_plan(S, T, bq, bk, *, causal: bool, window: int):
    """(computed, total) tile pairs of one (batch, head): those the kernels
    compute, and all."""
    computed = sum(
        bool(_block_visible(q_start, k_start, bq, bk, causal, window))
        for q_start in range(0, S, bq) for k_start in range(0, T, bk))
    return computed, (S // bq) * (T // bk)


# Tile caps, from a sweep of the kernels alone on a v5e at SmolLM-360M's
# training shapes (benchmarks/flash_tiles.py; PERF.md).  512 x 512 fits
# Mosaic's default scoped VMEM in all three kernels at every head width up
# to 256, f32 or bf16, so the caps depend on neither.
MAX_BLOCK_Q = 512
MAX_BLOCK_K = 512


def _pow2_tile(n: int, cap: int) -> int:
    """The largest power-of-two multiple of 128 that divides n, at most cap;
    min(128, n) where 128 does not divide n (a short sequence is one tile,
    and an untileable one is left for the caller to refuse)."""
    if n % 128:
        return min(128, n)
    t = 128
    while t * 2 <= cap and n % (t * 2) == 0:
        t *= 2
    return t


def choose_tiles(S, T, *, window, block_q=None, block_k=None):
    """(bq, bk) for S queries over T keys: an explicit block, clipped to its
    sequence, else the shape rule — tiles as wide as divide the sequence,
    because each grid step costs a fixed time whatever it computes; under
    a sliding window bk no wider than the smallest power-of-two multiple of
    128 that holds the window, so few columns beyond it are fetched."""
    if block_q is None:
        block_q = _pow2_tile(S, MAX_BLOCK_Q)
    if block_k is None:
        cap = MAX_BLOCK_K
        if window:
            cap = min(cap, max(128, 1 << (window - 1).bit_length()))
        block_k = _pow2_tile(T, cap)
    return min(block_q, S), min(block_k, T)


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
    *, scale: float, causal: bool, window: int, softcap: float,
    bq: int, bk: int, nk: int, policy=None,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    q_start = qi * bq
    k_start = ki * bk

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # block-level skip: no k in this block is visible from any q in the q
    # block (strictly above the diagonal, or entirely left of the window)
    needed = _block_visible(q_start, k_start, bq, bk, causal, window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)          # (bk, d)
        s = kernel_dot(q, k.T, policy) * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        if causal or window:
            mask = _tile_mask(q_start, k_start, bq, bk, causal, window)
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                 # (bq, 1)
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                              # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + kernel_dot(p, v, policy)
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[...]
        out = acc_ref[...] / jnp.maximum(l, 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)
        lse = m_ref[...] + jnp.log(jnp.maximum(l, 1e-30))
        lse_ref[0, 0] = lse


def _recompute_p_ds(
    q, k, v, do, lse_row, delta_row, q_start, k_start,
    *, scale, causal, window, softcap, bq, bk, policy=None,
):
    """Shared backward tile math: recompute p and ds = dL/d(pre-cap logits).

    All inputs f32: q/do (bq, d), k/v (bk, d), lse_row/delta_row (bq, 1).
    Returns (p, ds), both (bq, bk).  Matmuls (the q.kT recompute and dp =
    do.vT) run under the mixed-precision policy — the recomputed logits use
    the *same* quantized dot as the forward, so p matches the saved lse.
    """
    s = kernel_dot(q, k.T, policy) * scale
    if softcap:
        t = jnp.tanh(s / softcap)
        s = softcap * t
    p = jnp.exp(s - lse_row)
    if causal or window:
        # p is exactly the forward softmax: exp(masked logits - lse); masked
        # entries are exp(NEG_INF - lse) = 0, written explicitly to avoid
        # overflow paths.
        mask = _tile_mask(q_start, k_start, bq, bk, causal, window)
        p = jnp.where(mask, p, 0.0)
    dp = kernel_dot(do, v.T, policy)
    ds = p * (dp - delta_row)
    if softcap:
        # d tanh-cap: derivative from the *pre-mask* tanh, finite everywhere;
        # masked positions already have ds = 0 via p = 0.
        ds = ds * (1.0 - t * t)
    return p, ds * scale


def _flash_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref,
    *, scale: float, causal: bool, window: int, softcap: float,
    bq: int, bk: int, nk: int, policy=None,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    q_start = qi * bq
    k_start = ki * bk

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    needed = _block_visible(q_start, k_start, bq, bk, causal, window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse_row = lse_ref[0, 0]
        delta_row = delta_ref[0, 0]
        _, ds = _recompute_p_ds(
            q, k, v, do, lse_row, delta_row, q_start, k_start,
            scale=scale, causal=causal, window=window, softcap=softcap,
            bq=bq, bk=bk, policy=policy,
        )
        acc_ref[...] += kernel_dot(ds, k, policy)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc_ref, dv_acc_ref,
    *, scale: float, causal: bool, window: int, softcap: float,
    bq: int, bk: int, nq: int, n_group: int, policy=None,
):
    ki = pl.program_id(2)
    gi = pl.program_id(3)
    qi = pl.program_id(4)
    q_start = qi * bq
    k_start = ki * bk

    @pl.when(jnp.logical_and(gi == 0, qi == 0))
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    needed = _block_visible(q_start, k_start, bq, bk, causal, window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse_row = lse_ref[0, 0]
        delta_row = delta_ref[0, 0]
        p, ds = _recompute_p_ds(
            q, k, v, do, lse_row, delta_row, q_start, k_start,
            scale=scale, causal=causal, window=window, softcap=softcap,
            bq=bq, bk=bk, policy=policy,
        )
        dv_acc_ref[...] += kernel_dot(p.T, do, policy)
        dk_acc_ref[...] += kernel_dot(ds.T, q, policy)

    @pl.when(jnp.logical_and(gi == n_group - 1, qi == nq - 1))
    def _finalize():
        dk_ref[0, 0] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc_ref[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------
# The kernels see heads-major arrays — q/o/do (B, H, S, d), k/v (B, K, T, d),
# lse/delta (B, H, S, 1) — so every block's last two dims are a whole
# (rows, d) tile, the only shape the TPU compiler accepts for a one-head
# block.  The (B, S, H, d) <-> heads-major transposes happen outside
# pallas_call, once per call, in _flash_fn.

def _fwd_call(q, k, v, *, scale, causal, window, softcap, bq, bk, interpret,
              policy=None):
    B, H, S, d = q.shape
    K, T = k.shape[1], k.shape[2]
    G = H // K
    nq, nk = S // bq, T // bk
    kernel = functools.partial(
        _flash_kernel,
        scale=scale, causal=causal, window=window, softcap=softcap,
        bq=bq, bk=bk, nk=nk, policy=policy,
    )
    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, h, qi, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, h, qi, ki: (b, h // G, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, qi, ki: (b, h, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, d), q.dtype),
            jax.ShapeDtypeStruct((B, H, S, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),   # acc
            pltpu.VMEM((bq, 1), jnp.float32),   # m (running max)
            pltpu.VMEM((bq, 1), jnp.float32),   # l (running denom)
        ],
        interpret=interpret,
    )(q, k, v)


def _bwd_dq_call(
    q, k, v, do, lse, delta, *, scale, causal, window, softcap, bq, bk,
    interpret, policy=None,
):
    B, H, S, d = q.shape
    K, T = k.shape[1], k.shape[2]
    G = H // K
    nq, nk = S // bq, T // bk
    kernel = functools.partial(
        _flash_bwd_dq_kernel,
        scale=scale, causal=causal, window=window, softcap=softcap,
        bq=bq, bk=bk, nk=nk, policy=policy,
    )
    q_spec = pl.BlockSpec((1, 1, bq, d), lambda b, h, qi, ki: (b, h, qi, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, bk, d), lambda b, h, qi, ki: (b, h // G, ki, 0)
    )
    row_spec = pl.BlockSpec((1, 1, bq, 1), lambda b, h, qi, ki: (b, h, qi, 0))
    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, S, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)


def _bwd_dkv_call(
    q, k, v, do, lse, delta, *, scale, causal, window, softcap, bq, bk,
    interpret, policy=None,
):
    B, H, S, d = q.shape
    K, T = k.shape[1], k.shape[2]
    G = H // K
    nq, nk = S // bq, T // bk
    kernel = functools.partial(
        _flash_bwd_dkv_kernel,
        scale=scale, causal=causal, window=window, softcap=softcap,
        bq=bq, bk=bk, nq=nq, n_group=G, policy=policy,
    )
    q_block = functools.partial(
        _q_block, bq=bq, bk=bk, nq=nq, causal=causal, window=window)
    q_spec = pl.BlockSpec(
        (1, 1, bq, d),
        lambda b, kh, ki, g, qi: (b, kh * G + g, q_block(ki, qi), 0),
    )
    kv_spec = pl.BlockSpec(
        (1, 1, bk, d), lambda b, kh, ki, g, qi: (b, kh, ki, 0)
    )
    row_spec = pl.BlockSpec(
        (1, 1, bq, 1),
        lambda b, kh, ki, g, qi: (b, kh * G + g, q_block(ki, qi), 0),
    )
    return pl.pallas_call(
        kernel,
        grid=(B, K, nk, G, nq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, K, T, d), jnp.float32),
            jax.ShapeDtypeStruct((B, K, T, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),   # dk accumulator
            pltpu.VMEM((bk, d), jnp.float32),   # dv accumulator
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)


# ---------------------------------------------------------------------------
# custom_vjp plumbing
# ---------------------------------------------------------------------------

def _heads_major(x):
    """(B, S, H, d) <-> (B, H, S, d); its own inverse."""
    return x.transpose(0, 2, 1, 3)


@functools.lru_cache(maxsize=None)
def _flash_fn(scale, causal, window, softcap, bq, bk, interpret, policy=None):
    """A differentiable flash-attention closure for one static config.

    Cached so repeated calls with the same static config reuse one
    custom_vjp instance (and its jaxpr cache entries).  ``policy`` (a
    hashable quant.QuantPolicy) joins the cache key: changing precision
    builds a different kernel closure, it never retraces an existing one —
    that is the jit-stability contract of the mixed-precision policy.
    """
    kw = dict(
        scale=scale, causal=causal, window=window, softcap=softcap,
        bq=bq, bk=bk, interpret=interpret, policy=policy,
    )

    @jax.custom_vjp
    def fn(q, k, v):
        o, _ = fwd(q, k, v)
        return o

    def fwd(q, k, v):
        qt, kt, vt = _heads_major(q), _heads_major(k), _heads_major(v)
        ot, lse = _fwd_call(qt, kt, vt, **kw)
        return _heads_major(ot), (qt, kt, vt, ot, lse)

    def bwd(res, do):
        qt, kt, vt, ot, lse = res
        dot = _heads_major(do)
        # softmax-jacobian correction, rowsum(do * o): cheap elementwise
        # reduce in plain jnp, laid out (B, H, S, 1) to match lse tiles.
        delta = jnp.sum(
            dot.astype(jnp.float32) * ot.astype(jnp.float32), axis=-1,
            keepdims=True,
        )
        dq = _bwd_dq_call(qt, kt, vt, dot, lse, delta, **kw)
        dk, dv = _bwd_dkv_call(qt, kt, vt, dot, lse, delta, **kw)
        return (
            _heads_major(dq).astype(qt.dtype),
            _heads_major(dk).astype(kt.dtype),
            _heads_major(dv).astype(vt.dtype),
        )

    fn.defvjp(fwd, bwd)
    return fn


def flash_attention(
    q: jax.Array,          # (B, S, H, d)
    k: jax.Array,          # (B, T, K, d)
    v: jax.Array,          # (B, T, K, d)
    *,
    scale: float,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
    policy=None,
) -> jax.Array:
    """Pallas flash attention, differentiable (custom_vjp backward kernels);
    shapes must tile (S % block_q == 0 etc. after internal clamping).  A
    block left ``None`` comes from ``choose_tiles``.  Use
    kernels.ops.attention for the auto-fallback wrapper.

    ``policy`` routes every tile matmul (q.kT, p.v, and the dq/dk/dv
    recompute matmuls) through quant.kernel_dot with per-tile dynamic
    scales; master weights and the online-softmax state stay f32."""
    B, S, H, d = q.shape
    T, K = k.shape[1], k.shape[2]
    assert H % K == 0
    bq, bk = choose_tiles(S, T, window=window, block_q=block_q,
                          block_k=block_k)
    assert S % bq == 0 and T % bk == 0, (S, T, bq, bk)
    fn = _flash_fn(
        float(scale), bool(causal), int(window), float(softcap),
        bq, bk, bool(interpret), policy,
    )
    return fn(q, k, v)
