"""jit'd wrappers with TPU/interpret/reference dispatch.

The model code calls these; on TPU they run the Pallas kernels, on CPU they
either interpret the kernel (tests) or fall back to the jnp reference
(everything else, incl. the dry-run, which lowers pure XLA).

Dispatch contract (shared by every op here):

  impl="auto"       pallas on TPU, ref elsewhere.  ``REPRO_KERNELS`` in the
                    environment overrides the auto resolution (the CI
                    interpret job sets ``REPRO_KERNELS=interpret`` so kernel
                    *bodies* — not just the refs — run on every PR).  Off
                    TPU, shapes the kernel cannot tile fall back to ref.  On
                    TPU such a shape is a ValueError: the kernel is the main
                    path there, and a silent swap would run (and time) the
                    jnp reference in its place.
  impl="pallas"     the compiled Pallas kernel, or ValueError if the shape
                    does not tile.  Never a silent ref fallback — a test
                    that requests the kernel must fail loudly rather than
                    pass against the oracle it meant to check.
  impl="interpret"  the same kernel body on the Pallas interpreter (CPU
                    tests); same strict no-fallback rule.
  impl="ref"        the pure-jnp oracle from kernels/ref.py.

Resolution (auto -> concrete) and tileability checks run in thin python
wrappers *outside* the jit boundary, so the jitted inner functions are keyed
on the concrete impl — an ``REPRO_KERNELS`` change can never hit a stale
cache entry compiled for a different impl.

All three ops are differentiable under every impl: the ref path by plain
autodiff, the kernel paths via the custom_vjp backward kernels in their
modules (flash_attention.py, rmsnorm.py, cross_entropy.py).

``RESOLVED`` records, per op, the impl its latest call resolved to (after
any fallback), so a run can print and check which path actually ran; and,
under ``attention_tiles``, the tile plan of the latest attention call if it
ran a kernel: ``"<bq>x<bk> computed <c>/<n>"``, the tile pairs of one
(batch, head) the kernels compute, out of all of them.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as shd
from repro.kernels import cross_entropy as ce
from repro.kernels import decode_attention as da
from repro.kernels import flash_attention as fa
from repro.kernels import ref
from repro.kernels import rmsnorm as rn

_IMPLS = ("auto", "pallas", "interpret", "ref")

# op name -> impl its latest call resolved to (written at trace time)
RESOLVED: dict[str, str] = {}


# ---------------------------------------------------------------------------
# mesh-aware dispatch (shard_map around the Pallas kernels)
# ---------------------------------------------------------------------------
# pallas_call lowers to an opaque custom call that GSPMD cannot partition —
# left alone inside a sharded jit it would force every operand to be gathered
# into one replicated kernel instance per device.  When a sharding context
# (distributed.sharding.shardings) is active, the wrappers below instead run
# the kernel body under shard_map with the partitioning that keeps it
# collective-free:
#
#   attention    (attn_batch, heads)  each shard owns whole (b, h) attention
#                                     problems; kv heads partition alongside
#                                     q heads so GQA groups stay intact
#   decode       (slots, kv_heads)    each shard serves its own slots'
#                                     queries against its own kv-heads' page
#                                     blocks (q's head layout is kv-major, so
#                                     contiguous H partitioning = contiguous
#                                     K partitioning); page tables and stored
#                                     positions replicate per model shard
#   CE / rmsnorm (rows,)              rows over the batch axes; vocab /
#                                     feature dims stay whole per shard
#
# On a mesh of several devices every kernel call goes through shard_map,
# even when none of its dims partitions (then every device runs the whole
# op on replicated operands): a bare pallas_call inside a jit over several
# devices cannot be partitioned, and the TPU compiler refuses it.
#
# Axis resolution reuses logical_to_spec, so divisibility fallbacks and the
# at-most-once mesh-axis rule match with_sharding_constraint exactly.  The
# ref impl never takes these paths: plain jnp partitions fine under GSPMD.
# The shard_map decision runs in the un-jitted outer wrappers (the nested
# jits stay keyed on the static impl alone), so it is re-taken at every
# enclosing trace and a context change can never hit a stale cache entry.

def _mesh_axes(logical_axes, shape):
    """(mesh, per-dim mesh-axis entries) under the active sharding context,
    or None when there is no context or its mesh is a single device."""
    ctx = shd.current_context()
    if ctx is None or ctx[0].size == 1:
        return None
    mesh, rules = ctx
    try:
        spec = shd.logical_to_spec(mesh, rules, logical_axes, shape)
    except KeyError:
        spec = P()
    # logical_to_spec strips trailing Nones (jit-cache normalization); pad
    # back to one entry per dim so callers can unpack positionally
    entries = tuple(spec) + (None,) * (len(logical_axes) - len(tuple(spec)))
    return mesh, entries


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve_impl(impl: str) -> str:
    """auto -> concrete impl (env override first, then backend)."""
    if impl not in _IMPLS:
        raise ValueError(f"impl must be one of {_IMPLS}, got {impl!r}")
    if impl == "auto":
        impl = os.environ.get("REPRO_KERNELS", "auto")
        if impl not in _IMPLS:
            raise ValueError(f"REPRO_KERNELS must be one of {_IMPLS}")
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "ref"
    return impl


def _reject_untileable(op: str, impl: str, requested: str, detail: str) -> None:
    """Kernels never silently fall back to ref when asked for explicitly, or
    on TPU, where the kernel is the main path."""
    if requested == "auto" and not _on_tpu():
        return  # caller asked for "a correct answer": ref is fine
    raise ValueError(
        f"ops.{op}: impl={impl!r} (requested {requested!r}, backend "
        f"{jax.default_backend()!r}) but the shape does not tile ({detail}); "
        f"refusing to silently fall back to the jnp reference. Fix the "
        f"block size, or request impl='ref' explicitly."
    )


def _shard_map(fn, mesh, in_specs, out_specs):
    # pallas_call outputs carry no varying-manual-axes annotation, so the
    # replication check cannot type them: it is off, as the specs say
    # exactly how every operand partitions
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def _shard_map_attention(
    impl, q, k, v, scale, *, causal, window, softcap, block_q, block_k, policy
):
    """Kernel flash-attention under shard_map, or None to use the plain path."""
    B, H, K = q.shape[0], q.shape[2], k.shape[2]
    resolved = _mesh_axes(("attn_batch", "heads"), (B, H))
    if resolved is None:
        return None
    mesh, (b_ax, h_ax) = resolved
    if h_ax is not None and K % shd.mesh_axis_size(mesh, h_ax) != 0:
        # kv heads must partition identically to q heads or GQA groups would
        # straddle shards; fall back to batch-only partitioning
        h_ax = None
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
    kernel = functools.partial(
        fa.flash_attention, scale=1.0, causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_k=block_k,
        interpret=(impl == "interpret"), policy=policy,
    )
    spec = P(b_ax, None, h_ax, None)
    return _shard_map(kernel, mesh, (spec, spec, spec), spec)(qs, k, v)


def _shard_map_decode(
    multi, impl, q, k_pages, v_pages, pos_pages, page_table, q_pos, scale,
    k_scale, v_scale, *, window, softcap,
):
    """Flash-decode under shard_map, or None to use the plain path.

    Collective-free by construction: every shard runs the full online
    softmax for its own (slot, kv-head) sub-problems — no cross-shard
    reduction exists because attention never mixes information across heads
    or across batch rows.
    """
    B, K = q.shape[0], k_pages.shape[1]
    resolved = _mesh_axes(("slots", "kv_heads"), (B, K))
    if resolved is None:
        return None
    mesh, (slot_ax, kv_ax) = resolved
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
    fn = da.flash_decode_multi if multi else da.flash_decode
    interpret = impl == "interpret"

    def kernel(q_, kp, vp, pp, pt, qp, ks=None, vs=None):
        return fn(
            q_, kp, vp, pp, pt, qp, scale=1.0, window=window,
            softcap=softcap, k_scale=ks, v_scale=vs, interpret=interpret,
        )

    q_spec = (
        P(slot_ax, None, kv_ax, None) if multi else P(slot_ax, kv_ax, None)
    )
    pool_spec = P(None, kv_ax, None, None)
    in_specs = [
        q_spec, pool_spec, pool_spec, P(None, None), P(slot_ax, None),
        P(slot_ax, None) if multi else P(slot_ax),
    ]
    args = [qs, k_pages, v_pages, pos_pages, page_table, q_pos]
    if k_scale is not None:
        in_specs += [P(None, kv_ax), P(None, kv_ax)]
        args += [k_scale, v_scale]
    return _shard_map(kernel, mesh, tuple(in_specs), q_spec)(*args)


def _row_axis(lead: int):
    """(mesh, batch-rule mesh axes) for partitioning a leading row dim of
    size ``lead`` (CE / rmsnorm), or None to use the plain path."""
    resolved = _mesh_axes(("batch",), (lead,))
    if resolved is None:
        return None
    mesh, (b_ax,) = resolved
    return mesh, b_ax


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "softcap", "block_q", "block_k", "impl", "policy",
    ),
)
def _attention_jit(
    q, k, v, scale, *, causal, window, softcap, block_q, block_k, impl, policy
):
    if impl == "ref":
        if policy is not None and policy.active:
            return ref.attention_policy_ref(
                q, k, v, scale=scale, causal=causal, window=window,
                softcap=softcap, policy=policy,
            )
        return ref.attention_ref(
            q, k, v, scale=scale, causal=causal, window=window, softcap=softcap
        )
    # fold the (possibly traced) scale into q; softmax(q@kT * c) == softmax(
    # (q*c)@kT), and the multiply stays outside the custom_vjp so autodiff
    # routes d(scale) automatically.
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
    return fa.flash_attention(
        qs, k, v, scale=1.0, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, interpret=(impl == "interpret"),
        policy=policy,
    )


def attention(
    q, k, v, *, scale, causal: bool = True, window: int = 0,
    softcap: float = 0.0, block_q: int | None = None,
    block_k: int | None = None, impl: str = "auto", policy=None,
):
    """Flash attention with GQA/causal/sliding-window/softcap.

    ``block_q``/``block_k`` left ``None`` take the kernel's shape rule
    (``flash_attention.choose_tiles``).  The shard_map path splits only
    batch and heads, so each shard's S and T, and so its tiles, are the
    whole call's.

    ``scale`` may be a traced scalar (the vmap sweep engine threads
    alpha_attn through it): the kernel path folds it into q ahead of the
    Pallas call, whose internal scale stays the compile-time constant 1.

    ``policy`` (a quant.QuantPolicy, static) selects the matmul precision:
    the kernel paths run each tile matmul through quant.kernel_dot with
    per-tile dynamic scales; the ref path uses the straight-through
    attention_policy_ref so the same dtype choices apply under every impl.
    """
    requested = impl
    impl = _resolve_impl(impl)
    S, T = q.shape[1], k.shape[1]
    bq, bk = fa.choose_tiles(S, T, window=window, block_q=block_q,
                             block_k=block_k)
    if impl != "ref" and (S % bq or T % bk):
        _reject_untileable(
            "attention", impl, requested,
            f"S={S}, T={T} vs blocks ({bq}, {bk})",
        )
        impl = "ref"
    if policy is not None and not policy.active:
        policy = None
    RESOLVED["attention"] = impl
    RESOLVED.pop("attention_tiles", None)
    if impl != "ref":
        c, n = fa.tile_plan(S, T, bq, bk, causal=causal, window=window)
        RESOLVED["attention_tiles"] = f"{bq}x{bk} computed {c}/{n}"
        out = _shard_map_attention(
            impl, q, k, v, scale, causal=causal, window=window,
            softcap=softcap, block_q=bq, block_k=bk, policy=policy,
        )
        if out is not None:
            return out
    return _attention_jit(
        q, k, v, scale, causal=causal, window=window, softcap=softcap,
        block_q=bq, block_k=bk, impl=impl, policy=policy,
    )


# ---------------------------------------------------------------------------
# decode attention (paged, single query)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("window", "softcap", "impl"))
def _decode_attention_jit(
    q, k_pages, v_pages, pos_pages, page_table, q_pos, scale,
    k_scale, v_scale, *, window, softcap, impl,
):
    if impl == "ref":
        return ref.decode_attention_ref(
            q, k_pages, v_pages, pos_pages, page_table, q_pos,
            scale=scale, window=window, softcap=softcap,
            k_scale=k_scale, v_scale=v_scale,
        )
    # fold the (possibly traced) scale into q, as ops.attention does — the
    # kernel's internal scale stays the compile-time constant 1.
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
    return da.flash_decode(
        qs, k_pages, v_pages, pos_pages, page_table, q_pos,
        scale=1.0, window=window, softcap=softcap,
        k_scale=k_scale, v_scale=v_scale,
        interpret=(impl == "interpret"),
    )


def decode_attention(
    q, k_pages, v_pages, pos_pages, page_table, q_pos, *, scale,
    window: int = 0, softcap: float = 0.0,
    k_scale=None, v_scale=None, impl: str = "auto",
):
    """Flash-decode: single-query attention over a paged KV cache.

    ``q`` (B, H, d), pools (N, K, P, d) + (N, P) stored positions,
    ``page_table`` (B, C), ``q_pos`` (B,) (-1 = inactive slot -> zeros).
    With ``k_scale``/``v_scale`` ((N, K) f32) the pools hold int8 blocks,
    dequantized in-kernel (or post-gather in the ref oracle) by their
    per-page-per-head scales.  Pages are whole-block fetches — every shape
    tiles, no fallback needed.
    """
    impl = _resolve_impl(impl)
    RESOLVED["decode_attention"] = impl
    if impl != "ref":
        out = _shard_map_decode(
            False, impl, q, k_pages, v_pages, pos_pages, page_table, q_pos,
            scale, k_scale, v_scale, window=window, softcap=softcap,
        )
        if out is not None:
            return out
    return _decode_attention_jit(
        q, k_pages, v_pages, pos_pages, page_table, q_pos, scale,
        k_scale, v_scale,
        window=window, softcap=softcap, impl=impl,
    )


# ---------------------------------------------------------------------------
# decode attention (paged, multi-query: speculative verify / drafter catch-up)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("window", "softcap", "impl"))
def _decode_attention_multi_jit(
    q, k_pages, v_pages, pos_pages, page_table, q_pos, scale,
    k_scale, v_scale, *, window, softcap, impl,
):
    if impl == "ref":
        return ref.decode_attention_multi_ref(
            q, k_pages, v_pages, pos_pages, page_table, q_pos,
            scale=scale, window=window, softcap=softcap,
            k_scale=k_scale, v_scale=v_scale,
        )
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
    return da.flash_decode_multi(
        qs, k_pages, v_pages, pos_pages, page_table, q_pos,
        scale=1.0, window=window, softcap=softcap,
        k_scale=k_scale, v_scale=v_scale,
        interpret=(impl == "interpret"),
    )


def decode_attention_multi(
    q, k_pages, v_pages, pos_pages, page_table, q_pos, *, scale,
    window: int = 0, softcap: float = 0.0,
    k_scale=None, v_scale=None, impl: str = "auto",
):
    """Multi-query flash-decode: a T-token chunk per slot attends over the
    paged KV cache (speculative-decoding verify and drafter catch-up).

    ``q`` (B, T, H, d), pools (N, K, P, d) + (N, P) stored positions,
    ``page_table`` (B, C), ``q_pos`` (B, T) per-query positions (-1 rows ->
    zeros).  The chunk must already be written into the pages; per-row
    position masking then yields history visibility and intra-chunk
    causality.  Pages are whole-block fetches — every shape tiles.
    ``k_scale``/``v_scale`` select the int8-pool dequant path, as in
    decode_attention.
    """
    impl = _resolve_impl(impl)
    RESOLVED["decode_attention_multi"] = impl
    if impl != "ref":
        out = _shard_map_decode(
            True, impl, q, k_pages, v_pages, pos_pages, page_table, q_pos,
            scale, k_scale, v_scale, window=window, softcap=softcap,
        )
        if out is not None:
            return out
    return _decode_attention_multi_jit(
        q, k_pages, v_pages, pos_pages, page_table, q_pos, scale,
        k_scale, v_scale,
        window=window, softcap=softcap, impl=impl,
    )


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "impl"))
def _rmsnorm_jit(x, gain, *, eps, block_rows, impl):
    if impl == "ref":
        return ref.rmsnorm_ref(x, gain, eps)
    return rn.rmsnorm(
        x, gain, eps=eps, block_rows=block_rows,
        interpret=(impl == "interpret"),
    )


def fused_rmsnorm(x, gain, *, eps: float = 1e-6, block_rows: int = 256,
                  impl: str = "auto"):
    # rmsnorm pads rows internally — every shape tiles, no fallback needed
    impl = _resolve_impl(impl)
    RESOLVED["fused_rmsnorm"] = impl
    if impl != "ref" and x.ndim >= 2:
        resolved = _row_axis(x.shape[0])
        if resolved is not None:
            mesh, b_ax = resolved
            x_spec = P(b_ax, *([None] * (x.ndim - 1)))
            kernel = functools.partial(
                rn.rmsnorm, eps=eps, block_rows=block_rows,
                interpret=(impl == "interpret"),
            )
            return _shard_map(kernel, mesh, (x_spec, P(None)), x_spec)(x, gain)
    return _rmsnorm_jit(x, gain, eps=eps, block_rows=block_rows, impl=impl)


# ---------------------------------------------------------------------------
# softmax cross entropy
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("block_rows", "block_v", "impl"))
def _softmax_xent_jit(logits, labels, *, block_rows, block_v, impl):
    if impl == "ref":
        return ref.softmax_cross_entropy_ref(logits, labels)
    return ce.cross_entropy(
        logits, labels, block_rows=block_rows, block_v=block_v,
        interpret=(impl == "interpret"),
    )


def softmax_cross_entropy(
    logits, labels, *, block_rows: int = 256, block_v: int = 2048,
    impl: str = "auto",
):
    """Per-position softmax CE, f32, shape ``logits.shape[:-1]``.

    Negative (masked) labels are clamped; the caller applies its own mask to
    the returned losses (masked rows then also get zero cotangent, so their
    dlogits vanish).  The kernel path never materializes (B, S, V) log-probs
    — an online logsumexp over vocab chunks (see kernels/cross_entropy.py).
    """
    requested = impl
    impl = _resolve_impl(impl)
    V = logits.shape[-1]
    bv = min(block_v, V)
    if impl != "ref" and V % bv:
        _reject_untileable(
            "softmax_cross_entropy", impl, requested,
            f"V={V} vs vocab chunk {bv}",
        )
        impl = "ref"
    RESOLVED["softmax_cross_entropy"] = impl
    if impl != "ref":
        resolved = _row_axis(logits.shape[0])
        if resolved is not None:
            # rows over the data axes only: each row's loss is independent,
            # and the kernel chunks the (whole, per-shard) vocab internally
            mesh, b_ax = resolved
            l_spec = P(b_ax, *([None] * (logits.ndim - 1)))
            y_spec = P(b_ax, *([None] * (labels.ndim - 1)))
            kernel = functools.partial(
                ce.cross_entropy, block_rows=block_rows, block_v=bv,
                interpret=(impl == "interpret"),
            )
            return _shard_map(kernel, mesh, (l_spec, y_spec), y_spec)(
                logits, labels
            )
    return _softmax_xent_jit(
        logits, labels, block_rows=block_rows, block_v=bv, impl=impl
    )
