"""Slot-mapped paged KV cache: fixed block pool + per-slot page tables.

Layout
------
Each attention layer's cache is a *pool* of fixed-size pages shared by every
decode slot::

    {"k": (N, K, P, hd), "v": (N, K, P, hd), "pos": (N, P) int32}

(``N`` pages of ``P`` tokens, kv-head-major so one (page, kv head) is a
whole ``(P, hd)`` tile for the decode kernels; ``pos`` stores each entry's
token position,
-1 = empty — the same position-tagged convention as the dense cache in
models/attention.py, which remains the train/prefill/oracle path.)  Layers
in the repeated group are stacked over ``n_groups`` on a leading axis, so
the pool pytree drops into ``run_stack``'s scan exactly like the dense
cache.

With ``cfg.kv_dtype == "int8"`` the k/v leaves store quantized blocks and
the pool gains two f32 scale leaves ``{"k_scale", "v_scale"}: (N, K)`` —
one absmax/127 scale per page per kv head (see ``repro.quant.kv``).  A
page's scale only grows while the page is live: every write scatter-maxes
the new tokens' scales into the page, requantizes the page's existing
int8 bytes when the scale grew (round(int · old/new) — exact identity
when it didn't), then writes the new tokens at the final scale.
Invalidation zeroes the scale with the same scatter that clears ``pos``.
Scales are indexed by the same physical page id as the payload, so
page-table indirection (prefix sharing, eviction, re-admission) moves
both or neither — the allocator never learns quantization exists.

Indirection is by *page table*: slot ``s``'s logical page ``j`` lives at
physical page ``table[s, j]``.  Global layers give each slot
``ceil(max_total / P)`` logical pages; sliding-window layers give
``ceil(window / P) + 1`` pages used as a ring (logical page ``t // P`` maps
to table column ``(t // P) % wp``), so a long decode touches O(window)
cache, not O(T).  The +1 page makes wraparound safe: the page being
overwritten only ever holds positions strictly older than the window.

Allocation policy in this PR is static — tables are built once per engine
with pages *interleaved* across slots (slot s's page j = j * n_slots + s),
so correctness genuinely depends on the indirection; admission resets the
slot's pages (pos = -1) instead of popping from a free list.  A dynamic
allocator (prefix sharing, variable budgets) can replace `make_tables`
without touching the kernel, the pool layout, or the transformer.

Writes that must not land (inactive slots, out-of-budget positions, prompt
padding) are redirected to page id ``N`` — one past the pool — and dropped
by JAX's out-of-bounds scatter semantics.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard
from repro.quant.core import INT8_MAX

# block kinds the paged engine can serve (self-attention KV caches only;
# recurrent/ssd/cross-attention states need their own slot caches)
SERVABLE_KINDS = ("attn", "local", "moe", "local_moe")

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32, "int8": jnp.int8}

_SCALE_EPS = 1e-12


def kv_dtype_of(cfg) -> str:
    """Resolved pool storage dtype name: ``cfg.kv_dtype`` overrides
    ``cfg.dtype`` when set (the activation dtype stays untouched)."""
    return getattr(cfg, "kv_dtype", "") or cfg.dtype


def _windowed(kind: str) -> bool:
    return kind.startswith("local")


def check_servable(cfg) -> None:
    bad = [k for k in (*cfg.pattern, *cfg.tail) if k not in SERVABLE_KINDS]
    if bad:
        raise ValueError(
            f"{cfg.name}: paged serving engine supports block kinds "
            f"{SERVABLE_KINDS}, got {bad}; use the dense-loop driver "
            f"(launch/serve.py --dense) for this architecture"
        )


# ---------------------------------------------------------------------------
# geometry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PagedSpec:
    """Static paged-cache geometry for one (config, engine) pair."""

    n_slots: int
    page_size: int
    gp_cols: int           # logical pages per slot, global layers
    wp_cols: int           # ring pages per slot, windowed layers (0 = none)

    @property
    def n_global_pages(self) -> int:
        return self.n_slots * self.gp_cols

    @property
    def n_window_pages(self) -> int:
        return self.n_slots * self.wp_cols


def build_spec(
    cfg, n_slots: int, max_total: int, page_size: int, lookahead: int = 0
) -> PagedSpec:
    """max_total = max prompt + max generation length per request.

    ``lookahead`` is the speculative write-ahead: under draft-k speculation
    writes run up to k positions ahead of the earliest live query in the
    same forward (the verify chunk, and the drafter's catch-up whose queries
    start k positions behind its writes).  The ring must therefore cover
    ``window + lookahead`` positions before wrapping, or a write at position
    p would evict an entry still inside some chunk query's window.
    """
    gp = math.ceil(max_total / page_size)
    wp = 0
    if any(_windowed(k) for k in (*cfg.pattern, *cfg.tail)):
        # +1 ring page: the page being overwritten holds only positions
        # older than the window (wp * P > window + P - 1).  When the window
        # covers the whole budget the ring never wraps — clamp to gp.
        wp = min(gp, math.ceil((cfg.window_size + lookahead) / page_size) + 1)
    return PagedSpec(
        n_slots=n_slots, page_size=page_size, gp_cols=gp, wp_cols=wp
    )


def make_tables(spec: PagedSpec):
    """(global_table (S, gp), window_table (S, wp) or None), interleaved:
    slot s's j-th page is physical page j * S + s of its kind's pool."""
    s = jnp.arange(spec.n_slots, dtype=jnp.int32)[:, None]
    gtab = jnp.arange(spec.gp_cols, dtype=jnp.int32)[None, :] * spec.n_slots + s
    wtab = None
    if spec.wp_cols:
        wtab = (
            jnp.arange(spec.wp_cols, dtype=jnp.int32)[None, :] * spec.n_slots + s
        )
    return gtab, wtab


@dataclasses.dataclass
class PagedState:
    """Runtime handles threaded to the transformer via Ctx.paged."""

    global_table: jax.Array             # (S, gp) int32
    window_table: Optional[jax.Array]   # (S, wp) int32 or None
    active: jax.Array                   # (S,) bool — inactive writes dropped
    page_size: int                      # static


# ---------------------------------------------------------------------------
# pools
# ---------------------------------------------------------------------------

def init_pools(
    cfg,
    spec: PagedSpec,
    n_global: Optional[int] = None,
    n_window: Optional[int] = None,
) -> Dict[str, Any]:
    """Zeroed pool pytree mirroring run_stack's cache layout:
    {"groups": {"<i>_<kind>": {"attn": pool}}, "tail": {...}} with group
    pools stacked over n_groups.

    ``n_global``/``n_window`` override the pool sizes (default: the static
    interleaved geometry ``spec.n_*_pages``) — the dynamic allocator sizes
    pools independently of ``n_slots * cols`` so prefix-cached pages can
    outlive their slot.
    """
    K, hd = cfg.n_kv_heads, cfg.d_head
    kv_name = kv_dtype_of(cfg)
    dtype = _DTYPES[kv_name]

    def pool(n_pages, stacked):
        lead = (cfg.n_groups,) if stacked else ()
        p = {
            "k": jnp.zeros((*lead, n_pages, K, spec.page_size, hd), dtype),
            "v": jnp.zeros((*lead, n_pages, K, spec.page_size, hd), dtype),
            "pos": jnp.full((*lead, n_pages, spec.page_size), -1, jnp.int32),
        }
        if kv_name == "int8":
            p["k_scale"] = jnp.zeros((*lead, n_pages, K), jnp.float32)
            p["v_scale"] = jnp.zeros((*lead, n_pages, K), jnp.float32)
        return p

    def n_pages(kind):
        if _windowed(kind):
            return spec.n_window_pages if n_window is None else n_window
        return spec.n_global_pages if n_global is None else n_global

    # born on the mesh: constrain_pools places the fresh buffers exactly as
    # every later write will, so the first step never reshards them
    return constrain_pools({
        "groups": {
            f"{i}_{kind}": {"attn": pool(n_pages(kind), True)}
            for i, kind in enumerate(cfg.pattern)
        },
        "tail": {
            f"{i}_{kind}": {"attn": pool(n_pages(kind), False)}
            for i, kind in enumerate(cfg.tail)
        },
    })


def constrain_pools(pools: Dict[str, Any]) -> Dict[str, Any]:
    """Assert the canonical pool shardings on an existing pool pytree: pages
    replicate (any slot may own any page), the kv-head dim tensor-parallels
    over the model axis, matching paged_cache_write.  Identity without an
    active sharding context.  The dynamic engine re-asserts this on its step
    outputs so persistent pools carry the same sharding the next step's
    inputs expect (jit cache stability across host-loop iterations)."""

    def one(p, stacked):
        la = ("layers",) if stacked else ()
        q = {
            "k": shard(p["k"], *la, "pages", "kv_heads", None, "head_dim"),
            "v": shard(p["v"], *la, "pages", "kv_heads", None, "head_dim"),
            "pos": shard(p["pos"], *la, "pages", None),
        }
        if "k_scale" in p:
            q["k_scale"] = shard(p["k_scale"], *la, "pages", "kv_heads")
            q["v_scale"] = shard(p["v_scale"], *la, "pages", "kv_heads")
        return q

    return {
        "groups": {
            k: {"attn": one(v["attn"], True)}
            for k, v in pools["groups"].items()
        },
        "tail": {
            k: {"attn": one(v["attn"], False)}
            for k, v in pools["tail"].items()
        },
    }


def pool_bytes(cfg, spec: PagedSpec) -> int:
    """Total paged-pool footprint (all layers), for logging/benchmarks."""
    K, hd = cfg.n_kv_heads, cfg.d_head
    kv_name = kv_dtype_of(cfg)
    itemsize = jnp.dtype(_DTYPES[kv_name]).itemsize
    per_page = spec.page_size * (K * hd * 2 * itemsize + 4)
    if kv_name == "int8":
        per_page += 2 * K * 4      # per-page-per-head f32 scales (k + v)
    kinds = [k for k in cfg.pattern for _ in range(cfg.n_groups)] + list(cfg.tail)
    tot = 0
    for kind in kinds:
        n = spec.n_window_pages if _windowed(kind) else spec.n_global_pages
        tot += n * per_page
    return tot


# ---------------------------------------------------------------------------
# decode write (called from the transformer's decode branch, per layer)
# ---------------------------------------------------------------------------

def paged_cache_write(
    cache: Dict[str, jax.Array],   # {"k": (N,K,P,hd), "v": ..., "pos": (N,P)}
    k_new: jax.Array,              # (B, T, K, hd)
    v_new: jax.Array,
    positions: jax.Array,          # (B, T) int32; -1 = dropped
    table: jax.Array,              # (B, C) int32 — this slot batch's pages
    active: jax.Array,             # (B,) bool
    page_size: int,
    ring: bool,
) -> Dict[str, jax.Array]:
    """Scatter a T-token chunk per slot into its pages; returns new pools.

    T = 1 is the plain decode step; T > 1 is the speculative verify chunk
    and the drafter catch-up.  Chunk positions are consecutive and T is at
    most page-budget tokens, so no two chunk entries alias one (page, off)
    cell (ring aliasing needs positions C*P apart).  Invalid writes
    (inactive slot, pos < 0, past the page budget) go to page id N — out of
    bounds — and are dropped by JAX scatter semantics, so a retired slot can
    never corrupt pages re-used by its successor.
    """
    N = cache["k"].shape[0]
    C = table.shape[1]
    pos = positions                                     # (B, T)
    safe = jnp.maximum(pos, 0)
    logical = safe // page_size
    if ring:
        col = logical % C
        ok = pos >= 0
    else:
        col = jnp.minimum(logical, C - 1)
        ok = (pos >= 0) & (logical < C)
    page = jnp.take_along_axis(table, col, axis=1)      # (B, T)
    page = jnp.where(ok & active[:, None], page, N)
    off = safe % page_size
    p = cache["pos"].at[page, off].set(pos)
    if "k_scale" in cache:
        k, ks = _quantized_write(cache["k"], cache["k_scale"], k_new, page, off)
        v, vs = _quantized_write(cache["v"], cache["v_scale"], v_new, page, off)
        k = shard(k, "pages", "kv_heads", None, "head_dim")
        v = shard(v, "pages", "kv_heads", None, "head_dim")
        return {"k": k, "v": v, "pos": p,
                "k_scale": shard(ks, "pages", "kv_heads"),
                "v_scale": shard(vs, "pages", "kv_heads")}
    k = _write_tokens(cache["k"], page, off, k_new.astype(cache["k"].dtype))
    v = _write_tokens(cache["v"], page, off, v_new.astype(cache["v"].dtype))
    k = shard(k, "pages", "kv_heads", None, "head_dim")
    v = shard(v, "pages", "kv_heads", None, "head_dim")
    return {"k": k, "v": v, "pos": p}


def _write_tokens(store, page, off, val):
    """``store[page, :, off] = val``: token-major ``val`` (..., K, hd) into
    the kv-head-major pool (N, K, P, hd) at cells (page, off)."""
    return store.at[page, :, off].set(val)


def _quantized_write(store, scale, x_new, page, off):
    """Scatter a chunk into an int8 pool, growing per-page scales in place.

    Three sequenced scatters, all safe under the engine invariant that no
    two slots write the same physical page in one step:

      1. scatter-max the new tokens' absmax/127 into the page scales —
         duplicate (page) indices combine through max;
      2. requantize each touched page's existing bytes by old/new scale
         (whole-page set; duplicates write identical values, and when the
         scale did not grow the ratio is exactly 1.0 → bit-identical);
      3. write the new tokens quantized at the final page scale (cell set,
         overwriting step 2's doubly-rounded values at those cells).

    Dropped writes (page id == pool size) fall out of every scatter.
    """
    N = store.shape[0]
    page_c = jnp.clip(page, 0, N - 1)
    xf = x_new.astype(jnp.float32)                       # (B, T, K, hd)
    s_tok = jnp.max(jnp.abs(xf), axis=-1) / INT8_MAX     # (B, T, K)
    scale1 = scale.at[page].max(s_tok)
    ratio = jnp.where(
        scale1[page_c] > 0,
        scale[page_c] / jnp.maximum(scale1[page_c], _SCALE_EPS),
        1.0,
    )                                                    # (B, T, K)
    old = store[page_c].astype(jnp.float32)              # (B, T, K, P, hd)
    requant = jnp.round(old * ratio[..., None, None])
    store1 = store.at[page].set(
        jnp.clip(requant, -INT8_MAX, INT8_MAX).astype(jnp.int8)
    )
    sn = jnp.maximum(scale1[page_c], _SCALE_EPS)[..., None]
    q_tok = jnp.clip(jnp.round(xf / sn), -INT8_MAX, INT8_MAX)
    return _write_tokens(store1, page, off, q_tok.astype(jnp.int8)), scale1


# ---------------------------------------------------------------------------
# admission: reset a slot's pages + scatter a full-length prefill cache
# ---------------------------------------------------------------------------

def admit_slot(
    pools: Dict[str, Any],
    pcache: Dict[str, Any],
    cfg,
    spec: PagedSpec,
    gtab_row: jax.Array,             # (gp,) int32 — the slot's global pages
    wtab_row: Optional[jax.Array],   # (wp,) int32 or None
    plen: jax.Array,                 # () int32 — true prompt length
) -> Dict[str, Any]:
    """Scatter a (B=1) *full-length* prefill cache (forward(...,
    full_cache=True): every layer emits all ``Pmax`` entries, identity slot
    order, padding dropped) into the slot's pages.

    The slot's pages are first invalidated (pos = -1) so a previous
    occupant's entries can never alias the new request's positions; stale
    k/v bytes may remain but are masked by pos.
    """
    # prefill emission is identity-ordered: buffer slot t holds position t
    # for t < plen and is empty (-1, dropped padding) otherwise.
    any_leaf = next(iter(pcache["groups"].values()))["attn"]["k"] if (
        pcache["groups"]
    ) else next(iter(pcache["tail"].values()))["attn"]["k"]
    Pmax = any_leaf.shape[-3]
    t = jnp.arange(Pmax, dtype=jnp.int32)
    valid = t < plen
    off = t % spec.page_size
    pos_row = jnp.where(valid, t, -1)

    gcol = jnp.minimum(t // spec.page_size, spec.gp_cols - 1)
    g_ok = valid & (t // spec.page_size < spec.gp_cols)
    gpage_raw = gtab_row[gcol]
    w_ok = wpage_raw = None
    if spec.wp_cols:
        wcap = spec.wp_cols * spec.page_size
        w_ok = valid & (t >= plen - wcap)   # only the ring's reach survives
        wcol = (t // spec.page_size) % spec.wp_cols
        wpage_raw = wtab_row[wcol]

    out: Dict[str, Any] = {"groups": {}, "tail": {}}
    for section, kinds in (("groups", cfg.pattern), ("tail", cfg.tail)):
        for i, kind in enumerate(kinds):
            key = f"{i}_{kind}"
            pool = pools[section][key]["attn"]
            src = pcache[section][key]["attn"]
            win = _windowed(kind)
            # the drop page id is one past *this* pool (pools may be sized
            # independently of the static spec geometry by the dynamic
            # allocator, so the spec's page count is not a safe sentinel)
            n_pool = pool["pos"].shape[-2]
            page = jnp.where(
                w_ok if win else g_ok, wpage_raw if win else gpage_raw, n_pool
            )
            rows = wtab_row if win else gtab_row
            if section == "groups":
                ksrc, vsrc = src["k"][:, 0], src["v"][:, 0]  # (G, Pmax, K, hd)
                pos_pool = pool["pos"].at[:, rows].set(-1)
                pos_new = pos_pool.at[:, page, off].set(pos_row)
            else:
                ksrc, vsrc = src["k"][0], src["v"][0]        # (Pmax, K, hd)
                pos_pool = pool["pos"].at[rows].set(-1)
                pos_new = pos_pool.at[page, off].set(pos_row)
            stacked = section == "groups"
            if "k_scale" in pool:
                kq, ks = _admit_quantized(
                    pool["k"], pool["k_scale"], ksrc, page, off, rows, stacked
                )
                vq, vs = _admit_quantized(
                    pool["v"], pool["v_scale"], vsrc, page, off, rows, stacked
                )
                new = {"k": kq, "v": vq, "pos": pos_new,
                       "k_scale": ks, "v_scale": vs}
            else:
                new = {
                    "k": _admit_tokens(
                        pool["k"], page, off, ksrc.astype(pool["k"].dtype),
                        stacked,
                    ),
                    "v": _admit_tokens(
                        pool["v"], page, off, vsrc.astype(pool["v"].dtype),
                        stacked,
                    ),
                    "pos": pos_new,
                }
            out[section][key] = {"attn": new}
    return out


def _admit_tokens(store, page, off, val, stacked):
    """_write_tokens for one admission, mapped over the layer axis of a
    stacked (group) pool."""
    if stacked:
        return jax.vmap(lambda s, x: _write_tokens(s, page, off, x))(store, val)
    return _write_tokens(store, page, off, val)


def _admit_quantized(store, scale, src, page, off, rows, stacked):
    """Admission write into an int8 pool: the slot's rows were just reset,
    so scales start from zero — one scatter-max then quantize every token
    at its page's final scale (no requant pass needed)."""
    lead = (slice(None),) if stacked else ()
    scale = scale.at[(*lead, rows)].set(0.0)
    sf = src.astype(jnp.float32)                         # (..., Pmax, K, hd)
    s_tok = jnp.max(jnp.abs(sf), axis=-1) / INT8_MAX     # (..., Pmax, K)
    scale = scale.at[(*lead, page)].max(s_tok)
    n_pool = store.shape[-4]
    page_c = jnp.clip(page, 0, n_pool - 1)
    sn = jnp.maximum(scale[(*lead, page_c)], _SCALE_EPS)[..., None]
    q = jnp.clip(jnp.round(sf / sn), -INT8_MAX, INT8_MAX).astype(jnp.int8)
    return _admit_tokens(store, page, off, q, stacked), scale


# ---------------------------------------------------------------------------
# page invalidation (dynamic allocator: freshly popped pages may hold a
# previous occupant's entries)
# ---------------------------------------------------------------------------

def invalidate_pages(
    pools: Dict[str, Any],
    cfg,
    g_pages: jax.Array,              # (n,) int32 page ids; >= pool size = noop
    w_pages: Optional[jax.Array] = None,
) -> Dict[str, Any]:
    """Set pos = -1 on the given physical pages across every layer (global
    pools get ``g_pages``, windowed pools ``w_pages``).  Page ids at or past
    the pool size are dropped by scatter OOB semantics, so callers pad
    fixed-shape id arrays with the pool size to keep traces stable.  Stale
    k/v bytes remain but are masked by pos everywhere."""
    out: Dict[str, Any] = {"groups": {}, "tail": {}}
    for section, kinds in (("groups", cfg.pattern), ("tail", cfg.tail)):
        for i, kind in enumerate(kinds):
            key = f"{i}_{kind}"
            pool = pools[section][key]["attn"]
            pages = w_pages if _windowed(kind) else g_pages
            if pages is None:
                out[section][key] = {"attn": pool}
                continue
            lead = (slice(None),) if section == "groups" else ()
            upd = {"pos": pool["pos"].at[(*lead, pages)].set(-1)}
            if "k_scale" in pool:
                # a freshly popped page starts its scale life over; stale
                # int8 bytes are wiped to zero by the next write's requant
                # pass (old scale 0 -> ratio 0) and masked by pos meanwhile
                upd["k_scale"] = pool["k_scale"].at[(*lead, pages)].set(0.0)
                upd["v_scale"] = pool["v_scale"].at[(*lead, pages)].set(0.0)
            out[section][key] = {"attn": {**pool, **upd}}
    return out


# ---------------------------------------------------------------------------
# test/oracle helper
# ---------------------------------------------------------------------------

def gather_slot(
    pool: Dict[str, jax.Array], table_row: jax.Array
) -> Dict[str, jax.Array]:
    """Contiguous {"k": (C*P, K, hd), "v": ..., "pos": (C*P,)} view of one
    slot's pages from an *unstacked* pool leaf — the dense-cache-shaped
    oracle view used by tests."""
    N = pool["pos"].shape[-2]
    tab = jnp.clip(table_row, 0, N - 1)
    K, hd = pool["k"].shape[-3], pool["k"].shape[-1]
    k, v = pool["k"][tab], pool["v"][tab]               # (C, K, P, hd)
    if "k_scale" in pool:
        k = k.astype(jnp.float32) * pool["k_scale"][tab][..., None, None]
        v = v.astype(jnp.float32) * pool["v_scale"][tab][..., None, None]
    return {
        "k": k.swapaxes(1, 2).reshape(-1, K, hd),
        "v": v.swapaxes(1, 2).reshape(-1, K, hd),
        "pos": pool["pos"][tab].reshape(-1),
    }
