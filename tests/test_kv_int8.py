"""int8 paged KV cache: differential kernel tests, scale lifecycle, and
end-to-end serving behavior (plus the adaptive draft-length controller
that rides the same PR).

Tolerance tiers (docs/quantization.md):
  TIGHT (2e-5): kernel-int8 vs ref-int8 — identical quantized bytes and
    dequant math, all compute f32; agreement to ulps, like the f32 tests.
  LOOSE (5e-2): int8 path vs the f32 dense oracle — genuine quantization
    error (per-page absmax/127 half-steps through the softmax).
  Behavioral: greedy serving with int8 pools must keep >= 99% top-1
    agreement with the f32 engine (ISSUE-8 acceptance bar).

Kernel test inputs must respect the engine's page-layout invariant:
logical page j of a slot holds positions j*P .. (j+1)*P - 1.  The kernels
skip pages past ``q_pos // P`` (dead-page elision); a pool violating the
layout diverges from the ref oracle by construction, not by bug.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.kernels import ops, ref
from repro.kernels.decode_attention import flash_decode, flash_decode_multi
from repro.models import attention as A
from repro.models.model import build_model
from repro.quant import pack_kv
from repro.serving import kv_cache
from repro.serving.engine import DynamicEngine, Engine, EngineConfig

TIGHT = 2e-5
LOOSE = 5e-2


# ---------------------------------------------------------------------------
# paged int8 case builder (engine-consistent page layout)
# ---------------------------------------------------------------------------

def _paged_case(B, K, G, d, P, C, T, seed=0):
    """Interleaved-table paged pool holding T contiguous tokens per slot,
    plus the dense (B, T, K, d) arrays the f32 oracle attends over."""
    H = K * G
    N = B * C + 3
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, H, d), jnp.float32)
    k_dense = jax.random.normal(ks[1], (B, C * P, K, d), jnp.float32)
    v_dense = jax.random.normal(ks[2], (B, C * P, K, d), jnp.float32)
    tab = ((jnp.arange(C)[None, :] * B + jnp.arange(B)[:, None] + 2) % N)
    tab = tab.astype(jnp.int32)
    kp = jnp.zeros((N, K, P, d), jnp.float32)   # kv-head-major pool
    vp = jnp.zeros((N, K, P, d), jnp.float32)
    pos = jnp.full((N, P), -1, jnp.int32)
    t = jnp.arange(T)
    cols = t // P
    pages = jnp.take_along_axis(
        tab, jnp.broadcast_to(cols[None], (B, T)), axis=1
    )
    offs = jnp.broadcast_to((t % P)[None], (B, T))
    b_idx = jnp.broadcast_to(jnp.arange(B)[:, None], (B, T))
    kp = kp.at[pages, :, offs].set(k_dense[b_idx, t[None, :]])
    vp = vp.at[pages, :, offs].set(v_dense[b_idx, t[None, :]])
    pos = pos.at[pages, offs].set(jnp.broadcast_to(t[None], (B, T)))
    q_pos = jnp.full((B,), T - 1, jnp.int32)
    return q, kp, vp, pos, tab, q_pos, k_dense[:, :T], v_dense[:, :T]


def _dense_oracle(q, k, v, q_pos, window, softcap):
    B, T = k.shape[:2]
    kv_pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    mask = A.make_mask(q_pos[:, None], kv_pos, causal=True, window=window)
    return A.attend(q[:, None], k, v, mask, 0.125, softcap)[:, 0]


# ---------------------------------------------------------------------------
# int8 decode kernels vs ref vs f32 oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("K,G", [(1, 4), (2, 2), (4, 1)])  # MQA / GQA / MHA
@pytest.mark.parametrize("window", [0, 9])
@pytest.mark.parametrize("softcap", [0.0, 30.0])
def test_int8_kernel_ref_oracle_chain(K, G, window, softcap):
    B, d, P, C, T = 2, 8, 4, 6, 21
    q, kp, vp, pos, tab, q_pos, kd, vd = _paged_case(B, K, G, d, P, C, T)
    k_q, v_q, k_s, v_s = pack_kv(kp, vp)
    got_ref = ref.decode_attention_ref(
        q, k_q, v_q, pos, tab, q_pos, scale=0.125, window=window,
        softcap=softcap, k_scale=k_s, v_scale=v_s,
    )
    # loose: quantization error vs the f32 dense oracle
    want = _dense_oracle(q, kd, vd, q_pos, window, softcap)
    np.testing.assert_allclose(np.asarray(got_ref), np.asarray(want),
                               atol=LOOSE)
    # tight: the kernel's in-kernel dequant vs the ref's post-gather dequant
    got_k = flash_decode(
        q, k_q, v_q, pos, tab, q_pos, scale=0.125, window=window,
        softcap=softcap, k_scale=k_s, v_scale=v_s, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(got_k), np.asarray(got_ref),
                               atol=TIGHT)


@pytest.mark.parametrize("K,G", [(1, 4), (2, 2)])
@pytest.mark.parametrize("window,softcap", [(0, 0.0), (9, 30.0)])
def test_int8_multi_kernel_ref_oracle_chain(K, G, window, softcap):
    B, d, P, C, T, Tq = 2, 8, 4, 6, 21, 5
    _, kp, vp, pos, tab, _, kd, vd = _paged_case(B, K, G, d, P, C, T)
    q = jax.random.normal(jax.random.PRNGKey(9), (B, Tq, K * G, d))
    q_pos = jnp.broadcast_to(
        jnp.arange(T - Tq, T)[None], (B, Tq)
    ).astype(jnp.int32)
    k_q, v_q, k_s, v_s = pack_kv(kp, vp)
    kv_pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    mask = A.make_mask(q_pos, kv_pos, causal=True, window=window)
    want = A.attend(q, kd, vd, mask, 0.125, softcap)
    got_ref = ref.decode_attention_multi_ref(
        q, k_q, v_q, pos, tab, q_pos, scale=0.125, window=window,
        softcap=softcap, k_scale=k_s, v_scale=v_s,
    )
    np.testing.assert_allclose(np.asarray(got_ref), np.asarray(want),
                               atol=LOOSE)
    got_k = flash_decode_multi(
        q, k_q, v_q, pos, tab, q_pos, scale=0.125, window=window,
        softcap=softcap, k_scale=k_s, v_scale=v_s, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(got_k), np.asarray(got_ref),
                               atol=TIGHT)


def test_int8_ops_dispatch_and_inactive_rows():
    B, K, G, d, P, C, T = 3, 2, 2, 8, 4, 4, 11
    q, kp, vp, pos, tab, q_pos, *_ = _paged_case(B, K, G, d, P, C, T)
    k_q, v_q, k_s, v_s = pack_kv(kp, vp)
    q_pos = q_pos.at[1].set(-1)
    outs = {}
    for impl in ("ref", "interpret"):
        out = ops.decode_attention(
            q, k_q, v_q, pos, tab, q_pos, scale=0.125,
            k_scale=k_s, v_scale=v_s, impl=impl,
        )
        assert bool(jnp.all(out[1] == 0)), impl
        assert bool(jnp.all(jnp.isfinite(out))), impl
        outs[impl] = out
    np.testing.assert_allclose(np.asarray(outs["interpret"]),
                               np.asarray(outs["ref"]), atol=TIGHT)


# ---------------------------------------------------------------------------
# scale lifecycle: write / requant / gather / invalidate
# ---------------------------------------------------------------------------

def _int8_cache(N, P, K, hd):
    return {
        "k": jnp.zeros((N, K, P, hd), jnp.int8),
        "v": jnp.zeros((N, K, P, hd), jnp.int8),
        "pos": jnp.full((N, P), -1, jnp.int32),
        "k_scale": jnp.zeros((N, K), jnp.float32),
        "v_scale": jnp.zeros((N, K), jnp.float32),
    }


def _write(cache, k_new, v_new, positions, tab, P):
    return kv_cache.paged_cache_write(
        cache, k_new, v_new, positions, tab, jnp.array([True]), P, ring=False
    )


def test_paged_write_scale_grows_and_requants():
    P, K, hd = 4, 2, 8
    cache = _int8_cache(6, P, K, hd)
    tab = jnp.array([[0, 2, 4]], jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    small = 0.1 * jax.random.normal(ks[0], (1, 1, K, hd), jnp.float32)
    c1 = _write(cache, small, small, jnp.array([[0]]), tab, P)
    s1 = np.asarray(c1["k_scale"])
    assert s1[0].max() > 0 and s1[1:].max() == 0       # only page 0 touched

    # a 10x larger token lands in the same page: the scale must GROW and the
    # earlier token's bytes must be requantized, staying within a step of
    # its true value at the new (coarser) grid
    big = 10.0 * jax.random.normal(ks[1], (1, 1, K, hd), jnp.float32)
    c2 = _write(c1, big, big, jnp.array([[1]]), tab, P)
    s2 = np.asarray(c2["k_scale"])
    assert np.all(s2 >= s1 - 1e-12)                    # monotone while live
    assert np.all(s2[0] > s1[0])
    deq0 = np.asarray(c2["k"][0, :, 0], np.float32) * s2[0][:, None]
    assert np.all(np.abs(deq0 - np.asarray(small[0, 0])) <= s2[0][:, None])

    # a small write cannot shrink the scale, and untouched cells of the
    # page stay bit-identical (requant ratio is exactly 1.0)
    c3 = _write(c2, small, small, jnp.array([[2]]), tab, P)
    np.testing.assert_array_equal(np.asarray(c3["k_scale"]), s2)
    np.testing.assert_array_equal(np.asarray(c3["k"][0, :, :2]),
                                  np.asarray(c2["k"][0, :, :2]))
    assert np.asarray(c3["pos"][0]).tolist() == [0, 1, 2, -1]


def test_gather_slot_dequantizes_within_halfstep():
    P, K, hd, T = 4, 2, 8, 8
    cache = _int8_cache(8, P, K, hd)
    tab = jnp.array([[1, 5]], jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    k_new = jax.random.normal(ks[0], (1, T, K, hd), jnp.float32)
    v_new = jax.random.normal(ks[1], (1, T, K, hd), jnp.float32)
    positions = jnp.arange(T, dtype=jnp.int32)[None]
    c = _write(cache, k_new, v_new, positions, tab, P)
    g = kv_cache.gather_slot(c, tab[0])
    assert g["k"].dtype == jnp.float32                 # dequantized view
    assert np.asarray(g["pos"][:T]).tolist() == list(range(T))
    step = float(np.max(np.asarray(c["k_scale"])))
    np.testing.assert_allclose(np.asarray(g["k"][:T]),
                               np.asarray(k_new[0]), atol=step / 2 + 1e-6)


def test_invalidate_pages_zeroes_scales():
    cfg = get_smoke_config("smollm-135m").replace(
        dtype="float32", kv_dtype="int8"
    )
    spec = kv_cache.build_spec(cfg, n_slots=2, max_total=16, page_size=4)
    pools = kv_cache.init_pools(cfg, spec)
    leaf = pools["groups"]["0_attn"]["attn"]
    leaf["k_scale"] = jnp.ones_like(leaf["k_scale"])
    leaf["v_scale"] = jnp.ones_like(leaf["v_scale"])
    leaf["pos"] = jnp.zeros_like(leaf["pos"])
    out = kv_cache.invalidate_pages(pools, cfg, jnp.array([0, 3], jnp.int32))
    got = out["groups"]["0_attn"]["attn"]
    for p in (0, 3):                                   # invalidated pages
        assert float(jnp.max(got["k_scale"][:, p])) == 0.0
        assert float(jnp.max(got["v_scale"][:, p])) == 0.0
        assert int(jnp.max(got["pos"][:, p])) == -1
    assert float(jnp.min(got["k_scale"][:, 1])) == 1.0  # others untouched
    assert int(jnp.min(got["pos"][:, 1])) == 0


def test_pool_bytes_int8_capacity_ratio():
    """The headline: at a fixed byte budget int8 pools hold >= 1.8x the
    slots of bf16 pools (per-page scale overhead included)."""
    cfg = get_smoke_config("smollm-135m").replace(dtype="float32")
    spec = kv_cache.build_spec(cfg, n_slots=8, max_total=48, page_size=16)
    b16 = kv_cache.pool_bytes(cfg.replace(kv_dtype="bfloat16"), spec)
    b8 = kv_cache.pool_bytes(cfg.replace(kv_dtype="int8"), spec)
    assert b16 / b8 >= 1.8, b16 / b8
    assert kv_cache.kv_dtype_of(cfg.replace(kv_dtype="int8")) == "int8"
    assert kv_cache.kv_dtype_of(cfg) == "float32"


# ---------------------------------------------------------------------------
# end-to-end serving: greedy top-1 agreement, prefix sharing, eviction
# ---------------------------------------------------------------------------

_ENG = dict(n_slots=2, page_size=4, max_prompt_len=16, max_gen_len=6)


@pytest.fixture(scope="module")
def quant_m():
    cfg = get_smoke_config("smollm-135m").replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    model8 = build_model(cfg.replace(kv_dtype="int8"))
    return cfg, model, model8, params


def _prompts(cfg, R, L, seed=1):
    prompts = jax.random.randint(
        jax.random.PRNGKey(seed), (R, L), 0, cfg.vocab_size
    )
    lens = jax.random.randint(jax.random.PRNGKey(seed + 1), (R,), 1, L + 1)
    return prompts, lens


def _shared_prefix_prompts(cfg, R=5, L=16, seed=23):
    """Rows 0..R-2 share an 8-token (2-page) prefix; the last is disjoint."""
    rng = np.random.default_rng(seed)
    base = rng.integers(0, cfg.vocab_size, size=8)
    rows = []
    for _ in range(R - 1):
        rows.append(np.concatenate(
            [base, rng.integers(0, cfg.vocab_size, size=L - 8)]
        ))
    rows.append(rng.integers(0, cfg.vocab_size, size=L))
    lens = np.concatenate([rng.integers(10, L + 1, size=R - 1), [L]])
    return jnp.asarray(np.stack(rows), jnp.int32), jnp.asarray(lens, jnp.int32)


def test_engine_int8_top1_agreement(quant_m):
    """>= 99% greedy top-1 agreement with the f32 engine, zero recompiles
    (ISSUE-8 acceptance bar).  Same params, only the pool dtype differs."""
    cfg, model, model8, params = quant_m
    f32 = Engine(model, EngineConfig(**_ENG))
    e8 = Engine(model8, EngineConfig(**_ENG))
    prompts, lens = _prompts(cfg, R=5, L=16)
    a = f32.serve(params, prompts, lens)
    b = e8.serve(params, prompts, lens)
    la, lb = np.asarray(a["lengths"]), np.asarray(b["lengths"])
    np.testing.assert_array_equal(la, lb)
    ta, tb = np.asarray(a["tokens"]), np.asarray(b["tokens"])
    valid = np.arange(ta.shape[1])[None] < la[:, None]
    agree = float(np.mean(ta[valid] == tb[valid]))
    assert agree >= 0.99, f"top-1 agreement {agree:.3f}"
    e8.serve(params, *_prompts(cfg, R=5, L=16, seed=7))
    assert e8.compile_count() == 1


def test_dynamic_int8_prefix_cache_carries_scales(quant_m):
    """Shared and re-admitted pages carry their scales: a warm radix tree
    serving int8 pages must be token-for-token the cache-off int8 engine,
    across two serves (the second re-admits evicted/shared pages)."""
    cfg, _, model8, params = quant_m
    on = DynamicEngine(model8, EngineConfig(
        prefill_chunk=4, prefix_cache=True, **_ENG
    ))
    off = DynamicEngine(model8, EngineConfig(**_ENG))
    prompts, lens = _shared_prefix_prompts(cfg)
    want = off.serve(params, prompts, lens)
    g1 = on.serve(params, prompts, lens)
    g2 = on.serve(params, prompts, lens)               # warm tree: more hits
    for got in (g1, g2):
        np.testing.assert_array_equal(np.asarray(got["tokens"]),
                                      np.asarray(want["tokens"]))
    assert g1["prefill_cached"] > 0
    assert g2["prefill_cached"] > g1["prefill_cached"]
    assert on.compile_count() == 1
    on.blocks.check_invariants()


def test_dynamic_int8_eviction_readmission(quant_m):
    """Near-zero cache headroom forces LRU eviction on most admissions;
    re-quantized re-admissions must still match the cache-off engine."""
    cfg, _, model8, params = quant_m
    spec = kv_cache.build_spec(
        cfg, _ENG["n_slots"], _ENG["max_prompt_len"] + _ENG["max_gen_len"],
        _ENG["page_size"],
    )
    n_pages = 2 * spec.gp_cols + 2
    on = DynamicEngine(model8, EngineConfig(
        prefill_chunk=4, prefix_cache=True, n_pages=n_pages, **_ENG
    ))
    off = DynamicEngine(model8, EngineConfig(n_pages=n_pages, **_ENG))
    rng = np.random.default_rng(31)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (6, 16)), jnp.int32)
    lens = jnp.full((6,), 16, jnp.int32)
    got = on.serve(params, prompts, lens)
    want = off.serve(params, prompts, lens)
    np.testing.assert_array_equal(np.asarray(got["tokens"]),
                                  np.asarray(want["tokens"]))
    on.blocks.check_invariants()


# ---------------------------------------------------------------------------
# adaptive draft length (per-slot, host-controlled, zero recompiles)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def drafter(quant_m):
    cfg, _, _, _ = quant_m
    dcfg = cfg.scaled(0.5, min_d_head=8)
    dmodel = build_model(dcfg)
    dparams = dmodel.init(jax.random.PRNGKey(7))
    return dmodel, dparams


def test_adaptive_draft_matches_static_greedy(quant_m, drafter):
    """Truncating the draft is unbiased: greedy tokens are identical to the
    fixed-k engine; the controller only trims *proposals* (the random-init
    drafter's acceptance is low, so per-slot k shrinks below draft_k)."""
    cfg, model, _, params = quant_m
    dmodel, dparams = drafter
    static = Engine(model, EngineConfig(draft_k=3, **_ENG),
                    draft_model=dmodel)
    adapt = DynamicEngine(
        model, EngineConfig(draft_k=3, adaptive_draft=True, **_ENG),
        draft_model=dmodel,
    )
    prompts, lens = _prompts(cfg, R=5, L=16)
    want = static.serve(params, prompts, lens, draft_params=dparams)
    out = adapt.serve(params, prompts, lens, draft_params=dparams)
    np.testing.assert_array_equal(np.asarray(out["tokens"]),
                                  np.asarray(want["tokens"]))
    assert int(out["proposed"]) < int(want["proposed"])
    # controller state is per-serve and the step is traced-data driven:
    # a second serve is deterministic and hits the same compiled program
    out2 = adapt.serve(params, prompts, lens, draft_params=dparams)
    np.testing.assert_array_equal(np.asarray(out2["tokens"]),
                                  np.asarray(out["tokens"]))
    assert int(out2["proposed"]) == int(out["proposed"])
    assert adapt.compile_count() == 1


def test_static_engine_rejects_adaptive_draft(quant_m):
    _, model, _, _ = quant_m
    with pytest.raises(ValueError, match="DynamicEngine"):
        Engine(model, EngineConfig(adaptive_draft=True, **_ENG))


def test_adaptive_draft_requires_draft_k(quant_m):
    _, model, _, _ = quant_m
    with pytest.raises(ValueError, match="draft_k"):
        DynamicEngine(model, EngineConfig(adaptive_draft=True, **_ENG))
