"""Pallas kernels vs pure-jnp oracles (interpret=True on CPU), with
shape/dtype sweeps and hypothesis property tests (the latter ride along
only when hypothesis is installed — the parametrized sweeps run
everywhere).  Gradient-level differential tests live in
tests/test_kernel_grads.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

ATOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _qkv(B, S, T, H, K, d, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, S, H, d), dtype)
    k = jax.random.normal(ks[1], (B, T, K, d), dtype)
    v = jax.random.normal(ks[2], (B, T, K, d), dtype)
    return q, k, v


SHAPE_SWEEP = [
    # B, S, H, K, d, causal, window, softcap, block (None: the shape rule)
    (1, 128, 4, 4, 64, True, 0, 0.0, 64),
    (2, 128, 4, 2, 64, True, 0, 0.0, 64),       # GQA
    (2, 256, 8, 1, 32, True, 0, 0.0, 64),       # MQA
    (1, 256, 4, 2, 64, True, 64, 0.0, 64),      # sliding window
    (1, 128, 4, 2, 128, True, 0, 50.0, 64),     # gemma2 softcap
    (1, 256, 2, 2, 64, True, 32, 30.0, 64),     # window + softcap
    (2, 128, 4, 4, 16, False, 0, 0.0, 64),      # non-causal (encoder)
    # the shape rule's tiles: 512 x 512 at S = 1024, 512 x 256 under a 192
    # window (its edge inside a k tile), 128 x 128 at S = 384
    (1, 1024, 4, 2, 64, True, 0, 0.0, None),    # causal GQA
    (1, 1024, 2, 1, 64, True, 192, 0.0, None),  # sliding window
    (1, 1024, 2, 2, 64, True, 0, 30.0, None),   # softcap
    (1, 1024, 2, 2, 64, False, 0, 0.0, None),   # non-causal
    (1, 384, 2, 1, 64, True, 0, 0.0, None),     # falls back to 128
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", SHAPE_SWEEP)
def test_flash_attention_matches_oracle(case, dtype):
    B, S, H, K, d, causal, window, softcap, block = case
    q, k, v = _qkv(B, S, S, H, K, d, dtype)
    scale = 1.0 / d  # muP 1/d attention folded into the kernel scale
    out = ops.attention(
        q, k, v, scale=scale, causal=causal, window=window, softcap=softcap,
        block_q=block, block_k=block, impl="interpret",
    )
    want = ref.attention_ref(
        q, k, v, scale=scale, causal=causal, window=window, softcap=softcap
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=ATOL[dtype], rtol=1e-2,
    )


if HAVE_HYPOTHESIS:

    @settings(max_examples=15, deadline=None)
    @given(
        B=st.integers(1, 2),
        nq=st.integers(1, 3),
        K=st.sampled_from([1, 2, 4]),
        G=st.sampled_from([1, 2]),
        d=st.sampled_from([16, 32, 64]),
        window=st.sampled_from([0, 48]),
        softcap=st.sampled_from([0.0, 20.0]),
        seed=st.integers(0, 5),
    )
    def test_flash_attention_property(B, nq, K, G, d, window, softcap, seed):
        S = 64 * nq
        H = K * G
        q, k, v = _qkv(B, S, S, H, K, d, jnp.float32, seed)
        out = ops.attention(
            q, k, v, scale=1.0 / d, causal=True, window=window,
            softcap=softcap, block_q=64, block_k=64, impl="interpret",
        )
        want = ref.attention_ref(
            q, k, v, scale=1.0 / d, causal=True, window=window, softcap=softcap
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=3e-5)


def test_attention_is_convex_combination():
    """Property: each output row is a convex combination of v rows, so
    max |out| <= max |v| — catches softmax/normalization bugs."""
    q, k, v = _qkv(2, 128, 128, 4, 2, 32, jnp.float32)
    out = ops.attention(
        q, k, v, scale=0.1, causal=True, impl="interpret",
        block_q=64, block_k=64,
    )
    assert float(jnp.max(jnp.abs(out))) <= float(jnp.max(jnp.abs(v))) + 1e-4


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rows,D,block", [(37, 96, 16), (256, 64, 128), (8, 512, 8)])
def test_rmsnorm_matches_oracle(rows, D, block, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (rows, D), dtype)
    g = (jax.random.normal(jax.random.PRNGKey(1), (D,)) * 0.1).astype(dtype)
    out = ops.fused_rmsnorm(x, g, impl="interpret", block_rows=block)
    want = ref.rmsnorm_ref(x, g)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=ATOL[dtype],
    )


if HAVE_HYPOTHESIS:

    @settings(max_examples=10, deadline=None)
    @given(
        rows=st.integers(1, 70),
        D=st.sampled_from([32, 128, 384]),
        scale=st.floats(0.5, 100.0),  # below ~0.5 the eps term visibly
    )                                  # breaks exact invariance
    def test_rmsnorm_scale_invariance(rows, D, scale):
        """RMSNorm(c*x) ~= RMSNorm(x) for c > 0 — the kernel must preserve
        it."""
        x = jax.random.normal(jax.random.PRNGKey(2), (rows, D))
        g = jnp.zeros((D,))
        a = ops.fused_rmsnorm(x, g, impl="interpret", block_rows=16)
        b = ops.fused_rmsnorm(x * scale, g, impl="interpret", block_rows=16)
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-3, rtol=1e-3
        )


@pytest.mark.parametrize("impl", ["pallas", "interpret"])
def test_attention_explicit_impl_never_silently_falls_back(impl):
    """Regression: non-tileable shapes used to silently run the jnp
    reference even when impl="pallas"/"interpret" was requested — so a
    broken kernel could pass tests against the oracle it was meant to be
    checked against.  Explicit impls must raise instead."""
    q, k, v = _qkv(1, 100, 100, 4, 2, 32, jnp.float32)  # 100 % 64 != 0
    with pytest.raises(ValueError, match="refusing to silently fall back"):
        ops.attention(
            q, k, v, scale=0.1, causal=True, block_q=64, block_k=64, impl=impl
        )


def test_attention_auto_falls_back_on_untileable():
    """auto keeps the best-effort contract: correct answer via ref."""
    q, k, v = _qkv(1, 100, 100, 4, 2, 32, jnp.float32)
    out = ops.attention(q, k, v, scale=0.1, causal=True, impl="auto")
    want = ref.attention_ref(q, k, v, scale=0.1, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("op", ["attention", "softmax_cross_entropy"])
def test_auto_never_falls_back_on_tpu(op, monkeypatch):
    """On TPU the kernel is the main path: an untileable shape under
    impl="auto" raises instead of running (and being timed as) the ref."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.delenv("REPRO_KERNELS", raising=False)
    with pytest.raises(ValueError, match="refusing to silently fall back"):
        if op == "attention":
            q, k, v = _qkv(1, 100, 100, 4, 2, 32, jnp.float32)
            ops.attention(q, k, v, scale=0.1, block_q=64, block_k=64)
        else:
            x = jax.random.normal(jax.random.PRNGKey(0), (8, 100))
            ops.softmax_cross_entropy(x, jnp.zeros((8,), jnp.int32),
                                      block_v=64)


def test_resolved_records_the_impl_that_ran(monkeypatch):
    """ops.RESOLVED names what each op's latest call ran, after fallback."""
    monkeypatch.delenv("REPRO_KERNELS", raising=False)
    q, k, v = _qkv(1, 100, 100, 4, 2, 32, jnp.float32)
    ops.attention(q, k, v, scale=0.1, block_q=64, block_k=64)
    assert ops.RESOLVED["attention"] == "ref"           # untileable -> ref
    q, k, v = _qkv(1, 64, 64, 4, 2, 32, jnp.float32)
    ops.attention(q, k, v, scale=0.1, block_q=64, block_k=64,
                  impl="interpret")
    assert ops.RESOLVED["attention"] == "interpret"


@pytest.mark.parametrize("S,window,tiles", [
    (2048, 0, (512, 512)),     # the SmolLM-360M training cell
    (512, 0, (512, 512)),      # the sweep engine's proxy
    (384, 0, (128, 128)),      # 3 x 128: no wider power of two divides it
    (256, 0, (256, 256)),
    (64, 0, (64, 64)),         # shorter than a tile: one tile
    (100, 0, (100, 100)),      # untileable at 128: as before
    (200, 0, (128, 128)),      # untileable: the caller refuses it
    (1024, 192, (512, 256)),   # bk holds the window and no more
    (1024, 64, (512, 128)),
    (2048, 4096, (512, 512)),
])
def test_tile_rule(S, window, tiles):
    from repro.kernels import flash_attention as fa

    assert fa.choose_tiles(S, S, window=window) == tiles
    # explicit blocks win, clipped to the sequence
    assert fa.choose_tiles(S, S, window=window, block_q=64,
                           block_k=1 << 20) == (min(64, S), S)


def test_tile_plan_counts_computed_pairs():
    from repro.kernels import flash_attention as fa

    # causal 4 x 4: the 10 pairs on or below the diagonal
    assert fa.tile_plan(2048, 2048, 512, 512, causal=True, window=0) == (
        10, 16)
    assert fa.tile_plan(2048, 2048, 512, 512, causal=False, window=0) == (
        16, 16)
    # window 192 over 512 x 256: each q tile sees the k tiles of its own
    # rows and the one before
    assert fa.tile_plan(1024, 1024, 512, 256, causal=True, window=192) == (
        5, 8)


def test_attention_records_its_tile_plan(monkeypatch):
    monkeypatch.delenv("REPRO_KERNELS", raising=False)
    q, k, v = _qkv(1, 1024, 1024, 2, 1, 32, jnp.float32)
    ops.attention(q, k, v, scale=0.1, causal=True, impl="interpret")
    assert ops.RESOLVED["attention_tiles"] == "512x512 computed 3/4"
    ops.attention(q, k, v, scale=0.1, causal=True, impl="ref")
    assert "attention_tiles" not in ops.RESOLVED   # no kernel ran


@pytest.mark.parametrize("window", [0, 192, 700])
@pytest.mark.parametrize("bq,bk", [(512, 512), (512, 256), (256, 512),
                                   (128, 128)])
def test_invisible_tiles_fetch_nothing(bq, bk, window):
    """The dk/dv kernel's clamped q index maps: every step that computes
    fetches its own block, and the steps that compute nothing repeat a
    block that an adjacent step holds, so a k tile's sweep over q makes one
    copy per visible block."""
    from repro.kernels import flash_attention as fa

    S = 2048
    nq, nk = S // bq, S // bk
    kw = dict(bq=bq, bk=bk, causal=True, window=window)
    for ki in range(nk):
        blocks = [int(fa._q_block(ki, qi, nq=nq, **kw)) for qi in range(nq)]
        seen = [qi for qi in range(nq) if bool(fa._block_visible(
            qi * bq, ki * bk, bq, bk, True, window))]
        assert all(blocks[qi] == qi for qi in seen)
        assert sorted(set(blocks)) == seen
        assert sum(a != b for a, b in zip(blocks, blocks[1:])) == (
            len(seen) - 1)


def test_clamped_q_maps_change_no_bits(monkeypatch):
    """The dk/dv kernel fetches a clamped q block where it computes
    nothing: unclamping its index maps changes neither the output nor the
    gradients."""
    from repro.kernels import flash_attention as fa

    q, k, v = _qkv(1, 512, 512, 2, 1, 64, jnp.float32, seed=3)

    def run():
        jax.clear_caches()
        fa._flash_fn.cache_clear()

        def f(q, k, v):
            o = fa.flash_attention(q, k, v, scale=0.125, causal=True,
                                   window=300, block_q=128, block_k=128,
                                   interpret=True)
            return jnp.sum(o * o), o

        (_, o), g = jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(
            q, k, v)
        return [np.asarray(x) for x in (o, *g)]

    clamped = run()
    monkeypatch.setattr(fa, "_q_block", lambda ki, qi, **kw: qi)
    unclamped = run()
    monkeypatch.undo()
    fa._flash_fn.cache_clear()
    jax.clear_caches()
    for a, b in zip(clamped, unclamped):
        np.testing.assert_array_equal(a, b)


def test_cross_entropy_explicit_impl_never_silently_falls_back():
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 100))  # 100 % 64 != 0
    with pytest.raises(ValueError, match="refusing to silently fall back"):
        ops.softmax_cross_entropy(
            x, jnp.zeros((8,), jnp.int32), block_v=64, impl="interpret"
        )


def test_bad_impl_rejected():
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
    with pytest.raises(ValueError, match="impl must be one of"):
        ops.fused_rmsnorm(x, jnp.zeros((64,)), impl="cuda")


CE_SWEEP = [
    # N, V, block_rows, block_v
    (64, 1024, 16, 128),
    (37, 512, 8, 512),       # padded rows, single vocab chunk
    (128, 32768, 64, 2048),  # GPT-class vocab
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", CE_SWEEP)
def test_cross_entropy_matches_oracle(case, dtype):
    N, V, br, bv = case
    x = (jax.random.normal(jax.random.PRNGKey(0), (N, V)) * 3).astype(dtype)
    lab = jax.random.randint(jax.random.PRNGKey(1), (N,), 0, V)
    out = ops.softmax_cross_entropy(
        x, lab, impl="interpret", block_rows=br, block_v=bv
    )
    want = ref.softmax_cross_entropy_ref(x, lab)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want),
        atol={jnp.float32: 1e-4, jnp.bfloat16: 5e-2}[dtype], rtol=1e-3,
    )


def test_model_path_equals_kernel_path():
    """The model's jnp attention (models/attention.attend) and the Pallas
    kernel agree — so the TPU use_pallas switch is numerically safe."""
    from repro.models import attention as A

    q, k, v = _qkv(2, 128, 128, 4, 2, 64, jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(128)[None], (2, 128))
    mask = A.make_mask(pos, pos, True, 32)
    a = A.attend(q, k, v, mask, 1.0 / 64, 50.0)
    b = ops.attention(
        q, k, v, scale=1.0 / 64, causal=True, window=32, softcap=50.0,
        block_q=64, block_k=64, impl="interpret",
    )
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
