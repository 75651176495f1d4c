"""Gradient differential tests: jax.grad through every Pallas kernel
(interpret mode on CPU) vs jax.grad through the pure-jnp oracles in
kernels/ref.py.

muP correctness lives in *gradient* scales — a backward kernel that is
subtly wrong (a dropped softmax-jacobian term, a bad mask in ds, a missing
group-sum for GQA) can leave the forward bit-exact while silently breaking
every Table-8 scaling rule.  So each custom_vjp ships with a differential
test over the same shape/dtype/GQA/window/softcap grid as the forward
tests, plus fp32-vs-bf16 tolerance tiers.

Hypothesis property tests ride along when hypothesis is installed (CI);
the parametrized grid below runs everywhere.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # local envs without hypothesis still run the grid
    HAVE_HYPOTHESIS = False

# fp32 tier is the acceptance bar (atol <= 2e-4); bf16 inputs quantize the
# incoming cotangent and the saved residuals, so the bar is ~bf16 eps.
GRAD_ATOL = {jnp.float32: 2e-4, jnp.bfloat16: 5e-2}
GRAD_RTOL = {jnp.float32: 1e-3, jnp.bfloat16: 5e-2}


def _assert_grads_close(got, want, dtype):
    for g, w in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            atol=GRAD_ATOL[dtype], rtol=GRAD_RTOL[dtype],
        )


def _qkvw(B, S, T, H, K, d, dtype, seed=0):
    """Like test_kernels._qkv plus a cotangent-weight tensor w."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, S, H, d), dtype)
    k = jax.random.normal(ks[1], (B, T, K, d), dtype)
    v = jax.random.normal(ks[2], (B, T, K, d), dtype)
    w = jax.random.normal(ks[3], (B, S, H, d), dtype)
    return q, k, v, w


# same config space as tests/test_kernels.py SHAPE_SWEEP
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
def test_attention_grads_match_oracle(case, dtype):
    B, S, H, K, d, causal, window, softcap, block = case
    q, k, v, w = _qkvw(B, S, S, H, K, d, dtype)
    scale = 1.0 / d  # muP 1/d attention
    wf = w.astype(jnp.float32)

    def f_kernel(q, k, v):
        o = ops.attention(
            q, k, v, scale=scale, causal=causal, window=window,
            softcap=softcap, block_q=block, block_k=block, impl="interpret",
        )
        return jnp.sum(o.astype(jnp.float32) * wf)

    def f_ref(q, k, v):
        o = ref.attention_ref(
            q, k, v, scale=scale, causal=causal, window=window, softcap=softcap
        )
        return jnp.sum(o.astype(jnp.float32) * wf)

    got = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    _assert_grads_close(got, want, dtype)


def test_attention_grad_of_traced_scale():
    """d(loss)/d(scale) flows through the kernel path (the sweep engine
    threads alpha_attn through `scale` as a traced scalar)."""
    q, k, v, w = _qkvw(1, 128, 128, 4, 2, 32, jnp.float32)

    def f(s, impl):
        o = ops.attention(
            q, k, v, scale=s, causal=True, block_q=64, block_k=64, impl=impl
        )
        return jnp.sum(o * w)

    g_kernel = jax.grad(lambda s: f(s, "interpret"))(jnp.float32(1 / 32))
    g_ref = jax.grad(lambda s: f(s, "ref"))(jnp.float32(1 / 32))
    np.testing.assert_allclose(
        np.asarray(g_kernel), np.asarray(g_ref), atol=2e-4, rtol=1e-3
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "rows,D,block", [(37, 96, 16), (256, 64, 128), (8, 512, 8)]
)
def test_rmsnorm_grads_match_oracle(rows, D, block, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (rows, D), dtype)
    g = (jax.random.normal(jax.random.PRNGKey(1), (D,)) * 0.1).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(2), (rows, D))

    def f_kernel(x, g):
        y = ops.fused_rmsnorm(x, g, impl="interpret", block_rows=block)
        return jnp.sum(y.astype(jnp.float32) * w)

    def f_ref(x, g):
        return jnp.sum(ref.rmsnorm_ref(x, g).astype(jnp.float32) * w)

    got = jax.grad(f_kernel, argnums=(0, 1))(x, g)
    want = jax.grad(f_ref, argnums=(0, 1))(x, g)
    _assert_grads_close(got, want, dtype)


def test_rmsnorm_grads_3d_padded():
    """(B, S, D) inputs with row padding: padded rows must contribute
    nothing to dgain."""
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 19, 64))
    g = jax.random.normal(jax.random.PRNGKey(4), (64,)) * 0.1

    def f(x, g, impl):
        return jnp.sum(
            jnp.sin(ops.fused_rmsnorm(x, g, impl=impl, block_rows=16))
        )

    got = jax.grad(lambda x, g: f(x, g, "interpret"), argnums=(0, 1))(x, g)
    want = jax.grad(lambda x, g: f(x, g, "ref"), argnums=(0, 1))(x, g)
    _assert_grads_close(got, want, jnp.float32)


# forward-value CE coverage over this sweep lives in tests/test_kernels.py
CE_SWEEP = [
    # N, V, block_rows, block_v
    (64, 1024, 16, 128),
    (37, 512, 8, 512),      # padded rows, single vocab chunk
    (128, 32768, 64, 2048),  # GPT-class vocab
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", CE_SWEEP)
def test_cross_entropy_grads_match_oracle(case, dtype):
    N, V, br, bv = case
    x = (jax.random.normal(jax.random.PRNGKey(0), (N, V)) * 3).astype(dtype)
    # include masked (-100) labels: the model contract zeroes their weight
    lab = jax.random.randint(jax.random.PRNGKey(1), (N,), -1, V)
    mask = (lab >= 0).astype(jnp.float32)

    def masked_mean(losses):
        return jnp.sum(losses * mask) / jnp.maximum(jnp.sum(mask), 1.0)

    def f_kernel(x):
        return masked_mean(ops.softmax_cross_entropy(
            x, lab, impl="interpret", block_rows=br, block_v=bv
        ))

    def f_ref(x):
        return masked_mean(ref.softmax_cross_entropy_ref(x, lab))

    got = jax.grad(f_kernel)(x)
    want = jax.grad(f_ref)(x)
    _assert_grads_close((got,), (want,), dtype)


def test_cross_entropy_dlogits_rowsum_zero():
    """Property: for unmasked rows, d-logits sum to ~0 over the vocab
    (softmax minus one-hot) — catches a dropped one-hot or lse term."""
    x = jax.random.normal(jax.random.PRNGKey(5), (32, 512)) * 2
    lab = jax.random.randint(jax.random.PRNGKey(6), (32,), 0, 512)
    g = jax.grad(lambda x: jnp.sum(ops.softmax_cross_entropy(
        x, lab, impl="interpret", block_rows=16, block_v=128
    )))(x)
    np.testing.assert_allclose(
        np.asarray(jnp.sum(g, axis=-1)), np.zeros(32), atol=1e-5
    )


# ---------------------------------------------------------------------------
# end-to-end: the whole model trains through interpret kernels
# ---------------------------------------------------------------------------

def test_model_grads_interpret_kernels_match_ref(monkeypatch):
    """jax.grad through Model.loss_fn with every op forced onto the Pallas
    interpreter (REPRO_KERNELS=interpret) matches the jnp-reference path —
    attention, rmsnorm and chunked CE backward kernels, composed."""
    from repro.configs import get_smoke_config
    from repro.data.pipeline import make_pipeline
    from repro.models.model import build_model

    cfg = get_smoke_config("mup-gpt").replace(dtype="float32", use_pallas=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    pipe = make_pipeline(cfg.vocab_size, 32, 2, seed=0)
    batch = {k: jnp.asarray(v) for k, v in pipe.batch(0).items()}

    def run():
        jax.clear_caches()  # impl is resolved pre-jit, but the model's
        # outer jit cache is keyed without the env var
        return jax.value_and_grad(model.loss_fn)(params, batch)

    monkeypatch.setenv("REPRO_KERNELS", "ref")
    loss_ref_, grads_ref = run()
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    loss_int, grads_int = run()
    monkeypatch.delenv("REPRO_KERNELS")
    jax.clear_caches()

    np.testing.assert_allclose(
        float(loss_ref_), float(loss_int), rtol=1e-5, atol=1e-5
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(grads_ref),
        jax.tree_util.tree_leaves(grads_int),
    ):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=2e-4, rtol=2e-3,
        )


# ---------------------------------------------------------------------------
# hypothesis property tests (CI tier)
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:

    @settings(max_examples=10, deadline=None)
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
    def test_attention_grads_property(B, nq, K, G, d, window, softcap, seed):
        S = 64 * nq
        H = K * G
        q, k, v, w = _qkvw(B, S, S, H, K, d, jnp.float32, seed)

        def f(q, k, v, impl):
            o = ops.attention(
                q, k, v, scale=1.0 / d, causal=True, window=window,
                softcap=softcap, block_q=64, block_k=64, impl=impl,
            )
            return jnp.sum(o * w)

        got = jax.grad(
            lambda q, k, v: f(q, k, v, "interpret"), argnums=(0, 1, 2)
        )(q, k, v)
        want = jax.grad(
            lambda q, k, v: f(q, k, v, "ref"), argnums=(0, 1, 2)
        )(q, k, v)
        _assert_grads_close(got, want, jnp.float32)

    @settings(max_examples=10, deadline=None)
    @given(
        rows=st.integers(1, 70),
        D=st.sampled_from([32, 128, 384]),
        seed=st.integers(0, 5),
    )
    def test_rmsnorm_grads_property(rows, D, seed):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        x = jax.random.normal(ks[0], (rows, D))
        g = jax.random.normal(ks[1], (D,)) * 0.1
        w = jax.random.normal(ks[2], (rows, D))

        def f(x, g, impl):
            y = ops.fused_rmsnorm(x, g, impl=impl, block_rows=16)
            return jnp.sum(y * w)

        got = jax.grad(
            lambda x, g: f(x, g, "interpret"), argnums=(0, 1)
        )(x, g)
        want = jax.grad(lambda x, g: f(x, g, "ref"), argnums=(0, 1))(x, g)
        _assert_grads_close(got, want, jnp.float32)

    @settings(max_examples=10, deadline=None)
    @given(
        N=st.sampled_from([8, 33, 64]),
        V=st.sampled_from([256, 512]),
        bv=st.sampled_from([128, 256]),
        seed=st.integers(0, 5),
    )
    def test_cross_entropy_grads_property(N, V, bv, seed):
        ks = jax.random.split(jax.random.PRNGKey(seed), 2)
        x = jax.random.normal(ks[0], (N, V)) * 4
        lab = jax.random.randint(ks[1], (N,), -1, V)
        mask = (lab >= 0).astype(jnp.float32)

        def f(x, impl):
            losses = ops.softmax_cross_entropy(
                x, lab, impl=impl, block_rows=16, block_v=bv
            )
            return jnp.sum(losses * mask)

        got = jax.grad(lambda x: f(x, "interpret"))(x)
        want = jax.grad(lambda x: f(x, "ref"))(x)
        _assert_grads_close((got,), (want,), jnp.float32)
