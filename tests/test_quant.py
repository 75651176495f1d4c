"""Low-precision subsystem tests (quant/): primitives, policy plumbing,
straight-through matmuls, policy-routed attention, and the u-µP claims
that license the dtype choices (docs/quantization.md).

Tolerance tiers:
  - exact / 1e-6: policy "none" must be bit-for-bit the f32 path;
  - 0.05 rel: quantized forward vs the f32 oracle (genuine rounding error,
    absmax/127 half-steps through a softmax or a tanh);
  - 0.25 rel: straight-through gradients vs f32 gradients (the STE runs
    the *same* policy on both backward matmuls, so error compounds once).
The behavioral claims — coord-check flatness and loss parity under amp —
get their own end-to-end assertions at the bottom.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.coord_check import coord_check
from repro.core.parametrization import Parametrization
from repro.core.transfer import HParams
from repro.data.pipeline import make_pipeline
from repro.kernels import ops
from repro.launch.train import train_loop
from repro.models.model import build_model
from repro.quant import (
    QuantPolicy,
    dequantize_int8,
    kernel_dot,
    pack_kv,
    policy_of,
    quant_matmul,
    quantize_int8,
    unpack_kv,
)


def _rel_err(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-6)


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------

def test_quantize_int8_roundtrip_halfstep():
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 37), jnp.float32)
    q, s = quantize_int8(x, axis=-1)
    assert q.dtype == jnp.int8 and s.shape == (5, 1)
    err = jnp.abs(dequantize_int8(q, s) - x)
    assert bool(jnp.all(err <= s / 2 + 1e-7))
    # every row's absmax saturates the grid (symmetric absmax/127 scales)
    assert bool(jnp.all(jnp.max(jnp.abs(q), axis=-1) == 127))


def test_pack_unpack_kv_halfstep():
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    k = jax.random.normal(ks[0], (6, 2, 4, 8), jnp.float32)   # (N, K, P, hd)
    v = jax.random.normal(ks[1], (6, 2, 4, 8), jnp.float32)
    k_q, v_q, k_scale, v_scale = pack_kv(k, v)
    assert k_q.dtype == v_q.dtype == jnp.int8
    assert k_scale.shape == v_scale.shape == (6, 2)           # per page/head
    kd, vd = unpack_kv(k_q, v_q, k_scale, v_scale)
    assert bool(jnp.all(
        jnp.abs(kd - k) <= k_scale[:, :, None, None] / 2 + 1e-7
    ))
    assert bool(jnp.all(
        jnp.abs(vd - v) <= v_scale[:, :, None, None] / 2 + 1e-7
    ))


def test_kernel_dot_modes():
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    a = jax.random.normal(ks[0], (8, 16), jnp.float32)
    b = jax.random.normal(ks[1], (16, 4), jnp.float32)
    want = a @ b
    for pol in (None, QuantPolicy()):
        got = kernel_dot(a, b, pol)
        assert got.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6)
    for mode in ("bf16", "int8"):
        got = kernel_dot(a, b, QuantPolicy(matmul=mode))
        assert got.dtype == jnp.float32
        assert _rel_err(got, want) < 0.05, mode


# ---------------------------------------------------------------------------
# policy object: hashable static arg AND leafless traced pytree
# ---------------------------------------------------------------------------

def test_policy_validation_and_flags():
    with pytest.raises(ValueError, match="matmul"):
        QuantPolicy(matmul="fp4")
    assert not QuantPolicy().active
    assert QuantPolicy(matmul="int8").active
    assert QuantPolicy(matmul="int8") == QuantPolicy(matmul="int8")
    assert hash(QuantPolicy(matmul="bf16")) == hash(QuantPolicy(matmul="bf16"))


def test_policy_jit_stable_both_ways():
    pol = QuantPolicy(matmul="int8")
    # leafless pytree: flatten yields no leaves, so a policy passed as a
    # *traced* argument never becomes a tracer inside the function
    assert jax.tree_util.tree_leaves(pol) == []
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 4), jnp.float32)
    as_pytree = jax.jit(lambda p, x: kernel_dot(x, x, p))(pol, x)
    as_static = jax.jit(
        lambda x, *, p: kernel_dot(x, x, p), static_argnames="p"
    )(x, p=pol)
    np.testing.assert_allclose(np.asarray(as_pytree), np.asarray(as_static))


def test_policy_of_resolves_cfg_amp():
    cfg = get_smoke_config("mup-gpt")
    assert not policy_of(cfg).active                   # amp unset -> none
    assert policy_of(cfg.replace(amp="int8")).matmul == "int8"
    assert policy_of(cfg.replace(amp="bf16")).matmul == "bf16"


# ---------------------------------------------------------------------------
# straight-through quant_matmul (readout / CE logit path)
# ---------------------------------------------------------------------------

def test_quant_matmul_none_is_exact():
    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    x = jax.random.normal(ks[0], (3, 16, 32), jnp.float32)  # leading batch dim
    w = jax.random.normal(ks[1], (32, 8), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(quant_matmul(x, w)), np.asarray(x @ w), atol=1e-5
    )


@pytest.mark.parametrize("mode", ["bf16", "int8"])
def test_quant_matmul_ste_grads(mode):
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    x = jax.random.normal(ks[0], (16, 32), jnp.float32)
    w = jax.random.normal(ks[1], (32, 8), jnp.float32)

    def grads(policy):
        f = lambda x, w: jnp.sum(jnp.tanh(quant_matmul(x, w, policy)))
        return jax.grad(f, argnums=(0, 1))(x, w)

    gx0, gw0 = grads(None)
    exact = jax.grad(
        lambda x, w: jnp.sum(jnp.tanh(x @ w)), argnums=(0, 1)
    )(x, w)
    np.testing.assert_allclose(np.asarray(gx0), np.asarray(exact[0]),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(gw0), np.asarray(exact[1]),
                               atol=1e-5)
    pol = QuantPolicy(matmul=mode)
    assert _rel_err(quant_matmul(x, w, pol), x @ w) < 0.05
    gx, gw = grads(pol)
    assert _rel_err(gx, gx0) < 0.25, mode
    assert _rel_err(gw, gw0) < 0.25, mode


# ---------------------------------------------------------------------------
# policy-routed attention through ops dispatch
# ---------------------------------------------------------------------------

def _attn_case(seed=0):
    B, S, K, G, d = 2, 32, 2, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, S, K * G, d), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, K, d), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, K, d), jnp.float32)
    return q, k, v


def test_attention_inactive_policy_is_none():
    q, k, v = _attn_case()
    want = ops.attention(q, k, v, scale=0.25, impl="ref")
    got = ops.attention(q, k, v, scale=0.25, impl="ref",
                        policy=QuantPolicy())
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("mode", ["bf16", "int8"])
def test_attention_policy_ref_and_interpret(mode):
    q, k, v = _attn_case(seed=1)
    pol = QuantPolicy(matmul=mode)
    want = ops.attention(q, k, v, scale=0.25, impl="ref")
    a = ops.attention(q, k, v, scale=0.25, impl="ref", policy=pol)
    b = ops.attention(q, k, v, scale=0.25, impl="interpret", policy=pol)
    # quantized vs f32 oracle: rounding error only
    assert _rel_err(a, want) < 0.05, mode
    assert _rel_err(b, want) < 0.05, mode
    # ref (per-row scales over full T) vs kernel (per-tile scales) agree up
    # to the scale-granularity difference, far inside the oracle tier
    assert _rel_err(b, a) < 0.05, mode


@pytest.mark.parametrize("mode", ["bf16", "int8"])
def test_attention_policy_grads_close(mode):
    q, k, v = _attn_case(seed=3)

    def grads(policy):
        def f(q, k, v):
            o = ops.attention(q, k, v, scale=0.25, impl="interpret",
                              policy=policy)
            return jnp.sum(o * o)
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    g0 = grads(None)
    g1 = grads(QuantPolicy(matmul=mode))
    for a, b in zip(g1, g0):
        assert bool(jnp.all(jnp.isfinite(a)))
        assert _rel_err(a, b) < 0.25, mode


# ---------------------------------------------------------------------------
# end-to-end claims: u-µP coord-check stays flat and loss stays within 1%
# ---------------------------------------------------------------------------

AMP_WIDTHS = [1.0, 2.0, 4.0]


def _amp_factory(amp):
    base = get_smoke_config("mup-gpt").replace(
        dtype="float32", n_layers=2, zero_init_readout=False,
        zero_init_query=False,
    )

    def make_model(width_i):
        cfg = base.scaled(AMP_WIDTHS[width_i]).replace(
            parametrization="umup", amp=amp
        )
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))

        def loss_fn(params, batch):
            return model.loss_fn(params, batch, collect_acts=True)

        return params, model.meta, loss_fn

    return make_model


def test_umup_coord_check_flat_under_int8_amp():
    """The licensing claim: unit scaling keeps matmul operands O(1), so
    scaled-int8 matmuls must not reintroduce width-dependent logit growth
    (same bar as the f32 muP coord check: slope < 0.1)."""
    pipe = make_pipeline(256, 32, 8, seed=0)
    batches = [
        {k: jnp.asarray(v) for k, v in pipe.batch(t).items()}
        for t in range(3)
    ]
    res = coord_check(
        _amp_factory("int8"),
        widths=list(range(len(AMP_WIDTHS))),
        batches=batches,
        parametrization=Parametrization("umup"),
        optimizer="adam",
        lr=2e-2,
    )
    res.records = {
        int(64 * AMP_WIDTHS[i]): v for i, v in res.records.items()
    }
    g = res.growth("logits.delta", t=-1)
    assert g < 0.1, f"int8 amp broke coord-check flatness: slope {g}"
    for recs in res.records.values():
        for step in recs:
            assert all(
                jnp.isfinite(x) for k, x in step.items() if k == "logits"
            )


@pytest.fixture(scope="module")
def f32_train_baseline():
    cfg = get_smoke_config("mup-gpt").replace(dtype="float32", n_layers=2)
    kw = dict(steps=10, hps=HParams(lr=1e-2, sigma=1.0), batch_size=4,
              seq_len=32, log_every=0)
    out = train_loop(cfg, **kw)
    return cfg, kw, out["losses"]


@pytest.mark.parametrize("amp", ["bf16", "int8"])
def test_amp_loss_parity(f32_train_baseline, amp):
    """Equal-step loss within 1% of the f32 run (ISSUE-8 acceptance bar);
    master weights and optimizer state stay f32, only matmuls quantize."""
    cfg, kw, base_losses = f32_train_baseline
    out = train_loop(cfg.replace(amp=amp), **kw)
    want = float(np.mean(base_losses[-3:]))
    got = float(np.mean(out["losses"][-3:]))
    assert abs(got - want) / want < 0.01, (amp, got, want)
    # the policy is genuinely on the training path, not a silent no-op
    assert out["losses"] != base_losses, amp
