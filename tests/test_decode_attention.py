"""Differential tests for the flash-decode kernels (paged decode attn).

Three-level oracle chain, for both the single-query decode kernel and the
multi-query verify kernel (speculative decoding's k-token chunk):
  dense attend/make_mask (models/attention.py, the repo's ground truth)
    == decode_attention[_multi]_ref (paged gather oracle, kernels/ref.py)
    == flash_decode[_multi] kernel body (interpret mode,
       kernels/decode_attention.py)

Tolerance policy matches the flash-attention forward tests: all compute is
f32 in both impls, so agreement is to a few ulps — atol 2e-5.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.decode_attention import flash_decode, flash_decode_multi
from repro.models import attention as A

ATOL = 2e-5


def _paged_case(B, K, G, d, P, C, T, seed=0, permute=True):
    """Build a paged pool holding a contiguous history of T tokens per slot.

    Returns (q, pools..., table, q_pos) plus the dense (B, T, K, d) arrays
    the oracle attends over.  The table is a nontrivial interleaved layout
    (slot s's page j at physical j*B + s + 2) so correctness depends on the
    indirection actually being followed.
    """
    H = K * G
    N = B * C + 3
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, H, d), jnp.float32)
    k_dense = jax.random.normal(ks[1], (B, C * P, K, d), jnp.float32)
    v_dense = jax.random.normal(ks[2], (B, C * P, K, d), jnp.float32)
    if permute:
        tab = (jnp.arange(C)[None, :] * B + jnp.arange(B)[:, None] + 2) % N
    else:
        tab = jnp.arange(B * C).reshape(B, C)
    tab = tab.astype(jnp.int32)
    kp = jnp.zeros((N, K, P, d), jnp.float32)   # kv-head-major pool
    vp = jnp.zeros((N, K, P, d), jnp.float32)
    pos = jnp.full((N, P), -1, jnp.int32)
    # scatter the first T tokens of each slot into its pages, page-major
    t = jnp.arange(T)
    cols = t // P
    pages = jnp.take_along_axis(
        tab, jnp.broadcast_to(cols[None], (B, T)), axis=1
    )  # (B, T)
    offs = jnp.broadcast_to((t % P)[None], (B, T))
    b_idx = jnp.broadcast_to(jnp.arange(B)[:, None], (B, T))
    kp = kp.at[pages, :, offs].set(k_dense[b_idx, t[None, :]])
    vp = vp.at[pages, :, offs].set(v_dense[b_idx, t[None, :]])
    pos = pos.at[pages, offs].set(jnp.broadcast_to(t[None], (B, T)))
    q_pos = jnp.full((B,), T - 1, jnp.int32)
    return q, kp, vp, pos, tab, q_pos, k_dense[:, :T], v_dense[:, :T]


def _dense_oracle(q, k, v, q_pos, window, softcap):
    """Single-query dense attention through the repo's attend/make_mask."""
    B, T = k.shape[:2]
    kv_pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    mask = A.make_mask(q_pos[:, None], kv_pos, causal=True, window=window)
    return A.attend(q[:, None], k, v, mask, 0.125, softcap)[:, 0]


@pytest.mark.parametrize("K,G", [(1, 4), (2, 2), (4, 1)])  # MQA / GQA / MHA
@pytest.mark.parametrize("window", [0, 9])
@pytest.mark.parametrize("softcap", [0.0, 30.0])
def test_ref_and_kernel_match_dense(K, G, window, softcap):
    B, d, P, C, T = 2, 8, 4, 6, 21
    q, kp, vp, pos, tab, q_pos, kd, vd = _paged_case(B, K, G, d, P, C, T)
    want = _dense_oracle(q, kd, vd, q_pos, window, softcap)
    got_ref = ref.decode_attention_ref(
        q, kp, vp, pos, tab, q_pos, scale=0.125, window=window, softcap=softcap
    )
    np.testing.assert_allclose(np.asarray(got_ref), np.asarray(want), atol=ATOL)
    got_k = flash_decode(
        q, kp, vp, pos, tab, q_pos, scale=0.125, window=window,
        softcap=softcap, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(got_k), np.asarray(got_ref), atol=ATOL)


def test_ops_dispatch_interpret_and_traced_scale():
    B, K, G, d, P, C, T = 2, 2, 2, 8, 4, 5, 17
    q, kp, vp, pos, tab, q_pos, kd, vd = _paged_case(B, K, G, d, P, C, T)
    want = ops.decode_attention(
        q, kp, vp, pos, tab, q_pos, scale=0.125, impl="ref"
    )
    got = ops.decode_attention(
        q, kp, vp, pos, tab, q_pos, scale=0.125, impl="interpret"
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL)
    # scale may be a traced scalar (alpha_attn threading): fold-into-q path
    scaled = jax.jit(
        lambda s: ops.decode_attention(
            q, kp, vp, pos, tab, q_pos, scale=s, impl="interpret"
        )
    )(jnp.float32(0.125))
    np.testing.assert_allclose(np.asarray(scaled), np.asarray(want), atol=ATOL)


def test_inactive_slot_returns_zeros():
    B, K, G, d, P, C, T = 3, 2, 2, 8, 4, 4, 11
    q, kp, vp, pos, tab, q_pos, *_ = _paged_case(B, K, G, d, P, C, T)
    q_pos = q_pos.at[1].set(-1)
    for impl in ("ref", "interpret"):
        out = ops.decode_attention(
            q, kp, vp, pos, tab, q_pos, scale=0.125, impl=impl
        )
        assert bool(jnp.all(out[1] == 0)), impl
        assert bool(jnp.all(jnp.isfinite(out)))


def test_page_permutation_invariance():
    """Attention must be invariant under a physical re-paging (pool permuted,
    table updated) — the defining property of the indirection."""
    B, K, G, d, P, C, T = 2, 2, 2, 8, 4, 5, 18
    q, kp, vp, pos, tab, q_pos, *_ = _paged_case(B, K, G, d, P, C, T)
    base = flash_decode(q, kp, vp, pos, tab, q_pos, scale=0.125, interpret=True)
    N = kp.shape[0]
    perm = jnp.roll(jnp.arange(N), 5)          # new physical location of page i
    inv = jnp.argsort(perm)
    out = flash_decode(
        q, kp[inv], vp[inv], pos[inv], perm[tab], q_pos,
        scale=0.125, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(base), atol=ATOL)


def test_ring_stale_entries_masked():
    """Entries whose stored position falls outside the window (the stale
    remainder of a partially-overwritten ring page) must have zero weight."""
    B, K, G, d, P, C = 1, 1, 2, 8, 4, 3
    T, window = 11, 7
    q, kp, vp, pos, tab, q_pos, kd, vd = _paged_case(B, K, G, d, P, C, T)
    # poison every entry older than the window; output must not move
    old = (q_pos[0] - pos) >= window
    vp2 = jnp.where(old[:, None, :, None], 1e4, vp)
    a = flash_decode(q, kp, vp, pos, tab, q_pos, scale=0.125, window=window,
                     interpret=True)
    b = flash_decode(q, kp, vp2, pos, tab, q_pos, scale=0.125, window=window,
                     interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=ATOL)
    # ... and the windowed result matches the dense windowed oracle
    want = _dense_oracle(q, kd, vd, q_pos, window, 0.0)
    np.testing.assert_allclose(np.asarray(a), np.asarray(want), atol=ATOL)


def test_half_filled_page():
    """q_pos mid-page: entries past q_pos in the current page are invisible."""
    B, K, G, d, P, C, T = 1, 2, 1, 8, 4, 4, 14
    q, kp, vp, pos, tab, q_pos, kd, vd = _paged_case(B, K, G, d, P, C, T)
    q_pos = jnp.array([9], jnp.int32)          # mid page 2; pages 3+ unused
    want = _dense_oracle(q, kd[:, :10], vd[:, :10], q_pos, 0, 0.0)
    got = flash_decode(q, kp, vp, pos, tab, q_pos, scale=0.125, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL)


# ---------------------------------------------------------------------------
# multi-query variant (speculative verify / drafter catch-up chunks)
# ---------------------------------------------------------------------------

def _multi_case(B, K, G, d, P, C, T, Tq, seed=0):
    """A paged history of T tokens plus a Tq-query chunk whose rows sit at
    positions T-Tq .. T-1 (the chunk already written, as the engine does)."""
    _, kp, vp, pos, tab, _, kd, vd = _paged_case(B, K, G, d, P, C, T, seed)
    q = jax.random.normal(jax.random.PRNGKey(seed + 9), (B, Tq, K * G, d))
    q_pos = jnp.broadcast_to(
        jnp.arange(T - Tq, T)[None], (B, Tq)
    ).astype(jnp.int32)
    return q, kp, vp, pos, tab, q_pos, kd, vd


def _dense_oracle_multi(q, k, v, q_pos, window, softcap):
    B, T = k.shape[:2]
    kv_pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    mask = A.make_mask(q_pos, kv_pos, causal=True, window=window)
    return A.attend(q, k, v, mask, 0.125, softcap)


@pytest.mark.parametrize("K,G", [(1, 4), (2, 2), (4, 1)])  # MQA / GQA / MHA
@pytest.mark.parametrize("window", [0, 9])
@pytest.mark.parametrize("softcap", [0.0, 30.0])
def test_multi_ref_and_kernel_match_dense(K, G, window, softcap):
    B, d, P, C, T, Tq = 2, 8, 4, 6, 21, 5
    q, kp, vp, pos, tab, q_pos, kd, vd = _multi_case(B, K, G, d, P, C, T, Tq)
    want = _dense_oracle_multi(q, kd, vd, q_pos, window, softcap)
    got_ref = ref.decode_attention_multi_ref(
        q, kp, vp, pos, tab, q_pos, scale=0.125, window=window, softcap=softcap
    )
    np.testing.assert_allclose(np.asarray(got_ref), np.asarray(want), atol=ATOL)
    got_k = flash_decode_multi(
        q, kp, vp, pos, tab, q_pos, scale=0.125, window=window,
        softcap=softcap, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(got_k), np.asarray(got_ref), atol=ATOL)


def test_multi_agrees_with_single_query_rows():
    """Each chunk row must equal the single-query kernel at that position —
    the property that makes a (k+1)-token verify interchangeable with k+1
    sequential decode steps."""
    B, K, G, d, P, C, T, Tq = 2, 2, 2, 8, 4, 6, 19, 4
    q, kp, vp, pos, tab, q_pos, *_ = _multi_case(B, K, G, d, P, C, T, Tq)
    multi = flash_decode_multi(
        q, kp, vp, pos, tab, q_pos, scale=0.125, interpret=True
    )
    for t in range(Tq):
        single = flash_decode(
            q[:, t], kp, vp, pos, tab, q_pos[:, t], scale=0.125,
            interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(multi[:, t]), np.asarray(single), atol=ATOL
        )


def test_multi_ops_dispatch_interpret_and_traced_scale():
    B, K, G, d, P, C, T, Tq = 2, 2, 2, 8, 4, 5, 17, 3
    q, kp, vp, pos, tab, q_pos, *_ = _multi_case(B, K, G, d, P, C, T, Tq)
    want = ops.decode_attention_multi(
        q, kp, vp, pos, tab, q_pos, scale=0.125, impl="ref"
    )
    got = ops.decode_attention_multi(
        q, kp, vp, pos, tab, q_pos, scale=0.125, impl="interpret"
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL)
    scaled = jax.jit(
        lambda s: ops.decode_attention_multi(
            q, kp, vp, pos, tab, q_pos, scale=s, impl="interpret"
        )
    )(jnp.float32(0.125))
    np.testing.assert_allclose(np.asarray(scaled), np.asarray(want), atol=ATOL)


def test_multi_masked_rows_return_zeros():
    """Whole-slot q_pos = -1 (inactive) and single -1 rows (the drafter
    catch-up before a short prompt) both produce exact zeros."""
    B, K, G, d, P, C, T, Tq = 3, 2, 2, 8, 4, 4, 11, 3
    q, kp, vp, pos, tab, q_pos, *_ = _multi_case(B, K, G, d, P, C, T, Tq)
    q_pos = q_pos.at[1].set(-1)     # inactive slot
    q_pos = q_pos.at[0, 0].set(-1)  # one masked leading row
    for impl in ("ref", "interpret"):
        out = ops.decode_attention_multi(
            q, kp, vp, pos, tab, q_pos, scale=0.125, impl=impl
        )
        assert bool(jnp.all(out[1] == 0)), impl
        assert bool(jnp.all(out[0, 0] == 0)), impl
        assert bool(jnp.all(jnp.isfinite(out))), impl
