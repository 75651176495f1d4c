"""Speculative-decoding tests: losslessness, rollback, PRNG, distribution.

The engine's speculative path must be *invisible* in outputs: greedy spec
serves are compared token-for-token against the non-speculative engine and
the dense-loop oracle (including EOS retirement mid-draft-chunk and
windowed-ring wraparound during rollback), and stochastic spec serves are
compared in distribution against the target-only process.  The drafter is
either the target's narrow µP proxy with random params (acceptance near
chance — the rejection/resample path dominates) or the target itself
(acceptance 1 — the all-accept/bonus path dominates); losslessness must
hold for ANY drafter, so both extremes run.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models.model import build_model
from repro.serving import kv_cache, sampling
from repro.serving.engine import Engine, EngineConfig


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def global_m():
    cfg = get_smoke_config("smollm-135m").replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def proxy_m(global_m):
    """The µTransfer drafter: a narrow proxy of the target (random params —
    worst-case acceptance, best-case rejection coverage)."""
    cfg, _, _ = global_m
    dcfg = cfg.scaled(0.5, min_d_head=8)
    dmodel = build_model(dcfg)
    dparams = dmodel.init(jax.random.PRNGKey(7))
    return dcfg, dmodel, dparams


def _prompts(cfg, R, L, seed=1):
    prompts = jax.random.randint(
        jax.random.PRNGKey(seed), (R, L), 0, cfg.vocab_size
    )
    lens = jax.random.randint(jax.random.PRNGKey(seed + 1), (R,), 1, L + 1)
    return prompts, lens


_ECFG = dict(n_slots=2, page_size=4, max_prompt_len=16, max_gen_len=6)


# ---------------------------------------------------------------------------
# unit: multi-token paged writes == sequential single-token writes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ring", [False, True])
def test_chunk_write_equals_single_writes(ring):
    B, T, K, hd, P, C = 2, 5, 2, 8, 4, 3
    N = B * C
    rng = jax.random.PRNGKey(0)
    kc = jax.random.normal(jax.random.fold_in(rng, 1), (B, T, K, hd))
    vc = jax.random.normal(jax.random.fold_in(rng, 2), (B, T, K, hd))
    table = (jnp.arange(C)[None] * B + jnp.arange(B)[:, None]).astype(jnp.int32)
    positions = jnp.array([[3, 4, 5, 6, 7], [-1, 9, 10, 11, 12]], jnp.int32)
    active = jnp.array([True, True])
    blank = {
        "k": jnp.zeros((N, K, P, hd)), "v": jnp.zeros((N, K, P, hd)),
        "pos": jnp.full((N, P), -1, jnp.int32),
    }
    chunk = kv_cache.paged_cache_write(
        blank, kc, vc, positions, table, active, P, ring
    )
    steps = blank
    for t in range(T):
        steps = kv_cache.paged_cache_write(
            steps, kc[:, t:t + 1], vc[:, t:t + 1], positions[:, t:t + 1],
            table, active, P, ring,
        )
    for leaf in ("k", "v", "pos"):
        np.testing.assert_array_equal(
            np.asarray(chunk[leaf]), np.asarray(steps[leaf]), err_msg=leaf
        )


def test_build_spec_lookahead_grows_ring():
    cfg = get_smoke_config("gemma2-2b").replace(window_size=6)
    base = kv_cache.build_spec(cfg, 2, 64, 4)
    spec = kv_cache.build_spec(cfg, 2, 64, 4, lookahead=4)
    # window 6 needs ceil(6/4)+1 = 3 ring pages; +4 lookahead needs
    # ceil(10/4)+1 = 4 — the write-ahead must widen the ring
    assert base.wp_cols == 3 and spec.wp_cols == 4


# ---------------------------------------------------------------------------
# unit: rejection sampling reproduces the target distribution exactly
# ---------------------------------------------------------------------------

def test_spec_accept_greedy_exact():
    """One-hot p/q: accept iff the drafter hit the target argmax; the
    resample always returns the target argmax."""
    V = 16
    key = jax.random.PRNGKey(3)
    p_log = jax.random.normal(jax.random.fold_in(key, 1), (V,))
    q_log = jax.random.normal(jax.random.fold_in(key, 2), (V,))
    greedy = lambda lg: sampling.filtered_dist(
        lg[None], jnp.zeros(1), jnp.zeros(1, jnp.int32), jnp.ones(1)
    )[0]
    p, q = greedy(p_log), greedy(q_log)
    keys = jax.random.split(jax.random.PRNGKey(4), 32)
    for i in range(0, 32, 2):
        for d in (int(jnp.argmax(p)), int(jnp.argmax(q)), 0):
            n_acc, extra = sampling.spec_accept(
                jnp.stack([p, p])[None], q[None, None],
                jnp.array([[d]], jnp.int32),
                keys[i].reshape(1, 1, 2), jnp.stack([keys[i + 1]] * 2)[None],
            )
            if d == int(jnp.argmax(p)):
                assert int(n_acc[0]) == 1
                assert int(extra[0]) == int(jnp.argmax(p))  # bonus
            else:
                assert int(n_acc[0]) == 0
                assert int(extra[0]) == int(jnp.argmax(p))  # resample


def test_spec_accept_matches_target_distribution():
    """draft ~ q, accept with p/q, resample from the residual: the output
    marginal must be exactly p (TV < sampling noise over 6000 chains)."""
    V, N = 8, 6000
    key = jax.random.PRNGKey(0)
    p_log = jax.random.normal(jax.random.fold_in(key, 1), (V,)) * 1.5
    q_log = jax.random.normal(jax.random.fold_in(key, 2), (V,)) * 1.5
    p = sampling.filtered_dist(
        p_log[None], jnp.array([0.9]), jnp.array([5], jnp.int32),
        jnp.array([0.85]),
    )[0]
    q = sampling.filtered_dist(
        q_log[None], jnp.array([1.1]), jnp.array([0], jnp.int32),
        jnp.array([1.0]),
    )[0]

    def one_chain(k):
        kd, ka, ks = jax.random.split(k, 3)
        d = jax.random.categorical(kd, jnp.log(q))[None, None].astype(jnp.int32)
        n_acc, extra = sampling.spec_accept(
            jnp.stack([p, p])[None], q[None, None], d,
            ka.reshape(1, 1, 2), jnp.stack([ks, ks])[None],
        )
        return jnp.where(n_acc[0] > 0, d[0, 0], extra[0])

    toks = jax.vmap(one_chain)(jax.random.split(jax.random.PRNGKey(42), N))
    emp = np.bincount(np.asarray(toks).ravel(), minlength=V) / N
    tv = 0.5 * np.abs(emp - np.asarray(p)).sum()
    assert tv < 0.03, tv


# ---------------------------------------------------------------------------
# engine: greedy losslessness (proxy and self drafters, several k)
# ---------------------------------------------------------------------------

def test_greedy_spec_matches_engine_token_for_token(global_m, proxy_m):
    cfg, model, params = global_m
    _, dmodel, dparams = proxy_m
    prompts, lens = _prompts(cfg, R=5, L=16)
    base = Engine(model, EngineConfig(**_ECFG))
    want = base.serve(params, prompts, lens)
    for dm, dp, k in ((dmodel, dparams, 2), (model, params, 3)):
        eng = Engine(
            model, EngineConfig(**_ECFG, draft_k=k), draft_model=dm
        )
        out = eng.serve(params, prompts, lens, draft_params=dp)
        np.testing.assert_array_equal(
            np.asarray(out["tokens"]), np.asarray(want["tokens"])
        )
        np.testing.assert_array_equal(
            np.asarray(out["lengths"]), np.asarray(want["lengths"])
        )
        assert int(out["proposed"]) > 0
        # speculation must commit > 1 token/iteration somewhere: fewer
        # engine iterations than the one-token-per-step baseline
        assert int(out["steps"]) <= int(want["steps"])


def test_spec_zero_recompile_and_determinism(global_m, proxy_m):
    """One compile across workloads (content is traced data), and the same
    workload twice gives the same tokens — spec keys are (request,
    position)-derived, never wall-clock or iteration state."""
    cfg, model, params = global_m
    _, dmodel, dparams = proxy_m
    eng = Engine(model, EngineConfig(**_ECFG, draft_k=2), draft_model=dmodel)
    p1, l1 = _prompts(cfg, R=4, L=16, seed=3)
    t = jnp.array([0.0, 1.0, 0.7, 0.0])
    a = eng.serve(params, p1, l1, temperature=t, seed=5, draft_params=dparams)
    b = eng.serve(params, p1, l1, temperature=t, seed=5, draft_params=dparams)
    np.testing.assert_array_equal(np.asarray(a["tokens"]), np.asarray(b["tokens"]))
    # different content, same envelope -> same compiled program
    p2, l2 = _prompts(cfg, R=4, L=16, seed=11)
    eng.serve(params, p2, l2, temperature=t, seed=6, draft_params=dparams)
    assert eng.compile_count() == 1


def test_spec_eos_mid_draft_retirement(global_m):
    """EOS landing inside an accepted draft chunk must truncate the commit
    there: nothing after the EOS is emitted, lengths match the
    non-speculative engine exactly."""
    cfg, model, params = global_m
    Gmax = _ECFG["max_gen_len"]
    probe_engine = Engine(model, EngineConfig(**_ECFG))
    # the prompts are chosen by property, not by seed: most random prompts
    # send the untrained model's greedy stream into a single-token
    # attractor, which leaves no mid-stream EOS candidate.  Pick the first
    # prompt set with an EOS the greedy stream actually emits such that
    # some row's first hit lands strictly inside the budget — mid-run
    # retirement.
    eos = -1
    for seed in range(2, 34, 2):
        prompts, lens = _prompts(cfg, R=5, L=16, seed=seed)
        toks = np.asarray(probe_engine.serve(params, prompts, lens)["tokens"])
        for e in np.unique(toks):
            first = np.where(
                (toks == e).any(1), (toks == e).argmax(1) + 1, Gmax
            )
            if np.any((first > 1) & (first < Gmax)):
                eos = int(e)
                break
        if eos >= 0:
            break
    assert eos >= 0, toks
    base = Engine(model, EngineConfig(**_ECFG, eos_token_id=eos))
    want = base.serve(params, prompts, lens)
    # self-drafting: acceptance 1, so every commit is a full k+1 chunk and
    # the EOS (when it comes) is mid-chunk unless it happens to align
    eng = Engine(
        model, EngineConfig(**_ECFG, eos_token_id=eos, draft_k=3),
        draft_model=model,
    )
    out = eng.serve(params, prompts, lens, draft_params=params)
    L = np.asarray(want["lengths"])
    np.testing.assert_array_equal(np.asarray(out["lengths"]), L)
    for r in range(len(L)):
        np.testing.assert_array_equal(
            np.asarray(out["tokens"])[r, :L[r]],
            np.asarray(want["tokens"])[r, :L[r]],
        )
    # the scenario must actually exercise mid-draft retirement: some row
    # stops strictly inside the budget at a non-chunk-aligned length
    assert np.any((L > 1) & (L < Gmax)), L


def test_spec_windowed_ring_wraparound(global_m):
    """Windowed (gemma2-style) model, window 6, 20 generated tokens: the
    ring wraps several times while speculative chunks write ahead of the
    committed position — rollback overwrites must stay lossless."""
    cfg = get_smoke_config("gemma2-2b").replace(dtype="float32", window_size=6)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    dcfg = cfg.scaled(0.5, min_d_head=8)
    dmodel = build_model(dcfg)
    dparams = dmodel.init(jax.random.PRNGKey(7))
    prompts, _ = _prompts(cfg, R=4, L=12, seed=3)
    lens = jnp.array([12, 5, 9, 1], jnp.int32)
    ecfg = dict(n_slots=2, page_size=4, max_prompt_len=12, max_gen_len=20)
    want = Engine(model, EngineConfig(**ecfg)).serve(params, prompts, lens)
    for dm, dp in ((dmodel, dparams), (model, params)):
        eng = Engine(
            model, EngineConfig(**ecfg, draft_k=3), draft_model=dm
        )
        out = eng.serve(params, prompts, lens, draft_params=dp)
        np.testing.assert_array_equal(
            np.asarray(out["tokens"]), np.asarray(want["tokens"])
        )
        np.testing.assert_array_equal(
            np.asarray(out["lengths"]), np.asarray(want["lengths"])
        )


# ---------------------------------------------------------------------------
# PRNG: (request, position)-folded keys — the satellite regression
# ---------------------------------------------------------------------------

def test_stochastic_stream_invariant_to_admission_timing(global_m):
    """A request's sample stream is a pure function of (seed, request,
    position).  Under speculation slots advance by data-dependent accepted
    lengths, so the same request gets admitted at *different loop
    iterations* depending on what ran before it — iteration-folded keys
    (the old scheme) would give it different tokens.  Serve [B1, A] and
    [B2, A] with n_slots=1: B's content changes its own acceptance pattern
    and retirement iteration, A's stream must not move."""
    cfg, model, params = global_m
    eng = Engine(
        model,
        EngineConfig(n_slots=1, page_size=4, max_prompt_len=16, max_gen_len=6,
                     draft_k=2),
        draft_model=model,
    )
    pA = jax.random.randint(jax.random.PRNGKey(21), (1, 16), 0, cfg.vocab_size)
    outs = []
    steps = []
    for seedB in (31, 32):
        pB = jax.random.randint(
            jax.random.PRNGKey(seedB), (1, 16), 0, cfg.vocab_size
        )
        prompts = jnp.concatenate([pB, pA])
        lens = jnp.array([16, 9], jnp.int32)
        out = eng.serve(
            params, prompts, lens,
            temperature=jnp.array([0.9, 1.0]),
            top_k=jnp.array([0, 8], jnp.int32),
            seed=2, draft_params=params,
        )
        outs.append(np.asarray(out["tokens"][1]))
        steps.append(int(out["steps"]))
    np.testing.assert_array_equal(outs[0], outs[1])
    assert eng.compile_count() == 1


def test_identical_requests_get_independent_streams(global_m):
    """Two copies of the same stochastic request must not mirror each other
    (keys fold the request id, not just the position)."""
    cfg, model, params = global_m
    eng = Engine(model, EngineConfig(**_ECFG))
    lens = jnp.array([16, 16], jnp.int32)
    # temp 2: the untrained model's logits are peaked enough that temp 1
    # sampling is near-deterministic and both rows would agree by chance.
    # Even at temp 2 some prompts sit in a peaked region, so the prompt is
    # chosen by property: keys that ignored the request id would give equal
    # rows for *every* prompt, so one prompt with unequal rows proves it.
    rows_differ = []
    for key in range(5, 21):
        p = jax.random.randint(
            jax.random.PRNGKey(key), (1, 16), 0, cfg.vocab_size
        )
        out = eng.serve(
            params, jnp.concatenate([p, p]), lens,
            temperature=jnp.array([2.0, 2.0]), seed=0,
        )
        toks = np.asarray(out["tokens"])
        rows_differ.append(not np.array_equal(toks[0], toks[1]))
        if rows_differ[-1]:
            break
    assert rows_differ[-1], rows_differ


# ---------------------------------------------------------------------------
# distribution: stochastic spec sampling == target-only sampling
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_spec_sampling_matches_target_distribution(global_m, proxy_m):
    """Temperature/top-k spec serving must sample from the target process:
    pool the (first, second) generated-token pairs of many i.i.d. requests
    (same prompt, per-request keys) and TV-compare spec vs non-spec.  The
    proxy drafter's random params make acceptance near chance, so most
    tokens go through the reject/residual path — a bias there (e.g.
    sampling from the drafter's distribution) would push TV toward 1."""
    cfg, model, params = global_m
    _, dmodel, dparams = proxy_m
    R, L = 192, 8
    prompts = jnp.tile(
        jax.random.randint(jax.random.PRNGKey(17), (1, L), 0, cfg.vocab_size),
        (R, 1),
    )
    lens = jnp.full((R,), L, jnp.int32)
    kw = dict(
        temperature=jnp.full((R,), 0.7),
        top_k=jnp.full((R,), 4, jnp.int32),
        seed=13,
    )
    ecfg = dict(n_slots=4, page_size=4, max_prompt_len=8, max_gen_len=2)
    base = Engine(model, EngineConfig(**ecfg))
    spec = Engine(
        model, EngineConfig(**ecfg, draft_k=2), draft_model=dmodel
    )
    a = base.serve(params, prompts, lens, **kw)
    b = spec.serve(params, prompts, lens, **kw, draft_params=dparams)

    def pairs(out):
        t = np.asarray(out["tokens"])
        return [tuple(row) for row in t]

    support = sorted(set(pairs(a)) | set(pairs(b)))
    pa = np.array([pairs(a).count(s) for s in support], float) / R
    pb = np.array([pairs(b).count(s) for s in support], float) / R
    tv = 0.5 * np.abs(pa - pb).sum()
    # top-k 4 over 2 positions: <= ~16 live outcomes; at R=192 two honest
    # empirical draws sit around TV ~ 0.1-0.15
    assert tv < 0.25, (tv, support)
