"""Serving cells: ``DynamicEngine.serve`` under open-loop arrivals.

One serve call replays the run's requests at their due times; the window is
that call, from the first arrival to the last completion.  Set-up builds the
engine and its weights on the device from the seed and compiles the
engine's one step for this request count by a serve of the same requests
that stops after its first step.  After the window the engine is freed and
the reference runs a sample of the finished requests in float32.
"""
from __future__ import annotations

import numpy as np

from harness import cells, reference as ref, stats, traffic as traffic_lib
from harness.record import Check, Run, Timer


class _FirstStepDone(Exception):
    pass


class _StopAfterFirstStep:
    """A tracer that ends a serve once its first step has run."""

    def event(self, *a, **k):
        pass

    def complete(self, *a, **k):
        raise _FirstStepDone


def _engine(cell, seed):
    import jax

    from repro.models.model import ACT_DTYPES, build_model
    from repro.serving.engine import DynamicEngine, EngineConfig

    tr = cell.traffic
    cfg = cells.family(cell).program_config(cell.config,
                                            kv_dtype=tr.get("kv_dtype", ""))
    model = build_model(cfg)
    key = jax.random.PRNGKey(traffic_lib.key_bits(seed))
    dtype = ACT_DTYPES[cfg.dtype]
    params = jax.jit(lambda k: model.init(k, dtype=dtype))(key)
    ecfg = EngineConfig(
        n_slots=tr["n_slots"], page_size=tr["page_size"],
        max_prompt_len=tr["max_prompt_len"], max_gen_len=tr["gen_len"],
        prefill_chunk=tr["prefill_chunk"], n_pages=tr.get("n_pages"),
    )
    return cfg, model, params, DynamicEngine(model, ecfg)


def _fresh_blocks(engine):
    """Free what the aborted warm-up serve took from the page allocator."""
    from repro.serving.allocator import BlockManager

    b = engine.blocks
    engine.blocks = BlockManager(
        n_pages=engine.n_pages, page_size=b.page_size, gp_cols=b.gp_cols,
        wp_cols=b.wp_cols, n_window_pages=engine.n_window_pages,
        prefix_cache=engine.ecfg.prefix_cache,
    )


def _warm(engine, params, reqs):
    """Compile and run the engine's step once for this request count: a
    serve of the same requests that stops after its first step, then the
    pages it took handed back."""
    from repro.obs import MetricsRegistry, ServeObs

    engine.obs = ServeObs(metrics=MetricsRegistry(),
                          tracer=_StopAfterFirstStep())
    try:
        engine.serve(params, reqs["prompts"], reqs["lens"])
    except _FirstStepDone:
        pass
    _fresh_blocks(engine)
    engine.obs = None


def knee(cell, seed, seconds, rates, devices):
    """The knee sweep: one engine, the run's requests replayed at each
    arrival rate (the same gaps, scaled); one latency summary per rate."""
    import jax

    from repro.distributed.sharding import make_rules, shardings

    tr = cell.traffic
    cfg, model, params, engine = _engine(cell, seed)
    n = traffic_lib.n_requests(tr, seconds)
    mesh = cells.mesh(cell, devices)
    rules = make_rules(mesh, cfg=cfg, fsdp=False, kind="decode")
    out = []
    with shardings(mesh, rules):
        for rate in rates:
            reqs = traffic_lib.serve_requests(tr, seed, n, cfg.vocab_size,
                                              rate=rate)
            _warm(engine, params, reqs)
            res = engine.serve(params, reqs["prompts"], reqs["lens"],
                               arrivals=reqs["arrivals"], record_times=True)
            lengths = np.asarray(jax.device_get(res["lengths"]))
            lat = stats.latency(res["token_times"], reqs["arrivals"], lengths)
            lat.update(rate=rate, requests=n, steps=int(res["steps"]),
                       offered_s=float(reqs["arrivals"][-1]))
            out.append(lat)
    return out


def serve_window(cell, seed, seconds, timer: Timer, tracer, trace: bool,
                 devices):
    """Set-up and window of one serving run; returns what was served."""
    import jax

    from repro.distributed.sharding import make_rules, shardings
    from repro.obs import MetricsRegistry, ServeObs, Tracer

    tr = cell.traffic
    cfg, model, params, engine = _engine(cell, seed)
    n = traffic_lib.n_requests(tr, seconds)
    reqs = traffic_lib.serve_requests(tr, seed, n, cfg.vocab_size)
    mesh = cells.mesh(cell, devices)
    rules = make_rules(mesh, cfg=cfg, fsdp=False, kind="decode")
    with shardings(mesh, rules):
        _warm(engine, params, reqs)
        obs = None
        if trace:
            obs = ServeObs(metrics=MetricsRegistry(), tracer=Tracer())
        engine.obs = obs
        with tracer.window():
            timer.window_start()
            with tracer.span("serve"):
                out = engine.serve(params, reqs["prompts"], reqs["lens"],
                                   arrivals=reqs["arrivals"],
                                   record_times=True)
                tokens = np.asarray(jax.device_get(out["tokens"]))
            timer.window_stop()
    lengths = np.asarray(jax.device_get(out["lengths"]))
    served = {
        "tokens": tokens, "lengths": lengths, "token_times":
        out["token_times"], "arrivals": np.asarray(reqs["arrivals"]),
        "prompts": reqs["prompts"], "lens": reqs["lens"],
        "steps": int(out["steps"]), "obs": obs,
        "compile_count": engine.compile_count(),
    }
    peak = timer.memory_peak()
    del engine, params, out
    return served, peak


def run(cell, seed: int, seconds: float, trace: bool, timer: Timer,
        tracer, devices, control=None) -> Run:
    tr = cell.traffic
    served, peak = serve_window(cell, seed, seconds, timer, tracer, trace,
                                devices)
    lat = stats.latency(served["token_times"], served["arrivals"],
                        served["lengths"])
    gen = int(tr["gen_len"])
    n = len(served["lens"])
    failed = int(np.sum(served["lengths"] < gen))
    r = Run(kind="serve", cell=cell, window_s=timer.window_s, attempted=n,
            failed=failed, memory_peak_bytes=peak, tokens=lat["tokens"],
            serve=served)
    r.e2e.update({
        "serve_tokens_per_s": lat["tokens_per_s"],
        "ttft_p95_s": lat["ttft_p95_s"],
        "itl_p95_s": lat["itl_p95_s"],
    })
    r.extra["latency"] = lat
    r.checks = check(cell, seed, served, control == "reference")
    return r


# ---------------------------------------------------------------------------
# the comparison with the reference
# ---------------------------------------------------------------------------

def sample(served: dict, k: int, seed: int) -> np.ndarray:
    """k finished requests drawn from the seed, the longest among them."""
    lens = served["lens"] + served["lengths"]
    done = np.nonzero(served["lengths"] > 0)[0]
    longest = done[np.argmax(lens[done])]
    rest = np.setdiff1d(done, [longest])
    g = traffic_lib.rng(seed, 2)
    pick = g.choice(rest, size=min(k - 1, rest.size), replace=False)
    return np.sort(np.concatenate([[longest], pick])).astype(int)


def reference_gaps(cell, seed: int, served: dict, idx: np.ndarray,
                   low: bool = False, group: int = 4) -> np.ndarray:
    """For each sampled request, the gap by which each served token's
    logit lies below the reference's best, at the position it was served
    from: (len(idx), gen) float64, NaN where nothing was served.  With
    ``low``, the token read at each position is the one the reference in
    the traffic file's lower operand type puts first (the control)."""
    import jax
    import jax.numpy as jnp

    tr = cell.traffic
    fam = cells.family(cell)
    s = fam.Spec.from_config(cell.config)
    m = cell.config["mup"]
    hp = ref.HP(sigma=m["sigma"], alpha_output=m["alpha_output"],
                alpha_attn=m["alpha_attn"], alpha_embed=m["alpha_embed"])
    key = jax.random.PRNGKey(traffic_lib.key_bits(seed))
    dtype = jnp.dtype(cell.config["dtype"])
    theta = jax.jit(lambda k: fam.init(k, s, hp.sigma, dtype))(key)
    G = int(tr["gen_len"])
    width = int(tr["max_prompt_len"]) + G

    def logits(theta, toks, start):
        h = fam.hidden(theta, toks, s, hp)
        pos = start[:, None] + jnp.arange(G)[None]         # (b, G)
        hg = jnp.take_along_axis(h, pos[..., None], axis=1)
        return fam.readout(theta, hg, s, hp)                # (b, G, V)

    @jax.jit
    def gaps(theta, toks, start, picked):
        lg = logits(theta, toks, start)
        pick = jnp.take_along_axis(lg, picked[..., None], axis=-1)[..., 0]
        return jnp.max(lg, axis=-1) - pick

    batches = []
    for g0 in range(0, len(idx), group):
        rows = idx[g0:g0 + group]
        toks = np.zeros((group, width), np.int32)
        start = np.zeros((group,), np.int32)
        st = np.zeros((group, G), np.int32)
        for j, r in enumerate(rows):
            plen, n = int(served["lens"][r]), int(served["lengths"][r])
            toks[j, :plen] = served["prompts"][r, :plen]
            toks[j, plen:plen + n] = served["tokens"][r, :n]
            start[j] = plen - 1
            st[j, :n] = served["tokens"][r, :n]
        batches.append((g0, rows, toks, start, st))
    if low:
        with ref.operands(jnp.dtype(tr["control"]["operands"])):
            first = jax.jit(lambda th, t, st: jnp.argmax(
                logits(th, t, st), axis=-1).astype(jnp.int32))
            batches = [(g0, rows, toks, start, first(theta, toks, start))
                       for g0, rows, toks, start, _ in batches]

    out = np.full((len(idx), G), np.nan)
    for g0, rows, toks, start, st in batches:
        gp = np.asarray(gaps(theta, toks, start, st), np.float64)
        for j, r in enumerate(rows):
            n = int(served["lengths"][r])
            out[g0 + j, :n] = gp[j, :n]
    return out


def check(cell, seed, served, low: bool = False) -> list:
    import jax

    tr = cell.traffic
    idx = sample(served, int(tr["check_requests"]), seed)
    gp = reference_gaps(cell, seed, served, idx, low=low)
    jax.clear_caches()
    worst = float(np.nanmax(gp))
    r, j = np.unravel_index(int(np.nanargmax(gp)), gp.shape)
    n_tok = int(np.sum(~np.isnan(gp)))
    return [Check(
        "served_logit_gap", worst, cell.limits["served_logit_gap"],
        f"{n_tok} served tokens of {len(idx)} requests; worst at request "
        f"{int(idx[r])} token {int(j)}",
    )]
