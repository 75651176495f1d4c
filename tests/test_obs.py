"""Observability subsystem tests (repro.obs): metrics registry units,
Prometheus exposition round-trip, benchmark percentile dedup ("identical
outputs", not "approximately equal"), phase tracer schema, µP-health
telemetry equivalence against the coord-check golden fixtures, the
width-exponent drift detector separating SP from µP/u-µP at 4x the proxy
width, and the zero-recompile contract with instrumentation fully enabled
on the static / dynamic / speculative engines (meshes in the multidevice
variant)."""
import functools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.transfer import HParams
from repro.data.pipeline import make_pipeline
from repro.launch.steps import make_train_step
from repro.launch.train import train_loop
from repro.models.model import build_model
from repro.obs import (
    DriftDetector,
    Histogram,
    MetricsRegistry,
    RingBuffer,
    ServeObs,
    Tracer,
    TrainObs,
    flatten_stats,
    load_jsonl,
    parse_prometheus,
    percentile_summary,
)
from repro.optim.optimizer import Optimizer
from repro.serving.engine import DynamicEngine, Engine, EngineConfig

GOLDEN_PATH = os.path.join(
    os.path.dirname(__file__), "golden", "coord_check.json"
)


# ---------------------------------------------------------------------------
# metrics registry units
# ---------------------------------------------------------------------------

def test_counter_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("requests_total", "help text")
    c.inc()
    c.inc(3)
    assert c.value == 4
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("pool_occupancy")
    g.set(7)
    g.inc()
    g.dec(2)
    assert g.value == 6
    # get-or-create: same object back, kind clash rejected
    assert reg.counter("requests_total") is c
    with pytest.raises(ValueError):
        reg.gauge("requests_total")
    assert "requests_total" in reg
    assert reg.get("missing") is None


def test_histogram_exact_percentiles():
    rng = np.random.default_rng(0)
    xs = rng.exponential(0.01, size=500)
    h = Histogram("lat_seconds")
    for x in xs:
        h.observe(x)
    assert h.count == 500
    np.testing.assert_allclose(h.sum, xs.sum())
    want = np.percentile(xs, [50, 95, 99])
    assert h.percentiles() == tuple(float(v) for v in want)
    # bucket counts: cumulative, monotone, total == count
    cum = h.cumulative_counts()
    assert cum == sorted(cum) and cum[-1] == 500
    # summary keying
    s = h.summary((50, 95, 99), unit=1e3, suffix="_ms")
    assert set(s) == {"p50_ms", "p95_ms", "p99_ms"}
    assert s["p50_ms"] == float(want[0]) * 1e3


def test_histogram_observe_many_matches_scalar_path():
    rng = np.random.default_rng(1)
    xs = rng.exponential(0.01, size=300)
    one, many = Histogram("a"), Histogram("b")
    for x in xs:
        one.observe(x)
    many.observe_many(xs)
    assert one.count == many.count
    np.testing.assert_allclose(one.sum, many.sum)
    assert one.cumulative_counts() == many.cumulative_counts()
    assert one.percentiles() == many.percentiles()


def test_histogram_sample_cap_keeps_sum_exact():
    h = Histogram("capped", max_samples=64)
    h.observe_many(np.ones(1000))
    assert h.count == 1000 and h.sum == 1000.0
    assert len(h.samples) <= 64      # quantile window degraded, not wrong


def test_percentile_summary_identical_to_old_benchmark_formula():
    """The dedup contract: percentile_summary must be bit-identical to the
    ``np.percentile(np.asarray(x) * 1e3, [50, 95, 99])`` the benchmarks
    used before the shared helper replaced their private copies."""
    rng = np.random.default_rng(2)
    xs = list(rng.exponential(0.02, size=137))
    want = np.percentile(np.asarray(xs) * 1e3, [50, 95, 99])
    got = percentile_summary(xs)
    assert got["p50_ms"] == want[0]
    assert got["p95_ms"] == want[1]
    assert got["p99_ms"] == want[2]


def test_latency_metrics_identical_to_old_private_impl():
    """benchmarks/common.latency_metrics (now on the obs histogram) must
    reproduce perf_traffic's old private implementation exactly."""
    from benchmarks.common import latency_metrics

    out = {
        "token_times": [[0.010, 0.022, 0.041], [0.015, 0.030], []],
        "arrivals": np.array([0.0, 0.005, 0.1]),
        "lengths": np.array([3, 2, 0]),
    }
    # the pre-dedup formula, verbatim shape
    ttft, itl = [], []
    for r, times in enumerate(out["token_times"]):
        if not times:
            continue
        ttft.append(times[0] - out["arrivals"][r])
        itl.extend(np.diff(times))
    pct = lambda v: dict(zip(
        ("p50_ms", "p95_ms", "p99_ms"),
        (float(x) for x in np.percentile(np.asarray(v) * 1e3, [50, 95, 99])),
    ))
    makespan = max(t[-1] for t in out["token_times"] if t)
    got = latency_metrics(out)
    assert got["ttft"] == pct(ttft)
    assert got["itl"] == pct(itl)
    assert got["goodput_tok_s"] == 5 / makespan
    assert got["tokens"] == 5


# ---------------------------------------------------------------------------
# Prometheus exposition round-trip + JSON snapshot
# ---------------------------------------------------------------------------

def _populated_registry():
    reg = MetricsRegistry()
    reg.counter("serve_requests_total", "requests served").inc(12)
    reg.gauge("serve_compile_count", "compiled programs").set(1)
    h = reg.histogram("serve_ttft_seconds", "ttft")
    h.observe_many([0.001, 0.004, 0.04, 0.4, 2.0])
    return reg


def test_prometheus_round_trip(tmp_path):
    reg = _populated_registry()
    text = reg.to_prometheus()
    parsed = parse_prometheus(text)
    assert parsed["serve_requests_total"] == 12
    assert parsed["serve_compile_count"] == 1
    hist = parsed["serve_ttft_seconds"]
    assert hist["count"] == 5
    np.testing.assert_allclose(hist["sum"], 2.445)
    # cumulative bucket counts survive the round trip, +Inf bucket == count
    h = reg.get("serve_ttft_seconds")
    for le, cum in zip((*h.buckets, math.inf), h.cumulative_counts()):
        key = "+Inf" if math.isinf(le) else repr(float(le))
        assert hist["buckets"][key] == cum
    assert hist["buckets"]["+Inf"] == 5
    # writers produce the same content
    reg.write_prometheus(str(tmp_path / "m.prom"))
    assert (tmp_path / "m.prom").read_text() == text


def test_prometheus_parser_is_strict():
    with pytest.raises(ValueError):
        parse_prometheus("not a metric line at all!\n")
    with pytest.raises(ValueError):
        parse_prometheus("untyped_metric 3\n")     # no # TYPE line


def test_snapshot_json_round_trip(tmp_path):
    reg = _populated_registry()
    snap = reg.snapshot()
    assert snap["serve_requests_total"] == 12
    hist = snap["serve_ttft_seconds"]
    assert hist["count"] == 5
    assert hist["p50"] == np.percentile([0.001, 0.004, 0.04, 0.4, 2.0], 50)
    path = str(tmp_path / "m.json")
    reg.write_json(path)
    with open(path) as f:
        assert json.load(f)["serve_compile_count"] == 1


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_tracer_span_event_schema(tmp_path):
    tr = Tracer()
    tr.event("admission", req=0, slot=1)
    with tr.span("step", phase="decode"):
        pass
    ev, sp = tr.events
    assert ev["ph"] == "i" and ev["args"] == {"req": 0, "slot": 1}
    assert sp["ph"] == "X" and sp["dur"] >= 0 and sp["ts"] >= ev["ts"]
    # the schema: exactly these keys, args exactly as given
    assert set(ev) == {"name", "ph", "ts", "args"}
    assert set(sp) == {"name", "ph", "ts", "dur", "args"}
    assert sp["args"] == {"phase": "decode"}
    path = str(tmp_path / "trace.jsonl")
    assert tr.dump(path) == 2
    assert load_jsonl(path) == tr.events


def test_tracer_complete_matches_span_schema():
    tr = Tracer()
    t0 = tr.t0
    tr.complete("step", t0 + 0.001, t0 + 0.003, phase="verify")
    (ev,) = tr.events
    assert ev["ph"] == "X"
    np.testing.assert_allclose(ev["ts"], 1e3, rtol=1e-6)
    np.testing.assert_allclose(ev["dur"], 2e3, rtol=1e-6)
    assert ev == {"name": "step", "ph": "X", "ts": ev["ts"],
                  "dur": ev["dur"], "args": {"phase": "verify"}}


def test_tracer_bounded():
    tr = Tracer(max_events=3)
    for i in range(5):
        tr.event("e", i=i)
    assert len(tr.events) == 3 and tr.dropped == 2


# ---------------------------------------------------------------------------
# telemetry host-side pieces
# ---------------------------------------------------------------------------

def test_flatten_stats_and_ring():
    rec = {"logits": np.float32(2.0), "block/g0": np.array([1.0, 3.0])}
    flat = flatten_stats(rec)
    assert flat == {"logits": 2.0, "block/g0/0": 1.0, "block/g0/1": 3.0}
    ring = RingBuffer(capacity=2)
    for v in (1.0, 2.0, 3.0):
        ring.append({"x": v})
    assert len(ring) == 2 and ring.total == 3
    assert list(ring.series("x")) == [2.0, 3.0]
    assert ring.mean_record() == {"x": 2.5}
    assert ring.last()[0] == {"x": 3.0}


def test_drift_detector_synthetic():
    det = DriftDetector(64, {"logits": 1.0, "embed": 1.0}, tol=0.2)
    # width^0.5 blowup at 4x width -> slope 0.5, flagged
    rep = det.observe(256, {"logits": 2.0, "embed": 1.02})
    assert not rep.ok and "logits" in rep.flagged
    np.testing.assert_allclose(rep.flagged["logits"], 0.5, atol=1e-6)
    assert "embed" not in rep.flagged
    assert "width^+0.5" in str(rep)
    # in-spec scales pass; same width is trivially in-spec
    assert det.observe(256, {"logits": 1.05, "embed": 0.98}).ok
    assert det.observe(64, {"logits": 123.0}).ok
    # zero-at-both-widths statistics carry no drift signal (zero-init
    # readout logits at step 0) and must not poison the slope
    det0 = DriftDetector(64, {"z": 0.0})
    assert det0.observe(256, {"z": 0.0}).ok


# ---------------------------------------------------------------------------
# telemetry aux from the real train step
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _telemetry_run(p13n: str, width_mult: float, steps: int = 3):
    """Train the smoke mup-gpt for a few steps with the telemetry aux on;
    returns (d_model, ring of per-step health records)."""
    cfg = get_smoke_config("mup-gpt").replace(dtype="float32", n_layers=2)
    cfg = cfg.scaled(width_mult).replace(parametrization=p13n)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = Optimizer.create(
        "adam", lr=1e-2, parametrization=model.p13n, meta=model.meta
    )
    state = opt.init(params)
    step = jax.jit(make_train_step(model, opt, telemetry=True))
    pipe = make_pipeline(cfg.vocab_size, 32, 8, seed=0)
    ring = RingBuffer()
    for t in range(steps):
        batch = {k: jnp.asarray(v) for k, v in pipe.batch(t).items()}
        params, state, metrics = step(params, state, batch)
        ring.append(jax.device_get(metrics["obs"]))
    return cfg.d_model, ring


def test_telemetry_aux_is_plumbing_free():
    """telemetry=True must not change the training trajectory: loss and
    grad-norm match the uninstrumented step bit-for-bit."""
    cfg = get_smoke_config("mup-gpt").replace(dtype="float32", n_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = Optimizer.create(
        "adam", lr=1e-2, parametrization=model.p13n, meta=model.meta
    )
    pipe = make_pipeline(cfg.vocab_size, 32, 8, seed=0)
    batch = {k: jnp.asarray(v) for k, v in pipe.batch(0).items()}
    plain = jax.jit(make_train_step(model, opt))
    instr = jax.jit(make_train_step(model, opt, telemetry=True))
    _, _, m0 = plain(params, opt.init(params), batch)
    _, _, m1 = instr(params, opt.init(params), batch)
    assert float(m0["loss"]) == float(m1["loss"])
    assert float(m0["grad_norm"]) == float(m1["grad_norm"])
    # aux shape contract: coord-size scalars + per-group stacks + u2w keys
    aux = m1["obs"]
    assert {"embed", "final_norm", "logits"} <= set(aux)
    assert any(k.startswith("block/") for k in aux)
    assert any(k.startswith("u2w/") for k in aux)


def test_telemetry_rejects_microbatching():
    cfg = get_smoke_config("mup-gpt").replace(dtype="float32", n_layers=2)
    model = build_model(cfg)
    opt = Optimizer.create(
        "adam", lr=1e-2, parametrization=model.p13n, meta=model.meta
    )
    with pytest.raises(ValueError, match="telemetry"):
        make_train_step(model, opt, telemetry=True, num_microbatches=2)


@pytest.mark.parametrize("p13n", ["sp", "mup", "umup"])
def test_obs_aux_matches_coord_check_golden(p13n):
    """The online aux is *literally* the offline coord check's statistic:
    at step 0 (initial params, same seed/batch as the golden harness) the
    traced ``collect_stats`` embed/logits coord sizes must equal the
    committed golden fixture values."""
    with open(GOLDEN_PATH) as f:
        golden = json.load(f)
    base = get_smoke_config("mup-gpt").replace(
        dtype="float32", n_layers=2, zero_init_readout=False,
        zero_init_query=False,
    )
    pipe = make_pipeline(256, 32, 8, seed=0)
    batch = {k: jnp.asarray(v) for k, v in pipe.batch(0).items()}
    for mult in (1.0, 4.0):
        cfg = base.scaled(mult).replace(parametrization=p13n)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        _, stats = model.loss_fn(params, batch, collect_stats=True)
        want = golden[p13n][str(cfg.d_model)][0]
        for key in ("embed", "logits"):
            np.testing.assert_allclose(
                float(stats[key]), want[key], rtol=5e-3,
                err_msg=f"{p13n} d_model={cfg.d_model} {key}",
            )


@pytest.mark.parametrize(
    "p13n,expect_flag", [("sp", True), ("mup", False), ("umup", False)]
)
def test_drift_detector_separates_sp_from_mup(p13n, expect_flag):
    """The Fig-5 diagnostic as a monitor: baseline the detector on the
    proxy-width run, then observe a 4x-width run of the same
    parametrization.  SP's residual stream blows up with width (slope ~+1
    after a few Adam steps) and must be flagged; µP and u-µP stay Theta(1)
    and must pass.  Scoped to the activation keys whose µP prediction is
    exponent 0 — raw logits carry the Theta(1/sqrt(n)) init artifact (see
    docs/observability.md)."""
    base_w, base_ring = _telemetry_run(p13n, 1.0)
    keys = [
        k for k in base_ring.last()[0]
        if k.startswith(("block/", "embed", "final_norm"))
    ]
    assert keys, "telemetry aux lost its activation statistics"
    det = DriftDetector.from_ring(
        base_w, base_ring, last_n=1, keys=keys, tol=0.25
    )
    wide_w, wide_ring = _telemetry_run(p13n, 4.0)
    assert wide_w == 4 * base_w
    report = det.observe(wide_w, wide_ring.last()[0])
    if expect_flag:
        assert not report.ok, "SP-at-4x-width escaped the drift detector"
        assert max(abs(s) for s in report.flagged.values()) > 0.5
        assert "DRIFT" in str(report)
    else:
        assert report.ok, (
            f"false positive on {p13n}: {report.flagged}"
        )


def test_train_obs_records_and_flags():
    obs = TrainObs(metrics=MetricsRegistry(), telemetry=True, verbose=False,
                   detector=DriftDetector(64, {"logits": 1.0}, tol=0.2))
    obs.record_step(0, loss=2.0, grad_norm=1.0, dt=0.1, tokens=512,
                    width=256, aux={"logits": 2.0})
    snap = obs.metrics.snapshot()
    assert snap["train_steps_total"] == 1
    assert snap["train_tokens_total"] == 512
    assert snap["train_loss"] == 2.0
    assert snap["train_mup_drift_flags_total"] == 1
    assert len(obs.ring) == 1
    assert not obs.drift_reports[0].ok


# ---------------------------------------------------------------------------
# train_loop / sweep integration
# ---------------------------------------------------------------------------

def test_train_loop_with_obs():
    cfg = get_smoke_config("mup-gpt").replace(dtype="float32", n_layers=2)
    obs = TrainObs(metrics=MetricsRegistry(), telemetry=True,
                   tracer=Tracer(), verbose=False)
    out = train_loop(
        cfg, steps=3, hps=HParams(lr=1e-2), batch_size=2, seq_len=16,
        log_every=0, obs=obs,
    )
    assert np.isfinite(out["final_loss"])
    snap = obs.metrics.snapshot()
    assert snap["train_steps_total"] == 3
    assert snap["train_tokens_total"] == 3 * 2 * 16
    assert snap["train_step_seconds"]["count"] == 3
    assert len(obs.ring) == 3                 # telemetry drained every step
    spans = [e for e in obs.tracer.events if e["name"] == "train_step"]
    assert len(spans) == 3
    data = [e for e in obs.tracer.events if e["name"] == "train.data"]
    assert [e["args"]["step"] for e in data] == [0, 1, 2]
    for d, st in zip(data, spans):          # the batch, then its step
        assert d["ts"] + d["dur"] <= st["ts"]
    parse_prometheus(obs.metrics.to_prometheus())   # exposition well-formed


def test_sweep_tracer_lifecycle():
    from repro.launch.sweep import run_sweep

    cfg = get_smoke_config("mup-gpt").replace(dtype="float32", n_layers=2)
    tracer = Tracer()
    res = run_sweep(
        cfg, [HParams(lr=1e-3), HParams(lr=3e-3)], steps=4, batch_size=2,
        seq_len=16, verbose=False, tracer=tracer,
    )
    names = [e["name"] for e in tracer.events]
    assert "sweep" in names and "sweep_done" in names
    done = next(e for e in tracer.events if e["name"] == "sweep_done")
    assert done["args"]["best"] == res.best_index


# ---------------------------------------------------------------------------
# serving engines: zero-recompile with instrumentation fully on
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def serve_m():
    cfg = get_smoke_config("smollm-135m").replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def proxy_m(serve_m):
    cfg, _, _ = serve_m
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


def _engine_pair(model, ecfg, cls, draft_model=None):
    obs = ServeObs(tracer=Tracer())
    plain = cls(model, ecfg, draft_model=draft_model)
    instr = cls(model, ecfg, draft_model=draft_model, obs=obs)
    return plain, instr, obs


@pytest.mark.parametrize("variant", ["static", "dynamic", "speculative"])
def test_zero_recompile_with_obs(variant, serve_m, proxy_m):
    """compile_count() == 1 with the full obs bundle attached, across two
    serves, with tokens identical to the uninstrumented engine."""
    cfg, model, params = serve_m
    _, dmodel, dparams = proxy_m
    prompts, lens = _prompts(cfg, R=4, L=16)
    if variant == "static":
        cls, ecfg, draft, kw = Engine, EngineConfig(**_ECFG), None, {}
    elif variant == "dynamic":
        cls = DynamicEngine
        ecfg = EngineConfig(**_ECFG, prefix_cache=True, prefill_chunk=4)
        draft, kw = None, {}
    else:
        cls = DynamicEngine
        ecfg = EngineConfig(**_ECFG, draft_k=2)
        draft, kw = dmodel, {"draft_params": dparams}
    plain, instr, obs = _engine_pair(model, ecfg, cls, draft_model=draft)
    for _ in range(2):
        out_p = plain.serve(params, prompts, lens, **kw)
        out_i = instr.serve(params, prompts, lens, **kw)
    assert plain.compile_count() == 1
    assert instr.compile_count() == 1, (
        f"{variant}: instrumentation broke the zero-recompile contract"
    )
    assert np.array_equal(np.asarray(out_i["tokens"]),
                          np.asarray(out_p["tokens"])), variant
    fams = parse_prometheus(obs.metrics.to_prometheus())
    assert "serve_requests_total" in fams
    assert fams["serve_requests_total"] == 8        # 2 serves x 4 requests
    assert fams["serve_compile_count"] == 1
    assert obs.tracer.events
    if variant != "static":
        phases = {
            e["args"]["phase"] for e in obs.tracer.events
            if e["name"] == "step"
        }
        assert phases <= {"prefill", "chunk_prefill", "decode", "verify"}
        if variant == "dynamic":
            assert "chunk_prefill" in phases and "decode" in phases
            assert fams["prefill_prompt_tokens_total"] > 0
        else:
            assert "verify" in phases
            if fams.get("spec_drafts_proposed_total", 0):
                assert "spec_acceptance_rate" in fams


# ---------------------------------------------------------------------------
# the profiler's trace: host spans on the device's clock
# ---------------------------------------------------------------------------

_ENGINE_PHASES = ("engine.admit", "engine.prepare", "engine.dispatch",
                  "engine.sync", "engine.bookkeep")


def _host_spans(logdir, prefix):
    """(name, start_ns, end_ns, args) of the host events named ``prefix*``
    in the one profiler trace under ``logdir``, in time order."""
    from jax.profiler import ProfileData

    (path,) = logdir.glob("plugins/profile/*/*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    out = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
           for plane in pd.planes if plane.name.startswith("/host:")
           for line in plane.lines for e in line.events
           if e.name.startswith(prefix)]
    return sorted(out, key=lambda s: (s[1], -s[2]))


def test_tracer_span_lands_on_profiler_trace(tmp_path):
    tr = Tracer()
    with jax.profiler.trace(str(tmp_path)):
        with tr.span("train_step", phase="train_step", step=3):
            pass
        tr.complete("step", tr.t0, tr.t0 + 1e-3, phase="decode")
    ((name, a, b, args),) = _host_spans(tmp_path, "train_step")
    assert args == {"phase": "train_step", "step": 3} and b >= a
    # complete() records an interval already over: JSONL only
    assert _host_spans(tmp_path, "step") == []
    assert [e["name"] for e in tr.events] == ["train_step", "step"]


def test_dynamic_engine_phases_on_profiler_trace(serve_m, tmp_path):
    """A traced DynamicEngine serve puts its host loop on the profiler's
    trace: one engine.step per dispatched step, its phases back to back
    inside it, with the args the bench's metrics read."""
    cfg, model, params = serve_m
    prompts, lens = _prompts(cfg, R=4, L=16)
    ecfg = EngineConfig(**_ECFG, prefix_cache=True, prefill_chunk=4)
    plain, instr, obs = _engine_pair(model, ecfg, DynamicEngine)
    ref = plain.serve(params, prompts, lens)
    instr.serve(params, prompts, lens)        # compiles outside the trace
    obs.tracer.events.clear()
    arrivals = [0.0, 0.0, 0.3, 0.3]          # the engine idles in between
    with jax.profiler.trace(str(tmp_path)):
        out = instr.serve(params, prompts, lens, arrivals=arrivals)
    assert np.array_equal(np.asarray(out["tokens"]),
                          np.asarray(ref["tokens"]))
    assert instr.compile_count() == 1
    spans = _host_spans(tmp_path, "engine.")
    ((_, s0, s1, serve_args),) = [s for s in spans if s[0] == "engine.serve"]
    assert serve_args["requests"] == 4 and serve_args["slots"] == 2
    # the tracer's clock at the loop's start, before its first step event
    first = next(e for e in obs.tracer.events if e["name"] == "step")
    assert 0 <= serve_args["tracer_us"] <= first["ts"]
    steps = [s for s in spans if s[0] == "engine.step"]
    assert len(steps) == int(out["steps"])
    assert [s[3]["step"] for s in steps] == list(range(len(steps)))
    jsonl = [e["args"]["phase"] for e in obs.tracer.events
             if e["name"] == "step"]
    assert [s[3]["phase"] for s in steps] == jsonl
    assert {"chunk_prefill", "decode"} <= set(jsonl)
    for _, a, b, args in steps:
        assert s0 <= a <= b <= s1
        assert 1 <= args["live"] <= 2
        assert (args["chunk_len"] > 0) == (args["phase"] == "chunk_prefill")
        kids = [s for s in spans if s[0] in _ENGINE_PHASES
                and a <= s[1] and s[2] <= b]
        names = [k[0] for k in kids]
        assert names in (list(_ENGINE_PHASES), list(_ENGINE_PHASES[1:]))
        for k0, k1 in zip(kids, kids[1:]):
            assert k0[2] <= k1[1], "phases overlap"
    admits = [s for s in spans if s[0] == "engine.admit"]
    assert sorted(s[3]["req"] for s in admits) == [0, 1, 2, 3]
    assert all("cached" in s[3] for s in admits)
    # every phase sits in a step; waits sit between steps
    inside = sum(1 for s in spans if s[0] in _ENGINE_PHASES)
    assert inside == sum(len([k for k in spans if k[0] in _ENGINE_PHASES
                              and a <= k[1] and k[2] <= b])
                         for _, a, b, _ in steps)
    waits = [s for s in spans if s[0] == "engine.wait_arrival"]
    assert waits
    for _, a, b, _ in waits:
        assert not any(sa < b and a < sb for _, sa, sb, _ in steps)
    # the JSONL holds the same spans, with the same args, as complete events
    ev = [e for e in obs.tracer.events if e["name"].startswith("engine.")]
    assert all(e["ph"] == "X" for e in ev)
    assert sorted(repr((e["name"], e.get("args", {}))) for e in ev) == sorted(
        repr((n, args)) for n, _, _, args in spans)
    (jserve,) = [e for e in ev if e["name"] == "engine.serve"]
    assert jserve["ts"] == serve_args["tracer_us"]
    assert serve_args["setup_us"] > 0
    # on the tracer's clock too, a step's phases fill it back to back
    for st in (e for e in ev if e["name"] == "engine.step"):
        kids = sorted((e for e in ev if e["name"] in _ENGINE_PHASES
                       and st["ts"] <= e["ts"] < st["ts"] + st["dur"]),
                      key=lambda e: e["ts"])
        assert [k["name"] for k in kids][-4:] == list(_ENGINE_PHASES[1:])
        for k0, k1 in zip(kids, kids[1:]):
            assert k0["ts"] + k0["dur"] == pytest.approx(k1["ts"], abs=1e-3)
        assert kids[-1]["ts"] + kids[-1]["dur"] == pytest.approx(
            st["ts"] + st["dur"], abs=1e-3)


class _EventCompleteOnly:
    """A tracer with nothing but event() and complete(), as a stand-in
    tracer may be."""

    def __init__(self):
        self.calls = []

    def event(self, name, **args):
        self.calls.append(("event", name))

    def complete(self, name, t_start, t_end, **args):
        self.calls.append(("complete", name))


class _FirstStepDone(Exception):
    pass


class _StopAtFirstComplete(_EventCompleteOnly):
    """A stand-in whose complete() ends the serve, as a warm-up's may."""

    def complete(self, name, t_start, t_end, **args):
        super().complete(name, t_start, t_end, **args)
        raise _FirstStepDone


def test_dynamic_engine_stops_after_first_step(serve_m):
    """The engine's first complete() is the first step's ``step`` event,
    sent once the step has run: a tracer that stops the serve there stops
    it after one compiled step, with no engine.* event sent before it."""
    cfg, model, params = serve_m
    prompts, lens = _prompts(cfg, R=3, L=16)
    tracer = _StopAtFirstComplete()
    eng = DynamicEngine(model, EngineConfig(**_ECFG, prefill_chunk=4),
                        obs=ServeObs(tracer=tracer))
    with pytest.raises(_FirstStepDone):
        eng.serve(params, prompts, lens)
    assert eng.compile_count() == 1
    assert [c for c in tracer.calls if c[0] == "complete"] == [
        ("complete", "step")]


@pytest.mark.parametrize("mode", ["obs_none", "event_complete_only"])
def test_dynamic_engine_tracer_contract(mode, serve_m, monkeypatch):
    """With obs=None the engine makes no profiler call; with a tracer it
    calls nothing on it but event() and complete().  One compile either
    way, tokens unchanged."""
    import repro.serving.engine as engine_mod

    cfg, model, params = serve_m
    prompts, lens = _prompts(cfg, R=3, L=16)
    ecfg = EngineConfig(**_ECFG, prefill_chunk=4)
    ref = DynamicEngine(model, ecfg).serve(params, prompts, lens)
    opened = []
    real = engine_mod.annotate
    monkeypatch.setattr(engine_mod, "annotate",
                        lambda name, **a: opened.append(name) or real(name, **a))
    tracer = _EventCompleteOnly() if mode != "obs_none" else None
    obs = ServeObs(tracer=tracer) if tracer is not None else None
    eng = DynamicEngine(model, ecfg, obs=obs)
    out = eng.serve(params, prompts, lens)
    assert np.array_equal(np.asarray(out["tokens"]), np.asarray(ref["tokens"]))
    assert eng.compile_count() == 1
    if tracer is None:
        assert opened == []
    else:
        steps = int(out["steps"])
        assert opened.count("engine.step") == steps
        assert tracer.calls.count(("complete", "step")) == steps
        assert tracer.calls.count(("complete", "engine.step")) == steps
        assert tracer.calls.count(("complete", "engine.serve")) == 1
        assert {c for c, _ in tracer.calls} == {"event", "complete"}


def test_dynamic_record_times_with_obs(serve_m):
    """record_times keeps its pre-obs return shape (token_times + arrivals,
    one deprecation cycle — docs/observability.md), stamps are monotonic,
    and the same latencies land in the TTFT/ITL histograms."""
    cfg, model, params = serve_m
    prompts, lens = _prompts(cfg, R=3, L=16)
    obs = ServeObs(tracer=Tracer())
    eng = DynamicEngine(model, EngineConfig(**_ECFG), obs=obs)
    out = eng.serve(params, prompts, lens, record_times=True)
    assert "token_times" in out and "arrivals" in out
    n_tok = 0
    for ts in out["token_times"]:
        assert list(ts) == sorted(ts), "token stamps not monotonic"
        n_tok += len(ts)
    snap = obs.metrics.snapshot()
    assert snap["serve_ttft_seconds"]["count"] == sum(
        1 for ts in out["token_times"] if ts
    )
    assert snap["serve_itl_seconds"]["count"] == sum(
        max(0, len(ts) - 1) for ts in out["token_times"]
    )
    assert snap["serve_step_seconds"]["count"] > 0
    assert eng.compile_count() == 1


multidevice = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count>=4",
)


@multidevice
@pytest.mark.parametrize("cls", [Engine, DynamicEngine])
def test_zero_recompile_with_obs_on_mesh(cls, serve_m):
    """The contract must also hold on a (2, 2) data x model mesh — the
    instrumentation is host-side, so sharding cannot re-trace it."""
    from repro.launch.mesh import make_mesh_shape

    cfg, model, params = serve_m
    prompts, lens = _prompts(cfg, R=4, L=16)
    obs = ServeObs(tracer=Tracer())
    eng = cls(model, EngineConfig(**_ECFG), mesh=make_mesh_shape((2, 2)),
              obs=obs)
    sparams = eng.shard_params(params)
    for _ in range(2):
        out = eng.serve(sparams, prompts, lens)
    assert eng.compile_count() == 1
    assert int(np.asarray(out["lengths"]).sum()) > 0
    assert parse_prometheus(obs.metrics.to_prometheus())[
        "serve_compile_count"
    ] == 1
