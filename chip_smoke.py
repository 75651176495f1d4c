"""Bring-up smoke: the training and serving main paths on a TPU.

Runs in one process and touches JAX once.  With no arguments it needs one
chip and runs two phases at the full width of the repo's configurations
(weights random, from ``--seed``):

  train  ``launch.train.train_loop`` on mup-gpt (8 layers, d_model 1024,
         vocab 2048) with the Pallas kernels on (flash attention forward
         and backward, rmsnorm, chunked cross-entropy), batch 8 x 512
         tokens.  Losses must be finite and falling, and the step-0 loss
         must match the same step run with ``REPRO_KERNELS=ref`` within the
         bf16 tier of docs/kernels.md.
  serve  ``DynamicEngine`` (the ``launch/serve.py`` default path) on
         smollm-135m (30 layers, d_model 576, 9 heads / 3 kv heads, vocab
         49152): 8 requests of 128-token prompts, 32 generated tokens, 8
         slots, 16-token pages; float32 KV pools with 64-token chunked
         prefill, and int8 pools with one-shot admission.  Checked at
         "highest" matmul precision against an oracle (the dense
         ``generate`` loop; for int8 pools the same engine on the jnp
         reference ops): greedy tokens equal, and the logits the engine
         sampled the first two tokens from within the bf16 tier.

``--chips 4`` runs only the paths that exist across chips, each against
its one-device run: 5 ``train_loop`` steps on a (2, 2) mesh with FSDP
(every step's loss and the final params within docs/distributed.md's
cross-mesh tolerances, matmuls at "highest" precision) and
``DynamicEngine`` serving mup-gpt on a (1, 4) tensor-parallel mesh
(token for token).

Every kernel op on the path must resolve to ``pallas``; the script exits
non-zero, printing no result, on any failure or when JAX finds no TPU.
The last line of a passing run is one JSON object naming the device.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # 2x2 host
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import sys
import time
import traceback
from pathlib import Path

# docs/kernels.md bf16 tier (forward values).  Serving logits are held to
# it at "highest" matmul precision: at TPU default precision (bf16 passes)
# XLA's matmuls in the oracle round differently from the kernels, and 30
# layers carry that to |diff| ~1 on logits of magnitude ~150
BF16_ATOL = 2e-2
# docs/distributed.md cross-mesh tolerances: train loss, and params after
# one optimizer step (held here after MESH_STEPS).  They are stated for
# fp32 arithmetic, so the mesh comparison runs its matmuls at "highest"
# precision: at TPU default precision (bf16 passes) a different reduction
# order flips bf16 roundings and mup-gpt's step-0 losses on (1, 1) and
# (2, 2) v5e meshes differed by 5.4e-4
MESH_LOSS_ATOL = 1e-4
MESH_PARAM_ATOL = 1e-3
MESH_STEPS = 5


# every failed check and every phase that raised; any entry fails the run
FAILURES: list[str] = []


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> bool:
    """Record a failed check and go on, so one run reports every fault."""
    if not ok:
        FAILURES.append(what)
        log(f"[FAIL] {what}")
    return ok


def run_phase(name: str, fn, *args) -> None:
    """Run one phase; an exception fails the run but not the next phase."""
    t0 = time.perf_counter()
    try:
        fn(*args)
    except Exception:
        FAILURES.append(f"phase {name} raised")
        log(f"[FAIL] phase {name} raised:\n{traceback.format_exc()}")
    log(f"[{name}] phase took {time.perf_counter() - t0:.1f} s")


@contextlib.contextmanager
def ref_kernels():
    """Run the enclosed calls on the jnp reference ops (REPRO_KERNELS)."""
    before = os.environ.get("REPRO_KERNELS")
    os.environ["REPRO_KERNELS"] = "ref"
    try:
        yield
    finally:
        if before is None:
            del os.environ["REPRO_KERNELS"]
        else:
            os.environ["REPRO_KERNELS"] = before


def _kernels_ran(ops) -> dict:
    """Snapshot of the impls the ops resolved to (and the attention tile
    plan); every impl must be pallas."""
    got = dict(ops.RESOLVED)
    bad = {k: v for k, v in got.items()
           if k != "attention_tiles" and v != "pallas"}
    check(bool(got) and not bad,
          f"kernel ops did not resolve to 'pallas': {got}")
    return got


def _peak_bytes(jax) -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def train_phase(cfg, *, steps, batch, seq, seed):
    """train_loop with the kernels on; returns its losses and step times."""
    from repro.core.transfer import HParams
    from repro.kernels import ops
    from repro.launch.train import train_loop

    hps = HParams(lr=1e-2, sigma=1.0)
    kw = dict(hps=hps, batch_size=batch, seq_len=seq, seed=seed, log_every=1)
    ops.RESOLVED.clear()
    out = train_loop(cfg, steps, **kw)
    impls = _kernels_ran(ops)
    losses, times = out["losses"], out["step_times"]
    log(f"[train] kernels: {impls}")
    log(f"[train] losses: {losses}")
    log(f"[train] step 0 (compile + run) {times[0]:.3f} s; later steps "
        f"{[round(t, 4) for t in times[1:]]} s")
    check(all(map(math.isfinite, losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")

    with ref_kernels():
        ref_loss = train_loop(cfg, 1, **kw)["losses"][0]
    diff = abs(losses[0] - ref_loss)
    log(f"[train] step-0 loss: kernels {losses[0]!r}, ref {ref_loss!r}, "
        f"|diff| {diff!r} (bf16 tier atol {BF16_ATOL})")
    check(diff <= BF16_ATOL, "step-0 loss differs from the ref run")
    return out


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def tap_logits(model) -> list:
    """Make ``model`` record, on the host, the last row of every forward's
    logits with its position: one (positions (B,), logits (B, V)) pair per
    call.  Engine steps and the dense ``generate`` loop both reach
    ``model.forward``, so the rows are the logits each path sampled from."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rows, forward = [], model.forward

    def record(pos, logits):
        rows.append((np.asarray(pos), np.asarray(logits)))

    def tapped(params, tokens, **kw):
        logits, cache = forward(params, tokens, **kw)
        pos = kw.get("positions")
        last = (jnp.full(tokens.shape[:1], tokens.shape[1] - 1, jnp.int32)
                if pos is None else pos[:, -1])
        jax.debug.callback(record, last, logits[:, -1])
        return logits, cache

    model.forward = tapped
    return rows


def tapped_serve(cfg, serve, prompt_len):
    """``serve(model)`` -> greedy tokens (R, G), run on a logit-tapped model
    at "highest" matmul precision.  Returns the tokens and the logits each
    request sampled its first generated token from (at the last prompt
    position) and its second from (its first decode step), (R, V) each, in
    request order: admissions run one at a time in queue order, so the
    k-th row recorded at a position belongs to the k-th request."""
    import jax
    import numpy as np

    from repro.models.model import build_model

    model = build_model(cfg)
    rows = tap_logits(model)
    with jax.default_matmul_precision("highest"):
        toks = np.asarray(serve(model))
    jax.effects_barrier()

    def at(pos):
        return np.concatenate([lg[p == pos] for p, lg in rows])

    return toks, at(prompt_len - 1), at(prompt_len)


def timed_serve(model, params, prompts, lens, ecfg, tag, mesh_shape=None):
    """Serve twice on one ``DynamicEngine`` at the default matmul
    precision: compile + run, then warm."""
    import jax

    from repro.kernels import ops
    from repro.launch.mesh import make_mesh_shape
    from repro.serving.engine import DynamicEngine

    emesh = None if mesh_shape is None else make_mesh_shape(mesh_shape)
    engine = DynamicEngine(model, ecfg, mesh=emesh)
    p = params if emesh is None else engine.shard_params(params)
    ops.RESOLVED.clear()
    times = []
    for _ in range(2):
        t0 = time.perf_counter()
        out = engine.serve(p, prompts, lens)
        jax.block_until_ready(out["tokens"])
        times.append(time.perf_counter() - t0)
    log(f"{tag} kernels: {_kernels_ran(ops)}")
    log(f"{tag} first serve (compile + run) {times[0]:.3f} s; warm serve "
        f"{times[1]:.3f} s for {int(out['lengths'].sum())} tokens, "
        f"{out['steps']} engine steps")
    check(engine.compile_count() == 1,
          f"engine step compiled {engine.compile_count()} times")
    return out


def serve_phase(cfg, seed, prefill_chunk=0, mesh_shape=None, *, n_req=8,
                prompt_len=128, gen_len=32, slots=8, page_size=16):
    """``DynamicEngine`` on ``cfg``; returns its greedy tokens.

    Without a mesh, the engine is then checked against its oracle at
    "highest" matmul precision: the dense ``generate`` loop for float
    pools, the same engine on the jnp reference ops for int8 pools (whose
    distance to the float oracle is the quantization error).  Its greedy
    tokens must equal the oracle's, and the logits it sampled the first
    and second token from must agree within the bf16 tier."""
    import jax
    import numpy as np

    from repro.distributed.sharding import make_rules, shardings
    from repro.kernels import ops
    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve import generate
    from repro.models.model import build_model
    from repro.serving.engine import DynamicEngine, EngineConfig

    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    prompts = jax.random.randint(
        jax.random.PRNGKey(seed + 1), (n_req, prompt_len), 0, cfg.vocab_size
    )
    lens = jax.numpy.full((n_req,), prompt_len, jax.numpy.int32)
    mesh = make_host_mesh()
    rules = make_rules(mesh, cfg=cfg, fsdp=False, kind="decode")
    ecfg = EngineConfig(n_slots=slots, page_size=page_size,
                        max_prompt_len=prompt_len, max_gen_len=gen_len,
                        prefill_chunk=prefill_chunk)
    int8 = (cfg.kv_dtype or cfg.dtype) == "int8"
    tag = (f"[serve {cfg.name} kv={cfg.kv_dtype or cfg.dtype}"
           + (f" chunk={prefill_chunk}" if prefill_chunk else "")
           + (f" mesh={mesh_shape}]" if mesh_shape else "]"))

    def engine(model):
        return DynamicEngine(model, ecfg).serve(params, prompts, lens)["tokens"]

    def dense(model):
        return generate(model, params, prompts, gen_len)

    with shardings(mesh, rules):
        out = timed_serve(model, params, prompts, lens, ecfg, tag, mesh_shape)
        toks = np.asarray(out["tokens"])
        if mesh_shape is not None:
            return toks
        ops.RESOLVED.clear()
        got = tapped_serve(cfg, engine, prompt_len)
        _kernels_ran(ops)
        d_toks, *d_logits = tapped_serve(
            cfg.replace(kv_dtype=""), dense, prompt_len
        )
        if int8:
            with ref_kernels():
                want = tapped_serve(cfg, engine, prompt_len)
        else:
            want = (d_toks, *d_logits)
    oracle = "int8 ref engine" if int8 else "dense oracle"
    log(f"{tag} greedy tokens equal to the dense oracle: "
        f"{float(np.mean(toks == d_toks))!r} (default precision), "
        f"{float(np.mean(got[0] == d_toks))!r} (highest)")
    check(np.array_equal(got[0], want[0]),
          f"{tag} greedy tokens differ from the {oracle}")
    e1, e2 = (float(np.max(np.abs(g - w))) for g, w in zip(got[1:], want[1:]))
    log(f"{tag} logits vs the {oracle}, max |diff|: first token {e1!r}, "
        f"second token {e2!r} (bf16 tier atol {BF16_ATOL}; max |logit| "
        f"{float(np.max(np.abs(want[1])))!r})")
    check(max(e1, e2) <= BF16_ATOL, f"{tag} logits differ from the {oracle}")
    return toks


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def one_chip(seed: int) -> None:
    from repro.configs import get_config

    run_phase("train", lambda: train_phase(
        get_config("mup-gpt").replace(dtype="float32", use_pallas=True),
        steps=10, batch=8, seq=512, seed=seed,
    ))
    smollm = get_config("smollm-135m").replace(dtype="float32")
    run_phase("serve kv=float32", serve_phase, smollm, seed, 64)
    # int8 pools admit in one shot: a later prefill chunk attends to the
    # int8 pages of the earlier ones, where a rounding flip moves the
    # logits by 0.2-0.4 for a 2**-20 relative change of the weights even on
    # the reference ops, past the tier; one-shot admission leaves a single
    # decode step over int8 pages before each compared logit
    run_phase("serve kv=int8", serve_phase, smollm.replace(kv_dtype="int8"),
              seed, 0)


def four_chips(seed: int) -> None:
    from repro.configs import get_config

    cfg = get_config("mup-gpt").replace(dtype="float32", use_pallas=True)
    run_phase("train mesh", mesh_train_phase, cfg, seed)
    run_phase("serve mesh", mesh_serve_phase, cfg, seed)


def mesh_train_phase(cfg, seed: int) -> None:
    """(2, 2) FSDP training vs the same steps on one device: every step's
    loss, and the params after the last optimizer update."""
    import jax
    import numpy as np

    from repro.core.transfer import HParams
    from repro.kernels import ops
    from repro.launch.mesh import make_mesh_shape
    from repro.launch.train import train_loop

    kw = dict(hps=HParams(lr=1e-2, sigma=1.0), batch_size=8, seq_len=512,
              seed=seed, log_every=1)
    ops.RESOLVED.clear()
    with jax.default_matmul_precision("highest"):
        one = train_loop(cfg, MESH_STEPS, mesh=make_mesh_shape((1, 1)), **kw)
        four = train_loop(cfg, MESH_STEPS, mesh=make_mesh_shape((2, 2)),
                          fsdp=True, **kw)
    log(f"[train mesh] kernels: {_kernels_ran(ops)}")
    dloss = float(np.max(np.abs(np.subtract(one["losses"], four["losses"]))))
    dparam = max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a, b: float(np.max(np.abs(np.asarray(a) - np.asarray(b)))),
        one["params"], four["params"],
    )))
    log(f"[train mesh] losses (1,1) {one['losses']}; (2,2)+fsdp "
        f"{four['losses']}; max |diff| {dloss!r} (atol {MESH_LOSS_ATOL}); "
        f"params after {MESH_STEPS} steps, max |diff| {dparam!r} "
        f"(atol {MESH_PARAM_ATOL})")
    check(dloss <= MESH_LOSS_ATOL, "2x2 FSDP losses differ from one device")
    check(dparam <= MESH_PARAM_ATOL,
          "2x2 FSDP params differ from one device")


def mesh_serve_phase(cfg, seed: int) -> None:
    """(1, 4) tensor-parallel serving vs (1, 1), greedy, token for token."""
    import numpy as np

    base = serve_phase(cfg, seed, mesh_shape=(1, 1))
    tp = serve_phase(cfg, seed, mesh_shape=(1, 4))
    same = np.array_equal(base, tp)
    log(f"[serve mesh] (1,4) tokens identical to (1,1): {same}")
    check(same, "tensor-parallel serving differs from one device")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    from repro.launch import compile_cache

    cache_dir = compile_cache.enable()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 1
    log(f"[chip_smoke] {len(devices)} x {dev.device_kind} ({dev.platform}); "
        f"jax {jax.__version__}; compile cache {cache_dir}")

    t0 = time.perf_counter()
    (one_chip if args.chips == 1 else four_chips)(args.seed)
    log(f"[chip_smoke] phases took {time.perf_counter() - t0:.1f} s; peak "
        f"device memory {_peak_bytes(jax)} bytes; compile cache hits "
        f"{compile_cache.EVENTS['hits']}, misses "
        f"{compile_cache.EVENTS['misses']}")
    if FAILURES:
        print("chip_smoke: FAILED\n  " + "\n  ".join(FAILURES),
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
