"""Serving driver: continuous-batching engine CLI + dense-loop oracle.

Two paths share this entry point:

- **engine** (default): the continuous-batching engine (serving/engine.py)
  — paged KV cache, slot scheduler, flash-decode kernel.  By default the
  *dynamic* engine: host-side page allocator, radix-tree prefix caching
  (``--prefix-cache``) and chunked prefill (``--prefill-chunk``), with one
  jitted step.  ``--static`` selects the original fully-jitted engine
  (whole serve in one while_loop, fixed page tables).
- **dense** (``--dense``, and the automatic fallback for architectures the
  paged engine cannot serve yet — recurrent/SSD/cross-attention caches):
  the original host-side loop over a dense per-request cache, one jitted
  ``decode_step`` per token.  It doubles as the correctness oracle the
  engine is differential-tested against (tests/test_serving.py).

Usage:
    python -m repro.launch.serve --arch smollm-135m --smoke \
        --requests 8 --prompt-len 32 --gen-len 16 --slots 4
    python -m repro.launch.serve --arch smollm-135m --smoke \
        --prefix-cache --prefill-chunk 32 --pool-pages 64
    python -m repro.launch.serve --arch gemma2-2b --smoke --dense
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.distributed.sharding import make_rules, shardings as sharding_ctx
from repro.launch import compile_cache
from repro.launch.mesh import make_host_mesh, make_mesh_shape
from repro.models.model import build_model
from repro.serving.engine import DynamicEngine, Engine, EngineConfig
from repro.serving.kv_cache import SERVABLE_KINDS, kv_dtype_of, pool_bytes


def generate(
    model, params, prompts: jax.Array, gen_len: int,
    memory_inputs=None, temperature: float = 0.0, seed: int = 0,
    eos_token_id=None,
):
    """Dense-loop reference: prompts (B, P) -> generated tokens (B, gen_len).

    One jitted ``decode_step`` per token (the dispatch overhead the engine
    exists to remove).  Stops early once every row has emitted the stop
    token (``eos_token_id``, default the config's knob; -1 disables); rows
    that finish first are padded with the stop token.
    """
    B, P = prompts.shape
    cache_len = P + gen_len
    eos = model.cfg.eos_token_id if eos_token_id is None else int(eos_token_id)
    last_logits, cache = model.prefill(
        params, prompts, memory_inputs=memory_inputs, cache_len=cache_len
    )

    def sample(logits, key):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, logits / temperature).astype(jnp.int32)

    decode = jax.jit(model.decode_step)

    # thread keys: the root key is only ever split, never consumed — the
    # first sampled token previously reused `key` that the loop then split
    # again, correlating step 0 with step 1.
    key = jax.random.PRNGKey(seed)
    key, sub = jax.random.split(key)
    tok = sample(last_logits, sub)[:, None]                    # (B,1)
    done = (tok[:, 0] == eos) if eos >= 0 else jnp.zeros((B,), bool)
    out = [tok]
    for i in range(gen_len - 1):
        if eos >= 0 and bool(jnp.all(done)):
            break
        pos = jnp.full((B, 1), P + i, jnp.int32)
        logits, cache = decode(params, tok, pos, cache)
        key, sub = jax.random.split(key)
        tok = sample(logits[:, 0], sub)[:, None]
        if eos >= 0:
            tok = jnp.where(done[:, None], eos, tok)
            done = done | (tok[:, 0] == eos)
        out.append(tok)
    toks = jnp.concatenate(out, axis=1)
    if toks.shape[1] < gen_len:  # early stop: pad with the stop token
        pad = jnp.full((B, gen_len - toks.shape[1]), eos, jnp.int32)
        toks = jnp.concatenate([toks, pad], axis=1)
    return toks


def _servable(cfg) -> bool:
    return all(k in SERVABLE_KINDS for k in (*cfg.pattern, *cfg.tail))


def _count_generated(toks, eos: int) -> int:
    """Real generated tokens in a dense ``generate`` output: everything up
    to and including each row's first stop token — the EOS padding after an
    early stop is not generation (the engine's ``lengths`` counts the same
    way, so the two drivers' tok/s are comparable)."""
    toks = np.asarray(toks)
    if eos < 0:
        return toks.size
    hit = toks == eos
    first = np.where(hit.any(axis=1), hit.argmax(axis=1) + 1, toks.shape[1])
    return int(first.sum())


def _memory_inputs(cfg, batch: int):
    mem = {}
    if cfg.n_image_tokens:
        mem["images"] = jax.random.normal(
            jax.random.PRNGKey(2),
            (batch, cfg.n_image_tokens, cfg.frontend_feat_dim),
        )
    if cfg.family == "encdec":
        mem["frames"] = jax.random.normal(
            jax.random.PRNGKey(2),
            (batch, cfg.encoder_seq, cfg.frontend_feat_dim),
        )
    return mem or None


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--eos", type=int, default=None,
                    help="stop token id (default: config's eos_token_id)")
    ap.add_argument("--draft-width", type=float, default=0.0,
                    help="speculative decoding: drafter width as a fraction "
                         "of the target (builds the µP proxy via "
                         "cfg.scaled; 0 disables speculation)")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="speculative draft length per verify (with "
                         "--draft-width)")
    ap.add_argument("--draft-min-d-head", type=int, default=8,
                    help="d_head floor for the drafter proxy")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--static", action="store_true",
                    help="use the static fully-jitted engine (fixed page "
                         "tables) instead of the dynamic allocator engine")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix-tree prompt-prefix page sharing (dynamic "
                         "engine only; global-attention configs)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="admit prompts in chunks of this many tokens "
                         "(page-size multiple; 0 = one-shot prefill; "
                         "dynamic engine only)")
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="global page-pool size override (dynamic engine "
                         "only; default: n_slots * pages-per-slot)")
    ap.add_argument("--kv-dtype", default="",
                    choices=["", "int8", "bfloat16", "float32"],
                    help="paged KV pool dtype; int8 stores per-page-per-head "
                         "scaled blocks dequantized in-kernel (~2x the pages "
                         "per byte; see docs/quantization.md)")
    ap.add_argument("--adaptive-draft", action="store_true",
                    help="adapt per-slot draft length from measured "
                         "acceptance (dynamic engine + --draft-width)")
    ap.add_argument("--dense", action="store_true",
                    help="force the dense per-token-loop driver")
    ap.add_argument("--mixed-lens", action="store_true",
                    help="random per-request prompt lengths (engine only: "
                         "the dense driver always pads to --prompt-len, so "
                         "its tok/s would not be comparable)")
    ap.add_argument("--mesh", default=None, metavar="DATA,MODEL",
                    help="serve on an explicit (data, model) mesh, e.g. "
                         "'1,2' for 2-way tensor parallelism over kv-heads/"
                         "ffn/vocab (engine only; needs data*model devices; "
                         "see docs/distributed.md)")
    ap.add_argument("--obs", action="store_true",
                    help="record serving metrics + a phase trace; prints "
                         "the Prometheus exposition at exit (see "
                         "docs/observability.md)")
    args = ap.parse_args(argv)
    compile_cache.enable()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    cfg = cfg.replace(dtype="float32", kv_dtype=args.kv_dtype)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))

    mesh = make_host_mesh()
    rules = make_rules(mesh, cfg=cfg, fsdp=False, kind="decode")
    R, P = args.requests, args.prompt_len
    prompts = jax.random.randint(
        jax.random.PRNGKey(args.seed + 1), (R, P), 0, cfg.vocab_size
    )

    use_engine = not args.dense and _servable(cfg)
    if not args.dense and not use_engine:
        print(f"[serve] {cfg.name}: pattern {cfg.pattern} not paged-servable "
              f"yet; falling back to the dense-loop driver")

    emesh = None
    if args.mesh:
        try:
            dm = tuple(int(x) for x in args.mesh.split(","))
            if len(dm) != 2 or min(dm) < 1:
                raise ValueError
        except ValueError:
            ap.error(f"--mesh wants 'DATA,MODEL' positive ints, "
                     f"got {args.mesh!r}")
        if not use_engine:
            ap.error("--mesh needs the paged engine (not --dense / "
                     "dense-fallback archs)")
        emesh = make_mesh_shape(dm)
        print(f"[serve] mesh {dm}: slots data-parallel x{dm[0]}, "
              f"kv-heads/ffn/vocab tensor-parallel x{dm[1]}")

    obs = None
    if args.obs:
        from repro.obs import ServeObs, Tracer

        obs = ServeObs(tracer=Tracer())
    # default workload: every prompt at full width, so engine and --dense
    # runs of the same CLI serve the *same* requests and their printed
    # tok/s are directly comparable
    lens = jnp.full((R,), P, jnp.int32)
    if args.mixed_lens:
        if not use_engine:
            print("[serve] --mixed-lens ignored: the dense driver pads all "
                  "prompts to --prompt-len")
        else:
            lens = jax.random.randint(
                jax.random.PRNGKey(args.seed + 2), (R,), max(1, P // 4), P + 1
            )

    speculate = use_engine and args.draft_width > 0
    draft_model = draft_params = None
    if args.draft_width > 0 and not use_engine:
        print("[serve] --draft-width ignored: speculation needs the paged "
              "engine")
    if speculate:
        # the µTransfer story: the narrow proxy shares the target's µP base
        # shape, so it is a distribution-matched drafter by construction
        dcfg = cfg.scaled(args.draft_width, min_d_head=args.draft_min_d_head)
        draft_model = build_model(dcfg)
        draft_params = draft_model.init(jax.random.PRNGKey(args.seed + 7))
        print(f"[serve] drafter {dcfg.name}: d_model {dcfg.d_model}, "
              f"{dcfg.n_heads} heads, draft_k={args.draft_k}")

    if args.static and (args.prefix_cache or args.prefill_chunk
                        or args.adaptive_draft
                        or args.pool_pages is not None):
        ap.error("--prefix-cache/--prefill-chunk/--pool-pages/"
                 "--adaptive-draft need the dynamic engine (drop --static)")
    if args.adaptive_draft and not speculate:
        ap.error("--adaptive-draft needs a drafter (set --draft-width)")

    t0 = time.time()
    with sharding_ctx(mesh, rules):
        if use_engine:
            ecfg = EngineConfig(
                n_slots=args.slots, page_size=args.page_size,
                max_prompt_len=P, max_gen_len=args.gen_len,
                eos_token_id=args.eos,
                draft_k=args.draft_k if speculate else 0,
                prefix_cache=args.prefix_cache,
                prefill_chunk=args.prefill_chunk,
                n_pages=args.pool_pages,
                adaptive_draft=args.adaptive_draft,
            )
            cls = Engine if args.static else DynamicEngine
            engine = cls(
                model, ecfg, draft_model=draft_model, mesh=emesh, obs=obs
            )
            if emesh is not None:
                params = engine.shard_params(params)
                if draft_params is not None:
                    draft_params = engine.shard_params(
                        draft_params, model=draft_model
                    )
            n_global = getattr(engine, "n_pages", None)
            print(f"[serve] paged KV pools ({kv_dtype_of(cfg)}): "
                  f"{pool_bytes(cfg, engine.spec)/2**20:.1f} MiB "
                  f"({engine.spec.n_slots} slots x {engine.spec.gp_cols} global"
                  + (f" + {engine.spec.wp_cols} ring" if engine.spec.wp_cols else "")
                  + f" pages of {engine.spec.page_size} tokens"
                  + (f"; dynamic pool of {n_global}" if n_global else "")
                  + ")")
            out = engine.serve(
                params, prompts, lens,
                temperature=jnp.full((R,), args.temperature),
                top_k=jnp.full((R,), args.top_k, jnp.int32),
                top_p=jnp.full((R,), args.top_p),
                seed=args.seed,
                draft_params=draft_params,
            )
            toks, n_tok = out["tokens"], int(out["lengths"].sum())
            jax.block_until_ready(toks)
            if speculate:
                prop = max(1, int(out["proposed"]))
                print(f"[serve] speculation: {int(out['accepted'])}/{prop} "
                      f"drafts accepted ({int(out['accepted'])/prop:.1%}) "
                      f"over {int(out['steps'])} engine iterations")
            if "prefill_cached" in out and out["prefill_total"]:
                print(f"[serve] prefix cache: {out['prefill_cached']}/"
                      f"{out['prefill_total']} prompt tokens served from "
                      f"shared pages "
                      f"({out['prefill_cached']/out['prefill_total']:.1%})")
        else:
            if args.top_k or args.top_p < 1.0:
                print("[serve] --top-k/--top-p ignored: the dense driver "
                      "samples with temperature only")
            toks = generate(
                model, params, prompts, args.gen_len,
                memory_inputs=_memory_inputs(cfg, R),
                temperature=args.temperature, seed=args.seed,
                eos_token_id=args.eos,
            )
            jax.block_until_ready(toks)
            eos = cfg.eos_token_id if args.eos is None else args.eos
            n_tok = _count_generated(toks, eos)
    dt = time.time() - t0
    mode = "engine" if use_engine else "dense"
    print(f"[serve:{mode}] generated {toks.shape} ({n_tok} tokens) "
          f"in {dt:.2f}s ({n_tok/dt:.1f} tok/s)")
    print(toks[:, :16])
    if obs is not None:
        print(f"[obs] {len(obs.tracer.events)} trace events")
        print(obs.metrics.to_prometheus(), end="")
    return toks


if __name__ == "__main__":
    main()
