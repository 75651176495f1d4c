"""Continuous-batching serving engine: one jitted loop, zero per-token Python.

The dense-loop driver (launch/serve.py ``generate``) crosses the host
dispatch boundary once per generated token and holds the whole batch to one
prompt length and one stop condition.  This engine instead runs the entire
serve — admission, prefill-into-slot, batched decode, sampling, EOS/length
retirement — inside a single ``jax.lax.while_loop`` under one ``jax.jit``:

  - A fixed decode batch of ``n_slots`` *slots*.  A request queue (padded
    prompts + per-request sampling params, all fixed-shape arrays) is
    admitted one request per loop step into the first free slot; finished
    slots retire and free their pages for the next request.  Mixed prompt
    lengths, staggered admissions and early EOS exits therefore never change
    any traced shape: after the single warmup compile the loop re-runs for
    any workload of the same (n_requests, max lengths) envelope with zero
    recompilation (asserted in tests via the jit cache size).
  - Prefill runs as a (1, max_prompt_len) forward under ``lax.cond`` with
    right-padding masked by positions (pads sit at position Pmax: invisible
    to real queries, scatter-dropped from the cache) and is paged into the
    slot via serving/kv_cache.admit_slot.
  - Decode is one (n_slots, 1) forward over the paged block pool — the
    flash-decode Pallas kernel (kernels/decode_attention.py) on TPU.
  - Sampling is serving/sampling.py: greedy/temperature/top-k/top-p as
    traced per-slot params.  PRNG keys are folded from the *(request,
    absolute position)* of each sampling event — never from the loop
    iteration.  Slots advance at different rates (speculation commits a
    variable number of tokens per iteration; admission timing depends on
    other requests' lengths), so iteration-folded keys would both correlate
    draws across slots and make a request's stream depend on when it was
    admitted.  Position-folded keys make every request's sample stream a
    pure function of (seed, request, position).

Speculative decoding (``EngineConfig.draft_k`` + a drafter model — in this
repo the natural drafter is the request model's narrow µP proxy, see
repro/api.py): each loop iteration drafts k tokens autoregressively on the
drafter, verifies them with ONE (k+1)-token multi-query target forward
(kernels/ops.decode_attention_multi — shaped like a k-token chunked prefill
against the paged cache), and commits via standard rejection sampling
(serving/sampling.spec_accept), so the output distribution is exactly the
target's — token-for-token identical under greedy.  Rollback is implicit:
rejected drafts leave stale KV entries *ahead* of the committed position,
and every such position is rewritten by the next iteration's chunk before
any committed query can see it (position tags mask entries beyond each
query's own position, and chunk writes always cover [pos, pos + k]).  The
drafter keeps its own slot-mapped page pools; its per-iteration catch-up
forward (a (k+1)-token chunk over the last committed tokens) repairs the
draft-cache holes left by whatever the target rejected.  The whole
draft -> verify -> accept cycle stays inside the same while_loop under the
same single jit: zero per-token Python, trace-stable cache.

Throughput-wise the win is structural: the host loop pays dispatch latency
per token; here XLA sees the whole generation as one program, and
speculation collapses ~(1 + accepted) target tokens into one target forward
(benchmarks/perf_serve.py measures both gaps).

Two engines share the step bodies above:

  - :class:`Engine` — the whole serve in ONE ``lax.while_loop`` under one
    jit, with static interleaved page tables.  Minimum dispatch overhead;
    the oracle for everything below.
  - :class:`DynamicEngine` — a host-side scheduler driving ONE jitted step.
    Page tables come from serving/allocator.py (free-list allocator +
    radix-tree prefix cache), so admissions pop pages instead of resetting
    a fixed stripe, full prompt pages shared with earlier requests map
    copy-free (prefill skipped for the shared span), and long prompts
    prefill in page-multiple chunks interleaved with decode.  Everything
    the host decides per step travels as a fixed-shape traced ``ctrl``
    block, so the zero-recompile contract survives: one compile per
    (n_requests,) envelope, any tables/chunks/arrival pattern.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import (
    make_rules,
    named_sharding,
    shard,
    shardings as sharding_ctx,
)
from repro.obs.trace import annotate
from repro.serving import kv_cache, sampling
from repro.serving.allocator import BlockManager

# PRNG event tags: one stream per (request, position, event kind)
_TAG_SAMPLE = 0   # committed-token sampling (direct, residual resample, bonus)
_TAG_ACCEPT = 1   # speculative accept/reject uniform draw
_TAG_DRAFT = 2    # drafter proposal draw


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    n_slots: int = 4             # fixed decode batch size
    page_size: int = 16          # tokens per KV page
    max_prompt_len: int = 64     # prompt buffer length (prompts right-padded)
    max_gen_len: int = 16        # per-request generation budget
    eos_token_id: Optional[int] = None   # None -> model config's knob
    draft_k: int = 0             # speculative draft length; 0 = off
    # --- DynamicEngine-only knobs (static Engine rejects them) ---
    prefix_cache: bool = False   # radix-tree prompt-prefix page sharing
    prefill_chunk: int = 0       # admit prompts in chunks of this many
    #                              tokens (page_size multiple); 0 = one-shot
    n_pages: Optional[int] = None        # global pool size override
    n_window_pages: Optional[int] = None  # window pool size override
    adaptive_draft: bool = False  # per-slot draft length from measured
    #                               acceptance (host control; needs draft_k)


class Engine:
    """Slot scheduler + fully-jitted generation loop over a paged KV cache.

    One Engine instance owns one compiled program per (n_requests,) queue
    shape; all request *content* (prompts, lengths, sampling params, seed)
    is traced data.  Pass ``draft_model`` (same vocab; typically the µP
    proxy of the target) with ``ecfg.draft_k >= 1`` to enable lossless
    speculative decoding.

    Pass ``mesh`` (a ``(data, model)`` jax Mesh) to serve multi-device:
    slots shard data-parallel, the flash-decode kernels run tensor-parallel
    over kv-heads (q's head layout is kv-major, so GQA groups never straddle
    shards), page tables and stored positions replicate per model shard.
    The serve program still compiles exactly once — the mesh only changes
    *where* the one program's operands live (see docs/distributed.md).

    Pass ``obs`` (a ``repro.obs.ServeObs``) to record serving metrics and
    phase traces.  The instrumentation is strictly host-side — it never
    enters a traced program, so the zero-recompile contract holds with
    observability fully enabled (see docs/observability.md).
    """

    def __init__(self, model, ecfg: EngineConfig = EngineConfig(),
                 draft_model=None, mesh=None, obs=None):
        if ecfg.prefix_cache or ecfg.prefill_chunk or ecfg.adaptive_draft or (
            ecfg.n_pages is not None or ecfg.n_window_pages is not None
        ):
            raise ValueError(
                "prefix_cache / prefill_chunk / n_pages / n_window_pages / "
                "adaptive_draft need the dynamic engine — use DynamicEngine"
            )
        # lookahead: speculative chunks write up to draft_k positions ahead
        # of the earliest query in the same forward — the windowed ring must
        # cover window + k before wrapping (see kv_cache.build_spec).
        self._init_common(model, ecfg, draft_model, lookahead=ecfg.draft_k)
        self._init_mesh(model, mesh)
        self.obs = obs
        self.gtable, self.wtable = kv_cache.make_tables(self.spec)
        self._serve = jax.jit(self._run)

    def _init_mesh(self, model, mesh):
        self.mesh = mesh
        self._rules = None if mesh is None else make_rules(
            mesh, cfg=model.cfg, fsdp=False, kind="decode"
        )

    def _sharding_ctx(self):
        """The engine's sharding context: entered around every traced call,
        so the ONE trace of the serve program sees the same mesh every
        device-side ``shard()`` / kernel-dispatch decision reads."""
        return sharding_ctx(self.mesh, self._rules)

    def _constrain_state(self, st):
        """Pin every engine-state leaf to its canonical sharding (per-slot
        vectors over "slots", pools per constrain_pools, everything else
        replicated).  Identity without a mesh.  The dynamic engine applies
        this to both the initial state and the step outputs, so the jitted
        step sees identical input shardings on every host-loop iteration —
        without it, XLA's freely-chosen output shardings would differ from
        the fresh inputs' and the second call would recompile."""
        if self.mesh is None:
            return st
        out = dict(st)
        for k in ("active", "slot_req", "slot_pos", "slot_last",
                  "slot_ntok", "last_acc", "last_prop"):
            if k in out:
                out[k] = shard(out[k], "slots")
        if "slot_ctx" in out:
            out["slot_ctx"] = shard(out["slot_ctx"], "slots", None)
        for k in ("step", "next_req", "accepted", "proposed"):
            if k in out:
                out[k] = shard(out[k])
        out["out_toks"] = shard(out["out_toks"], None, None)
        out["out_len"] = shard(out["out_len"], None)
        out["pools"] = kv_cache.constrain_pools(out["pools"])
        if out.get("dpools") is not None:
            out["dpools"] = kv_cache.constrain_pools(out["dpools"])
        return out

    def shard_params(self, params, model=None):
        """device_put ``params`` onto the engine's mesh per the decode
        sharding rules (TP over heads/ffn/vocab; no fsdp — serving wants
        weights resident, not gathered per step).  Identity without a mesh.
        Pass ``model=self.draft_model`` to place drafter params (the
        divisibility fallback re-resolves per tensor, so a drafter with
        unshardable head counts simply replicates those tensors)."""
        if self.mesh is None:
            return params
        from repro.core.meta import ParamMeta  # local: avoid import cycles

        meta = (model or self.model).meta
        sh = jax.tree_util.tree_map(
            lambda m: named_sharding(
                self.mesh, self._rules, m.sharding, m.infshape.shape
            ),
            meta, is_leaf=lambda x: isinstance(x, ParamMeta),
        )
        return jax.tree_util.tree_map(jax.device_put, params, sh)

    def _init_common(self, model, ecfg: EngineConfig, draft_model, lookahead):
        """Validation + geometry shared by the static and dynamic engines."""
        kv_cache.check_servable(model.cfg)
        if min(ecfg.n_slots, ecfg.page_size, ecfg.max_prompt_len,
               ecfg.max_gen_len) < 1:
            raise ValueError(f"engine dimensions must be >= 1, got {ecfg}")
        if (ecfg.draft_k > 0) != (draft_model is not None):
            raise ValueError(
                "speculative decoding needs both draft_k >= 1 and a "
                f"draft_model (got draft_k={ecfg.draft_k}, "
                f"draft_model={'set' if draft_model is not None else 'None'})"
            )
        self.model = model
        self.draft_model = draft_model
        self.ecfg = ecfg
        eos = ecfg.eos_token_id
        if eos is None:
            eos = model.cfg.eos_token_id
        self.eos = int(eos)
        max_total = ecfg.max_prompt_len + ecfg.max_gen_len
        self.spec = kv_cache.build_spec(
            model.cfg, ecfg.n_slots, max_total, ecfg.page_size,
            lookahead=lookahead,
        )
        if draft_model is not None:
            kv_cache.check_servable(draft_model.cfg)
            if draft_model.cfg.vocab_size != model.cfg.vocab_size:
                raise ValueError(
                    "drafter vocab must match the target "
                    f"({draft_model.cfg.vocab_size} != {model.cfg.vocab_size})"
                )
            self.dspec = kv_cache.build_spec(
                draft_model.cfg, ecfg.n_slots, max_total, ecfg.page_size,
                lookahead=lookahead,
            )
            self.dgtable, self.dwtable = kv_cache.make_tables(self.dspec)

    # ------------------------------------------------------------------
    def compile_count(self) -> int:
        """Number of distinct compilations of the serve program (trace
        stability: stays 1 across runs of the same queue shape)."""
        return int(self._serve._cache_size())

    # ------------------------------------------------------------------
    def serve(
        self,
        params,
        prompts,                  # (R, L <= max_prompt_len) int32
        prompt_lens,              # (R,) int32 true lengths
        *,
        temperature=None,         # (R,) float32; <= 0 -> greedy
        top_k=None,               # (R,) int32;  <= 0 -> off
        top_p=None,               # (R,) float32; >= 1 -> off
        seed: int = 0,
        draft_params=None,        # drafter params (speculative engines only)
    ) -> Dict[str, jax.Array]:
        """Serve R requests; returns {"tokens": (R, max_gen_len) int32,
        "lengths": (R,) int32, "steps": () int32 loop-iteration count,
        "accepted": () int32, "proposed": () int32} (generated tokens incl.
        the EOS, if hit; accepted/proposed count speculative drafts and stay
        0 for non-speculative engines)."""
        if (self.draft_model is not None) and draft_params is None:
            raise ValueError("speculative engine: serve() needs draft_params")
        prompts = jnp.asarray(prompts, jnp.int32)
        prompt_lens = jnp.asarray(prompt_lens, jnp.int32)
        R, L = prompts.shape
        Pmax = self.ecfg.max_prompt_len
        if L > Pmax:
            raise ValueError(f"prompt buffer {L} > max_prompt_len {Pmax}")
        if int(prompt_lens.min()) < 1 or int(prompt_lens.max()) > L:
            raise ValueError(f"prompt_lens must be in [1, {L}]")
        if L < Pmax:
            prompts = jnp.pad(prompts, ((0, 0), (0, Pmax - L)))
        t0, k0, p0 = sampling.default_params(R)
        queue = {
            "prompts": prompts,
            "lens": jnp.asarray(prompt_lens, jnp.int32),
            "temperature": t0 if temperature is None
            else jnp.asarray(temperature, jnp.float32),
            "top_k": k0 if top_k is None else jnp.asarray(top_k, jnp.int32),
            "top_p": p0 if top_p is None else jnp.asarray(top_p, jnp.float32),
            "seed": jnp.asarray(seed, jnp.int32),
        }
        if self.obs is None:
            with self._sharding_ctx():
                return self._serve(params, draft_params, queue)
        tracer = self.obs.tracer
        t_start = time.monotonic()
        span = (
            tracer.span("serve", engine="static", requests=R)
            if tracer is not None else contextlib.nullcontext()
        )
        with span:
            with self._sharding_ctx():
                out = self._serve(params, draft_params, queue)
            # block inside the span so its duration covers the device work
            agg = jax.device_get(
                {k: out[k] for k in ("lengths", "steps", "accepted",
                                     "proposed")}
            )
        self._record_serve(
            duration=time.monotonic() - t_start, requests=R,
            tokens=int(np.sum(agg["lengths"])), steps=int(agg["steps"]),
            accepted=int(agg["accepted"]), proposed=int(agg["proposed"]),
        )
        return out

    def _record_serve(self, *, duration, requests, tokens, steps,
                      accepted, proposed):
        """End-of-serve aggregate metrics (shared by both engines)."""
        m = self.obs.metrics
        m.counter("serve_requests_total", "requests served").inc(requests)
        m.counter("serve_tokens_total", "tokens generated").inc(tokens)
        m.counter(
            "serve_steps_total", "engine loop iterations run"
        ).inc(steps)
        m.histogram(
            "serve_duration_seconds", "wall time per serve() call"
        ).observe(duration)
        m.gauge(
            "serve_tokens_per_second", "last serve() decode throughput"
        ).set(tokens / max(duration, 1e-9))
        if proposed:
            m.counter(
                "spec_drafts_proposed_total", "speculative drafts proposed"
            ).inc(proposed)
            m.counter(
                "spec_drafts_accepted_total", "speculative drafts accepted"
            ).inc(accepted)
            m.gauge(
                "spec_acceptance_rate", "last serve() draft acceptance rate"
            ).set(accepted / proposed)
        m.gauge(
            "serve_compile_count",
            "distinct compilations of the serve program (contract: 1)",
        ).set(self.compile_count())

    # ------------------------------------------------------------------
    def _is_eos(self, tok: jax.Array) -> jax.Array:
        if self.eos < 0:
            return jnp.zeros_like(tok, bool)
        return tok == self.eos

    @staticmethod
    def _event_key(base_key, pos, req, tag):
        """PRNG key of one sampling event: folded from the event's absolute
        input position and owning request — invariant to admission timing,
        loop iteration, slot assignment and (under speculation) how many
        drafts earlier iterations accepted."""
        k = jax.random.fold_in(base_key, pos)
        k = jax.random.fold_in(k, req)
        return jax.random.fold_in(k, jnp.int32(tag))

    def _req_params(self, queue, req):
        r = jnp.maximum(req, 0)
        return (
            queue["temperature"][r], queue["top_k"][r], queue["top_p"][r]
        )

    def _event_keys(self, base_key, positions, req, tag):
        """Keys for a (S,) or (S, T) grid of event positions."""
        one = lambda p, r: self._event_key(base_key, p, r, tag)
        if positions.ndim == 1:
            return jax.vmap(one)(positions, req)
        return jax.vmap(
            lambda ps, r: jax.vmap(lambda p: one(p, r))(ps)
        )(positions, req)

    # ------------------------------------------------------------------
    # step bodies, shared between the static single-jit loop (_run) and
    # the dynamic host-scheduled engine (DynamicEngine._step_impl): page
    # tables are parameters — compile-time constants for the static
    # engine, traced per-step data for the dynamic one.
    # ------------------------------------------------------------------

    def _admit_into(self, params, draft_params, queue, base_key, st,
                    slot, req, gtab_row, wtab_row):
        """One-shot admission of ``req`` into ``slot``: full-prompt
        prefill-mode forward, page the emitted cache into the slot's rows,
        sample the first generated token.  Queue advancement is the
        caller's business (the static loop bumps next_req; the dynamic
        host scheduler tracks its own queue)."""
        model, cfg, spec = self.model, self.model.cfg, self.spec
        Pmax, Gmax = self.ecfg.max_prompt_len, self.ecfg.max_gen_len
        prompt = queue["prompts"][req]
        plen = queue["lens"][req]
        idx = jnp.arange(Pmax, dtype=jnp.int32)
        # pads at position Pmax: > every real q_pos during prefill (so
        # invisible through make_mask) and scatter-dropped from the
        # emitted cache (out of range for the Pmax-entry buffer).
        positions = jnp.where(idx < plen, idx, Pmax)[None]
        logits, pcache = model.forward(
            params, prompt[None], positions=positions, mode="prefill",
            cache_len=Pmax, full_cache=True,
        )
        last = logits[0, plen - 1]
        pools = kv_cache.admit_slot(
            st["pools"], pcache, cfg, spec, gtab_row, wtab_row, plen
        )
        # first generated token: the event at input position plen - 1
        key = self._event_key(base_key, plen - 1, req, _TAG_SAMPLE)
        t, tk, tp = self._req_params(queue, req)
        tok = sampling.sample_token(last, t, tk, tp, key)
        finished = self._is_eos(tok) | (Gmax <= 1)
        st = {
            **st,
            "active": st["active"].at[slot].set(~finished),
            "slot_req": st["slot_req"].at[slot].set(req),
            "slot_pos": st["slot_pos"].at[slot].set(plen),
            "slot_last": st["slot_last"].at[slot].set(tok),
            "slot_ntok": st["slot_ntok"].at[slot].set(1),
            "out_toks": st["out_toks"].at[req, 0].set(tok),
            "out_len": st["out_len"].at[req].set(1),
            "pools": pools,
        }
        if self.draft_model is None:
            return st
        return self._drafter_admit(
            draft_params, queue, st, slot, req, plen, tok
        )

    def _drafter_admit(self, draft_params, queue, st, slot, req, plen, tok):
        """Drafter admission: prefill the same prompt into the drafter's
        own pools, and seed the catch-up context with the last dk prompt
        tokens + the freshly sampled one (clipped gathers for plen <= dk
        are harmless: those entries sit at positions < 0 in the catch-up
        chunk and are masked + scatter-dropped)."""
        Pmax = self.ecfg.max_prompt_len
        dk = self.ecfg.draft_k
        prompt = queue["prompts"][req]
        idx = jnp.arange(Pmax, dtype=jnp.int32)
        positions = jnp.where(idx < plen, idx, Pmax)[None]
        _, dpcache = self.draft_model.forward(
            draft_params, prompt[None], positions=positions,
            mode="prefill", cache_len=Pmax, full_cache=True,
        )
        dwrow = None if self.dwtable is None else self.dwtable[slot]
        dpools = kv_cache.admit_slot(
            st["dpools"], dpcache, self.draft_model.cfg, self.dspec,
            self.dgtable[slot], dwrow, plen,
        )
        gidx = plen - dk + jnp.arange(dk, dtype=jnp.int32)
        ctx_row = jnp.concatenate(
            [prompt[jnp.clip(gidx, 0, Pmax - 1)], tok[None]]
        )
        return {
            **st,
            "dpools": dpools,
            "slot_ctx": st["slot_ctx"].at[slot].set(ctx_row),
        }

    def _decode_body(self, params, queue, base_key, st, gtable, wtable):
        model, spec = self.model, self.spec
        Gmax = self.ecfg.max_gen_len
        R = queue["prompts"].shape[0]
        active = st["active"]
        # the decode batch is the slot axis — data-parallel at serve time
        toks = shard(st["slot_last"][:, None], "slots", None)
        positions = shard(
            jnp.where(active, st["slot_pos"], -1)[:, None], "slots", None
        )
        paged = kv_cache.PagedState(
            global_table=gtable, window_table=wtable,
            active=active, page_size=spec.page_size,
        )
        logits, pools = model.forward(
            params, toks, positions=positions, mode="decode",
            cache=st["pools"], paged=paged,
        )
        t, tk, tp = self._req_params(queue, st["slot_req"])
        keys = self._event_keys(
            base_key, st["slot_pos"], st["slot_req"], _TAG_SAMPLE
        )
        tok = sampling.sample(
            shard(logits[:, 0], "slots", "vocab"), t, tk, tp, keys
        )
        # inactive slots write to row R — out of bounds, dropped
        wr = jnp.where(active, st["slot_req"], R)
        out_toks = st["out_toks"].at[wr, st["slot_ntok"]].set(tok)
        ntok = st["slot_ntok"] + active.astype(jnp.int32)
        out_len = st["out_len"].at[wr].set(ntok)
        finished = self._is_eos(tok) | (ntok >= Gmax)
        return {
            **st,
            "active": active & ~finished,
            "slot_pos": st["slot_pos"] + active.astype(jnp.int32),
            "slot_last": jnp.where(active, tok, st["slot_last"]),
            "slot_ntok": jnp.where(active, ntok, st["slot_ntok"]),
            "out_toks": out_toks,
            "out_len": out_len,
            "pools": pools,
        }

    def _decode_spec_body(self, params, draft_params, queue, base_key, st,
                          gtable, wtable, k_eff=None):
        """One speculative decode iteration (draft -> verify -> accept).

        ``k_eff`` ((S,) int32 in [1, draft_k], traced) truncates each
        slot's draft chain without recompiling: draft positions >= k_eff
        are force-rejected in spec_accept AND their q rows zeroed (the
        residual then degenerates to the plain target draw — unbiased),
        their drafter/target cache writes are position-masked to -1
        (scatter-dropped), and ``proposed`` counts only min(dk, k_eff).
        The drafter still runs dk scan iterations — fixed shapes, one
        compiled program — it just drafts into masked-out positions.
        """
        model, spec = self.model, self.spec
        S = spec.n_slots
        Gmax = self.ecfg.max_gen_len
        dk = self.ecfg.draft_k
        R = queue["prompts"].shape[0]
        active = st["active"]
        pos = st["slot_pos"]
        req = st["slot_req"]
        t, tk, tp = self._req_params(queue, req)
        joff = jnp.arange(dk + 1, dtype=jnp.int32)
        k_used = (
            jnp.full((S,), dk, jnp.int32) if k_eff is None
            else jnp.clip(k_eff, 1, dk)
        )
        dpaged = kv_cache.PagedState(
            global_table=self.dgtable, window_table=self.dwtable,
            active=active, page_size=self.dspec.page_size,
        )

        # --- draft: catch-up chunk, then dk - 1 more single steps ---
        # The catch-up (dk+1)-token forward re-feeds the last committed
        # tokens: it simultaneously repairs drafter-cache holes from the
        # previous rejection and yields the logits for the first draft.
        cpos = pos[:, None] - dk + joff[None]
        cpos = jnp.where(active[:, None] & (cpos >= 0), cpos, -1)
        dlogits, dpools = self.draft_model.forward(
            draft_params, shard(st["slot_ctx"], "slots", None),
            positions=cpos, mode="decode", cache=st["dpools"],
            paged=dpaged,
        )

        def draft_step(carry, j):
            logits, dpools = carry          # (S, V) at input pos + j
            qj = sampling.filtered_dist(logits, t, tk, tp)
            dkeys = self._event_keys(base_key, pos + j, req, _TAG_DRAFT)
            dj = sampling._categorical_from(dkeys, qj)
            # feed the draft back (writes drafter KV at pos + 1 + j);
            # the last feed's logits go unused but keep the scan body
            # uniform, and its cache entry saves next iteration's
            # catch-up from a hole when everything is accepted.
            dposj = jnp.where(active & (j < k_used), pos + 1 + j, -1)[:, None]
            nlog, dpools = self.draft_model.forward(
                draft_params, shard(dj[:, None], "slots", None),
                positions=dposj, mode="decode", cache=dpools,
                paged=dpaged,
            )
            return (nlog[:, 0], dpools), (dj, qj)

        (_, dpools), (drafts_j, q_j) = jax.lax.scan(
            draft_step, (dlogits[:, -1], dpools),
            jnp.arange(dk, dtype=jnp.int32),
        )
        drafts = drafts_j.T                  # (S, dk)
        q_dist = jnp.moveaxis(q_j, 0, 1)     # (S, dk, V)
        jmask = None
        if k_eff is not None:
            # truncate the chain at k_used: zero the q rows past it so the
            # forced rejection's residual is exactly p (see spec_accept)
            jmask = joff[None, :dk] < k_used[:, None]          # (S, dk)
            q_dist = jnp.where(jmask[..., None], q_dist, 0.0)

        # --- verify: ONE (dk+1)-token target forward ---
        # [y_pos, d_0 .. d_{dk-1}] at positions pos .. pos+dk; logits
        # row i is the target's filtered dist for the token at
        # pos + 1 + i.  The chunk write doubles as rollback: it lands
        # exactly on whatever stale entries the last rejection left.
        tokens_v = jnp.concatenate(
            [st["slot_last"][:, None], drafts], axis=1
        )
        vpos = jnp.where(
            active[:, None] & (joff[None] <= k_used[:, None]),
            pos[:, None] + joff[None], -1,
        )
        paged = kv_cache.PagedState(
            global_table=gtable, window_table=wtable,
            active=active, page_size=spec.page_size,
        )
        vlogits, pools = model.forward(
            params, shard(tokens_v, "slots", None), positions=vpos,
            mode="decode", cache=st["pools"], paged=paged,
        )
        V = vlogits.shape[-1]
        rep = lambda a: jnp.repeat(a, dk + 1, axis=0)
        p_dist = sampling.filtered_dist(
            vlogits.reshape(S * (dk + 1), V), rep(t), rep(tk), rep(tp)
        ).reshape(S, dk + 1, V)

        # --- accept / resample (rejection sampling) ---
        akeys = self._event_keys(
            base_key, pos[:, None] + joff[None, :dk], req, _TAG_ACCEPT
        )
        skeys = self._event_keys(
            base_key, pos[:, None] + joff[None], req, _TAG_SAMPLE
        )
        n_acc, extra = sampling.spec_accept(
            p_dist, q_dist, drafts, akeys, skeys, accept_mask=jmask
        )
        n_acc = jnp.where(active, n_acc, 0)

        # commit chunk: accepted drafts + the resampled/bonus token,
        # truncated at the first committed EOS and the length budget
        cand = jnp.concatenate(
            [drafts, jnp.zeros((S, 1), jnp.int32)], axis=1
        )
        cand = jnp.where(joff[None] == n_acc[:, None], extra[:, None], cand)
        m_raw = n_acc + 1
        in_commit = self._is_eos(cand) & (joff[None] < m_raw[:, None])
        any_eos = jnp.any(in_commit, axis=1)
        first_eos = jnp.argmax(in_commit, axis=1)
        m_eos = jnp.where(any_eos, first_eos + 1, m_raw)
        room = Gmax - st["slot_ntok"]
        m = jnp.where(active, jnp.minimum(m_eos, room), 0)

        wr = jnp.where(active, req, R)
        commit = joff[None] < m[:, None]
        col = jnp.where(commit, st["slot_ntok"][:, None] + joff[None], Gmax)
        out_toks = st["out_toks"].at[wr[:, None], col].set(cand)
        ntok = st["slot_ntok"] + m
        out_len = st["out_len"].at[wr].set(ntok)
        finished = (any_eos & (first_eos < m)) | (ntok >= Gmax)
        last_tok = jnp.take_along_axis(
            cand, jnp.maximum(m - 1, 0)[:, None], axis=1
        )[:, 0]
        # slide the catch-up context by the commit length
        full_ctx = jnp.concatenate([st["slot_ctx"], cand], axis=1)
        new_ctx = jnp.take_along_axis(
            full_ctx, m[:, None] + joff[None], axis=1
        )
        upd = active & (m > 0)
        out = {
            **st,
            "active": active & ~finished,
            "slot_pos": pos + m,
            "slot_last": jnp.where(upd, last_tok, st["slot_last"]),
            "slot_ntok": jnp.where(active, ntok, st["slot_ntok"]),
            "slot_ctx": jnp.where(upd[:, None], new_ctx, st["slot_ctx"]),
            "out_toks": out_toks,
            "out_len": out_len,
            "pools": pools,
            "dpools": dpools,
            "accepted": st["accepted"]
            + jnp.sum(jnp.where(active, n_acc, 0)),
            "proposed": st["proposed"]
            + jnp.sum(jnp.where(active, k_used, 0)),
        }
        if "last_acc" in st:
            # per-slot telemetry for the host's adaptive-draft controller
            out["last_acc"] = jnp.where(active, n_acc, 0)
            out["last_prop"] = jnp.where(active, k_used, 0)
        return out

    def _run(self, params, draft_params, queue: Dict[str, Any]):
        cfg, spec = self.model.cfg, self.spec
        S = spec.n_slots
        Gmax = self.ecfg.max_gen_len
        dk = self.ecfg.draft_k
        R = queue["prompts"].shape[0]
        base_key = jax.random.PRNGKey(queue["seed"])
        # ≤ R admissions + ≤ R*Gmax token steps; the counter is a backstop
        # so a scheduling bug hangs a test assertion, not the test run.
        max_steps = R * (Gmax + 1) + S + 2

        state = {
            "step": jnp.int32(0),
            "next_req": jnp.int32(0),
            "active": jnp.zeros((S,), bool),
            "slot_req": jnp.full((S,), -1, jnp.int32),
            "slot_pos": jnp.zeros((S,), jnp.int32),   # next write position
            "slot_last": jnp.zeros((S,), jnp.int32),  # last sampled token
            "slot_ntok": jnp.zeros((S,), jnp.int32),  # tokens emitted
            "out_toks": jnp.zeros((R, Gmax), jnp.int32),
            "out_len": jnp.zeros((R,), jnp.int32),
            "accepted": jnp.int32(0),                 # spec drafts accepted
            "proposed": jnp.int32(0),                 # spec drafts proposed
            "pools": kv_cache.init_pools(cfg, spec),
        }
        if self.draft_model is not None:
            state["dpools"] = kv_cache.init_pools(
                self.draft_model.cfg, self.dspec
            )
            # last dk+1 committed tokens per slot, ending at slot_pos — the
            # drafter's catch-up chunk (covers every cache hole a rejection
            # can leave, since one iteration commits at most dk+1 tokens)
            state["slot_ctx"] = jnp.zeros((S, dk + 1), jnp.int32)

        # -------------------------- admission --------------------------
        def admit(st):
            slot = jnp.argmin(st["active"].astype(jnp.int32))  # first free
            req = st["next_req"]
            wrow = None if self.wtable is None else self.wtable[slot]
            st = self._admit_into(
                params, draft_params, queue, base_key, st, slot, req,
                self.gtable[slot], wrow,
            )
            return {**st, "next_req": req + 1}

        # --------------------------- decode ----------------------------
        def decode(st):
            return self._decode_body(
                params, queue, base_key, st, self.gtable, self.wtable
            )

        # ------------------- speculative decode ------------------------
        def decode_spec(st):
            return self._decode_spec_body(
                params, draft_params, queue, base_key, st,
                self.gtable, self.wtable,
            )

        # ------------------------- the one loop -------------------------
        def cond(st):
            pending = st["next_req"] < R
            return (pending | jnp.any(st["active"])) & (st["step"] < max_steps)

        step_fn = decode_spec if self.draft_model is not None else decode

        def body(st):
            can_admit = (st["next_req"] < R) & ~jnp.all(st["active"])
            st = jax.lax.cond(can_admit, admit, lambda s: s, st)
            st = step_fn(st)
            return {**st, "step": st["step"] + 1}

        final = jax.lax.while_loop(cond, body, state)
        return {
            "tokens": final["out_toks"],
            "lengths": final["out_len"],
            "steps": final["step"],
            "accepted": final["accepted"],
            "proposed": final["proposed"],
        }


class _Span:
    """One open span: a profiler annotation, and the start and args of
    the ``complete`` event it becomes."""

    __slots__ = ("name", "t", "args", "ann")

    def __init__(self, name: str, t: float, args: Dict[str, Any]):
        self.name, self.t, self.args = name, t, args
        self.ann = annotate(name, **args)
        self.ann.__enter__()

    def close(self, t: float, out: list) -> None:
        self.ann.__exit__(None, None, None)
        out.append((self.name, self.t, t, self.args))


class _LoopSpans:
    """The host loop of one ``DynamicEngine.serve``, on the profiler's trace
    and in the tracer's JSONL alike.

    ``engine.serve`` around the loop; inside it ``engine.wait_arrival``
    around each idle sleep, and one ``engine.step`` per dispatched step whose
    phases (``engine.admit``, ``engine.prepare``, ``engine.dispatch``,
    ``engine.sync``, ``engine.bookkeep``) follow one another without
    overlap: :meth:`begin` ends the running phase and starts the next at one
    ``time.monotonic()`` stamp.  Each span is an annotation while it is open
    and, once its step or sleep is over, a ``complete`` event of the same
    name and args.  Those events go out at a step's end, after the loop's
    own ``step`` event, so a tracer whose ``complete`` ends the serve (the
    bench's warm-up) ends it after the first step.  Leaving the ``with``
    block by an exception ends the open annotations and sends nothing
    more.
    """

    def __init__(self, tracer, t0: float, **args):
        self._tracer = tracer
        self._serve = _Span("engine.serve", t0, args)
        self._step = self._phase = None
        self._done: list = []

    def __enter__(self) -> "_LoopSpans":
        return self

    def __exit__(self, exc_type, *exc) -> None:
        t = time.monotonic()
        self.end(t, send=exc_type is None)
        self._serve.close(t, self._done)
        if exc_type is None:
            self._send()

    def _send(self) -> None:
        for name, t_start, t_end, args in self._done:
            self._tracer.complete(name, t_start, t_end, **args)
        self._done.clear()

    def step(self, **args) -> None:
        self._step = _Span("engine.step", time.monotonic(), args)

    def begin(self, name: str, **args) -> None:
        t = time.monotonic()
        if self._phase is not None:
            self._phase.close(t, self._done)
        self._phase = _Span(name, t, args)

    def args(self, **args) -> None:
        """Add args to the running phase."""
        self._phase.args.update(args)
        self._phase.ann.set_metadata(**args)

    def end(self, t: Optional[float] = None, send: bool = True,
            **step_args) -> None:
        """End the running phase, and the step (with ``step_args``); a
        step's end sends its events and those of the sleeps before it."""
        t = time.monotonic() if t is None else t
        if self._phase is not None:
            self._phase.close(t, self._done)
            self._phase = None
        if self._step is not None:
            self._step.args.update(step_args)
            if step_args:
                self._step.ann.set_metadata(**step_args)
            self._step.close(t, self._done)
            self._step = None
            if send:
                self._send()


class DynamicEngine(Engine):
    """Host-scheduled engine over the dynamic page allocator + prefix cache.

    The device program is ONE jitted step (admission cond + chunk-prefill
    cond + decode cond); the host loop around it owns everything that varies
    per request — which physical pages back each slot (allocator.BlockManager
    free lists + refcounts), which prompt prefixes are already resident
    (radix-tree prefix cache: full shared pages map copy-free and skip
    prefill), when a request may be admitted (full page budget reserved up
    front; requests queue head-of-line until retirements free pages), and
    the chunk schedule for long prompts (``prefill_chunk``-token pieces on
    absolute page-aligned boundaries, interleaved with decode steps).  All
    of it reaches the device as fixed-shape traced data (page tables + a
    ``ctrl`` block), so the step compiles once per (n_requests,) envelope.

    Determinism contract: PRNG keys are (request, position)-folded exactly
    as in the static engine, chunk boundaries sit on absolute multiples of
    ``prefill_chunk``, and shared spans are floored to the same boundaries —
    so with prefix caching ON or OFF (and admission chunked or not) a greedy
    serve is token-for-token identical, and matched-chunk configs are
    bitwise identical (tests/test_serving.py pins both).

    Prefix sharing applies to global-attention pages only; windowed configs
    run with sharing disabled (ring pages are overwritten in place by
    decode, so a shared ring page would be corrupted — see allocator.py).

    KV pools and the prefix cache persist across ``serve()`` calls, so a
    later serve hits prefixes cached by an earlier one.
    """

    def __init__(self, model, ecfg: EngineConfig = EngineConfig(),
                 draft_model=None, mesh=None, obs=None):
        C = ecfg.prefill_chunk
        if C < 0 or (C and C % ecfg.page_size):
            raise ValueError(
                f"prefill_chunk must be a multiple of page_size "
                f"({ecfg.page_size}), got {C}"
            )
        if ecfg.adaptive_draft and ecfg.draft_k < 1:
            raise ValueError(
                "adaptive_draft adapts the speculative draft length — it "
                f"needs draft_k >= 1 (got draft_k={ecfg.draft_k})"
            )
        # chunk forwards write up to chunk_len - 1 positions ahead of their
        # earliest query — the windowed ring needs the same lookahead margin
        # as speculative verify chunks (kv_cache.build_spec)
        self._init_common(
            model, ecfg, draft_model,
            lookahead=max(ecfg.draft_k, C - 1 if C else 0),
        )
        self._init_mesh(model, mesh)
        self.obs = obs
        spec = self.spec
        self.n_pages = ecfg.n_pages or spec.n_global_pages
        self.n_window_pages = (
            (ecfg.n_window_pages or spec.n_window_pages)
            if spec.wp_cols else 0
        )
        self.blocks = BlockManager(
            n_pages=self.n_pages, page_size=spec.page_size,
            gp_cols=spec.gp_cols, wp_cols=spec.wp_cols,
            n_window_pages=self.n_window_pages,
            prefix_cache=ecfg.prefix_cache,
        )
        self._align = max(C // spec.page_size, 1)
        self._cmax = C if C else ecfg.max_prompt_len
        self._evicted_seen = 0      # prefix-cache eviction counter watermark
        # host-side mirror of the page tables, shipped to the step as data
        self._gtab = np.zeros((spec.n_slots, spec.gp_cols), np.int32)
        self._wtab = (
            np.zeros((spec.n_slots, spec.wp_cols), np.int32)
            if spec.wp_cols else None
        )
        # pools persist across serve() calls: prefix-cached pages stay warm.
        # created under the sharding context so the persistent buffers are
        # born on the mesh (kv-heads TP) instead of being resharded by the
        # first step.
        with self._sharding_ctx():
            self._pools = kv_cache.init_pools(
                model.cfg, spec, n_global=self.n_pages,
                n_window=self.n_window_pages,
            )
            self._dpools = (
                kv_cache.init_pools(draft_model.cfg, self.dspec)
                if draft_model is not None else None
            )
        self._step = jax.jit(self._step_impl)

    # ------------------------------------------------------------------
    def compile_count(self) -> int:
        return int(self._step._cache_size())

    # ------------------------------------------------------------------
    def _ctrl0(self) -> Dict[str, Any]:
        """No-op control block: no admission, invalidation ids past the
        pool (scatter-dropped).  Host code mutates a fresh copy per step —
        every leaf is np-typed so jit treats it as traced data."""
        ctrl = {
            "admit_full": np.bool_(False),
            "admit_chunk": np.bool_(False),
            "chunk_last": np.bool_(False),
            "slot": np.int32(0),
            "req": np.int32(0),
            "plen": np.int32(1),
            "chunk_start": np.int32(0),
            "chunk_len": np.int32(0),
            "inval_g": np.full((self.spec.gp_cols,), self.n_pages, np.int32),
        }
        if self.spec.wp_cols:
            ctrl["inval_w"] = np.full(
                (self.spec.wp_cols,), self.n_window_pages, np.int32
            )
        if self.ecfg.adaptive_draft:
            # per-slot effective draft length; the host controller rewrites
            # it between steps — traced data, so adaptation never recompiles
            ctrl["draft_k"] = np.full(
                (self.spec.n_slots,), self.ecfg.draft_k, np.int32
            )
        return ctrl

    # ------------------------------------------------------------------
    def _step_impl(self, params, draft_params, st, queue, tables, ctrl):
        model, cfg, spec = self.model, self.model.cfg, self.spec
        Pmax, Gmax = self.ecfg.max_prompt_len, self.ecfg.max_gen_len
        base_key = jax.random.PRNGKey(queue["seed"])
        gtable = shard(tables["g"], "slots", "page_cols")
        wtable = tables.get("w")
        if wtable is not None:
            wtable = shard(wtable, "slots", "page_cols")
        slot, req, plen = ctrl["slot"], ctrl["req"], ctrl["plen"]

        def admit_full(st):
            wrow = None if wtable is None else wtable[slot]
            return self._admit_into(
                params, draft_params, queue, base_key, st, slot, req,
                gtable[slot], wrow,
            )

        def admit_chunk(st):
            # freshly popped pages may hold a previous occupant's entries:
            # the host sends their ids on a request's first chunk (and
            # pool-size no-ops otherwise — shared pages are never reset)
            pools = kv_cache.invalidate_pages(
                st["pools"], cfg, ctrl["inval_g"], ctrl.get("inval_w")
            )
            # a decode-mode multi-token forward against the paged cache —
            # exactly the speculative verify-chunk machinery: the chunk's
            # own writes land before attention, and per-row position masks
            # give intra-chunk causality (rows past chunk_len sit at
            # position -1: masked everywhere, scatter-dropped)
            j = jnp.arange(self._cmax, dtype=jnp.int32)
            idx = ctrl["chunk_start"] + j
            toks = queue["prompts"][req][jnp.clip(idx, 0, Pmax - 1)][None]
            pos = jnp.where(j < ctrl["chunk_len"], idx, -1)[None]
            paged = kv_cache.PagedState(
                global_table=gtable[slot][None],
                window_table=None if wtable is None else wtable[slot][None],
                active=jnp.ones((1,), bool),
                page_size=spec.page_size,
            )
            logits, pools = model.forward(
                params, toks, positions=pos, mode="decode",
                cache=pools, paged=paged,
            )
            st = {**st, "pools": pools}

            def finish(st):
                # the prompt is fully resident: sample the first generated
                # token from the last chunk row, keyed exactly like the
                # one-shot path — (plen - 1, req, SAMPLE)
                last = logits[0, jnp.maximum(ctrl["chunk_len"] - 1, 0)]
                key = self._event_key(base_key, plen - 1, req, _TAG_SAMPLE)
                t, tk, tp = self._req_params(queue, req)
                tok = sampling.sample_token(last, t, tk, tp, key)
                finished = self._is_eos(tok) | (Gmax <= 1)
                st = {
                    **st,
                    "active": st["active"].at[slot].set(~finished),
                    "slot_req": st["slot_req"].at[slot].set(req),
                    "slot_pos": st["slot_pos"].at[slot].set(plen),
                    "slot_last": st["slot_last"].at[slot].set(tok),
                    "slot_ntok": st["slot_ntok"].at[slot].set(1),
                    "out_toks": st["out_toks"].at[req, 0].set(tok),
                    "out_len": st["out_len"].at[req].set(1),
                }
                if self.draft_model is None:
                    return st
                return self._drafter_admit(
                    draft_params, queue, st, slot, req, plen, tok
                )

            return jax.lax.cond(ctrl["chunk_last"], finish, lambda s: s, st)

        st = jax.lax.cond(ctrl["admit_full"], admit_full, lambda s: s, st)
        st = jax.lax.cond(ctrl["admit_chunk"], admit_chunk, lambda s: s, st)

        if self.draft_model is not None:
            k_eff = ctrl.get("draft_k")

            def dec(s):
                return self._decode_spec_body(
                    params, draft_params, queue, base_key, s, gtable, wtable,
                    k_eff=k_eff,
                )
        else:
            def dec(s):
                return self._decode_body(
                    params, queue, base_key, s, gtable, wtable
                )
        st = jax.lax.cond(jnp.any(st["active"]), dec, lambda s: s, st)
        st = self._constrain_state(st)
        info = {
            "active": st["active"],
            "slot_ntok": st["slot_ntok"],
            "out_len": st["out_len"],
        }
        if "last_acc" in st:
            info["last_acc"] = st["last_acc"]
            info["last_prop"] = st["last_prop"]
        return st, info

    # ------------------------------------------------------------------
    def serve(
        self,
        params,
        prompts,                  # (R, L <= max_prompt_len) int32
        prompt_lens,              # (R,) int32 true lengths
        *,
        temperature=None,
        top_k=None,
        top_p=None,
        seed: int = 0,
        draft_params=None,
        arrivals=None,            # (R,) seconds from serve start, ascending
        record_times: bool = False,
    ) -> Dict[str, Any]:
        """Serve R requests (FIFO, optionally arrival-gated).

        Returns the static engine's dict plus ``prefill_cached`` /
        ``prefill_total`` (prompt tokens served from shared pages vs total)
        and — with ``record_times`` — per-token wall-clock timestamps and
        the arrival vector, for the traffic benchmark's latency percentiles.
        Timestamps are ``time.monotonic()``-based (immune to wall-clock
        adjustments), relative to serve start.  With ``obs`` attached the
        same stamps also feed the registry's TTFT / inter-token-latency
        histograms — the raw-list return is kept for compatibility and is
        deprecated in favor of the metrics snapshot (docs/observability.md).
        """
        t_call = time.monotonic()
        if (self.draft_model is not None) and draft_params is None:
            raise ValueError("speculative engine: serve() needs draft_params")
        prompts_np = np.asarray(prompts, np.int32)
        lens_np = np.asarray(prompt_lens, np.int32)
        R, L = prompts_np.shape
        Pmax = self.ecfg.max_prompt_len
        if L > Pmax:
            raise ValueError(f"prompt buffer {L} > max_prompt_len {Pmax}")
        if int(lens_np.min()) < 1 or int(lens_np.max()) > L:
            raise ValueError(f"prompt_lens must be in [1, {L}]")
        if L < Pmax:
            prompts_np = np.pad(prompts_np, ((0, 0), (0, Pmax - L)))
        t0p, k0p, p0p = sampling.default_params(R)
        queue = {
            "prompts": jnp.asarray(prompts_np),
            "lens": jnp.asarray(lens_np),
            "temperature": t0p if temperature is None
            else jnp.asarray(temperature, jnp.float32),
            "top_k": k0p if top_k is None else jnp.asarray(top_k, jnp.int32),
            "top_p": p0p if top_p is None else jnp.asarray(top_p, jnp.float32),
            "seed": jnp.asarray(seed, jnp.int32),
        }
        spec = self.spec
        S, Gmax, C = spec.n_slots, self.ecfg.max_gen_len, self.ecfg.prefill_chunk
        arr = (
            np.zeros((R,), np.float64) if arrivals is None
            else np.asarray(arrivals, np.float64)
        )
        st = {
            "step": jnp.int32(0),
            "active": jnp.zeros((S,), bool),
            "slot_req": jnp.full((S,), -1, jnp.int32),
            "slot_pos": jnp.zeros((S,), jnp.int32),
            "slot_last": jnp.zeros((S,), jnp.int32),
            "slot_ntok": jnp.zeros((S,), jnp.int32),
            "out_toks": jnp.zeros((R, Gmax), jnp.int32),
            "out_len": jnp.zeros((R,), jnp.int32),
            "accepted": jnp.int32(0),
            "proposed": jnp.int32(0),
            "pools": self._pools,
        }
        if self.draft_model is not None:
            st["dpools"] = self._dpools
            st["slot_ctx"] = jnp.zeros((S, self.ecfg.draft_k + 1), jnp.int32)
            if self.ecfg.adaptive_draft:
                st["last_acc"] = jnp.zeros((S,), jnp.int32)
                st["last_prop"] = jnp.zeros((S,), jnp.int32)
        with self._sharding_ctx():
            # eager placement: the fresh leaves start on the mesh with the
            # same shardings the step's outputs are constrained to, so the
            # step compiles once and never reshards its own carried state
            st = self._constrain_state(st)

        # adaptive-draft controller state: per-slot acceptance-rate EMA
        # drives the next step's effective draft length (pure host control —
        # ctrl["draft_k"] is traced data, so adapting never recompiles)
        adaptive = self.ecfg.adaptive_draft
        dk0 = self.ecfg.draft_k
        k_cur = np.full((S,), dk0, np.int32)
        acc_ema = np.full((S,), 0.5, np.float64)

        pending = list(range(R))
        free = list(range(S))
        occupied: Dict[int, int] = {}     # slot -> req (decoding, holds pages)
        cur = None                        # the one in-flight admission
        prefill_cached = prefill_total = 0
        token_times: list = [[] for _ in range(R)]
        prev_len = np.zeros((R,), np.int64)
        steps = 0
        chunks_bound = (Pmax // C + 2) if C else 2
        max_steps = R * (Gmax + chunks_bound + 2) + S + 8
        obs = self.obs
        metrics = obs.metrics if obs is not None else None
        tracer = obs.tracer if obs is not None else None
        step_hist = (
            metrics.histogram(
                "serve_step_seconds", "wall time per dynamic-engine step"
            ) if metrics is not None else None
        )
        # the host loop on the profiler's trace and in the tracer's JSONL,
        # only while tracing: it calls nothing on the tracer but event() and
        # complete()
        t0 = time.monotonic()
        spans = None
        if tracer is not None:
            # setup_us: from the serve() call to t0; tracer_us: the
            # tracer's clock at t0, which places its JSONL on the trace
            args = {"requests": R, "slots": S,
                    "setup_us": (t0 - t_call) * 1e6}
            if getattr(tracer, "t0", None) is not None:
                args["tracer_us"] = (t0 - tracer.t0) * 1e6
            spans = _LoopSpans(tracer, t0, **args)
        with spans or contextlib.nullcontext():
            while pending or cur is not None or occupied:
                now = time.monotonic() - t0
                # idle until the next arrival when nothing is running
                if (cur is None and not occupied and pending
                        and arr[pending[0]] > now):
                    if spans is not None:
                        spans.begin("engine.wait_arrival")
                    time.sleep(min(arr[pending[0]] - now, 2e-3))
                    if spans is not None:
                        spans.end()
                    continue
                if spans is not None:
                    spans.step(step=steps)
                # ---- start a new admission (at most one in flight) ----
                if (cur is None and pending and free
                        and arr[pending[0]] <= now):
                    req = pending[0]
                    if spans is not None:
                        spans.begin("engine.admit", req=req)
                    plen = int(lens_np[req])
                    prompt = [int(x) for x in prompts_np[req, :plen]]
                    slot = min(free)
                    adm = self.blocks.try_admit(
                        slot, prompt, align_pages=self._align
                    )
                    if adm is None:
                        # head-of-line wait: retirements will free pages
                        if not occupied:
                            raise RuntimeError(
                                f"admission stalled: request {req} needs "
                                "pages but no live request will ever free any"
                            )
                    else:
                        pending.pop(0)
                        free.remove(slot)
                        self._gtab[slot, :] = adm.table_row
                        if self._wtab is not None:
                            self._wtab[slot, :] = adm.wtab_row
                        c = adm.cached_len
                        prefill_cached += c
                        prefill_total += plen
                        if C:
                            chunks = [
                                (s0, min(C, plen - s0))
                                for s0 in range(c, plen, C)
                            ]
                        elif c:
                            chunks = [(c, plen - c)]   # one suffix chunk
                        else:
                            chunks = None              # one-shot prefill path
                        cur = {"req": req, "slot": slot, "plen": plen,
                               "prompt": prompt, "chunks": chunks, "i": 0,
                               "adm": adm}
                        if tracer is not None:
                            spans.args(cached=c)
                            tracer.event(
                                "admission", req=req, slot=slot, plen=plen,
                                cached=c, chunks=len(chunks) if chunks else 0,
                            )
                # ---- this step's control block ----
                if spans is not None:
                    spans.begin("engine.prepare")
                ctrl = self._ctrl0()
                finishing = None
                if cur is not None:
                    ctrl["slot"] = np.int32(cur["slot"])
                    ctrl["req"] = np.int32(cur["req"])
                    ctrl["plen"] = np.int32(cur["plen"])
                    if cur["chunks"] is None:
                        ctrl["admit_full"] = np.bool_(True)
                        finishing, cur = cur, None
                    else:
                        s0, l0 = cur["chunks"][cur["i"]]
                        ctrl["admit_chunk"] = np.bool_(True)
                        ctrl["chunk_start"] = np.int32(s0)
                        ctrl["chunk_len"] = np.int32(l0)
                        if cur["i"] == 0:
                            adm = cur["adm"]
                            n = len(adm.fresh_pages)
                            ctrl["inval_g"][:n] = adm.fresh_pages
                            if "inval_w" in ctrl and adm.fresh_wpages:
                                ctrl["inval_w"][:len(adm.fresh_wpages)] = (
                                    adm.fresh_wpages
                                )
                        if cur["i"] == len(cur["chunks"]) - 1:
                            ctrl["chunk_last"] = np.bool_(True)
                            finishing, cur = cur, None
                        else:
                            cur["i"] += 1
                if adaptive:
                    ctrl["draft_k"] = k_cur.copy()
                tables = {"g": jnp.asarray(self._gtab)}
                if self._wtab is not None:
                    tables["w"] = jnp.asarray(self._wtab)
                t_step = time.monotonic()
                if spans is not None:
                    spans.begin("engine.dispatch")
                with self._sharding_ctx():
                    st, info = self._step(
                        params, draft_params, st, queue, tables, ctrl
                    )
                if spans is not None:
                    spans.begin("engine.sync")
                # the device_get syncs, so the span/histogram cover the
                # device work of this step, not just its dispatch
                info = jax.device_get(info)
                steps += 1
                t_done = time.monotonic()
                tnow = t_done - t0
                if tracer is not None:
                    spans.begin("engine.bookkeep")
                    if ctrl["admit_full"]:
                        phase, prompt_len = "prefill", int(ctrl["plen"])
                    elif ctrl["admit_chunk"]:
                        phase = "chunk_prefill"
                        prompt_len = int(ctrl["chunk_len"])
                    else:
                        phase = ("verify" if self.draft_model is not None
                                 else "decode")
                        prompt_len = 0
                    # slots holding a request, the one admitted here too
                    live = len(occupied) + (prompt_len > 0)
                    # complete(), not span(): this loop runs once per
                    # generated token, and the contextmanager protocol costs
                    # real µs here
                    tracer.complete("step", t_step, t_done, phase=phase)
                if step_hist is not None:
                    step_hist.observe(t_done - t_step)
                # ---- host bookkeeping ----
                if finishing is not None:
                    # prompt fully resident: publish its full pages to the
                    # radix tree before any chance of retirement
                    self.blocks.complete(finishing["slot"],
                                         finishing["prompt"])
                    occupied[finishing["slot"]] = finishing["req"]
                new_len = np.asarray(info["out_len"], np.int64)
                for r in np.nonzero(new_len > prev_len)[0]:
                    token_times[r].extend(
                        [tnow] * int(new_len[r] - prev_len[r])
                    )
                prev_len = new_len
                if adaptive:
                    # EMA of the per-slot acceptance rate steers k: confident
                    # drafters earn longer chains, struggling ones shorter —
                    # speculation stays profitable per slot, not on average
                    la = np.asarray(info["last_acc"], np.int64)
                    lp = np.asarray(info["last_prop"], np.int64)
                    stepped = lp > 0
                    rate = la[stepped] / lp[stepped]
                    acc_ema[stepped] = 0.8 * acc_ema[stepped] + 0.2 * rate
                    grow = stepped & (acc_ema > 0.8)
                    shrink = stepped & (acc_ema < 0.4)
                    k_cur[grow] = np.minimum(k_cur[grow] + 1, dk0)
                    k_cur[shrink] = np.maximum(k_cur[shrink] - 1, 1)
                for slot in sorted(occupied):
                    if not bool(info["active"][slot]):
                        if tracer is not None:
                            tracer.event("retire", slot=slot,
                                         req=occupied[slot])
                        self.blocks.retire(slot)
                        del occupied[slot]
                        free.append(slot)
                        if adaptive:     # next occupant starts from scratch
                            k_cur[slot] = dk0
                            acc_ema[slot] = 0.5
                if spans is not None:
                    spans.end(phase=phase, live=live, chunk_len=prompt_len)
                if steps > max_steps:
                    raise RuntimeError(
                        f"dynamic engine exceeded {max_steps} steps — "
                        "host scheduler bug"
                    )

        # pools stay warm: the next serve() hits prefixes cached by this one
        self._pools = st["pools"]
        if self.draft_model is not None:
            self._dpools = st["dpools"]
        out = {
            "tokens": st["out_toks"],
            "lengths": st["out_len"],
            "steps": jnp.int32(steps),
            "accepted": st["accepted"],
            "proposed": st["proposed"],
            "prefill_cached": prefill_cached,
            "prefill_total": prefill_total,
        }
        if obs is not None:
            acc, prop = map(int, jax.device_get(
                (st["accepted"], st["proposed"])
            ))
            self._record_serve(
                duration=time.monotonic() - t0, requests=R,
                tokens=int(sum(len(ts) for ts in token_times)),
                steps=steps, accepted=acc, proposed=prop,
            )
            if metrics is not None:
                ttft = metrics.histogram(
                    "serve_ttft_seconds", "arrival to first generated token"
                )
                itl = metrics.histogram(
                    "serve_itl_seconds", "inter-token latency"
                )
                ttft_vals, itl_vals = [], []
                for r, ts in enumerate(token_times):
                    if ts:
                        ttft_vals.append(ts[0] - arr[r])
                        itl_vals.extend(np.diff(ts))
                ttft.observe_many(ttft_vals)
                itl.observe_many(itl_vals)
                metrics.counter(
                    "prefill_prompt_tokens_total", "prompt tokens admitted"
                ).inc(prefill_total)
                metrics.counter(
                    "prefill_cached_tokens_total",
                    "prompt tokens served from the prefix cache",
                ).inc(prefill_cached)
                if self.blocks.cache is not None:
                    metrics.counter(
                        "prefix_cache_evicted_pages_total",
                        "pages LRU-evicted from the prefix cache",
                    ).inc(self.blocks.cache.n_evicted - self._evicted_seen)
                    self._evicted_seen = self.blocks.cache.n_evicted
                    metrics.gauge(
                        "prefix_cache_pages",
                        "pages resident in the prefix cache",
                    ).set(len(self.blocks.cache))
                metrics.gauge(
                    "kv_pages_free", "free pages in the global pool"
                ).set(self.blocks.galloc.n_free)
                metrics.gauge(
                    "kv_pages_allocated", "allocated pages (incl. cached)"
                ).set(self.blocks.galloc.n_allocated)
        if record_times:
            out["token_times"] = token_times
            out["arrivals"] = arr
        return out
