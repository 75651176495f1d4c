"""End-to-end training driver (deliverable b: the e2e example).

Features exercised at every scale (1 CPU here; the same code paths target
the 16x16 / 2x16x16 production meshes):
  - muP-parametrized model + muP AdamW with per-tensor LRs,
  - deterministic stateless-resumable synthetic data pipeline,
  - step-atomic checkpoints with async writes (off the critical path),
  - checkpoint/restart fault tolerance: `--simulate-failure N` raises at
    step N, then main() restarts the loop in-process and resumes from the
    last committed checkpoint (the real-cluster path is identical: the job
    scheduler relaunches the binary, restore finds LATEST),
  - elastic restore: restoring onto a different mesh re-shards parameters,
  - per-step wall-clock watchdog (straggler detection),
  - optional bf16 gradient compression and microbatch accumulation.

Usage:
    python -m repro.launch.train --arch mup-gpt --steps 200 --width 0.25
    python -m repro.launch.train --arch smollm-135m --smoke --steps 50 \
        --simulate-failure 20
"""
from __future__ import annotations

import argparse
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint.checkpoint import Checkpointer
from repro.configs import get_config, get_smoke_config
from repro.core.parametrization import available_parametrizations
from repro.core.transfer import HParams, transfer
from repro.data.pipeline import make_pipeline
from repro.distributed.sharding import (
    make_rules,
    named_sharding,
    shardings as sharding_ctx,
)
from repro.launch import compile_cache, steps as steps_lib
from repro.launch.mesh import make_host_mesh, set_scaleout_xla_flags
from repro.models.model import build_model
from repro.optim import schedules as sched_lib
from repro.optim.optimizer import Optimizer


class SimulatedFailure(RuntimeError):
    pass


def train_loop(
    cfg,
    steps: int,
    hps: HParams,
    ckpt_dir: Optional[str] = None,
    batch_size: int = 8,
    seq_len: int = 128,
    ckpt_every: int = 20,
    simulate_failure_at: Optional[int] = None,
    watchdog_factor: float = 10.0,
    num_microbatches: int = 1,
    compress_grads: bool = False,
    log_every: int = 10,
    seed: int = 0,
    mesh=None,
    model_parallel: int = 1,
    fsdp: bool = False,
    obs=None,
) -> Dict[str, Any]:
    """One training run (possibly resuming). Returns final metrics.

    ``model_parallel`` > 1 (or an explicit ``mesh``) trains on a 2-D
    (data × model) mesh: batch data-parallel, heads/ffn/vocab tensor-
    parallel over "model", and with ``fsdp`` the weights additionally
    ZeRO-3-sharded over "data" (see docs/distributed.md).  The requested
    degree degrades by halving until it divides the device count, so the
    same invocation runs on 1 CPU and on a pod.

    ``obs`` (a :class:`repro.obs.TrainObs`) attaches the observability
    subsystem: loss/grad-norm/step-time metrics into its registry every
    step, and — when ``obs.telemetry`` — the µP-health aux (activation
    coord sizes, logit scale, update-to-weight ratios) emitted by the
    jitted step and drained host-side every ``obs.every`` steps into
    ``obs.ring`` / through ``obs.detector`` (see docs/observability.md).
    """
    xfer = transfer(hps, cfg)
    cfg = cfg.replace(**xfer["model"])
    model = build_model(cfg)
    schedule = sched_lib.make_schedule(
        "linear", total_steps=steps, warmup_steps=hps.warmup_steps
    )
    opt = Optimizer.create(
        "adamw", parametrization=model.p13n, meta=model.meta,
        schedule=schedule, weight_decay=hps.weight_decay, **xfer["optim"],
    )
    telemetry = bool(obs is not None and obs.telemetry)
    step_fn = steps_lib.make_train_step(
        model, opt, num_microbatches=num_microbatches,
        compress_grads=compress_grads, telemetry=telemetry,
    )

    if mesh is None:
        mesh = make_host_mesh(model_parallel)
    rules = make_rules(mesh, cfg=cfg, fsdp=fsdp)
    p_sh = steps_lib.param_shardings(mesh, rules, model.meta)
    batch_sh = lambda v: jax.device_put(
        v,
        named_sharding(
            mesh, rules, ("batch",) + (None,) * (v.ndim - 1), v.shape
        ),
    )

    params = model.init(jax.random.PRNGKey(seed))
    params = jax.tree_util.tree_map(jax.device_put, params, p_sh)
    # placed as the step returns it, so step 1 reuses step 0's executable
    o_sh = steps_lib.opt_state_shardings(
        mesh, rules, model.meta, opt, NamedSharding(mesh, P())
    )
    opt_state = jax.device_put(opt.init(params), o_sh)
    start_step = 0

    ckpt = Checkpointer(ckpt_dir) if ckpt_dir else None
    if ckpt and ckpt.latest_step() is not None:
        (params, opt_state), start_step, extra = ckpt.restore(
            (params, opt_state),
            shardings=(p_sh, o_sh),
        )
        # restore() device_puts params with the current mesh's shardings —
        # the elastic-restart path when the device count changed.
        params = jax.tree_util.tree_map(jax.device_put, params, p_sh)
        print(f"[train] resumed from step {start_step}")

    pipe = make_pipeline(cfg.vocab_size, seq_len, batch_size, seed=seed)
    make_batch = lambda t: {
        k: batch_sh(jnp.asarray(v)) for k, v in pipe.batch(t).items()
    }
    jit_step = jax.jit(step_fn, donate_argnums=(0, 1))
    tracer = obs.tracer if obs is not None else None

    losses = []
    step_times = []
    with sharding_ctx(mesh, rules):
        for t in range(start_step, steps):
            if simulate_failure_at is not None and t == simulate_failure_at:
                # drain in-flight async saves first: the injected crash
                # models a failure *between* steps, not one that races the
                # previous checkpoint's commit (which would make the resume
                # point nondeterministic)
                if ckpt:
                    ckpt.wait()
                raise SimulatedFailure(f"injected node failure at step {t}")
            t0 = time.time()
            if tracer is not None:
                # both spans also land on the profiler's trace, where the
                # device idles through train.data (the host makes the batch)
                with tracer.span("train.data", step=t):
                    batch = make_batch(t)
                with tracer.span("train_step", phase="train_step", step=t):
                    params, opt_state, metrics = jit_step(
                        params, opt_state, batch
                    )
                    loss = float(metrics["loss"])
            else:
                batch = make_batch(t)
                params, opt_state, metrics = jit_step(params, opt_state, batch)
                loss = float(metrics["loss"])
            dt = time.time() - t0
            step_times.append(dt)
            losses.append(loss)
            if obs is not None:
                aux = None
                if telemetry and t % max(obs.every, 1) == 0:
                    aux = jax.device_get(metrics["obs"])
                obs.record_step(
                    t, loss=loss, grad_norm=float(metrics["grad_norm"]),
                    dt=dt, tokens=batch_size * seq_len,
                    width=cfg.d_model, aux=aux,
                )
            # straggler watchdog: flag steps >> median
            if len(step_times) > 10:
                med = float(np.median(step_times[-50:]))
                if dt > watchdog_factor * med:
                    print(f"[watchdog] step {t} took {dt:.2f}s (median {med:.2f}s)")
            if log_every and t % log_every == 0:
                print(f"[train] step {t} loss {loss:.4f} ({dt*1000:.0f} ms)")
            if ckpt and (t + 1) % ckpt_every == 0:
                ckpt.save(t + 1, (params, opt_state), async_save=True)
    if ckpt:
        # drain the in-flight async save first: when steps is a multiple of
        # ckpt_every it writes the same step directory as this final save
        ckpt.wait()
        ckpt.save(steps, (params, opt_state))
    return {
        "final_loss": losses[-1] if losses else float("nan"),
        "losses": losses,
        "step_times": step_times,
        "params": params,
        "steps_run": steps - start_step,
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mup-gpt")
    ap.add_argument("--smoke", action="store_true", help="use reduced config")
    ap.add_argument("--width", type=float, default=None,
                    help="width factor vs the config (muTransfer family)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--sigma", type=float, default=1.0)
    ap.add_argument("--parametrization", default="mup",
                    choices=[str(p) for p in available_parametrizations()])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--simulate-failure", type=int, default=None)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--amp", default="", choices=["", "bf16", "int8"],
                    help="mixed-precision matmul policy (attention q·k/p·v "
                         "+ their backward + readout logits); master weights "
                         "and optimizer state stay f32 — safe under u-µP "
                         "unit scaling (see docs/quantization.md)")
    ap.add_argument("--model-parallel", type=int, default=1,
                    help="tensor-parallel degree on the mesh's model axis "
                         "(degrades by halving until it divides the device "
                         "count; 1 = pure data parallel)")
    ap.add_argument("--fsdp", action="store_true",
                    help="additionally ZeRO-3-shard weights over the data "
                         "axis (all-gather/reduce-scatter pairs inserted by "
                         "SPMD; overlapped via the async-collective flags)")
    ap.add_argument("--telemetry", action="store_true",
                    help="emit the µP-health aux from the train step "
                         "(activation coord sizes, logit scale, update/"
                         "weight ratios; see docs/observability.md)")
    ap.add_argument("--obs-dir", default=None,
                    help="write metrics.prom / metrics.json (+ telemetry "
                         "ring and trace when --telemetry) here at exit; "
                         "implies metrics collection")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # must precede any jax operation: XLA reads the flags at backend init
    set_scaleout_xla_flags()
    compile_cache.enable()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    cfg = cfg.replace(parametrization=args.parametrization, dtype="float32",
                      amp=args.amp)
    if args.width:
        cfg = cfg.scaled(args.width)
    hps = HParams(lr=args.lr, sigma=args.sigma)

    obs = None
    if args.telemetry or args.obs_dir:
        from repro.obs import MetricsRegistry, TrainObs, Tracer

        obs = TrainObs(
            metrics=MetricsRegistry(),
            telemetry=args.telemetry,
            tracer=Tracer() if args.obs_dir else None,
        )

    kw = dict(
        steps=args.steps, hps=hps, ckpt_dir=args.ckpt_dir,
        batch_size=args.batch_size, seq_len=args.seq_len,
        ckpt_every=args.ckpt_every, num_microbatches=args.microbatches,
        compress_grads=args.compress_grads, seed=args.seed,
        model_parallel=args.model_parallel, fsdp=args.fsdp, obs=obs,
    )
    try:
        out = train_loop(cfg, simulate_failure_at=args.simulate_failure, **kw)
    except SimulatedFailure as e:
        print(f"[train] {e}; restarting from last checkpoint ...")
        if not args.ckpt_dir:
            raise
        out = train_loop(cfg, simulate_failure_at=None, **kw)
    if obs is not None and args.obs_dir:
        import json
        import os

        os.makedirs(args.obs_dir, exist_ok=True)
        obs.metrics.write_prometheus(os.path.join(args.obs_dir, "metrics.prom"))
        obs.metrics.write_json(os.path.join(args.obs_dir, "metrics.json"))
        if obs.ring is not None:
            with open(os.path.join(args.obs_dir, "telemetry.jsonl"), "w") as f:
                for rec in obs.ring.records:
                    f.write(json.dumps(rec) + "\n")
        if obs.tracer is not None:
            obs.tracer.dump(os.path.join(args.obs_dir, "trace.jsonl"))
        print(f"[obs] wrote {args.obs_dir}/metrics.prom")
    print(f"[train] done: final loss {out['final_loss']:.4f}")
    return out


if __name__ == "__main__":
    main()
