"""Sweep driver: device-sharded, streaming, pruning HP sweeps.

Wraps the vmapped engine (``core.tuning.train_proxy_batched``) with:

  - **candidate-axis sharding**: the stacked (params, opt state, HP) pytrees
    carry the N-candidate batch on their leading axis; a 1-D ``candidates``
    mesh shards that axis across every visible device (pure data parallelism
    over *candidates* — zero cross-candidate communication, so it scales
    linearly).  Resolution reuses ``distributed.sharding``'s logical-axis
    machinery, including its divisibility fallback.
  - **streaming**: per-interval best-loss / alive-count lines while the
    sweep runs, and the full per-candidate loss curves afterwards.
  - **pruning**: divergence always prunes; ``--prune-factor`` additionally
    drops candidates whose EMA loss exceeds factor x the running best
    (checked every ``--prune-every`` steps).  See docs/sweeps.md.

Usage:
    python -m repro.launch.sweep --arch mup-gpt --n 16 --steps 30
    python -m repro.launch.sweep --arch mup-gpt --lrs 1e-3,2e-3,4e-3 \
        --steps 50 --prune-factor 3.0
"""
from __future__ import annotations

import argparse
import contextlib
import time
from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.configs import get_config, get_smoke_config
from repro.core.parametrization import available_parametrizations, resolve
from repro.core.transfer import HParams
from repro.core.tuning import (
    SweepResult,
    grid_candidates,
    train_proxy_batched,
)
from repro.distributed.sharding import ShardingRules, named_sharding
from repro.launch import compile_cache


def candidate_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1-D mesh over the devices that will each own a slice of candidates."""
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), ("candidates",))


def leading_axis_put(mesh: Mesh) -> Callable[[Any], Any]:
    """Shard every array leaf's leading (candidate) axis over the mesh;
    scalars replicate.  Divisibility fallback comes from
    ``distributed.sharding.logical_to_spec`` (a non-divisible candidate
    count degrades to replication rather than erroring).

    Works both eagerly (device_put on concrete arrays) and under tracing
    (with_sharding_constraint) — the engine calls it *inside* the jitted
    init so stacked candidate states are born distributed instead of
    materializing on one device first."""
    rules = ShardingRules(rules={"candidates": "candidates"})

    def put_leaf(x):
        x = jnp.asarray(x)
        if x.ndim == 0:
            return x
        axes = ("candidates",) + (None,) * (x.ndim - 1)
        sh = named_sharding(mesh, rules, axes, x.shape)
        if isinstance(x, jax.core.Tracer):
            # device_put under jit ignores the partition spec (it only pins
            # the memory kind); the constraint is the traced-side spelling
            return jax.lax.with_sharding_constraint(x, sh)
        return jax.device_put(x, sh)

    return lambda tree: jax.tree_util.tree_map(put_leaf, tree)


def _sliced(res: SweepResult, n: int) -> SweepResult:
    """Drop padding candidates appended for device divisibility."""
    return SweepResult(
        candidates=res.candidates[:n],
        losses=res.losses[:n],
        curves=res.curves[:, :n],
        active=res.active[:n],
        steps_run=res.steps_run,
    )


def run_sweep(
    cfg,
    candidates: Sequence[HParams],
    *,
    steps: int = 50,
    batch_size: int = 16,
    seq_len: int = 64,
    seed: int = 0,
    optimizer: str = "adamw",
    prune_factor: Optional[float] = None,
    prune_every: int = 10,
    n_devices: Optional[int] = None,
    log_every: int = 10,
    verbose: bool = True,
    tracer=None,
) -> SweepResult:
    """Run a batched HP sweep with the candidate axis sharded across devices.

    Pads the candidate list to a device-count multiple (duplicating the last
    candidate; padding rows are dropped from the result) so every device
    holds the same number of candidate slices.

    ``tracer`` (a ``repro.obs.Tracer``) records the candidate lifecycle:
    one ``sweep`` span for the run, a ``prune`` instant event whenever the
    alive count drops (with the pruned candidate indices), and a final
    ``sweep_done`` event carrying the best candidate.
    """
    candidates = list(candidates)
    if not candidates:
        raise ValueError("run_sweep: empty candidate list")
    n = len(candidates)
    mesh = candidate_mesh(n_devices)
    ndev = mesh.devices.size
    pad = (-n) % ndev
    padded = candidates + [candidates[-1]] * pad
    if verbose:
        print(
            f"[sweep] {n} candidates (+{pad} pad) x {steps} steps on "
            f"{ndev} device(s); optimizer={optimizer}"
        )

    prev_active = np.ones((n,), bool)

    def stream(t: int, losses: np.ndarray, active: np.ndarray):
        if verbose and log_every and (t % log_every == 0 or t == steps - 1):
            alive = losses[: n][active[: n]]
            best = float(alive.min()) if alive.size else float("inf")
            print(
                f"[sweep] step {t:4d}  best loss {best:.4f}  "
                f"alive {int(active[:n].sum())}/{n}",
                flush=True,
            )
        if tracer is not None:
            nonlocal prev_active
            act = np.asarray(active[:n], bool)
            pruned = np.nonzero(prev_active & ~act)[0]
            if pruned.size:
                tracer.event(
                    "prune", step=t,
                    candidates=[int(i) for i in pruned],
                    alive=int(act.sum()),
                )
            prev_active = act

    t0 = time.time()
    span = (
        tracer.span("sweep", candidates=n, steps=steps, devices=ndev)
        if tracer is not None else contextlib.nullcontext()
    )
    with span:
        res = train_proxy_batched(
            cfg, padded, steps=steps, batch_size=batch_size, seq_len=seq_len,
            seed=seed, optimizer=optimizer, prune_factor=prune_factor,
            prune_every=prune_every,
            put_candidate_axis=leading_axis_put(mesh), stream=stream,
        )
    dt = time.time() - t0
    res = _sliced(res, n)
    if tracer is not None:
        tracer.event(
            "sweep_done", best=res.best_index, best_loss=res.best_loss,
            steps_run=int(res.steps_run),
        )
    if verbose:
        rate = n * res.steps_run / max(dt, 1e-9)
        print(f"[sweep] done in {dt:.1f}s — {rate:.1f} candidate-steps/sec")
    return res


def _parse_candidates(ap, args, cfg) -> List[HParams]:
    # the sweepable axis set comes from the config's parametrization
    # (u-µP: no sigma axis) — resolved through the registry
    space = resolve(cfg.parametrization).hp_space()
    if args.lrs:
        try:
            lrs = tuple(float(x) for x in args.lrs.split(",") if x)
        except ValueError:
            ap.error(f"--lrs must be comma-separated floats, got {args.lrs!r}")
        if not lrs:
            ap.error("--lrs is empty")
        fields = dict(lr=lrs)
        if not space.axis("sigma").fixed:
            fields["sigma"] = (args.sigma,)
        elif args.sigma != 1.0:
            ap.error(
                f"--sigma is not an axis of the {space.name} HP space"
            )
        try:
            return grid_candidates(space=space, **fields)
        except ValueError as e:
            ap.error(str(e))
    if args.n < 1:
        ap.error("--n must be >= 1")
    return space.sample_n(args.n, seed=args.seed)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="mup-gpt")
    ap.add_argument("--full", action="store_true",
                    help="full config (default: smoke config)")
    ap.add_argument("--parametrization", default=None,
                    choices=[str(p) for p in available_parametrizations()],
                    help="override the config's rule (registry name)")
    ap.add_argument("--n", type=int, default=16,
                    help="random-search candidate count")
    ap.add_argument("--lrs", default=None,
                    help="comma-separated LR grid (overrides --n)")
    ap.add_argument("--sigma", type=float, default=1.0)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--prune-factor", type=float, default=None)
    ap.add_argument("--prune-every", type=int, default=10)
    ap.add_argument("--devices", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    compile_cache.enable()

    cfg = (get_config if args.full else get_smoke_config)(args.arch)
    if args.parametrization:
        cfg = cfg.replace(parametrization=args.parametrization)
    candidates = _parse_candidates(ap, args, cfg)
    res = run_sweep(
        cfg, candidates, steps=args.steps, batch_size=args.batch_size,
        seq_len=args.seq_len, seed=args.seed, optimizer=args.optimizer,
        prune_factor=args.prune_factor, prune_every=args.prune_every,
        n_devices=args.devices,
    )
    order = np.argsort(res.losses)
    print(f"[sweep] ranking ({len(order)} candidates):")
    for rank, i in enumerate(order):
        h = res.candidates[i]
        tag = "" if res.active[i] else "  [pruned]"
        print(
            f"  #{rank:<3d} loss {res.losses[i]:<10.4f} lr={h.lr:.3e} "
            f"sigma={h.sigma:g} a_out={h.alpha_output:g} "
            f"a_attn={h.alpha_attn:g} a_embed={h.alpha_embed:g}{tag}"
        )
    best = res.best
    print(f"[sweep] best: lr={best.lr:.3e} sigma={best.sigma:g} "
          f"alpha_output={best.alpha_output:g} (loss {res.best_loss:.4f})")
    return res


if __name__ == "__main__":
    main()
