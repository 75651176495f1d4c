"""Tile sweep of the three flash-attention kernels alone, on the chip.

Times the forward, dq and dk/dv ``pallas_call``s of
``kernels/flash_attention.py`` at one training shape for every (bq, bk)
pair of the sweep, each call on its own (host clock around ``--iters``
calls, after a compiled warm-up), and prints one JSON line per kernel and
tile pair.  The tile caps of ``flash_attention.choose_tiles`` come from
this sweep at the SmolLM-360M training cell's shapes (the defaults).  With
``--ab`` it then times the three kernels at the shape rule's tiles in two
schedules, in interleaved rounds: as built, and with the dk/dv kernel's q
index maps unclamped; one JSON line per schedule and kernel gives the
median and range over the rounds, and whether its outputs are bit for bit
those of the built one.  A last line compares the differentiable op at the shape rule's
tiles against the 128 x 128 tiles on one batch row: the largest gap of the
loss and of each gradient, over that quantity's largest magnitude.

    PYTHONPATH=src python -m benchmarks.flash_tiles --tiles 128,256,512,1024 --ab 5

It refuses to run off a TPU: an interpreter's timings say nothing of the
kernels.
"""
from __future__ import annotations

import argparse
import contextlib
import itertools
import json
import statistics
import time

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as fa


def _time(fn, args, iters):
    """Median seconds per call over three batches of ``iters`` calls."""
    jax.block_until_ready(fn(*args))
    runs = []
    for _ in range(3):
        t = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        runs.append((time.perf_counter() - t) / iters)
    return statistics.median(runs)


# the A/B schedules: each names what it takes away from the built kernels
SCHEDULES = {
    "built": {},
    "unclamped": {"_q_block": lambda ki, qi, **kw: qi},
}


@contextlib.contextmanager
def _schedule(name):
    """Trace the kernels under one A/B schedule."""
    saved = {k: getattr(fa, k) for k in SCHEDULES[name]}
    try:
        for k, f in SCHEDULES[name].items():
            setattr(fa, k, f)
        yield
    finally:
        for k, f in saved.items():
            setattr(fa, k, f)


def _ab(calls, kw, bq, bk, rounds, iters, device):
    """Time every kernel under every schedule, in ``rounds`` interleaved
    rounds, each compiled once under its schedule before the first."""
    fns, same, built = {}, {}, {}
    for sched, (name, (call, operands)) in itertools.product(
            SCHEDULES, calls.items()):
        fn = jax.jit(lambda *a, call=call: call(*a, bq=bq, bk=bk, **kw))
        with _schedule(sched):
            out = jax.tree.leaves(fn(*operands))
        fns[sched, name] = fn, operands
        built.setdefault(name, out)
        same[sched, name] = all(bool(jnp.array_equal(a, b))
                                for a, b in zip(out, built[name]))
    times = {key: [] for key in fns}
    for _ in range(rounds):
        for key, (fn, operands) in fns.items():
            times[key].append(_time(fn, operands, iters) * 1e3)
    for (sched, name), ms in times.items():
        print(json.dumps({"ab": sched, "kernel": name, "bq": bq, "bk": bk,
                          "ms_median": statistics.median(ms),
                          "ms_min": min(ms), "ms_max": max(ms),
                          "bits_as_built": same[sched, name],
                          "rounds": rounds, "device": device}), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--heads", type=int, default=15)
    ap.add_argument("--kv-heads", type=int, default=5)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--tiles", default="128,256,512,1024")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--ab", type=int, default=0,
                    help="rounds of the schedule A/B at the rule's tiles")
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        raise SystemExit("flash_tiles times kernels on a TPU only")

    B, S, H, K, d = (args.batch, args.seq, args.heads, args.kv_heads,
                     args.head_dim)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    dt = jnp.bfloat16
    q = jax.random.normal(ks[0], (B, H, S, d), dt) * d ** -0.5
    k = jax.random.normal(ks[1], (B, K, S, d), dt)
    v = jax.random.normal(ks[2], (B, K, S, d), dt)
    do = jax.random.normal(ks[3], (B, H, S, d), dt)
    kw = dict(scale=1.0, causal=True, window=0, softcap=0.0,
              interpret=False)
    o, lse = jax.jit(lambda q, k, v: fa._fwd_call(
        q, k, v, bq=128, bk=128, **kw))(q, k, v)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1,
                    keepdims=True)
    calls = {
        "fwd": (fa._fwd_call, (q, k, v)),
        "dq": (fa._bwd_dq_call, (q, k, v, do, lse, delta)),
        "dkv": (fa._bwd_dkv_call, (q, k, v, do, lse, delta)),
    }
    tiles = [int(t) for t in args.tiles.split(",") if t]
    device = jax.devices()[0].device_kind
    for name, (call, operands) in calls.items():
        for bq, bk in itertools.product(tiles, tiles):
            if S % bq or S % bk:
                continue
            fn = jax.jit(lambda *a, call=call, bq=bq, bk=bk: call(
                *a, bq=bq, bk=bk, **kw))
            try:
                sec = _time(fn, operands, args.iters)
            except Exception as e:  # a tile pair the compiler refuses
                print(json.dumps({"kernel": name, "bq": bq, "bk": bk,
                                  "error": str(e).splitlines()[0][:200]}),
                      flush=True)
                continue
            print(json.dumps({"kernel": name, "bq": bq, "bk": bk,
                              "ms": sec * 1e3, "device": device}), flush=True)

    if args.ab:
        _ab(calls, kw, *fa.choose_tiles(S, S, window=0), args.ab,
            args.iters, device)

    # the differentiable op at the shape rule's tiles against 128 x 128
    def loss(bq, bk):
        def f(q, k, v):
            out = fa.flash_attention(q, k, v, scale=1.0, block_q=bq,
                                     block_k=bk)
            return jnp.sum(out.astype(jnp.float32) * w)
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))

    row = [x[:1].transpose(0, 2, 1, 3) for x in (q, k, v, do)]
    w = row[3].astype(jnp.float32)
    got = loss(None, None)(*row[:3])
    want = loss(128, 128)(*row[:3])
    gaps = []
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        gaps.append(float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))))
    print(json.dumps({"tiles": fa.choose_tiles(S, S, window=0),
                      "loss_dq_dk_dv_gap_over_max": gaps,
                      "device": device}), flush=True)


if __name__ == "__main__":
    main()
