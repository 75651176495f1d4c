"""Sweep cells: the batched sweep engine's vmapped step over N candidates.

Set-up builds what ``train_proxy_batched`` builds (the stacked candidate
states, born on the device from the seed, and ``make_batched_step``'s
compiled step), drives it through the first checked steps and hands it to
the window.  Each step is synced by reading the candidates' losses back, as
``batched_train`` does.  After the window the reference redoes the checked
steps of a sample of the candidates in float32, each with its own
hyperparameters.
"""
from __future__ import annotations

import time

import numpy as np

from harness import cells, reference as ref, traffic as traffic_lib
from harness.record import Run, Timer
from harness.run_train import check_numbers


def candidates(cell, seed: int):
    """The sweep's HP candidates: the muP space sampled from the seed."""
    from repro.core.parametrization import resolve

    space = resolve(cell.config["mup"]["parametrization"]).hp_space()
    return space.sample_n(int(cell.traffic["candidates"]),
                          seed=traffic_lib.key_bits(seed))


def rows(cell, seed: int):
    """(distinct_batches, batch, seq_len + 1) token rows, made on the device
    from the seed; every candidate is fed the same rows."""
    import jax

    tr = cell.traffic
    key = jax.random.PRNGKey(traffic_lib.key_bits(seed))
    return jax.jit(traffic_lib.train_rows, static_argnums=(1, 2, 3, 4))(
        jax.random.fold_in(key, 1), tr["distinct_batches"], tr["batch"],
        tr["seq_len"], cell.config["vocab_size"], tuple(tr["repeat_probs"]))


def _build(cell, seed):
    import jax

    from repro.core.hp import stack_hparams
    from repro.core.init import init_params
    from repro.core.tuning import candidate_rngs, make_batched_step
    from repro.models.model import build_model
    from repro.optim import schedules as sched_lib
    from repro.optim.optimizer import Optimizer

    tr = cell.traffic
    cfg = cells.family(cell).program_config(
        cell.config, use_pallas=tr["use_pallas"],
        remat=tr["remat"]).replace(dtype="float32")
    model = build_model(cfg)
    p13n = model.p13n
    cands = candidates(cell, seed)
    hp_stack = stack_hparams(cands)
    opt = Optimizer.create(
        "adamw", lr=0.0, parametrization=p13n, meta=model.meta,
        schedule=sched_lib.make_schedule("constant"),
    )
    bits = traffic_lib.key_bits(seed)
    rngs = candidate_rngs(bits, len(cands))

    def init_one(rng, hp):
        params = init_params(rng, model.meta, p13n, sigma=hp.sigma)
        return params, opt.init(params)

    params, opt_state = jax.jit(jax.vmap(init_one))(rngs, hp_stack)
    step = make_batched_step(
        lambda p, batch, hp: model.loss_fn(p, batch, hp=hp), opt)
    r = rows(cell, seed)
    batches = [{"tokens": r[i, :, :-1], "labels": r[i, :, 1:]}
               for i in range(tr["distinct_batches"])]
    active = jax.numpy.ones((len(cands),), bool)
    return dict(cands=cands, hp_stack=hp_stack, params=params,
                opt_state=opt_state, active=active, step=step,
                batches=batches, b1=opt.b1, init_one=jax.jit(
                    jax.vmap(init_one)), rngs=rngs)


def run(cell, seed: int, seconds: float, trace: bool, timer: Timer,
        tracer, devices, control=None) -> Run:
    import jax
    import jax.numpy as jnp

    tr = cell.traffic
    if tuple(tr["mesh"]) != (1, 1):
        raise ValueError("the sweep engine runs its candidates on one chip; "
                         f"traffic asks for mesh {tr['mesh']}")
    n_check = int(tr["checked_steps"])
    if control == "reference":
        timer.window_start()
        timer.window_stop()
        r = Run(kind="sweep", cell=cell, window_s=0.0, attempted=0, failed=0)
        r.checks = check(cell, seed, None, None, None, devices, n=n_check,
                         low=True)
        return r
    o = _build(cell, seed)
    step, batches, hp = o["step"], o["batches"], o["hp_stack"]
    params, opt_state, active = o.pop("params"), o.pop("opt_state"), \
        o.pop("active")
    norms = jax.jit(jax.vmap(ref.leaf_norms))
    losses = []
    for t in range(n_check):
        params, opt_state, loss, active = step(params, opt_state, active, hp,
                                               batches[t])
        losses.append(np.asarray(loss, np.float64))
        if t == 0:
            g0 = np.asarray(norms(opt_state["mu"])) / (1.0 - o["b1"])
    theta0, _ = o["init_one"](o["rngs"], hp)
    diff = jax.jit(jax.vmap(lambda a, b: ref.leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, a, b))))
    d3 = np.asarray(diff(params, theta0))
    del theta0, _
    stamps, n_steps, k = [], 0, n_check
    with tracer.window():
        timer.window_start()
        t_end = timer.t_window + seconds
        while True:
            b = batches[k % len(batches)]
            t0 = time.perf_counter()
            with tracer.span("sweep_step"):
                params, opt_state, loss, active = step(
                    params, opt_state, active, hp, b)
                lf = np.asarray(loss, np.float32)
            t1 = time.perf_counter()
            stamps.append((t0, t1))
            n_steps += 1
            k += 1
            if t1 >= t_end:
                break
        timer.window_stop()
    peak = timer.memory_peak()
    alive = int(np.sum(np.isfinite(lf)))
    del params, opt_state, o
    n = len(losses[0])
    tokens = n_steps * n * tr["batch"] * tr["seq_len"]
    r = Run(kind="sweep", cell=cell, window_s=timer.window_s,
            attempted=n_steps, failed=0, memory_peak_bytes=peak,
            step_stamps=stamps, tokens=tokens)
    r.e2e["train_tokens_per_s"] = tokens / timer.window_s
    r.extra["alive"] = alive
    r.checks = check(cell, seed, np.stack(losses, 1), g0, d3, devices)
    return r


def check(cell, seed, losses, g0, d3, devices, n=None, low=False):
    """Compare a sample of candidates, drawn from the seed among those
    whose checked steps stayed finite, with the reference; each number is
    the worst over the sampled candidates.  With ``low`` the reference in
    the traffic file's lower operand type stands in the program's place,
    for the first candidates."""
    import jax.numpy as jnp

    from harness.run_train import reference_steps

    tr = cell.traffic
    cands = candidates(cell, seed)
    k = int(tr["check_candidates"])
    if low:
        pick = np.arange(k)
    else:
        n = losses.shape[1]
        finite = np.nonzero(np.all(np.isfinite(losses), axis=1))[0]
        g = traffic_lib.rng(seed, 3)
        pick = np.sort(g.choice(finite, size=min(k, finite.size),
                                replace=False))
    fed = rows(cell, seed)[:n]
    worst = None
    for i in pick:
        h = cands[int(i)]
        hp = ref.HP(lr=h.lr, sigma=h.sigma, alpha_output=h.alpha_output,
                    alpha_attn=h.alpha_attn, alpha_embed=h.alpha_embed)
        kw = dict(hp=hp, candidate=int(i), clip=False, total_steps=0)
        want = reference_steps(cell, seed, fed, devices, **kw)
        if low:
            with ref.operands(jnp.dtype(tr["control"]["operands"])):
                got_l, got_g, got_d = reference_steps(cell, seed, fed,
                                                      devices, **kw)
        else:
            got_l, got_g, got_d = list(losses[i]), g0[i], d3[i]
        got = check_numbers(cell, got_l, got_g, got_d, want,
                            f"candidate {int(i)}")
        worst = got if worst is None else [
            a if a.value >= b.value else b for a, b in zip(worst, got)]
    return worst or []
