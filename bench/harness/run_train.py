"""Training cells: the program's train step, fed and driven as ``train_loop``
drives it.

Set-up builds one object (the jitted step, its parameters and AdamW state,
on the device from the seed, and the program's synthetic data pipeline
seeded from the seed), drives it through the first checked steps and hands
the same object to the window.  Each step, in set-up and in the window, asks
the pipeline for its batch on the host, puts it on the device and runs the
step: the loop body of ``train_loop``.  Set-up reads each checked step's
loss before the next; the window keeps about ``AHEAD_S`` seconds of steps
dispatched ahead and reads each loss that many steps late, so the host makes
the next batch while the chip runs, and a host stall shorter than that
leaves the chip fed.  When the time is up it sends nothing more and waits
for every step sent: all of them count, over all of that time.  After the
window the program's state is freed and the reference redoes the checked
steps in float32 on the rows the pipeline fed them.

The program runs on the cell's own ``(data, model)`` mesh over exactly the
cell's chips, under the sharding policy of its configuration, with its
parameters and AdamW state sharded over the mesh (ZeRO-3) where the traffic
file says ``fsdp``.  The reference runs on the same chips: each of its
leaves split over them on its largest dimension that their number divides,
as many rows at a time as there are chips, one row to a chip.
"""
from __future__ import annotations

import collections
import math
import sys
import time

import numpy as np

from harness import cells, reference as ref, traffic as traffic_lib
from harness.record import Run, Check, Timer

# seconds of device steps the window keeps dispatched ahead of the loss it
# waits for, and a cap on the steps that makes
AHEAD_S, AHEAD_MAX = 6.0, 32


def pipeline(cell, seed):
    """The program's synthetic data pipeline for this cell, from the seed."""
    from repro.data.pipeline import make_pipeline

    tr = cell.traffic
    return make_pipeline(cell.config["vocab_size"], tr["seq_len"],
                         tr["batch"], seed=traffic_lib.key_bits(seed))


def rows_of(host: dict) -> np.ndarray:
    """(batch, seq_len + 1) token rows of one host batch."""
    return np.concatenate([host["tokens"], host["labels"][:, -1:]], axis=1)


def _build(cell, seed, devices):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.transfer import HParams, transfer
    from repro.distributed.sharding import make_rules, named_sharding
    from repro.launch import steps as steps_lib
    from repro.models.model import build_model
    from repro.optim import schedules as sched_lib
    from repro.optim.optimizer import Optimizer

    tr = cell.traffic
    cfg = cells.family(cell).program_config(
        cell.config, use_pallas=tr["use_pallas"], remat=tr["remat"],
        amp=tr.get("amp", ""))
    hps = HParams(lr=tr["lr"], sigma=cell.config["mup"]["sigma"])
    xfer = transfer(hps, cfg)
    cfg = cfg.replace(**xfer["model"])
    model = build_model(cfg)
    opt = Optimizer.create(
        "adamw", parametrization=model.p13n, meta=model.meta,
        schedule=sched_lib.make_schedule(
            "linear", total_steps=tr["total_steps"], warmup_steps=0),
        weight_decay=hps.weight_decay, **xfer["optim"],
    )
    mesh = cells.mesh(cell, devices)
    rules = make_rules(mesh, cfg=cfg, fsdp=bool(tr["fsdp"]))
    p_sh = steps_lib.param_shardings(mesh, rules, model.meta)
    replicated = NamedSharding(mesh, P())
    o_sh = steps_lib.opt_state_shardings(mesh, rules, model.meta, opt,
                                         replicated)
    key = jax.random.PRNGKey(traffic_lib.key_bits(seed))
    init = jax.jit(model.init, out_shardings=p_sh)
    params = init(key)
    opt_state = jax.jit(opt.init, out_shardings=o_sh)(params)
    # the state comes back placed as it went in, so every step is one program
    step = jax.jit(steps_lib.make_train_step(model, opt), donate_argnums=(0, 1),
                   out_shardings=(p_sh, o_sh, replicated))
    pipe = pipeline(cell, seed)

    def batch_sh(v):
        # as train_loop places each batch: rows split over "batch"
        return jax.device_put(v, named_sharding(
            mesh, rules, ("batch",) + (None,) * (v.ndim - 1), v.shape))

    def feed(t):
        """Step t's batch, made on the host and put on the device; also its
        host rows."""
        host = pipe.batch(t)
        return {k: batch_sh(jnp.asarray(v)) for k, v in host.items()}, host

    return dict(cfg=cfg, model=model, opt=opt, mesh=mesh, rules=rules,
                key=key, init=init, params=params, opt_state=opt_state,
                step=step, feed=feed, b1=opt.b1)


def run(cell, seed: int, seconds: float, trace: bool, timer: Timer,
        tracer, devices, control=None) -> Run:
    import jax
    import jax.numpy as jnp

    from repro.distributed.sharding import shardings as sharding_ctx

    tr = cell.traffic
    n_check = int(tr["checked_steps"])
    if control == "reference":
        timer.window_start()
        timer.window_stop()
        return reference_control(cell, seed, n_check, devices)
    o = _build(cell, seed, devices)
    step, feed = o["step"], o["feed"]
    params, opt_state = o.pop("params"), o.pop("opt_state")
    norms = jax.jit(ref.leaf_norms)
    losses, rows = [], []
    with sharding_ctx(o["mesh"], o["rules"]):
        # the checked steps: the window's own call and feed, rows all differ
        for t in range(n_check):
            batch, host = feed(t)
            t0 = time.perf_counter()
            params, opt_state, m = step(params, opt_state, batch)
            losses.append(float(m["loss"]))
            step_s = time.perf_counter() - t0
            rows.append(rows_of(host))
            if t == 0:
                g0 = np.asarray(norms(opt_state["mu"])) / (1.0 - o["b1"])
        theta0 = o["init"](o["key"])
        diff = jax.jit(lambda a, b: ref.leaf_norms(
            jax.tree_util.tree_map(jnp.subtract, a, b)))
        d3 = np.asarray(diff(params, theta0))
        del theta0, batch
        # the last checked step's time, from dispatch to its loss read (its
        # batch already made), sets how many steps are kept ahead
        ahead = max(1, min(AHEAD_MAX, math.ceil(AHEAD_S / step_s)))
        sent = collections.deque()
        done, data_s, n_steps, t, failed = [], [], 0, n_check, 0

        def wait_oldest():
            nonlocal failed
            failed += not np.isfinite(float(sent.popleft()))
            done.append(time.perf_counter())

        with tracer.window():
            timer.window_start()
            t_end = timer.t_window + seconds
            while time.perf_counter() < t_end:
                t0 = time.perf_counter()
                with tracer.span("train_data"):
                    batch, _ = feed(t)
                data_s.append(time.perf_counter() - t0)
                with tracer.span("train_step"):
                    params, opt_state, m = step(params, opt_state, batch)
                sent.append(m["loss"])
                n_steps += 1
                t += 1
                if len(sent) > ahead:
                    with tracer.span("train_wait"):
                        wait_oldest()
            with tracer.span("train_wait"):
                while sent:
                    wait_oldest()
            timer.window_stop()
    peak = timer.memory_peak()
    del params, opt_state, m, step, feed, batch, o
    tokens = n_steps * tr["batch"] * tr["seq_len"]
    run_ = Run(kind="train", cell=cell, window_s=timer.window_s,
               attempted=n_steps, failed=failed,
               memory_peak_bytes=peak, step_stamps=list(zip(done, done[1:])),
               tokens=tokens)
    print(f"[bench] window: {n_steps} steps, {ahead} kept ahead (a checked "
          f"step took {step_s!r} s)", file=sys.stderr, flush=True)
    run_.extra["data_s"] = data_s
    run_.e2e["train_tokens_per_s"] = tokens / timer.window_s
    run_.checks = check(cell, seed, losses, g0, d3, np.stack(rows), devices)
    return run_


def reference_control(cell, seed, n, devices):
    """The control in the program's place: the reference with its matmul
    operands rounded to the lower type the traffic file names."""
    import jax.numpy as jnp

    pipe = pipeline(cell, seed)
    rows = np.stack([rows_of(pipe.batch(t)) for t in range(n)])
    with ref.operands(jnp.dtype(cell.traffic["control"]["operands"])):
        low = reference_steps(cell, seed, rows, devices)
    r = Run(kind="train", cell=cell, window_s=0.0, attempted=0, failed=0)
    r.checks = check_numbers(cell, *low,
                             reference_steps(cell, seed, rows, devices))
    return r


def split_largest(structs, mesh):
    """A ``NamedSharding`` for each leaf of ``structs``: split over the
    one-axis ``mesh`` on its largest dimension that the mesh's size
    divides, replicated where none does."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = mesh.size

    def one(x):
        dims = [i for i, d in enumerate(x.shape) if d % n == 0]
        if not dims:
            return NamedSharding(mesh, P())
        i = max(dims, key=lambda i: x.shape[i])
        return NamedSharding(mesh, P(*[("x" if j == i else None)
                                       for j in range(x.ndim)]))

    return jax.tree_util.tree_map(one, structs)


def reference_steps(cell, seed: int, rows, devices, hp=None, candidate=None,
                    clip: bool = True, total_steps=None):
    """The reference's steps on ``rows`` (steps, batch, seq_len + 1), the
    token rows the program's checked steps were fed: per-step loss, per-leaf
    norms of the first gradient as AdamW gets it, and of the parameters'
    change after the last step (leaves in the system's order).
    ``candidate`` i of a sweep starts from ``fold_in(key, i)``.

    Parameters, AdamW moments and the gradient sum are split over
    ``devices`` (``split_largest``); each call takes as many rows as there
    are devices, one to a device, so one device takes one row at a time."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    tr = cell.traffic
    fam = cells.family(cell)
    s = fam.Spec.from_config(cell.config)
    if hp is None:
        m = cell.config["mup"]
        hp = ref.HP(lr=tr["lr"], sigma=m["sigma"],
                    alpha_output=m["alpha_output"],
                    alpha_attn=m["alpha_attn"], alpha_embed=m["alpha_embed"])
    adam = ref.Adam(
        clip=1.0 if clip else 0.0,
        total_steps=tr["total_steps"] if total_steps is None else total_steps)
    key = jax.random.PRNGKey(traffic_lib.key_bits(seed))
    n_dev, batch = len(devices), int(tr["batch"])
    if batch % n_dev:
        raise ValueError(f"batch {batch} does not split over {n_dev} chips")
    n = int(rows.shape[0])
    if candidate is not None:
        key = jax.random.fold_in(key, candidate)
    draw = lambda k: fam.init(k, s, hp.sigma)
    mesh = Mesh(np.asarray(devices), ("x",))
    sh = split_largest(jax.eval_shape(draw, key), mesh)
    init = jax.jit(draw, out_shardings=sh)
    theta = init(key)
    lr_fac = fam.lr_factors(s)
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t),
                    out_shardings=sh)
    mu, nu = zeros(theta), zeros(theta)
    rows_sh = NamedSharding(mesh, P("x", None))

    @jax.jit
    def group_grad(theta, rows):
        # the mean over the group's rows (one row a device) and its gradient
        return jax.value_and_grad(fam.loss)(
            theta, rows[:, :-1], rows[:, 1:], s, hp)

    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                  donate_argnums=0)
    scale = jax.jit(lambda a, c: jax.tree_util.tree_map(
        lambda x: x * c, a))
    clip = jax.jit(lambda g: ref.clip(g, adam.clip) if adam.clip else g)
    update = jax.jit(lambda th, g, mu, nu, t: ref.adam_step(
        th, g, mu, nu, t, hp.lr, lr_fac, adam), static_argnums=4,
        donate_argnums=(0, 2, 3))
    ordered = jax.jit(lambda t: norms_ordered(fam, t))
    losses, g0 = [], None
    for t in range(n):
        total, gsum = 0.0, None
        for r in range(0, batch, n_dev):
            group = jax.device_put(np.asarray(rows[t, r:r + n_dev], np.int32),
                                   rows_sh)
            l_r, g_r = group_grad(theta, group)
            total += float(l_r) * n_dev
            gsum = g_r if gsum is None else add(gsum, g_r)
            del g_r
        losses.append(total / batch)
        g = clip(scale(gsum, n_dev / batch))
        del gsum
        if t == 0:
            g0 = np.asarray(ordered(g))
        theta, mu, nu = update(theta, g, mu, nu, t + 1)
        del g
    del mu, nu
    diff = jax.jit(lambda a, b: norms_ordered(
        fam, jax.tree_util.tree_map(jnp.subtract, a, b)))
    dn = np.asarray(diff(theta, init(key)))
    return losses, g0, dn


def norms_ordered(fam, tree):
    """Norms of the reference's leaves, in the system's order."""
    import jax.numpy as jnp

    leaves = [ref.leaf(tree, p) for p in fam.INIT_ORDER]
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x))) for x in leaves])


def compare_norms(got, want, counted, leaves):
    """Worst leaf's gap between two norms, over the larger of the
    reference's norm of that leaf and the median leaf's norm."""
    want = np.asarray(want, np.float64)
    got = np.asarray(got, np.float64)
    med = float(np.median(want[counted])) if counted.any() else 0.0
    denom = np.maximum(want, med)
    gaps = np.where(counted, np.abs(got - want) / np.maximum(denom, 1e-30), 0)
    i = int(np.argmax(gaps))
    return float(gaps[i]), leaves[i]


def check(cell, seed, losses, g0, d3, rows, devices):
    """The numbers compared, each with its limit (bench/limits)."""
    import jax

    want = reference_steps(cell, seed, rows, devices)
    jax.clear_caches()
    return check_numbers(cell, losses, g0, d3, want)


def check_numbers(cell, losses, g0, d3, want, label=""):
    """Loss of each checked step; per-leaf norms of the first gradient and
    of the change after the checked steps, each by its worst leaf."""
    want_l, want_g, want_d = want
    lim = cell.limits
    n = len(losses)
    gaps = [abs(a - b) / abs(b) for a, b in zip(losses, want_l)]
    # the traffic file may compare the loss of the first steps only, where
    # later steps amplify rounding by the candidate's own dynamics
    k = int(cell.traffic.get("loss_steps", n))
    loss_gap = max(gaps[:k])
    # leaves whose reference gradient is nought to rounding move under
    # Adam by round-off alone: left out of the change by this rule
    med_g = float(np.median(want_g))
    counted = np.asarray(want_g) >= 1e-3 * med_g
    leaves = ["/".join(p) for p in cells.family(cell).INIT_ORDER]
    grad_gap, grad_leaf = compare_norms(g0, want_g, np.ones_like(counted),
                                        leaves)
    change_gap, change_leaf = compare_norms(d3, want_d, counted, leaves)
    pre = f"{label}: " if label else ""
    return [
        Check("loss_gap", loss_gap, lim["loss_gap"],
              f"{pre}steps 0-{k - 1} of program {list(map(float, losses))}, "
              f"reference {list(map(float, want_l))}; over all {n} steps "
              f"{max(gaps)!r}"),
        Check("grad_norm_gap", grad_gap, lim["grad_norm_gap"],
              f"{pre}worst leaf {grad_leaf}"),
        Check("change_norm_gap", change_gap, lim["change_norm_gap"],
              f"{pre}worst leaf {change_leaf}; leaves left out "
              f"{[leaves[i] for i in np.nonzero(~counted)[0]]}"),
    ]
