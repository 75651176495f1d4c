"""Faults planted underneath the harness: each breaks the timed path as a
faulty program would, and the comparison with the reference has to catch
it.  ``run.py --fault <name>`` reads them on the chip at a cell's own size;
the tests read them at test size.

- ``frozen_state``: the training step returns its state unchanged;
- ``half_batch``: the training step sees half of the batch, and the loss is
  the mean over that half;
- ``no_exchange``: the gradient is not reduced between the chips of the
  ``data`` axis: each chip updates its own shard of a weight with the
  gradient of its own rows alone (a weight split over no chip takes the
  first chip's rows);
- ``altered_token``: the serving engine's decode emits the token after the
  one it sampled.
"""
from __future__ import annotations

import contextlib


def _wrap_train_steps(wrap):
    """Patch both training-step factories so each built step is ``wrap``ped."""
    from repro.core import tuning
    from repro.launch import steps

    saved = []
    for mod, name in ((steps, "make_train_step"),
                      (tuning, "make_batched_step")):
        make = getattr(mod, name)
        saved.append((mod, name, make))
        setattr(mod, name,
                lambda *a, _make=make, **k: wrap(_make(*a, **k)))
    return saved


def _frozen_state():
    import jax
    import jax.numpy as jnp

    def wrap(step):
        def f(params, opt_state, *rest):
            # the step may donate its inputs: hand it copies, return these
            copy = jax.tree_util.tree_map(jnp.copy, (params, opt_state))
            out = step(*copy, *rest)
            return (params, opt_state) + tuple(out[2:])
        return f

    return _wrap_train_steps(wrap)


def _half_batch():
    def wrap(step):
        def f(*args):
            batch = args[-1]
            n = batch["tokens"].shape[0] // 2
            return step(*args[:-1], {k: v[:n] for k, v in batch.items()})
        return f

    return _wrap_train_steps(wrap)


def _no_exchange():
    import jax
    import jax.numpy as jnp

    from repro.launch import steps

    grads_of, shardings_of, placed = (steps.accumulate_gradients,
                                      steps.param_shardings, {})

    def param_shardings(*a, **k):
        placed["params"] = out = shardings_of(*a, **k)
        return out

    def own_chunk(x, sharding, chip, chips):
        """True where ``x``'s elements live on data-axis chip ``chip``."""
        for dim, axes in enumerate(sharding.spec):
            axes = axes if isinstance(axes, tuple) else (axes,)
            if axes[:1] == ("data",):
                shape = [1] * x.ndim
                shape[dim] = x.shape[dim]
                on = jnp.arange(x.shape[dim]) // (x.shape[dim] // chips)
                return (on == chip).reshape(shape)
        return jnp.asarray(chip == 0)

    def local_gradients(loss_fn, params, batch, num_microbatches):
        p_sh = placed["params"]
        chips = jax.tree_util.tree_leaves(p_sh)[0].mesh.shape["data"]
        rows = batch["tokens"].shape[0] // chips
        losses, out = [], None
        for c in range(chips):
            part = {k: v[c * rows:(c + 1) * rows] for k, v in batch.items()}
            loss, g = grads_of(loss_fn, params, part, num_microbatches)
            g = jax.tree_util.tree_map(
                lambda x, sh, c=c: jnp.where(own_chunk(x, sh, c, chips), x, 0),
                g, p_sh)
            losses.append(loss)
            out = g if out is None else jax.tree_util.tree_map(jnp.add, out, g)
        return jnp.mean(jnp.stack(losses)), out

    steps.param_shardings = param_shardings
    steps.accumulate_gradients = local_gradients
    return [(steps, "param_shardings", shardings_of),
            (steps, "accumulate_gradients", grads_of)]


def _altered_token():
    from repro.serving import sampling

    sample = sampling.sample

    def shifted(logits, *a, **k):
        return (sample(logits, *a, **k) + 1) % logits.shape[-1]

    sampling.sample = shifted
    return [(sampling, "sample", sample)]


FAULTS = {"frozen_state": _frozen_state, "half_batch": _half_batch,
          "no_exchange": _no_exchange, "altered_token": _altered_token}


@contextlib.contextmanager
def planted(name: str | None):
    """Run the block with fault ``name`` planted (None: no fault)."""
    saved = FAULTS[name]() if name else []
    try:
        yield
    finally:
        for mod, attr, orig in reversed(saved):
            setattr(mod, attr, orig)
