"""Plain reference, the parts every model family shares: float32
``jax.numpy`` matmuls at "highest" precision, clipped gradients and AdamW as
the configuration states, and per-leaf norms.  A family's own forward and
loss (``bench/families/<family>.py``) run every matmul through ``mm``.  It
imports nothing of the system under test.

Every matmul runs at "highest" precision, so a TPU gives float32 results.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import operator

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST

# the type matmul operands are rounded to before each product: None keeps
# float32 (the reference); a lower type makes the reference the control
OPERANDS: list = [None]


@contextlib.contextmanager
def operands(dtype):
    """Trace the reference with its matmul operands rounded to ``dtype``."""
    OPERANDS.append(dtype)
    try:
        yield
    finally:
        OPERANDS.pop()


def mm(eq, a, b):
    """``einsum`` at "highest" precision, operands rounded as ``operands``
    says."""
    dt = OPERANDS[-1]
    if dt is not None:
        a = a.astype(dt).astype(jnp.float32)
        b = b.astype(dt).astype(jnp.float32)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


@dataclasses.dataclass(frozen=True)
class HP:
    """The muTransferable hyperparameters one run trains with."""

    lr: float = 1e-2
    sigma: float = 1.0
    alpha_output: float = 1.0
    alpha_attn: float = 1.0
    alpha_embed: float = 1.0


def leaf(tree, path):
    """The leaf of nested dicts at ``path`` (a tuple of keys)."""
    return functools.reduce(operator.getitem, path, tree)


# ---------------------------------------------------------------------------
# training: clipped gradients and AdamW, as the configuration states
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Adam:
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    clip: float = 1.0
    weight_decay: float = 0.0
    total_steps: int = 0          # linear decay to 0 over this many; 0: constant


def schedule(opt: Adam, step: int) -> float:
    if not opt.total_steps:
        return 1.0
    return 1.0 - min(max(step / opt.total_steps, 0.0), 1.0)


def clip(grads, max_norm):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree_util.tree_leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-12))
    return jax.tree_util.tree_map(lambda g: g * scale, grads)


def adam_step(params, grads, mu, nu, t: int, lr, lr_fac, opt: Adam):
    """One AdamW update from the clipped ``grads``; t counts from 1."""
    mu = jax.tree_util.tree_map(lambda m, g: opt.b1 * m + (1 - opt.b1) * g,
                                mu, grads)
    nu = jax.tree_util.tree_map(lambda v, g: opt.b2 * v + (1 - opt.b2) * g * g,
                                nu, grads)
    bc1, bc2 = 1 - opt.b1 ** t, 1 - opt.b2 ** t
    sched = schedule(opt, t - 1)

    def upd(p, m, v, f):
        step = lr * sched * f * (m / bc1) / (jnp.sqrt(v / bc2) + opt.eps)
        return p - step - lr * sched * opt.weight_decay * p

    return jax.tree_util.tree_map(upd, params, mu, nu, lr_fac), mu, nu


def leaf_norms(tree) -> jax.Array:
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])
