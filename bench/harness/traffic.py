"""Inputs made from ``--seed``: training rows and serving requests.

Every seed gets the same schedule of requests: the prompt lengths and the
arrival times are drawn once, in order, from the traffic file's
``shape_seed``, and ``--seed`` draws only the token contents.  So the work
of a run and when it arrives are fixed by the traffic file, and seeds
differ only in the tokens.
"""
from __future__ import annotations

import numpy as np


def key_bits(seed: int) -> int:
    """A 31-bit JAX key seed from any whole number (large ones included)."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0] >> 1)


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**63 - 1), stream])


# ---------------------------------------------------------------------------
# training rows
# ---------------------------------------------------------------------------

def train_rows(key, batches: int, batch: int, seq_len: int, vocab: int,
               repeat_probs):
    """(batches, batch, seq_len + 1) int32 token rows, made on the device.

    Row r repeats its previous token with probability ``repeat_probs[r]``
    and otherwise draws a fresh uniform token, so rows differ in how
    predictable they are and a loss taken over part of the batch differs
    from the whole.  Each batch permutes the probabilities over its rows."""
    import jax
    import jax.numpy as jnp

    probs = jnp.asarray(repeat_probs, jnp.float32)
    assert probs.shape == (batch,), (probs.shape, batch)
    n = seq_len + 1

    def one(k):
        kp, kt, kr = jax.random.split(k, 3)
        p = jax.random.permutation(kp, probs)
        fresh = jax.random.randint(kt, (batch, n), 0, vocab, jnp.int32)
        rep = jax.random.uniform(kr, (batch, n)) < p[:, None]
        rep = rep.at[:, 0].set(False)
        t = jnp.arange(n, dtype=jnp.int32)
        src = jax.lax.cummax(jnp.where(rep, 0, t), axis=1)
        return jnp.take_along_axis(fresh, src, axis=1)

    return jax.vmap(one)(jax.random.split(key, batches))


# ---------------------------------------------------------------------------
# serving requests
# ---------------------------------------------------------------------------

def _lengths(spec: dict, n: int, g: np.random.Generator) -> np.ndarray:
    if spec["dist"] == "lognormal":
        x = np.exp(np.log(spec["median"]) + spec["sigma"] * g.standard_normal(n))
    elif spec["dist"] == "fixed":
        x = np.full(n, spec["value"], np.float64)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    lo, hi = spec.get("min", 1), spec.get("max", np.inf)
    return np.clip(np.rint(x), lo, hi).astype(np.int32)


def _gaps(spec: dict, n: int, g: np.random.Generator) -> np.ndarray:
    if spec["process"] == "poisson":
        return g.exponential(1.0 / spec["rate_per_s"], n)
    raise ValueError(f"unknown arrival process {spec['process']!r}")


def n_requests(traffic: dict, seconds: float) -> int:
    """Requests in one run: the arrival rate times the run's length."""
    return max(int(round(traffic["arrivals"]["rate_per_s"] * seconds)), 1)


def serve_requests(traffic: dict, seed: int, n: int, vocab: int,
                   rate: float | None = None) -> dict:
    """n requests: prompts (n, max_len) int32, lens (n,), arrivals (n,)
    seconds from the start with the first at 0.

    The prompt lengths and the inter-arrival gaps, in order, come from the
    traffic file's ``shape_seed``; ``seed`` fills the prompts.  ``rate``
    overrides the arrival rate (a knee sweep): the gaps are the same draws,
    scaled."""
    base = rng(traffic.get("shape_seed", 0), 0)
    lens = _lengths(traffic["prompt"], n, base)
    gaps = _gaps(traffic["arrivals"], n, base)
    if rate is not None:
        gaps = gaps * traffic["arrivals"]["rate_per_s"] / rate
    g = rng(seed, 1)
    arrivals = np.concatenate([[0.0], np.cumsum(gaps[1:])])
    width = int(traffic["max_prompt_len"])
    prompts = g.integers(0, vocab, size=(n, width), dtype=np.int32)
    prompts[np.arange(width)[None, :] >= lens[:, None]] = 0
    return {"prompts": prompts, "lens": lens, "arrivals": arrivals}
