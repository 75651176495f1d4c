"""Deterministic synthetic LM data pipeline.

Offline container => no real corpora; we generate a *structured* synthetic
language so training loss is meaningful (the model has something to learn):

  - Zipfian unigram distribution over the vocab (like natural text),
  - a planted first-order Markov structure (each token biases a small set of
    successor tokens), so CE can drop well below the unigram entropy,
  - deterministic: batch t of a given (seed, config) is a pure function of
    (seed, t) — the pipeline is *stateless-resumable*: after a failure the
    restarted job asks for step t and gets byte-identical data (no iterator
    state in checkpoints), and each host slices its own shard of the global
    batch, so the pipeline scales to any number of hosts.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class DataConfig:
    vocab_size: int
    seq_len: int
    global_batch: int
    seed: int = 0
    zipf_a: float = 1.2          # Zipf exponent
    markov_k: int = 4            # successors per token
    markov_p: float = 0.65       # prob mass on planted successors


class SyntheticLM:
    def __init__(self, cfg: DataConfig):
        self.cfg = cfg
        rng = np.random.RandomState(cfg.seed)
        V = cfg.vocab_size
        ranks = np.arange(1, V + 1, dtype=np.float64)
        self.unigram = ranks ** (-cfg.zipf_a)
        self.unigram /= self.unigram.sum()
        # the cumulative table RandomState.choice(V, p=unigram) builds on
        # every call, built once: ``_unigram_draw`` is that draw, bit for bit
        self._cdf = self.unigram.cumsum()
        self._cdf /= self._cdf[-1]
        # planted successor table: token v -> k preferred successors
        self.successors = rng.randint(0, V, size=(V, cfg.markov_k)).astype(np.int32)

    # ------------------------------------------------------------------
    def batch(
        self, step: int, host_id: int = 0, host_count: int = 1
    ) -> Dict[str, np.ndarray]:
        """The (host-sharded) batch for global step `step` (pure function)."""
        cfg = self.cfg
        assert cfg.global_batch % host_count == 0
        per_host = cfg.global_batch // host_count
        rng = np.random.RandomState(
            (cfg.seed * 1_000_003 + step) % (2**31 - 1)
        )
        # draw the whole global batch, slice this host's rows => identical
        # global data regardless of host layout (elastic-restart safe)
        B, S = cfg.global_batch, cfg.seq_len
        toks = np.empty((B, S + 1), np.int32)
        toks[:, 0] = self._unigram_draw(rng, B)
        for t in range(S):
            prev = toks[:, t]
            use_markov = rng.random_sample(B) < cfg.markov_p
            succ_pick = self.successors[
                prev, rng.randint(0, cfg.markov_k, size=B)
            ]
            indep = self._unigram_draw(rng, B)
            toks[:, t + 1] = np.where(use_markov, succ_pick, indep)
        rows = slice(host_id * per_host, (host_id + 1) * per_host)
        return {
            "tokens": toks[rows, :-1],
            "labels": toks[rows, 1:].astype(np.int32),
        }

    def _unigram_draw(self, rng: np.random.RandomState, n: int) -> np.ndarray:
        """``rng.choice(V, size=n, p=self.unigram)``: the same uniforms drawn
        and looked up in the same table, without rebuilding the table."""
        return self._cdf.searchsorted(rng.random_sample(n), side="right")

    def batches(
        self, start_step: int = 0, host_id: int = 0, host_count: int = 1
    ) -> Iterator[Dict[str, np.ndarray]]:
        step = start_step
        while True:
            yield self.batch(step, host_id, host_count)
            step += 1

    # ------------------------------------------------------------------
    def unigram_entropy(self) -> float:
        p = self.unigram
        return float(-(p * np.log(p)).sum())

    def markov_entropy_bound(self) -> float:
        """Lower bound on achievable CE (entropy of the planted process)."""
        cfg = self.cfg
        hm = -(
            cfg.markov_p * np.log(cfg.markov_p / cfg.markov_k)
            + (1 - cfg.markov_p) * np.log(max(1 - cfg.markov_p, 1e-12))
        )
        return float(min(hm, self.unigram_entropy()))


def make_pipeline(
    vocab_size: int, seq_len: int, global_batch: int, seed: int = 0
) -> SyntheticLM:
    return SyntheticLM(DataConfig(vocab_size, seq_len, global_batch, seed))
