"""Data pipeline: determinism, stateless resume, host sharding, statistics."""
import numpy as np
import pytest
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.data.pipeline import DataConfig, SyntheticLM, make_pipeline


class TestDeterminism:
    def test_same_step_same_batch(self):
        p1 = make_pipeline(512, 32, 8, seed=3)
        p2 = make_pipeline(512, 32, 8, seed=3)
        b1, b2 = p1.batch(17), p2.batch(17)
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])

    def test_different_steps_differ(self):
        p = make_pipeline(512, 32, 8)
        assert not np.array_equal(p.batch(0)["tokens"], p.batch(1)["tokens"])

    def test_stateless_resume(self):
        """Restarting at step t yields exactly the batches of a straight run —
        the checkpoint/restart path never replays or skips data."""
        p = make_pipeline(512, 16, 4, seed=9)
        straight = [p.batch(t)["tokens"] for t in range(8)]
        resumed = [
            b["tokens"]
            for b, _ in zip(p.batches(start_step=4), range(4))
        ]
        for a, b in zip(straight[4:], resumed):
            np.testing.assert_array_equal(a, b)


class TestUnigramDraw:
    @pytest.mark.parametrize("vocab", [128, 4096, 49152])
    def test_draw_is_numpy_choice(self, vocab):
        """The cached-table draw takes the same uniforms as
        ``RandomState.choice(p=unigram)`` and gives the same tokens."""
        p = make_pipeline(vocab, 16, 8, seed=5)
        a, b = np.random.RandomState(11), np.random.RandomState(11)
        for n in (1, 8, 1000):
            np.testing.assert_array_equal(
                p._unigram_draw(a, n), b.choice(vocab, size=n, p=p.unigram))
        assert a.random_sample() == b.random_sample()   # same stream left

    @pytest.mark.parametrize("seed,step", [(0, 0), (3, 17), (2**31 + 5, 2)])
    def test_batch_is_the_choice_built_stream(self, seed, step):
        """A batch is the stream drawn with ``RandomState.choice``, token
        for token (the pipeline's data did not change with the cached
        table)."""
        cfg = DataConfig(1000, 48, 6, seed=seed)
        p = SyntheticLM(cfg)
        rng = np.random.RandomState((seed * 1_000_003 + step) % (2**31 - 1))
        B, S, V = cfg.global_batch, cfg.seq_len, cfg.vocab_size
        want = np.empty((B, S + 1), np.int32)
        want[:, 0] = rng.choice(V, size=B, p=p.unigram)
        for t in range(S):
            use_markov = rng.random_sample(B) < cfg.markov_p
            succ = p.successors[want[:, t],
                                rng.randint(0, cfg.markov_k, size=B)]
            indep = rng.choice(V, size=B, p=p.unigram)
            want[:, t + 1] = np.where(use_markov, succ, indep)
        got = p.batch(step)
        np.testing.assert_array_equal(got["tokens"], want[:, :-1])
        np.testing.assert_array_equal(got["labels"], want[:, 1:])


class TestHostSharding:
    @settings(max_examples=10, deadline=None)
    @given(hosts=st.sampled_from([1, 2, 4, 8]), step=st.integers(0, 100))
    def test_host_shards_tile_the_global_batch(self, hosts, step):
        p = make_pipeline(256, 16, 16, seed=1)
        full = p.batch(step)["tokens"]
        parts = [
            p.batch(step, host_id=h, host_count=hosts)["tokens"]
            for h in range(hosts)
        ]
        np.testing.assert_array_equal(np.concatenate(parts, axis=0), full)


class TestStatistics:
    def test_labels_are_shifted_tokens(self):
        p = make_pipeline(128, 32, 4)
        b = p.batch(0)
        # labels[t] is the next token after tokens[t] (same underlying stream)
        np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])

    def test_markov_structure_is_learnable(self):
        """The planted bigram structure must be present: successor hit-rate
        well above the unigram top-k mass."""
        cfg = DataConfig(256, 64, 32, seed=0)
        p = SyntheticLM(cfg)
        b = p.batch(0)
        toks, labels = b["tokens"], b["labels"]
        hits = 0
        total = 0
        for row_t, row_l in zip(toks, labels):
            for t, l in zip(row_t, row_l):
                hits += int(l in p.successors[t])
                total += 1
        assert hits / total > 0.5  # markov_p = 0.65 minus collisions

    def test_entropy_bound_below_unigram(self):
        p = make_pipeline(512, 32, 8)
        assert p.markov_entropy_bound() < p.unigram_entropy()
