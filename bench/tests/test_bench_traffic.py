"""Traffic generators: a seed fixes the requests, every seed gets the same
sizes and arrivals with other tokens, and the draws follow the stated laws."""
import numpy as np
import pytest

from harness import cells, traffic

CHAT = cells.load_json(cells.BENCH / "traffic" / "serve-chat.json")
LONG = cells.load_json(cells.BENCH / "traffic" / "serve-longprompt.json")


def test_same_seed_same_requests():
    a = traffic.serve_requests(CHAT, 2**33 + 5, 200, 49152)
    b = traffic.serve_requests(CHAT, 2**33 + 5, 200, 49152)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])


def test_seeds_share_sizes_and_arrivals():
    a = traffic.serve_requests(CHAT, 1, 300, 49152)
    b = traffic.serve_requests(CHAT, 2, 300, 49152)
    np.testing.assert_array_equal(a["lens"], b["lens"])
    np.testing.assert_array_equal(a["arrivals"], b["arrivals"])
    assert a["arrivals"][0] == 0.0
    # the seed draws the tokens
    assert not np.array_equal(a["prompts"], b["prompts"])
    # a different schedule comes only from the traffic file's shape_seed
    other = dict(CHAT, shape_seed=CHAT["shape_seed"] + 1)
    c = traffic.serve_requests(other, 1, 300, 49152)
    assert not np.array_equal(a["lens"], c["lens"])


@pytest.mark.parametrize("mix", [CHAT, LONG], ids=["chat", "longprompt"])
def test_lengths_follow_the_clipped_lognormal(mix):
    p = mix["prompt"]
    r = traffic.serve_requests(mix, 3, 4000, 49152)
    lens = r["lens"]
    assert lens.min() >= p["min"] and lens.max() <= p["max"]
    assert np.median(lens) == pytest.approx(p["median"], rel=0.05)
    # prompts are zero past their length and fill the buffer's width
    assert r["prompts"].shape == (4000, mix["max_prompt_len"])
    i = int(np.argmin(lens))
    assert not r["prompts"][i, lens[i]:].any()


def test_arrivals_are_poisson_at_the_stated_rate():
    r = traffic.serve_requests(CHAT, 4, 5000, 49152)
    gaps = np.diff(r["arrivals"])
    rate = CHAT["arrivals"]["rate_per_s"]
    assert np.mean(gaps) == pytest.approx(1 / rate, rel=0.05)
    # exponential: standard deviation equals the mean
    assert np.std(gaps) == pytest.approx(np.mean(gaps), rel=0.08)
    faster = traffic.serve_requests(CHAT, 4, 5000, 49152, rate=2 * rate)
    np.testing.assert_allclose(faster["arrivals"], r["arrivals"] / 2)


def test_request_count_follows_rate_and_length():
    assert traffic.n_requests(CHAT, 30) == round(CHAT["arrivals"]["rate_per_s"] * 30)


def test_train_rows_repeat_at_their_stated_rates():
    import jax

    probs = (0.0, 0.5, 0.9, 0.99)
    key = jax.random.PRNGKey(traffic.key_bits(7))
    rows = np.asarray(traffic.train_rows(key, 3, 4, 4096, 1000, probs))
    again = np.asarray(traffic.train_rows(key, 3, 4, 4096, 1000, probs))
    np.testing.assert_array_equal(rows, again)
    assert rows.shape == (3, 4, 4097) and rows.min() >= 0 and rows.max() < 1000
    for batch in rows:
        rep = sorted(float(np.mean(r[1:] == r[:-1])) for r in batch)
        # a fresh uniform draw over 1000 ids repeats by chance 0.1% of the time
        for got, p in zip(rep, probs):
            assert got == pytest.approx(p + (1 - p) / 1000, abs=0.03)
    # batches permute the rates over their rows and all rows differ
    assert len({r.tobytes() for r in rows.reshape(12, -1)}) == 12


def test_key_bits_take_any_whole_number():
    bits = {traffic.key_bits(s) for s in (0, 1, 2**31 + 1, 2**40 + 1, 2**40 + 2)}
    assert len(bits) == 5 and all(0 <= b < 2**31 for b in bits)
