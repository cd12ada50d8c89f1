"""The traffic generator repeats exactly for a seed, and every seed
sends the same sizes and gaps in the same order, with its own token
ids."""
import numpy as np
import pytest

from bench import spec, traffic

MIXES = sorted(p.stem for p in (spec.BENCH / "traffic").glob("*.json"))
BIG_SEED = 2**31 + 977


def _load(name):
    return spec._json(spec.BENCH / "traffic" / f"{name}.json")


def _gaps(reqs):
    return np.diff([0.0] + [r.due_s for r in reqs])


def _sizes(reqs):
    return (sorted(len(r.tokens) for r in reqs),
            sorted(r.max_new_tokens for r in reqs))


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    a = traffic.generate(_load(mix), 49152, BIG_SEED, 30)
    b = traffic.generate(_load(mix), 49152, BIG_SEED, 30)
    assert len(a) == len(b) == traffic.request_count(_load(mix), 30)
    for x, y in zip(a, b):
        assert x.due_s == y.due_s and x.max_new_tokens == y.max_new_tokens
        assert np.array_equal(x.tokens, y.tokens)


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_differ_in_order_not_in_work(mix):
    # the order of sizes and gaps is fixed too: only the token ids differ
    m = _load(mix)
    a = traffic.generate(m, 49152, 5, 30)
    b = traffic.generate(m, 49152, BIG_SEED, 30)
    assert [len(r.tokens) for r in a] == [len(r.tokens) for r in b]
    assert any(not np.array_equal(x.tokens, y.tokens) for x, y in zip(a, b))
    sa, sb = _sizes(a), _sizes(b)
    assert sa[0] == sb[0] and sa[1] == sb[1]
    assert np.array_equal(_gaps(a), _gaps(b))


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_stay_in_the_mix_and_rate_holds(mix):
    m = _load(mix)
    reqs = traffic.generate(m, 1000, 3, 30)
    p, o = m["prompt"], m["output"]
    assert all(p["min"] <= len(r.tokens) <= p["max"] for r in reqs)
    assert all(o["min"] <= r.max_new_tokens <= o["max"] for r in reqs)
    assert all(0 <= int(r.tokens.max()) < 1000 for r in reqs)
    burst = m["arrivals"].get("initial_burst", 0)
    gaps = _gaps(reqs)[burst:]
    assert abs(gaps.mean() * m["arrivals"]["rate_rps"] - 1) < 0.1


@pytest.mark.parametrize("rule", ["gap_order", "length_order"])
def test_a_fixed_order_is_the_same_for_every_seed(rule):
    # and it is a shuffle, not the quantiles in rising order
    m = _load("chat_sat")
    m["arrivals"]["initial_burst"] = 0
    a = traffic.generate(m, 1000, 5, 30)
    b = traffic.generate(m, 1000, BIG_SEED, 30)
    seq = {"gap_order": _gaps, "length_order": lambda reqs: np.array(
        [len(r.tokens) for r in reqs], float)}[rule]
    assert np.array_equal(seq(a), seq(b))
    assert not np.array_equal(seq(a), np.sort(seq(a)))
