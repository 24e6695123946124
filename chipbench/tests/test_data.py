"""Data from the seed: the pool-size rule, sameness, special rows."""

import pytest

from chipbench import data

RULE = {"min_commits": 8, "cache_factor": 1.25}
ADV = {"small_order_validators": 4,
       "bad_commits": [[0.0, 0.5, "sig_bit"], [0.5, 1.0, "timestamp"]]}


@pytest.mark.parametrize("cache,consulted,want", [
    (65536, 10000, 9), (65536, 667, 123), (0, 10000, 8), (65536, 96, 854)])
def test_pool_size_rule(cache, consulted, want):
    n = data.pool_size(cache, consulted, **RULE)
    assert n == want
    assert n >= 8 and n * consulted >= 1.25 * cache


def _build(seed, mode="full"):
    return data.build(seed, "t", {"validators": 48}, ADV, mode, 0, RULE, 2)


def test_same_seed_same_bytes_other_seed_same_sizes():
    a, b, c = _build(2**31 + 5), _build(2**31 + 5), _build(77)
    assert a.pubs == b.pubs
    assert [pc.signatures for pc in a.pool] == [pc.signatures for pc in b.pool]
    assert a.pubs != c.pubs
    for d in (a, c):
        assert len(d.pool) == 8 and len(d.warmup) == 2 and d.consulted == 48
        kinds = sorted(k for pc in d.pool for k in pc.suspects.values())
        assert kinds.count("small_order") == 8 * 4
        assert kinds.count("sig_bit") == 1 and kinds.count("timestamp") == 1


def test_bad_rows_lie_in_their_ranges_and_light_consults_two_thirds():
    d = _build(5, "light")
    assert d.consulted == 33
    rows = {k: i for pc in d.pool for i, k in pc.suspects.items() if k != "small_order"}
    assert 0 <= rows["sig_bit"] < 24 <= rows["timestamp"] < 48


def test_rows_are_what_the_commit_carries():
    d = _build(6)
    pc = d.pool[0]
    pub, msg, sig = pc.row(3)
    assert pc.n_rows == d.consulted == 48
    cs = pc.commit.signatures[3]
    assert sig == cs.signature and pub == d.vset.validators[3].pub_key.bytes_()
    assert msg == pc.commit.vote_sign_bytes(data.CHAIN_ID, 3)
