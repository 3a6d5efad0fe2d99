"""The traffic generator: equal shares and the same arrivals per seed."""
import collections

import numpy as np
import pytest

import traffic

QUERIES = ["Q4", "Q5", "Q6"]


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_closed_mix_comes_in_rounds(seed):
    reqs = traffic.requests({"loop": "closed"}, QUERIES,
                            seed, 10, n_closed=300)
    for i in range(0, 300, 3):
        assert sorted(r.query for r in reqs[i:i + 3]) == QUERIES
    assert reqs == traffic.requests({"loop": "closed"},
                                    QUERIES, seed, 10, n_closed=300)


def test_open_arrivals_are_the_same_gaps_in_another_order():
    mix = {"loop": "open", "rate_qps": 2.0}
    a = traffic.requests(mix, QUERIES, 1, 50)
    b = traffic.requests(mix, QUERIES, 2, 50)
    assert len(a) == len(b) == 100
    ga = np.diff([0.0] + [r.due_s for r in a])
    gb = np.diff([0.0] + [r.due_s for r in b])
    assert not np.allclose(ga, gb)
    # same gaps up to the scale set by the last, unseen one
    assert abs(np.sort(ga)[50] / np.sort(gb)[50] - 1) < 0.1
    assert all(0 <= r.due_s < 50 for r in a)
    for reqs in (a, b):     # equal shares, up to the last partial round
        counts = collections.Counter(r.query for r in reqs)
        assert max(counts.values()) - min(counts.values()) <= 1
