"""The stratified generator: every seed offers the same work, in another order."""

import collections
import json
import os

import numpy as np
import pytest

from chipbench.generators import open_loop_rounds

HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.dirname(HERE)), "chipbench", "traffic")
SEEDS = [0, 1, 2, 3, 5, 8, 13, 21, 2**31 + 7, 2**31 + 123456, 987654321, 4242424242]


def load(name):
    with open(os.path.join(TRAFFIC_DIR, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["chat", "docqa"])
def test_twelve_seeds_offer_the_same_multiset_and_rate(name):
    traffic, seconds = load(name), 51.0
    per_round = len(traffic["prompt_tokens"])
    offered, orders = set(), set()
    for seed in SEEDS:
        requests = open_loop_rounds.schedule(traffic, seed, seconds, vocab=32000)
        assert [r.due for r in requests] == sorted(r.due for r in requests)
        warm = [r for r in requests if r.due < 0]
        assert len(warm) == traffic["warm_rounds"] * per_round
        full = (len(requests) // per_round) * per_round
        for k in range(0, full, per_round):  # every whole round: the same lengths and counts
            rnd = requests[k:k + per_round]
            assert sorted(len(r.prompt) for r in rnd) == sorted(traffic["prompt_tokens"])
            assert sorted(r.new_tokens for r in rnd) == sorted(traffic["new_tokens"])
        # each arrival inside its own slot of the even grid
        for i, r in enumerate(requests):
            slot = i - len(warm)
            if slot < 0 and traffic.get("warm_burst"):
                assert -1e-3 <= r.due < 0  # the warm rounds arrive together as the window opens
            else:
                assert slot / traffic["rate_per_s"] <= r.due < (slot + 1) / traffic["rate_per_s"]
        offered.add(len([r for r in requests if r.due >= 0]))
        orders.add(tuple(len(r.prompt) for r in requests[:per_round]))
        assert all(5 <= int(t) < 31999 for r in requests[:3] for t in r.prompt)
    assert len(offered) == 1, "every seed offers the same number of requests in the window"
    assert len(orders) > 6, "seeds order a round differently"


def test_same_seed_same_traffic():
    a = open_loop_rounds.schedule(load("chat"), 2**31 + 5, 10.0, 32000)
    b = open_loop_rounds.schedule(load("chat"), 2**31 + 5, 10.0, 32000)
    assert [r.due for r in a] == [r.due for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) and x.new_tokens == y.new_tokens for x, y in zip(a, b))


def test_offered_tokens_per_second_are_the_files():
    traffic = load("chat")
    requests = [r for r in open_loop_rounds.schedule(traffic, 9, 48.0, 32000) if r.due >= 0]
    whole = (len(requests) // 16) * 16
    tokens = collections.Counter()
    for r in requests[:whole]:
        tokens["prompt"] += len(r.prompt)
        tokens["new"] += r.new_tokens
    assert tokens["prompt"] / whole == pytest.approx(np.mean(traffic["prompt_tokens"]))
    assert tokens["new"] / whole == pytest.approx(np.mean(traffic["new_tokens"]))
