"""The traffic generator: the seed shuffles the order and never the work."""

import collections
import json
import os

import pytest

from perfbench.harness.traffic import Schedule

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TRAFFIC = os.path.join(ROOT, "perfbench", "traffic")
FILES = sorted(f for f in os.listdir(TRAFFIC) if f.endswith(".json"))


def load(name):
    with open(os.path.join(TRAFFIC, name)) as f:
        return json.load(f)


@pytest.mark.parametrize("name", FILES)
def test_every_seed_offers_the_same_multiset(name):
    spec = load(name)
    cycle = len(spec["prompt_lens"]) * len(spec["token_budgets"])
    want = None
    for seed in (0, 1, 7, 2 ** 31 + 11, 2 ** 40 + 3):
        sch = Schedule(spec, seed)
        reqs = [sch.request(k) for k in range(cycle)]
        got = (collections.Counter(r.prompt_len for r in reqs),
               collections.Counter(r.budget for r in reqs))
        want = want or got
        assert got == want
        assert all(0 <= r.sampling_seed < 2 ** 30 for r in reqs)
    assert want[0] == collections.Counter(
        {n: c * len(spec["token_budgets"]) for n, c in
         collections.Counter(spec["prompt_lens"]).items()})


@pytest.mark.parametrize("name", FILES)
def test_same_seed_same_schedule_and_ids(name):
    spec = load(name)
    a, b, c = Schedule(spec, 2 ** 31 + 5), Schedule(spec, 2 ** 31 + 5), \
        Schedule(spec, 6)
    assert [a.request(k) for k in range(40)] == \
        [b.request(k) for k in range(40)]
    assert a.prompt_ids(3, 50257) == b.prompt_ids(3, 50257)
    assert len(a.prompt_ids(3, 50257)) == a.request(3).prompt_len
    assert max(a.prompt_ids(3, 1000)) < 1000
    assert [a.request(k) for k in range(40)] != \
        [c.request(k) for k in range(40)]
    assert a.warm_lengths() == sorted(set(spec["prompt_lens"]))


def test_prompt_plus_budget_fits_the_slot():
    for name in FILES:
        spec = load(name)
        assert max(spec["prompt_lens"]) + max(spec["token_budgets"]) <= 1024


@pytest.mark.parametrize("arrival", ["uniform", "poisson"])
def test_open_loop_due_times(arrival):
    spec = dict(load("chat-sat8.json"), kind="open", rate_rps=4.0,
                arrival=arrival)
    a, b = Schedule(spec, 9), Schedule(spec, 9)
    due = [a.request(k).due_s for k in range(200)]
    assert due == [b.request(k).due_s for k in range(200)]
    assert all(y >= x for x, y in zip(due, due[1:]))
    assert due[199] / 199 == pytest.approx(0.25, rel=0.25)
    if arrival == "uniform":
        assert due[8] == 2.0


def test_bad_traffic_is_refused():
    spec = load("chat-sat8.json")
    with pytest.raises(ValueError):
        Schedule(dict(spec, kind="replay"), 0)
    with pytest.raises(ValueError):
        Schedule(dict(spec, kind="open"), 0)        # no rate
    with pytest.raises(ValueError):
        Schedule(dict(spec, prompt_lens=[]), 0)
