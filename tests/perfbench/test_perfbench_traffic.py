"""The traffic generator: a file states its (prompt, budget) pairs, and the
seed orders them and never chooses them."""

import collections
import importlib.util
import json
import os
import random

import pytest

from perfbench.harness import check, traffic
from perfbench.harness.manifest import Manifest
from perfbench.harness.traffic import Schedule, affine_pairings, pairs_of

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TRAFFIC = os.path.join(ROOT, "perfbench", "traffic")
FILES = sorted(f for f in os.listdir(TRAFFIC) if f.endswith(".json"))
MANIFEST = Manifest(ROOT)
MINI8 = os.path.join(ROOT, "tests", "perfbench", "fixtures", "family",
                     "perfbench", "traffic", "mini8.json")
# every residue modulo 4 (the bits the old mix dropped), small and large
SEEDS = (0, 1, 2, 3, 2 ** 31 + 8, 2 ** 31 + 13, 2147542231, 2 ** 40 + 3)
THOUSAND = ([*range(500)]
            + [random.Random(43).randrange(2 ** 31 + 1024)
               for _ in range(500)])


def load(name):
    with open(os.path.join(TRAFFIC, name)) as f:
        return json.load(f)


def cycle_of(spec, seed):
    sch = Schedule(spec, seed)
    return [sch.request(k) for k in range(len(spec["prompt_lens"]))]


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


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", FILES)
def test_every_seed_offers_the_file_s_pairs(name, seed):
    """The multiset of PAIRS a cycle is the file's, whatever the seed; so
    is each table's multiset, and so is sum(prompt x budget): the work."""
    spec = load(name)
    stated = [(spec["prompt_lens"][j], spec["token_budgets"][i])
              for j, i in enumerate(spec["pairing"])]
    reqs = cycle_of(spec, seed)
    assert collections.Counter((r.prompt_len, r.budget) for r in reqs) == \
        collections.Counter(stated)
    assert sorted(r.prompt_len for r in reqs) == sorted(spec["prompt_lens"])
    # each budget as often as the pairing names it: once a cycle where the
    # tables are as long as each other, n / m times where they are not
    n, m = len(spec["prompt_lens"]), len(spec["token_budgets"])
    assert sorted(r.budget for r in reqs) == sorted(
        spec["token_budgets"] * (n // m))
    assert sum(r.prompt_len * r.budget for r in reqs) == \
        sum(p * b for p, b in stated)
    # the next cycle is the same pairs in the same order
    sch = Schedule(spec, seed)
    assert [(sch.request(k + n).prompt_len, sch.request(k + n).budget)
            for k in range(n)] == [(r.prompt_len, r.budget) for r in reqs]


@pytest.mark.parametrize("name", FILES)
def test_the_seed_still_orders_the_pairs(name):
    spec = load(name)
    orders = {tuple((r.prompt_len, r.budget) for r in cycle_of(spec, seed))
              for seed in SEEDS}
    assert len(orders) > len(SEEDS) // 2


@pytest.mark.parametrize("name", FILES)
def test_the_stored_pairing_is_the_rule_s(name):
    """``lengths_why`` states the rule; ``pairing_rule`` says which of its
    candidates was taken (1 = the nearest) and with which (a, c)."""
    spec = load(name)
    prompts, budgets = spec["prompt_lens"], spec["token_budgets"]
    rule = spec["pairing_rule"]
    ranked = affine_pairings(prompts, budgets)
    a, c, pairing = ranked[rule["candidate"] - 1]
    assert (a, c) == (rule["a"], rule["c"])
    assert pairing == spec["pairing"]
    n, m = len(prompts), len(budgets)
    assert pairing == [((a * j + c) % n) % m for j in range(n)]
    work = sum(p * budgets[i] for p, i in zip(prompts, pairing))
    expected = sum(prompts) * sum(budgets) / m
    assert work / expected == pytest.approx(rule["work_over_expectation"],
                                            abs=1e-5)
    assert f"(a, c) = ({a}, {c})" in spec["lengths_why"]
    assert "shuffles each table" not in spec["lengths_why"]
    # nearest first: no candidate before the one taken is farther off
    gaps = [abs(m * sum(p * budgets[i] for p, i in zip(prompts, pr))
                - sum(prompts) * sum(budgets)) for _, _, pr in ranked]
    assert gaps == sorted(gaps)
    assert all(collections.Counter(pr) == collections.Counter(
        j % m for j in range(n)) for _, _, pr in ranked)


def test_the_rule_s_first_candidates_are_the_issue_s():
    first = {name: affine_pairings(load(name)["prompt_lens"],
                                   load(name)["token_budgets"])[0][:2]
             for name in FILES}
    assert first == {"chat-open8.json": (11, 8), "chat-sat8.json": (11, 8),
                     "decode16.json": (1, 3), "reason-sat8.json": (1, 6)}
    with pytest.raises(ValueError):
        affine_pairings([3, 2, 1], [1, 2, 3])       # not ascending


def test_a_file_without_pairing_pairs_j_with_j_mod_m():
    with open(MINI8) as f:
        mini = json.load(f)
    assert "pairing" not in mini
    assert pairs_of(mini) == [(6, 8), (14, 8), (22, 8)]
    spec = {k: v for k, v in load("decode16.json").items() if k != "pairing"}
    assert pairs_of(spec) == [
        (n, spec["token_budgets"][j % 4])
        for j, n in enumerate(spec["prompt_lens"])]
    assert collections.Counter(
        (r.prompt_len, r.budget) for r in cycle_of(spec, 5)) == \
        collections.Counter(pairs_of(spec))


@pytest.mark.parametrize("pairing", [[0, 1], [0] * 7 + [4], [0] * 7 + [-1],
                                     [0] * 7 + [1.0], [0] * 9])
def test_a_bad_pairing_is_refused(pairing):
    with pytest.raises(ValueError):
        Schedule(dict(load("decode16.json"), pairing=pairing), 0)


@pytest.fixture(scope="module")
def dry_traffic():
    found = importlib.util.spec_from_file_location(
        "perfbench_run", os.path.join(ROOT, "perfbench", "run.py"))
    run = importlib.util.module_from_spec(found)
    found.loader.exec_module(run)
    return run.dry_traffic


@pytest.mark.parametrize("name", FILES)
def test_dry_traffic_keeps_the_pairing(name, dry_traffic):
    """The CPU rehearsal scales lengths and leaves indices alone."""
    spec = load(name)
    dry = dry_traffic(spec)
    assert dry["pairing"] == spec["pairing"]
    assert pairs_of(dry) == [
        (max(4, p // 8), max(3, b // 8)) for p, b in pairs_of(spec)]


def test_the_mix_keeps_every_part_apart():
    """The old mix dropped the two bits in which parts 1 and 2 differ:
    three seeds in four shuffled both tables by ONE permutation, and four
    neighbouring requests shared a sampling seed and a prompt."""
    mix = traffic._mix
    for s in THOUSAND:
        assert mix(s, 1) != mix(s, 2)
        assert len({mix(s, part) for part in range(1, 6)}) == 5
    assert len({mix(s, 1) for s in THOUSAND}) == len(set(THOUSAND))
    assert all(0 <= mix(s, 4, 9) < 2 ** 64 for s in THOUSAND)
    assert mix(2 ** 70 + 5, 1) != mix(5, 1)           # any size of seed


@pytest.mark.parametrize("name", ["chat-sat8.json", "decode16.json"])
def test_neighbouring_requests_differ_in_seed_and_ids(name):
    spec = load(name)
    for s in THOUSAND:
        sch = Schedule(spec, s)
        seeds = [sch.request(k).sampling_seed for k in range(9)]
        assert all(x != y for x, y in zip(seeds, seeds[1:]))
        assert all(0 <= x < 2 ** 30 for x in seeds)
    for s in THOUSAND[::50]:
        sch = Schedule(spec, s)
        heads = [tuple(sch.prompt_ids(k, 50257)[:8]) for k in range(9)]
        assert len(set(heads)) == 9          # no prompt a prefix of the next


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


@pytest.mark.parametrize("cell",
                         [w["name"] for w in MANIFEST.data["workloads"]])
def test_prompt_plus_budget_fits_the_slot(cell):
    """A mix is held to the slot of the configuration its CELL names:
    the rows its longest stated pair writes, against that configuration's
    own ``--max_session_len``. A mix that no cell names has no slot to be
    held to, so every file of ``perfbench/traffic/`` is named by one."""
    w = MANIFEST.workload(cell)
    assert traffic.slot_rows(MANIFEST.traffic(w["traffic"])) <= \
        check.slot_len(MANIFEST.config(w["config"]))
    assert set(FILES) == {c["traffic"] + ".json"
                          for c in MANIFEST.data["workloads"]}


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
