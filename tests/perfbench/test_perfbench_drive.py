"""The check's drive comes with the family: what every family shares
(``check.build``, ``check.score``) against what drives an engine (a file
with one contract, ``harness/drive.py`` the stock one). Shown three ways:
the stock drive's episodes are what the check computed before the split
(the code it had, kept here as the oracle); ``score`` compiles one
reference program a distinct length and refuses episodes it cannot score;
and on the throw-away root of ``test_perfbench_family.py`` a drive brought
as a FILE (``fixtures/family/perfbench/drives``: four ids a step, an
episode a step) decides the check."""

import numpy as np
import pytest

from perfbench.harness import check, drive as stock_drive
from perfbench.harness.manifest import Manifest
from test_perfbench_family import (NO_BIAS, PLAIN, ROOT, add_config,  # noqa: F401
                                   check_process, family_root, run_check)

STEPS4 = "perfbench/drives/steps4.py"
SHIFTED = "perfbench/drives/steps4_shifted.py"
NO_ROW = "perfbench/drives/steps4_no_row.py"


def drive_before_the_split(eng, cfg, chk, server_args, lens, rng, dry):
    """``run_seed`` of the parent commit between "the engine exists" and
    its reference passes, as it stood: what each session consumed, the
    rows it compared and where it judged each emitted token."""
    burst = int(stock_drive.server_arg(server_args, "--burst", 0))
    steps = int(chk["decode_steps"])
    rounds = int(chk.get("burst_rounds", 1)) if burst else 0
    if dry:
        burst = min(burst, 4)
    pad_to = max(lens) + steps + rounds * burst + 1
    seqs = [rng.integers(0, cfg.vocab_size, (n + steps + 1,)).astype(np.int32)
            for n in lens]
    fed = rng.integers(0, cfg.vocab_size,
                       (len(lens), max(rounds, 1))).astype(np.int32)
    sids = [f"s{i}" for i in range(len(lens))]

    def logits_of(hidden):
        return np.asarray(eng.logits(hidden), np.float32).reshape(-1)

    got_rows = [[] for _ in lens]
    for sid, seq, n, rows in zip(sids, seqs, lens, got_rows):
        h = eng.prefill(sid, seq[None, :n])
        rows.append((n - 1, logits_of(h[:, -1:])))
    for j in range(steps):
        out = eng.decode_batch({sid: seq[None, n + j:n + j + 1]
                                for sid, seq, n in zip(sids, seqs, lens)})
        for sid, n, rows in zip(sids, lens, got_rows):
            rows.append((n + j, logits_of(out[sid])))
    consumed = [[int(t) for t in seq[:n + steps]]
                for seq, n in zip(seqs, lens)]
    judged = [[] for _ in lens]
    for k in range(rounds):
        entries = {}
        for i, (sid, seq, n) in enumerate(zip(sids, seqs, lens)):
            tok = int(seq[n + steps]) if k == 0 else int(fed[i, k])
            gen = (tuple(int(t) for t in seq[n:n + steps + 1]) if k == 0
                   else (tok,))
            entries[sid] = {"token": tok, "seed": 0, "budget": burst,
                            "eos": None, "generated": gen,
                            "temperature": 0.0, "top_p": 1.0, "top_k": 0,
                            "repetition_penalty": 1.0}
        res = eng.decode_burst(entries, burst)
        for i, sid in enumerate(sids):
            toks = [int(t) for t in res[sid]["tokens"]]
            start = len(consumed[i])
            judged[i] += [(k, start + j, t) for j, t in enumerate(toks)]
            consumed[i] += [entries[sid]["token"]] + toks[:-1]
    return [np.asarray(ids + [0] * (pad_to - len(ids)), np.int32)
            for ids in consumed], got_rows, judged, pad_to


@pytest.mark.parametrize("config,traffic,seed", [
    ("gpt2-xl", "chat-sat8", 11), ("gpt2-xl", "chat-sat8", 2 ** 31 + 129),
    ("qwen2-7b-int8", "decode16", 11),
    ("qwen2-7b-int8", "decode16", 2 ** 31 + 129)])
def test_the_stock_drive_gives_what_the_check_computed_before(config, traffic,
                                                              seed):
    man = Manifest(ROOT)
    body = man.config(config)
    assert "drive" not in body["check"]
    mod, file = check.drive_of(body)
    assert mod is stock_drive and file == "perfbench/harness/drive.py"
    b = check.build(body, man.traffic(traffic), seed, control=False, dry=True)
    args = b["server_args"]     # the rehearsal's: the burst cut to 4 ticks
    assert b["burst"] == 4 == int(stock_drive.server_arg(args, "--burst"))
    want_ids, want_rows, want_judged, pad_to = drive_before_the_split(
        b["eng"], b["cfg"], body["check"], check.server_args(body),
        b["lens"], np.random.default_rng(seed), True)
    # the same engine again: a prefill restarts its session
    episodes = b["drive"](b["eng"], b["cfg"], body["check"], args, b["lens"],
                          np.random.default_rng(seed), True)
    assert pad_to == stock_drive.rows_needed(body["check"], args, b["lens"],
                                             True)
    assert [ep["session"] for ep in episodes] == list(range(len(b["lens"])))
    for ep, ids, rows, judged in zip(episodes, want_ids, want_rows,
                                     want_judged):
        assert ep["ids"].dtype == np.int32 and np.array_equal(ep["ids"], ids)
        assert ep["judged"] == judged and judged
        assert [pos for pos, _ in ep["rows"]] == [pos for pos, _ in rows]
        assert len(rows) == 1 + body["check"]["decode_steps"]
        for (_, got), (_, want) in zip(ep["rows"], rows):
            assert got.dtype == np.float32 and np.array_equal(got, want)
    assert b["rounds"] == body["check"].get("burst_rounds", 1)
    assert {k for ep in episodes for k, _, _ in ep["judged"]} \
        == set(range(b["rounds"]))


class TableReference:
    """A reference whose pass is a look-up: row ``p`` of the logits is row
    ``ids[p]`` of the weights. ``traces`` counts how often it was COMPILED
    (the body of a jitted function runs once a distinct shape)."""

    def __init__(self):
        self.traces = 0

    def forward(self, hf, layers, weights, ids):
        self.traces += 1
        return weights[ids]


def table_episode(table, session, length, rows, judged, off=0.0):
    ids = (np.arange(length) * 5 + session) % len(table)
    return {"session": session, "ids": ids.astype(np.int32),
            "rows": [(p, table[ids[p]] + off) for p in rows],
            "judged": [(k, p, tok) for k, p, tok in judged]}


def test_episodes_of_one_length_share_one_compiled_reference_program():
    rng = np.random.default_rng(3)
    table = rng.normal(size=(16, 16)).astype(np.float32)
    best = table.argmax(-1)
    second = np.argsort(table, -1)[:, -2]
    ref = TableReference()
    eps = [table_episode(table, 0, 8, [3, 7], []),
           table_episode(table, 1, 12, [11], []),
           table_episode(table, 0, 12, [0, 5, 9], [], off=0.25),
           table_episode(table, 1, 8, [], []),
           table_episode(table, 0, 12, [], []),
           table_episode(table, 1, 12, [], [])]
    # round 0 takes the reference's best token, round 1 its second best
    for ep in eps[3:]:
        ids = ep["ids"]
        ep["judged"] = [(0, 2, int(best[ids[2]])), (1, 6, int(second[ids[6]]))]
    got = check.score(ref, {}, 0, table, eps, sessions=2, rounds=2,
                      drive_file="a/drive.py")
    assert ref.traces == 2 == got["reference_programs"]
    assert got["episodes"] == 6 and got["logit_rows"] == 6
    assert got["burst_tokens"] == 6 and got["finite"]
    off = [np.linalg.norm(np.full(16, 0.25, np.float32))
           / np.linalg.norm(table[eps[2]["ids"][p]]) for p in (0, 5, 9)]
    assert got["logit_rel_rms"] == pytest.approx(max(off), rel=1e-5)
    assert got["logit_rel_rms_mean"] == pytest.approx(sum(off) / 6, rel=1e-5)
    gaps = []
    for ep in eps[3:]:
        row = table[ep["ids"][6]]
        gaps.append((row.max() - row[second[ep["ids"][6]]])
                    / np.sqrt((row * row).mean()))
    assert got["burst_gap"] == pytest.approx(sum(gaps) / 6, rel=1e-5)
    assert got["burst_gap_max"] == pytest.approx(max(gaps), rel=1e-5)
    assert [n for _, n in got["burst_gap_by_round"]] == [3, 3]
    assert got["burst_gap_by_round"][0][0] == 0.0


def _sound(table):
    return [table_episode(table, 0, 8, [7], [(0, 3, 1), (1, 4, 1)]),
            table_episode(table, 1, 8, [7], [(0, 3, 1), (1, 4, 1)])]


def _edit(n, **change):
    def edit(eps):
        eps[n].update(change)
    return edit


# what a drive got wrong -> (edit of two sound episodes, what the refusal says)
UNSCORABLE = {
    "no episode": (lambda eps: eps.clear(), "returned no episode"),
    "a key is left out": (lambda eps: eps[0].pop("judged"), "lacks judged"),
    "ids are no whole numbers": (
        _edit(0, ids=np.zeros(8, np.float32)), "no row of whole numbers"),
    "a session the check does not have": (
        _edit(1, session=2), "session 2 is none of the check's 2"),
    "a session without a compared row": (
        _edit(1, rows=[]), "session 1 has no compared row"),
    "a burst round of a session without a judged token": (
        _edit(0, judged=[(0, 3, 1)]),
        "burst round 1 of session 0 has no judged token"),
    "a row beyond the pass": (
        lambda eps: eps[0]["rows"].append((8, eps[0]["rows"][0][1])),
        "position 8 of 8"),
    "a token judged in a round the check does not run": (
        lambda eps: eps[1]["judged"].append((2, 3, 1)), "round 2 of 2"),
    "a row of another width than the reference's": (
        lambda eps: eps[0].update(rows=[(7, np.zeros(5, np.float32))]),
        "has 5 logits, the reference's 16"),
}


@pytest.mark.parametrize("what", sorted(UNSCORABLE))
def test_episodes_that_cannot_be_scored_are_refused(what):
    edit, says = UNSCORABLE[what]
    table = np.random.default_rng(5).normal(size=(16, 16)).astype(np.float32)
    eps = _sound(table)
    check.score(TableReference(), {}, 0, table, eps, sessions=2, rounds=2,
                drive_file="a/drive.py")       # sound as they stand
    edit(eps)
    with pytest.raises(check.DriveRefused, match="drive a/drive.py: .*"
                       + says):
        check.score(TableReference(), {}, 0, table, eps, sessions=2,
                    rounds=2, drive_file="a/drive.py")


@pytest.mark.parametrize("drive,module,control,passes", [
    (STEPS4, PLAIN, False, True), (STEPS4, NO_BIAS, False, False),
    (STEPS4, PLAIN, True, False), (SHIFTED, PLAIN, False, False)])
def test_a_drive_brought_as_a_file_decides(family_root, drive, module,
                                           control, passes):
    def edit(body):
        body["reference"] = module
        body["check"]["drive"] = drive
    rel = add_config(family_root, "qwen2-steps4", edit)
    Manifest(family_root).validate()
    rc, row = run_check(family_root, rel, *(["--control"] if control else []))
    assert (rc == 0) is passes and row["pass"] is passes and row["finite"]
    assert row["drive"] == drive
    # 3 sessions x (the prefill + 2 steps of four ids, four rows each, then
    # the burst rounds); prompts 6, 14, 22, so the passes of 14 and of 22
    # ids are shared between sessions and the burst passes are of one length
    assert row["episodes"] == 12 and row["logit_rows"] == 36
    assert row["reference_programs"] == 8
    assert row["burst_rounds"] == 3 and row["burst_tokens"] >= 9
    if drive == SHIFTED or module == NO_BIAS:
        assert row["logit_rel_rms"] > 0.3         # another row, another model
    else:       # the limit stands between the engine and its control
        assert (row["logit_rel_rms"] < 0.008) is passes
        assert (row["logit_rel_rms"] > 0.015) is not passes


def test_a_drive_that_leaves_a_session_without_a_row_is_not_scored(
        family_root):
    rel = add_config(family_root, "qwen2-steps4", lambda body: body[
        "check"].update(drive=NO_ROW))
    Manifest(family_root).validate()
    res, rows = check_process(family_root, rel)
    assert res.returncode != 0 and not rows
    assert (f"DriveRefused: drive {NO_ROW}: session 2 has no compared row"
            in res.stderr[-3000:])
