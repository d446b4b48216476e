"""A drive of a family's own, as a later model whose step is not one token
a sequence would bring it: the engine is stepped FOUR ids a session at a
time (``decode_batch`` takes any shared width) and every step is an episode
of its own, at the length the model had been given by then, with four
compared rows; so one session has several episodes of growing length. The
served burst rounds follow as one more episode a session, judged only."""

import numpy as np

from perfbench.harness.drive import server_arg

T = 4


def _sizes(chk, server_args):
    burst = int(server_arg(server_args, "--burst", 0))
    return (int(chk["decode_steps"]),
            int(chk.get("burst_rounds", 1)) if burst else 0, burst)


def rows_needed(chk, server_args, lens, dry):
    steps, rounds, burst = _sizes(chk, server_args)
    return max(lens) + T * steps + rounds * burst + 1


def drive(eng, cfg, chk, server_args, lens, rng, dry):
    steps, rounds, burst = _sizes(chk, server_args)
    seqs = [rng.integers(0, cfg.vocab_size, (n + T * steps + 1,)).astype(
        np.int32) for n in lens]
    fed = rng.integers(0, cfg.vocab_size,
                       (len(lens), max(rounds, 1))).astype(np.int32)
    sids = [f"s{i}" for i in range(len(lens))]

    def last_rows(hidden, end):
        """The last T rows of a pass that ended before position ``end``."""
        logits = np.asarray(eng.logits(hidden[:, -T:]), np.float32)[0]
        return [(end - T + t, logits[t]) for t in range(T)]

    episodes = []
    for i, (sid, seq, n) in enumerate(zip(sids, seqs, lens)):
        h = eng.prefill(sid, seq[None, :n])
        episodes.append({"session": i, "ids": seq[:n],
                         "rows": last_rows(h, n), "judged": []})
    for j in range(steps):
        out = eng.decode_batch({sid: seq[None, n + T * j:n + T * (j + 1)]
                                for sid, seq, n in zip(sids, seqs, lens)})
        for i, (sid, seq, n) in enumerate(zip(sids, seqs, lens)):
            end = n + T * (j + 1)
            episodes.append({"session": i, "ids": seq[:end],
                             "rows": last_rows(out[sid], end), "judged": []})
    pad_to = rows_needed(chk, server_args, lens, dry)
    for i, (sid, seq, n) in enumerate(zip(sids, seqs, lens)):
        consumed = [int(t) for t in seq[:n + T * steps]]
        judged = []
        for k in range(rounds):
            tok = int(seq[n + T * steps]) if k == 0 else int(fed[i, k])
            res = eng.decode_burst({sid: {
                "token": tok, "seed": 0, "budget": burst, "eos": None,
                "generated": (tok,), "temperature": 0.0, "top_p": 1.0,
                "top_k": 0, "repetition_penalty": 1.0}}, burst)
            toks = [int(t) for t in res[sid]["tokens"]]
            judged += [(k, len(consumed) + j, t) for j, t in enumerate(toks)]
            consumed += [tok] + toks[:-1]
        episodes.append({
            "session": i, "rows": [], "judged": judged,
            "ids": np.asarray(consumed + [0] * (pad_to - len(consumed)),
                              np.int32)})
    return episodes
