"""The stock drive: what the check does to the program's stage engine
between "the engine exists" and "here is what it consumed and produced",
for a model whose step is one token a sequence.

A drive is a file that defines

  drive(eng, cfg, chk, server_args, lens, rng, dry) -> [episode, ...]

``eng`` is the engine the harness built (the program's
``BatchedStageExecutor``), ``cfg`` the program's configuration, ``chk``
the configuration file's ``check`` section, ``server_args`` the first
server's argument list as the check runs it (the CPU rehearsal's burst cut
to 4 ticks), ``lens`` one prompt length a session (taken by the harness
from the cell's traffic), ``rng`` a numpy generator seeded from the run's
seed, ``dry`` whether this is the CPU rehearsal (for a cut of a drive's
own; this file makes none). An EPISODE is one pass of the
reference, a dict of

  session   which of the check's sessions (an index into ``lens``)
  ids       int32: every id the model was GIVEN in that pass, at its
            position (ids that stand for a mask included), already at the
            length the pass is to run at
  rows      [(position, float32 logits)]: what the engine returned for
            that position; the harness compares each with the reference's
            row at it (``logit_rel_rms``)
  judged    [(round, position, token)]: a token the served burst program
            emitted, judged on the reference's row AT that position
            (``burst_gap``)

A drive computes no number: the harness runs the reference once an episode
and takes every statistic itself. A drive may call the program's engine;
the configuration's reference may not. A drive may also define
``rows_needed(chk, server_args, lens, dry)``, the cache rows a session
needs: the harness refuses a drive that needs more than the cell's slots
hold (the engine's are as long as the served ones; the CPU rehearsal's
are sized by it); without it the harness takes this file's.

This one: prefill, ``decode_steps`` single-id steps through the cache at
the served slot count, then ``burst_rounds`` greedy rounds of the served
burst program, each fed a fresh token (so a repeat stop ends one round,
not the reading). One episode a session: everything the engine consumed,
padded with zeros to ``rows_needed`` (causal: the pad changes no row)."""

from __future__ import annotations

import numpy as np


def server_arg(args: list, flag: str, default=None):
    return args[args.index(flag) + 1] if flag in args else default


def _sizes(chk: dict, server_args: list):
    burst = int(server_arg(server_args, "--burst", 0))
    return (int(chk["decode_steps"]),
            int(chk.get("burst_rounds", 1)) if burst else 0, burst)


def rows_needed(chk: dict, server_args: list, lens: list, dry: bool) -> int:
    steps, rounds, burst = _sizes(chk, server_args)
    return max(lens) + steps + rounds * burst + 1


def drive(eng, cfg, chk, server_args, lens, rng, dry) -> list:
    steps, rounds, burst = _sizes(chk, server_args)
    pad_to = rows_needed(chk, server_args, lens, dry)
    seqs = [rng.integers(0, cfg.vocab_size, (n + steps + 1,)).astype(np.int32)
            for n in lens]
    # the token each later burst round is fed (round 0 takes the sequence's
    # own next one): fresh, so a greedy repeat ends a round and no more
    fed = rng.integers(0, cfg.vocab_size,
                       (len(lens), max(rounds, 1))).astype(np.int32)
    sids = [f"s{i}" for i in range(len(lens))]

    def logits_of(hidden):
        return np.asarray(eng.logits(hidden), np.float32).reshape(-1)

    got_rows = [[] for _ in lens]       # per session: (position, logits)
    for sid, seq, n, rows in zip(sids, seqs, lens, got_rows):
        h = eng.prefill(sid, seq[None, :n])
        rows.append((n - 1, logits_of(h[:, -1:])))
    for j in range(steps):
        out = eng.decode_batch({sid: seq[None, n + j:n + j + 1]
                                for sid, seq, n in zip(sids, seqs, lens)})
        for sid, n, rows in zip(sids, lens, got_rows):
            rows.append((n + j, logits_of(out[sid])))
    # Everything the engine consumed, per session, and where each emitted
    # token is judged: the reference's row at the position of the token
    # consumed just before it.
    consumed = [[int(t) for t in seq[:n + steps]]
                for seq, n in zip(seqs, lens)]
    judged = [[] for _ in lens]         # per session: (round, position, token)
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
    return [{"session": i,
             "ids": np.asarray(ids + [0] * (pad_to - len(ids)), np.int32),
             "rows": rows, "judged": jud}
            for i, (ids, rows, jud) in enumerate(zip(consumed, got_rows,
                                                     judged))]
