"""What decides ``correct``: the program's stage engine against the plain
reference, on weights drawn from the seed, at the configuration's published
widths (depth cut as the configuration's ``check`` section says), in a
process of its own that runs after the servers have exited.

Two numbers are compared, each printed beside its limit:

  logit_rel_rms   prefill, then decode steps through the cache, at the
                  served slot count: worst row of ||engine - reference|| /
                  ||reference|| over the logits
  burst_gap       the served burst program, greedy, ``burst_rounds`` rounds
                  a session (each round is fed a fresh token, so a repeat
                  stop ends one round, not the reading): for every token it
                  emitted, how far that token's REFERENCE logit lies under
                  the reference's best one, over the RMS of the row; the
                  MEAN over the emitted tokens (the largest is printed
                  beside it). 0 when the burst picks the reference's
                  argmax; it grows with the SQUARE of the burst program's
                  own logit error (a noisier program flips more near-ties,
                  and wider ones), which is why it is read over hundreds
                  of tokens; large when the burst path reads the wrong
                  cache rows, positions or weights

The reference is the configuration's own module where its file names one
(``"reference"``: ``make_weights`` and ``forward`` with the stock
module's signatures), else ``harness/reference.py``. The program's
configuration is built the way the server builds it, from the
configuration's model arguments through the program's own parser. The
check may run smaller than the cell in what the cell already lists as
reduced (``check.layers``, ``check.model_args``, ``check.reduced_to``);
the ``CHECK`` line prints the sizes it ran at beside the cell's.

``--control`` builds the engine with the configuration's control
(``check.control``), the program's own next quantisation down (``int8``
for a bfloat16 configuration, ``nf4`` for an int8 one). It has to come out
as not correct.

Usage: python -m perfbench.harness.check --config F --traffic F
           --seeds N[,N...] [--control] [--dry-run-cpu]"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import json
import os
import sys
import time

from .manifest import load_module

PKG = "global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def server_arg(config: dict, flag: str, default=None):
    args = config["deployment"]["servers"][0]["args"]
    return args[args.index(flag) + 1] if flag in args else default


def check_lengths(traffic: dict, n: int) -> list:
    """n prompt lengths spread over the cell's own table."""
    table = sorted(set(traffic["prompt_lens"]))
    if n >= len(table):
        return table
    return [table[round(i * (len(table) - 1) / (n - 1))] for i in range(n)]


def reference_of(config: dict):
    """The module that gives ``make_weights`` and ``forward``."""
    if config.get("reference"):
        return load_module(os.path.join(ROOT, config["reference"]))
    from . import reference
    return reference


def program_config(model_args: list):
    """The program's configuration as the server builds it: whatever
    argument states a configuration's share reaches the check this way."""
    main = importlib.import_module(PKG + ".main")
    return main.load_config(main.build_parser().parse_args(list(model_args)))


def run_seed(config: dict, traffic: dict, seed: int, *, control: bool,
             dry: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    t0 = time.time()
    reference = reference_of(config)
    hf_import = importlib.import_module(PKG + ".models.hf_import")
    partition = importlib.import_module(PKG + ".models.partition")
    quant_mod = importlib.import_module(PKG + ".models.quant")
    batching = importlib.import_module(PKG + ".runtime.batching")

    chk = config["check"]
    layers = int(chk["layers"])
    if dry:
        hf, cut = chk["dry_run_hf_config"], {}
        cell_args = check_args = config["dry_run_model_args"]
    else:
        cut = chk.get("reduced_to", {})
        hf = dict(config["hf_config"], **cut)
        cell_args = config["deployment"]["model_args"]
        check_args = chk.get("model_args", cell_args)
    cell_cfg = program_config(cell_args)
    cfg = dataclasses.replace(
        cell_cfg if check_args == cell_args else program_config(check_args),
        num_layers=layers)
    sizes = {"check": dict(cut, layers=layers),
             "cell": dict({k: config["hf_config"][k] for k in cut},
                          layers=cell_cfg.num_layers)}
    if check_args != cell_args:
        sizes["check"]["model_args"] = check_args
        sizes["cell"]["model_args"] = cell_args
    quant = chk["control"] if control else server_arg(config, "--quant",
                                                      "none")
    slots = int(server_arg(config, "--slots", 8))
    burst = int(server_arg(config, "--burst", 0))
    steps = int(chk["decode_steps"])
    rounds = int(chk.get("burst_rounds", 1)) if burst else 0
    table = check_lengths(traffic, 3)   # shortest, middle, longest prompt
    lens = [table[i % len(table)] for i in range(int(chk["sessions"]))]
    if dry:
        lens = [max(4, n // 8) for n in lens]
        burst = min(burst, 4)
    dtype = jnp.bfloat16 if server_arg(
        config, "--dtype", "bfloat16") == "bfloat16" else jnp.float32

    weights = reference.make_weights(hf, layers, seed, dtype)
    params = hf_import.convert_state_dict(cfg, weights, dtype=dtype)
    if quant != "none":
        params = quant_mod.quantize_params(params, quant)
    spec = partition.StagePlan.even(cfg.num_layers, 1).stages[0]
    pad_to = max(lens) + steps + rounds * burst + 1
    eng = batching.BatchedStageExecutor(
        cfg, spec, params, slots=slots,
        max_len=min(pad_to + 8 if dry else int(
            server_arg(config, "--max_session_len", pad_to + 8)), 1024),
        dtype=dtype)

    rng = np.random.default_rng(seed % (1 << 63))
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
    del eng, params

    # The reference: one causal float32 pass per session over everything
    # the engine consumed (the bursts' own tokens included); the rows and
    # the gaps are taken on the device, only they come back.
    n_judge = max(1, rounds * burst)

    @jax.jit
    def ref_stats(w, ids, row_pos, tok_pos, toks):
        logits = reference.forward(hf, layers, w, ids)
        at = logits[tok_pos]
        took = jnp.take_along_axis(at, toks[:, None], -1)[:, 0]
        gap = (at.max(-1) - took) / jnp.sqrt((at * at).mean(-1))
        return logits[row_pos], gap

    worst_rms, sum_rms, n_rows, finite = 0.0, 0.0, 0, True
    worst_gap = 0.0
    by_round = [[0.0, 0] for _ in range(max(rounds, 1))]
    for ids, rows, jud in zip(consumed, got_rows, judged):
        ids = np.asarray(ids + [0] * (pad_to - len(ids)), np.int32)
        tok_pos = np.zeros(n_judge, np.int32)   # causal: the pad changes no row
        toks = np.zeros(n_judge, np.int32)
        for j, (_, pos, tok) in enumerate(jud):
            tok_pos[j], toks[j] = pos, tok
        want, gaps = ref_stats(weights, jnp.asarray(ids),
                               jnp.asarray([pos for pos, _ in rows]),
                               jnp.asarray(tok_pos), jnp.asarray(toks))
        want, gaps = np.asarray(want, np.float32), np.asarray(gaps)
        for (_, got), ref_row in zip(rows, want):
            finite = finite and bool(np.isfinite(got).all())
            rms = float(np.linalg.norm(got - ref_row)
                        / np.linalg.norm(ref_row))
            worst_rms, sum_rms, n_rows = max(worst_rms, rms), sum_rms + rms, \
                n_rows + 1
        for (k, _, _), gap in zip(jud, gaps):
            worst_gap = max(worst_gap, float(gap))
            by_round[k][0] += float(gap)
            by_round[k][1] += 1
    n_gap = sum(n for _, n in by_round)
    mean_gap = sum(s for s, _ in by_round) / n_gap if n_gap else 0.0
    lim = chk["limits"]
    dev = jax.devices()[0]
    ok = (finite and worst_rms <= lim["logit_rel_rms"]
          and mean_gap <= lim["burst_gap"]
          and (not rounds or n_gap >= len(lens)))
    return {"seed": seed, "control": chk["control"] if control else None,
            "quant": quant, "layers": layers, "sizes": sizes, "slots": slots,
            "prompt_lens": lens, "logit_rows": n_rows,
            "burst_rounds": rounds, "burst_tokens": n_gap,
            "logit_rel_rms": worst_rms,
            "logit_rel_rms_limit": lim["logit_rel_rms"],
            "logit_rel_rms_mean": sum_rms / n_rows,
            "burst_gap": mean_gap, "burst_gap_limit": lim["burst_gap"],
            "burst_gap_max": worst_gap,
            "burst_gap_by_round": [[round(s, 6), n] for s, n in by_round],
            "finite": finite, "pass": ok,
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())},
            "s": time.time() - t0}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seeds", required=True,
                    type=lambda s: [int(x) for x in s.split(",")])
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--dry-run-cpu", action="store_true")
    args = ap.parse_args()
    with open(args.config) as f:
        config = json.load(f)
    with open(args.traffic) as f:
        traffic = json.load(f)
    platform = importlib.import_module(PKG + ".utils.platform")
    platform.compile_cache_dir()
    ok = True
    for seed in args.seeds:
        res = run_seed(config, traffic, seed, control=args.control,
                       dry=args.dry_run_cpu)
        ok = ok and res["pass"]
        print("CHECK " + json.dumps(res), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
