"""What decides ``correct``: the program's stage engine against the plain
reference, on weights drawn from the seed, at the configuration's published
widths (depth cut as the configuration's ``check`` section says), in a
process of its own that runs after the servers have exited.

Two numbers are compared, each printed beside its limit:

  logit_rel_rms   the rows a drive had the engine compute (the stock
                  one: prefill, then decode steps through the cache, at
                  the served slot count): worst row of ||engine -
                  reference|| / ||reference|| over the logits
  burst_gap       the served burst program, greedy, ``burst_rounds`` rounds
                  a session (the stock drive feeds each round a fresh
                  token, so a repeat stop ends one round, not the reading):
                  for every token it emitted, how far that token's
                  REFERENCE logit lies under the reference's best one,
                  over the RMS of the row; the
                  MEAN over the emitted tokens (the largest is printed
                  beside it). 0 when the burst picks the reference's
                  argmax; it grows with the SQUARE of the burst program's
                  own logit error (a noisier program flips more near-ties,
                  and wider ones), which is why it is read over hundreds
                  of tokens; large when the burst path reads the wrong
                  cache rows, positions or weights

What every family shares is here: the program's configuration, the seeded
checkpoint and its conversion, the control, the engine's construction
(``build``), the reference passes, the two numbers and the ``CHECK`` line
(``score``, ``run_seed``). What DRIVES the engine, everything between "the
engine exists" and "here is what it consumed and produced", is a file of
its own with one contract (``harness/drive.py`` states it and is the stock
drive; a configuration whose step is not one token a sequence names its
own, ``check.drive``): it returns EPISODES, one pass of the reference
each, and computes no number. ``score`` refuses a drive that leaves a
session without a compared row or, where the configuration serves bursts,
a burst round of a session without a judged token.

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

from . import drive as stock_drive
from .drive import server_arg
from .manifest import load_module

PKG = "global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
STOCK_DRIVE = "perfbench/harness/drive.py"
EPISODE_KEYS = ("session", "ids", "rows", "judged")


class DriveRefused(ValueError):
    """A drive's episodes cannot be scored: nothing of them is."""


def server_args(config: dict) -> list:
    return config["deployment"]["servers"][0]["args"]


def slot_len(config: dict) -> int:
    """A cell's slot length: what its configuration's first server is
    started with. The check's engine and the slot test read it here, and
    no file of the harness holds a row count of its own."""
    rows = server_arg(server_args(config), "--max_session_len")
    if rows is None:
        raise ValueError(f"config {config.get('name')}: its first server "
                         f"states no --max_session_len")
    return int(rows)


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


def drive_of(config: dict):
    """The module that gives ``drive`` (and perhaps ``rows_needed``), and
    its file as the ``CHECK`` line names it."""
    rel = config["check"].get("drive")
    if rel:
        return load_module(os.path.join(ROOT, rel)), rel
    return stock_drive, STOCK_DRIVE


def program_config(model_args: list):
    """The program's configuration as the server builds it: whatever
    argument states a configuration's share reaches the check this way."""
    main = importlib.import_module(PKG + ".main")
    return main.load_config(main.build_parser().parse_args(list(model_args)))


def build(config: dict, traffic: dict, seed: int, *, control: bool,
          dry: bool) -> dict:
    """Everything up to "the engine exists": the sizes the check runs at,
    the seeded checkpoint, its conversion (the control's quantisation
    where asked) and the program's engine, its slots as long as the
    SERVED ones (so the check runs the shapes the servers ran, and a fault
    that depends on a slot's length is in what decides ``correct``); the
    rehearsal's are the drive's ``rows_needed`` and a margin. A drive that
    needs more rows than the cell serves is refused before anything is
    made."""
    import jax.numpy as jnp

    reference = reference_of(config)
    drive_mod, drive_file = drive_of(config)
    hf_import = importlib.import_module(PKG + ".models.hf_import")
    partition = importlib.import_module(PKG + ".models.partition")
    quant_mod = importlib.import_module(PKG + ".models.quant")
    batching = importlib.import_module(PKG + ".runtime.batching")

    chk, args = config["check"], server_args(config)
    burst = int(server_arg(args, "--burst", 0))
    # a configuration that serves bursts has every round judged
    rounds = int(chk.get("burst_rounds", 1)) if burst else 0
    if dry and burst > 4:       # the rehearsal's bursts are 4 ticks long
        args = list(args)
        args[args.index("--burst") + 1] = "4"
        burst = 4
    slots = int(server_arg(args, "--slots", 8))
    table = check_lengths(traffic, 3)   # shortest, middle, longest prompt
    lens = [table[i % len(table)] for i in range(int(chk["sessions"]))]
    if dry:
        lens = [max(4, n // 8) for n in lens]
    rows = getattr(drive_mod, "rows_needed", stock_drive.rows_needed)(
        chk, args, lens, dry)
    max_len = rows + 8 if dry else slot_len(config)
    if rows > max_len:
        raise ValueError(f"config {config.get('name')}: the check needs "
                         f"{rows} rows, the cell serves {max_len}")
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
    if not dry:
        sizes["check"]["max_session_len"] = \
            sizes["cell"]["max_session_len"] = max_len
    if check_args != cell_args:
        sizes["check"]["model_args"] = check_args
        sizes["cell"]["model_args"] = cell_args
    quant = chk["control"] if control else server_arg(args, "--quant", "none")
    dtype = jnp.bfloat16 if server_arg(
        args, "--dtype", "bfloat16") == "bfloat16" else jnp.float32

    weights = reference.make_weights(hf, layers, seed, dtype)
    params = hf_import.convert_state_dict(cfg, weights, dtype=dtype)
    if quant != "none":
        params = quant_mod.quantize_params(params, quant)
    spec = partition.StagePlan.even(cfg.num_layers, 1).stages[0]
    eng = batching.BatchedStageExecutor(cfg, spec, params, slots=slots,
                                        max_len=max_len, dtype=dtype)
    return {"eng": eng, "cfg": cfg, "reference": reference, "hf": hf,
            "layers": layers, "weights": weights, "lens": lens,
            "drive": drive_mod.drive, "drive_file": drive_file,
            "sizes": sizes, "quant": quant, "slots": slots,
            "server_args": args, "rounds": rounds, "burst": burst}


def _refuse_unless_scorable(episodes, sessions: int, rounds: int,
                            drive_file: str) -> list:
    """The episodes with ``ids`` as int32 arrays, or ``DriveRefused``."""
    import numpy as np

    def refuse(why):
        raise DriveRefused(f"drive {drive_file}: {why}")

    if not isinstance(episodes, list) or not episodes:
        refuse("returned no episode")
    out, has_row, has_judged = [], set(), set()
    for n, ep in enumerate(episodes):
        missing = [k for k in EPISODE_KEYS if k not in ep]
        if missing:
            refuse(f"episode {n} lacks {' and '.join(missing)}")
        ids = np.asarray(ep["ids"])
        if ids.ndim != 1 or ids.size == 0 or ids.dtype.kind not in "iu":
            refuse(f"episode {n}: ids are no row of whole numbers")
        i = ep["session"]
        if not (isinstance(i, (int, np.integer)) and 0 <= i < sessions):
            refuse(f"episode {n}: session {i!r} is none of the check's "
                   f"{sessions}")
        i = int(i)
        for pos, _ in ep["rows"]:
            if not 0 <= pos < ids.size:
                refuse(f"episode {n}: a compared row at position {pos} of "
                       f"{ids.size}")
        for k, pos, _ in ep["judged"]:
            if not (0 <= k < rounds and 0 <= pos < ids.size):
                refuse(f"episode {n}: a token judged in round {k} of "
                       f"{rounds} at position {pos} of {ids.size}")
            has_judged.add((i, k))
        if ep["rows"]:
            has_row.add(i)
        out.append(dict(ep, ids=ids.astype(np.int32)))
    for i in range(sessions):
        if i not in has_row:
            refuse(f"session {i} has no compared row")
        for k in range(rounds):
            if (i, k) not in has_judged:
                refuse(f"burst round {k} of session {i} has no judged token")
    return out


def score(reference, hf: dict, layers: int, weights, episodes, *,
          sessions: int, rounds: int, drive_file: str,
          judged_cap: int = 0) -> dict:
    """The two numbers, from a drive's episodes: one float32 pass of the
    reference an episode over the ids the model was given (one compiled
    program a distinct length; the compared rows and the gaps are taken on
    the device, only they come back). The judged tokens of an episode are
    padded to ``judged_cap`` (rounds x burst: what a session can emit), a
    size the configuration fixes, so that every seed runs the SAME program
    (on the TPU a row's mean of squares moves in its ninth digit with the
    number of rows gathered beside it) and finds it in the compile cache."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    episodes = _refuse_unless_scorable(episodes, sessions, rounds, drive_file)
    n_rows = max(len(ep["rows"]) for ep in episodes)
    n_judge = max(1, judged_cap, max(len(ep["judged"]) for ep in episodes))

    @jax.jit
    def ref_stats(w, ids, row_pos, tok_pos, toks):
        logits = reference.forward(hf, layers, w, ids)
        at = logits[tok_pos]
        took = jnp.take_along_axis(at, toks[:, None], -1)[:, 0]
        gap = (at.max(-1) - took) / jnp.sqrt((at * at).mean(-1))
        return logits[row_pos], gap

    worst_rms, sum_rms, n_compared, finite = 0.0, 0.0, 0, True
    worst_gap = 0.0
    by_round = [[0.0, 0] for _ in range(max(rounds, 1))]
    for n, ep in enumerate(episodes):
        rows, jud = ep["rows"], ep["judged"]
        row_pos = np.zeros(n_rows, np.int32)    # the pad's rows are not read
        row_pos[:len(rows)] = [pos for pos, _ in rows]
        tok_pos = np.zeros(n_judge, np.int32)
        toks = np.zeros(n_judge, np.int32)
        for j, (_, pos, tok) in enumerate(jud):
            tok_pos[j], toks[j] = pos, tok
        want, gaps = ref_stats(weights, jnp.asarray(ep["ids"]),
                               jnp.asarray(row_pos), jnp.asarray(tok_pos),
                               jnp.asarray(toks))
        want, gaps = np.asarray(want, np.float32), np.asarray(gaps)
        for (pos, got), ref_row in zip(rows, want):
            got = np.asarray(got, np.float32).reshape(-1)
            if got.shape != ref_row.shape:
                raise DriveRefused(
                    f"drive {drive_file}: episode {n}: the row at position "
                    f"{pos} has {got.size} logits, the reference's "
                    f"{ref_row.size}")
            finite = finite and bool(np.isfinite(got).all())
            rms = float(np.linalg.norm(got - ref_row)
                        / np.linalg.norm(ref_row))
            worst_rms, sum_rms, n_compared = max(worst_rms, rms), \
                sum_rms + rms, n_compared + 1
        for (k, _, _), gap in zip(jud, gaps):
            worst_gap = max(worst_gap, float(gap))
            by_round[k][0] += float(gap)
            by_round[k][1] += 1
    n_gap = sum(n for _, n in by_round)
    return {"episodes": len(episodes),
            "reference_programs": len({ep["ids"].size for ep in episodes}),
            "logit_rows": n_compared, "burst_tokens": n_gap,
            "logit_rel_rms": worst_rms,
            "logit_rel_rms_mean": sum_rms / n_compared,
            "burst_gap": sum(s for s, _ in by_round) / n_gap if n_gap
            else 0.0,
            "burst_gap_max": worst_gap,
            "burst_gap_by_round": [[round(s, 6), n] for s, n in by_round],
            "finite": finite}


def run_seed(config: dict, traffic: dict, seed: int, *, control: bool,
             dry: bool) -> dict:
    import jax
    import numpy as np

    t0 = time.time()
    chk = config["check"]
    b = build(config, traffic, seed, control=control, dry=dry)
    episodes = b["drive"](b.pop("eng"), b["cfg"], chk, b["server_args"],
                          b["lens"], np.random.default_rng(seed % (1 << 63)),
                          dry)
    # the engine and its converted tree are freed before the reference runs
    got = score(b["reference"], b["hf"], b["layers"], b["weights"], episodes,
                sessions=len(b["lens"]), rounds=b["rounds"],
                drive_file=b["drive_file"],
                judged_cap=b["rounds"] * b["burst"])
    lim = chk["limits"]
    dev = jax.devices()[0]
    ok = (got["finite"] and got["logit_rel_rms"] <= lim["logit_rel_rms"]
          and got["burst_gap"] <= lim["burst_gap"])
    return {"seed": seed, "control": chk["control"] if control else None,
            "quant": b["quant"], "layers": b["layers"], "sizes": b["sizes"],
            "slots": b["slots"], "prompt_lens": b["lens"],
            "drive": b["drive_file"], "burst_rounds": b["rounds"], **got,
            "logit_rel_rms_limit": lim["logit_rel_rms"],
            "burst_gap_limit": lim["burst_gap"], "pass": ok,
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
