"""One general traffic generator, driven by a data file.

A traffic file gives: ``kind`` (``closed``: N sessions, each sends its next
request when the last one ended; ``open``: requests fall due at a fixed
rate whether or not earlier ones have ended), the length tables, the
sampling parameters and the route. The file STATES its work: pair ``j`` of
a cycle is ``(prompt_lens[j], token_budgets[pairing[j]])``; without a
``pairing`` key, ``j % len(token_budgets)``. Every seed offers the SAME
multiset of (prompt length, token budget) PAIRS a cycle: the seed gives the
order of the pairs, the token ids, the sampling seeds and (Poisson) the due
times, and nothing else. "Work" means the pairs and not the two tables
apart: a tick's attention reads prompt + tokens so far, so which prompt
meets which budget is part of what a window costs. The same seed gives the
same schedule, token ids and sampling seeds."""

from __future__ import annotations

import dataclasses
import math
import random
from typing import List, Optional, Sequence, Tuple

KINDS = ("closed", "open")
ARRIVALS = ("uniform", "poisson")


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    prompt_len: int
    budget: int            # output tokens asked for
    sampling_seed: int     # < 2**30: the program keeps seeds in int32
    due_s: Optional[float]  # open loop: seconds after the start; else None


_M64 = (1 << 64) - 1


def _mix(seed: int, *parts: int) -> int:
    """A deterministic 64-bit mix of whole numbers (any size of seed).
    Every step is a bijection of the 64-bit state and no bit is dropped,
    so two lists of parts that differ in their last part never meet; the
    closing rounds spread a last part that moved by one over every bit
    (``% (1 << 30)`` keeps the low ones)."""
    x = (seed & _M64) ^ (seed >> 64)
    for p in parts:
        x = (x * 6364136223846793005 + p + 1442695040888963407) & _M64
        x ^= x >> 29
    x = (x * 0xBF58476D1CE4E5B9) & _M64
    x ^= x >> 32
    x = (x * 0x94D049BB133111EB) & _M64
    return x ^ (x >> 29)


def pairs_of(spec: dict) -> List[Tuple[int, int]]:
    """The (prompt length, token budget) pairs of one cycle, as the file
    states them."""
    prompts, budgets = list(spec["prompt_lens"]), list(spec["token_budgets"])
    if not prompts or not budgets:
        raise ValueError("empty length table")
    pairing = spec.get("pairing")
    if pairing is None:
        pairing = [j % len(budgets) for j in range(len(prompts))]
    if len(pairing) != len(prompts) or not all(
            isinstance(i, int) and 0 <= i < len(budgets) for i in pairing):
        raise ValueError(f"pairing {pairing!r}: one index into "
                         f"token_budgets for every entry of prompt_lens")
    return [(n, budgets[i]) for n, i in zip(prompts, pairing)]


# The program reads a slot by blocks of this many rows
# (``runtime.batching.ATTN_BLOCK``): a served slot is a whole number of them.
SLOT_BLOCK = 128


def slot_rows(spec: dict) -> int:
    """What a mix needs of a slot: the most cache rows the program writes
    for a session this file can send. A session of the pair (P, B) writes
    P rows at its prefill and one row for every token FED BACK, B - 1 of
    them: the last token is emitted and never written. A last burst
    writes nothing past its budget (``_burst_prep`` cuts a burst to
    ``min(budget, ticks)``, the client asks ``min(burst, tokens left)``,
    and ``_burst_collect`` grows a slot by the tokens emitted and no
    more), and the engine admits a burst where ``length + budget`` is at
    most the slot: P + B - 1 at the last one. The load generator's first
    warm-up request (``loadgen.Load.warm``) is the shortest prompt with a
    budget of burst + 2. It is the longest STATED pair that counts, not
    max + max of the two tables, which no session sends."""
    pairs = pairs_of(spec)
    burst = max(1, int(spec.get("route", {}).get("burst", 0)))
    return max(max(p + b - 1 for p, b in pairs),
               min(p for p, _ in pairs) + burst + 1)


def least_slot(spec: dict) -> int:
    """The shortest slot, in whole blocks, that holds the mix."""
    return -(-slot_rows(spec) // SLOT_BLOCK) * SLOT_BLOCK


def affine_pairings(prompts: Sequence[int], budgets: Sequence[int]
                    ) -> List[Tuple[int, int, List[int]]]:
    """The family a traffic file's ``pairing`` is taken from, in the order
    the rule tries it: ``j -> ((a * j + c) mod n) mod m`` for ``a`` coprime
    to ``n`` (n prompts, m budgets, both tables ascending), nearest first
    to what independent shuffles of the two tables would offer,
    sum(prompts) x mean(budget); ties to the smallest ``(a, c)``. A map
    another ``(a, c)`` gave before is not listed again."""
    n, m = len(prompts), len(budgets)
    if list(prompts) != sorted(prompts) or list(budgets) != sorted(budgets):
        raise ValueError("the rule reads both tables ascending")
    ranked = []
    for a in range(1, max(n, 2)):
        if math.gcd(a, n) != 1:
            continue
        for c in range(n):
            pairing = [((a * j + c) % n) % m for j in range(n)]
            work = sum(p * budgets[i] for p, i in zip(prompts, pairing))
            ranked.append((abs(m * work - sum(prompts) * sum(budgets)),
                           a, c, pairing))
    ranked.sort()
    out, seen = [], set()
    for _, a, c, pairing in ranked:
        if tuple(pairing) not in seen:
            seen.add(tuple(pairing))
            out.append((a, c, pairing))
    return out


class Schedule:
    """The endless sequence of requests one (traffic file, seed) gives."""

    def __init__(self, spec: dict, seed: int):
        if spec.get("kind") not in KINDS:
            raise ValueError(f"traffic kind {spec.get('kind')!r} not in "
                             f"{KINDS}")
        self.spec = spec
        self.seed = int(seed)
        self.kind = spec["kind"]
        self.pairs = pairs_of(spec)
        random.Random(_mix(self.seed, 1)).shuffle(self.pairs)
        self.sessions = int(spec["sessions"])
        self.rate = float(spec.get("rate_rps", 0.0))
        self.arrival = spec.get("arrival", "uniform")
        if self.kind == "open":
            if self.rate <= 0:
                raise ValueError("open traffic needs rate_rps > 0")
            if self.arrival not in ARRIVALS:
                raise ValueError(f"arrival {self.arrival!r}")
        self._due: List[float] = []
        self._due_rng = random.Random(_mix(self.seed, 3))

    def _due_s(self, k: int) -> Optional[float]:
        if self.kind != "open":
            return None
        if self.arrival == "uniform":
            return k / self.rate
        while len(self._due) <= k:
            last = self._due[-1] if self._due else 0.0
            self._due.append(last + self._due_rng.expovariate(self.rate))
        return self._due[k]

    def request(self, k: int) -> Request:
        prompt_len, budget = self.pairs[k % len(self.pairs)]
        return Request(
            index=k,
            prompt_len=prompt_len,
            budget=budget,
            sampling_seed=_mix(self.seed, 4, k) % (1 << 30),
            due_s=self._due_s(k))

    def prompt_ids(self, k: int, vocab_size: int) -> List[int]:
        rng = random.Random(_mix(self.seed, 5, k))
        n = self.request(k).prompt_len
        return [rng.randrange(vocab_size) for _ in range(n)]

    def warm_lengths(self) -> List[int]:
        """Every distinct prompt length, so each prefill shape the window
        can meet is built during set-up (whatever the program's buckets)."""
        return sorted({n for n, _ in self.pairs})
