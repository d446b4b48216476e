"""One general traffic generator, driven by a data file.

A traffic file gives: ``kind`` (``closed``: N sessions, each sends its next
request when the last one ended; ``open``: requests fall due at a fixed
rate whether or not earlier ones have ended), the length tables, the
sampling parameters and the route. Every seed gives the SAME multiset of
(prompt length, token budget) per cycle of the tables — the seed only
shuffles the order — so the seed never changes the amount of work, and the
same seed gives the same schedule, token ids and sampling seeds."""

from __future__ import annotations

import dataclasses
import random
from typing import List, Optional

KINDS = ("closed", "open")
ARRIVALS = ("uniform", "poisson")


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    prompt_len: int
    budget: int            # output tokens asked for
    sampling_seed: int     # < 2**30: the program keeps seeds in int32
    due_s: Optional[float]  # open loop: seconds after the start; else None


def _mix(seed: int, *parts: int) -> int:
    """A deterministic 62-bit mix of whole numbers (any size of seed)."""
    x = (seed & ((1 << 64) - 1)) ^ (seed >> 64)
    for p in parts:
        x = (x * 6364136223846793005 + p + 1442695040888963407) % (1 << 64)
        x ^= x >> 29
    return x >> 2


class Schedule:
    """The endless sequence of requests one (traffic file, seed) gives."""

    def __init__(self, spec: dict, seed: int):
        if spec.get("kind") not in KINDS:
            raise ValueError(f"traffic kind {spec.get('kind')!r} not in "
                             f"{KINDS}")
        self.spec = spec
        self.seed = int(seed)
        self.kind = spec["kind"]
        self.prompt_lens = list(spec["prompt_lens"])
        self.budgets = list(spec["token_budgets"])
        if not self.prompt_lens or not self.budgets:
            raise ValueError("empty length table")
        random.Random(_mix(self.seed, 1)).shuffle(self.prompt_lens)
        random.Random(_mix(self.seed, 2)).shuffle(self.budgets)
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
        return Request(
            index=k,
            prompt_len=self.prompt_lens[k % len(self.prompt_lens)],
            budget=self.budgets[k % len(self.budgets)],
            sampling_seed=_mix(self.seed, 4, k) % (1 << 30),
            due_s=self._due_s(k))

    def prompt_ids(self, k: int, vocab_size: int) -> List[int]:
        rng = random.Random(_mix(self.seed, 5, k))
        n = self.request(k).prompt_len
        return [rng.randrange(vocab_size) for _ in range(n)]

    def warm_lengths(self) -> List[int]:
        """Every distinct prompt length, so each prefill shape the window
        can meet is built during set-up (whatever the program's buckets)."""
        return sorted(set(self.prompt_lens))
