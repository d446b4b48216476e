"""Pipeline client: routing, journaled fault tolerance, generation loop.

TPU-native counterpart of the reference client stack:

  * ``run_rank0`` generation loop (``src/main.py:62-227``): tokenized prompt →
    local stage0 forward → remote pipeline walk → sampled token back from the
    final stage; EOS + 5×-repeat stopping; TTFT/decode metrics.
  * ``RpcTransport`` routing (``src/rpc_transport.py:393-501``): fixed
    stage-chain route, or greedy module route over block coverage (pick the
    candidate covering the next uncovered block with the largest
    ``end_block``, tie-break throughput; verify the last hop serves the final
    stage).
  * fault tolerance (``src/rpc_transport.py:587-712``): every activation sent
    to a remote stage is journaled; on failure the client marks the peer
    failed, re-discovers a replacement (excluding failed peers), REPLAYS the
    journal to rebuild the replacement's KV cache, and retries — at most 3
    attempts per call.

The journal is bounded per session by ``journal_max_entries`` (the reference
journals unboundedly, ``src/rpc_transport.py:106`` — a noted memory hazard;
SURVEY.md §7.3 hard part 4): when the bound is hit, the two oldest entries are
coalesced by concatenating along the sequence axis, which keeps replay exact
while capping Python-object overhead.
"""

from __future__ import annotations

import dataclasses
import logging
import random
import threading
import time
from collections import deque
from typing import (Any, Callable, Dict, Iterator, List, Optional, Sequence,
                    Tuple)

import jax.numpy as jnp
import numpy as np

from ..models.config import ModelConfig, refuse_single_pass
from ..models.partition import StagePlan, StageSpec
from ..ops.sampling import SamplingParams
from ..scheduling.registry import PlacementRegistry, ServerRecord
from ..telemetry import MetricsRegistry, get_tracer
from ..telemetry import catalog as _tm
from ..telemetry import events as _ev
from ..telemetry.profiling import get_profiler as _get_profiler
from . import errors as _errors
from .errors import register as _catalog
from .executor import StageExecutionError, StageExecutor
from .messages import StageRequest, StageResponse, clip_generated
from .transport import DeadlineExceeded, PeerUnavailable, Transport

logger = logging.getLogger(__name__)

MAX_ATTEMPTS = 3          # src/rpc_transport.py:597
SETTLE_SECONDS = 0.2      # src/rpc_transport.py:657
REPEAT_STOP = 5           # 5 consecutive identical tokens, src/main.py:197-204
# A coalesced replay chunk must stay replayable: the executor pads sequences
# up to SEQ_BUCKETS whose largest entry is 8192.
MAX_COALESCED_TOKENS = 4096
# Journal/route key for the single full-span hop a burst session pins
# (_generate_steps_full_span); _rediscover_excluding special-cases it.
BURST_HOP_KEY = "burst"


# Engines that serve prefill/decode of their FULL span only: they refuse
# beam/training/replay and sub-span requests, so exotic sessions and
# replay-failover must route around them. Speculative draft steps are the
# exception: batched peers verify drafts in-round (batching.py), so
# kind="spec" sessions route TO them; sp peers still refuse drafts.
SESSION_ONLY_ENGINES = ("batched", "sp")


def _engine_usable(rec, kind: str, full_span: bool = True,
                   min_context: Optional[int] = None) -> bool:
    """Can a session of `kind` (needing `min_context` total tokens) call
    `rec`'s engine for a hop that covers its full span iff `full_span`?"""
    if rec.engine not in SESSION_ONLY_ENGINES:
        return True
    if kind == "exotic" or not full_span:
        return False
    if (min_context is not None and rec.max_context is not None
            and rec.max_context < min_context):
        # A peer advertising a smaller context than this session needs
        # WILL refuse the prefill — don't route there just to bounce.
        # Applies to every kind, spec included (a batched peer's slots
        # have a max_len too).
        return False
    if kind == "spec":
        # Draft steps batch on batched peers (multi-token verify rounds,
        # batching.py); sp peers refuse them.
        return rec.engine == "batched"
    return True


def _soft_filter(items, pred):
    """Routing-policy filter with soft fallback: keep the items matching
    `pred` unless that would leave none. A candidate that will fail LOUDLY
    at call time (retryable stage error) beats an immediate NoRouteError
    when the swarm simply has nothing better."""
    kept = [it for it in items if pred(it)]
    return kept or items


@_catalog
class NoRouteError(RuntimeError):
    """No live servers cover the required span (route computation failed)."""


class _BreakerOpen(PeerUnavailable):
    """Synthetic dial refusal: the peer's circuit breaker is open. A
    PeerUnavailable subclass so the recovery wrapper's existing failover
    path handles it — but it is NOT counted as a failure observation (the
    peer was never dialed)."""


class CircuitBreaker:
    """Per-peer circuit breaker for the client's recovery wrapper.

    The 3-attempt retry loop treats every failure the same; without a
    breaker, a flapping peer gets re-dialed (connect timeout + replay) on
    every route that includes it, multiplying recovery latency swarm-wide.
    Classic state machine instead:

      closed     normal; `threshold` CONSECUTIVE failures open it.
      open       dials are skipped (no connection attempt) until the
                 backoff elapses: ``base * 2**(n_opens-1)`` capped at
                 ``max_backoff_s``, plus seeded jitter so a fleet of
                 clients doesn't re-probe a recovering server in
                 lockstep.
      half_open  backoff elapsed: exactly ONE probe call is let through.
                 Success closes the breaker (full readmission — no
                 blacklist clear needed); failure re-opens with doubled
                 backoff.

    Transitions emit breaker_open/breaker_half_open/breaker_close events
    and count in ``client_breaker_transitions_total{state=...}``; every
    skipped dial counts in ``client_breaker_open_skips_total``. `now` is
    injectable so tests drive the clock instead of sleeping.
    """

    def __init__(self, threshold: int = 3, base_backoff_s: float = 0.5,
                 max_backoff_s: float = 30.0, jitter: float = 0.1,
                 seed: int = 0,
                 now: Callable[[], float] = time.monotonic,
                 metrics=None):
        self.threshold = threshold
        self.base_backoff_s = base_backoff_s
        self.max_backoff_s = max_backoff_s
        self.jitter = jitter
        self.now = now
        self._rng = random.Random(seed)
        self._lock = threading.Lock()
        # peer -> {"state", "fails", "opened_at", "backoff", "opens"}
        self._peers: Dict[str, dict] = {}
        self._m_transitions = _tm.get("client_breaker_transitions_total",
                                      metrics)
        self._m_skips = _tm.get("client_breaker_open_skips_total", metrics)

    def _st(self, peer_id: str) -> dict:
        return self._peers.setdefault(
            peer_id, {"state": "closed", "fails": 0, "opened_at": 0.0,
                      "backoff": 0.0, "opens": 0})

    def state(self, peer_id: str) -> str:
        with self._lock:
            return self._peers.get(peer_id, {}).get("state", "closed")

    def allow(self, peer_id: str) -> bool:
        """May the caller dial this peer now? Open + backoff pending -> no
        (counted as a skipped dial); open + backoff elapsed -> yes, as the
        half-open single probe; half_open with the probe already granted ->
        no (one probe at a time, or N callers would stampede the
        recovering peer the breaker exists to protect)."""
        with self._lock:
            st = self._st(peer_id)
            if st["state"] == "closed":
                return True
            if st["state"] == "open":
                if self.now() - st["opened_at"] < st["backoff"]:
                    self._m_skips.inc()
                    return False
                st["state"] = "half_open"
                self._m_transitions.labels(state="half_open").inc()
                _ev.emit("breaker_half_open", peer=peer_id,
                         opens=st["opens"])
                return True
            # half_open: the single probe is already in flight.
            self._m_skips.inc()
            return False

    def record_success(self, peer_id: str) -> None:
        with self._lock:
            st = self._st(peer_id)
            was = st["state"]
            st.update(state="closed", fails=0, backoff=0.0, opens=0)
        if was != "closed":
            self._m_transitions.labels(state="close").inc()
            _ev.emit("breaker_close", peer=peer_id)

    def record_failure(self, peer_id: str) -> None:
        with self._lock:
            st = self._st(peer_id)
            st["fails"] += 1
            if st["state"] != "half_open" and st["fails"] < self.threshold:
                return
            # Threshold reached (closed) or the half-open probe failed:
            # (re-)open with exponentially grown, jittered backoff.
            st["opens"] += 1
            backoff = min(self.base_backoff_s * (2 ** (st["opens"] - 1)),
                          self.max_backoff_s)
            backoff *= 1.0 + self._rng.uniform(0.0, self.jitter)
            st.update(state="open", opened_at=self.now(), backoff=backoff,
                      fails=0)
            opens, b = st["opens"], backoff
        self._m_transitions.labels(state="open").inc()
        _ev.emit("breaker_open", peer=peer_id, opens=opens,
                 backoff_s=round(b, 4))


def _merge_entries(a: "JournalEntry", b: "JournalEntry") -> "JournalEntry":
    """Coalesce two adjacent journal entries into one replayable chunk.

    When `b` carries a beam reorder, the reorder is hoisted to the front of
    the merged chunk by permutation composition: replaying
    ``[reorder p_a; tokens A; reorder p_b; tokens B]`` equals
    ``[reorder p_a∘p_b; tokens A[p_b]; tokens B]`` — merged row j takes its
    A-tokens from A's row ``p_b[j]`` and its prefix KV from row
    ``p_a[p_b[j]]``, exactly what the two-entry replay produced. (Because
    rows attend only to their own KV, permuting whole rows commutes with the
    step.) This keeps beam-session journals bounded — without composition no
    reorder-carrying pair could ever merge."""
    if b.hypo_ids is None:
        hidden = np.concatenate([a.hidden, b.hidden], axis=1)
        hypo = a.hypo_ids
    else:
        sel = np.asarray(b.hypo_ids, np.int64)
        hidden = np.concatenate([a.hidden[sel], b.hidden], axis=1)
        hypo = (tuple(b.hypo_ids) if a.hypo_ids is None
                else tuple(a.hypo_ids[i] for i in b.hypo_ids))
    return JournalEntry(hidden=hidden, seq_len=a.seq_len + b.seq_len,
                        cur_len=a.cur_len, hypo_ids=hypo)


@dataclasses.dataclass
class Hop:
    """One remote hop of the route: a pinned peer serving [start, end)."""

    key: str                 # stable hop identity ("stage1" / "blocks8:16")
    peer_id: str
    start_block: int
    end_block: int
    expect_token: bool       # final hop returns a sampled token


@dataclasses.dataclass
class JournalEntry:
    hidden: np.ndarray       # [B, T, D] activation as sent
    seq_len: int
    cur_len: int             # session length before this entry
    # Beam reorder applied BEFORE this entry's step (replay must re-apply it
    # in order, or the rebuilt KV rows belong to the wrong hypotheses).
    hypo_ids: Optional[Tuple[int, ...]] = None


@dataclasses.dataclass
class BeamResult:
    tokens: List[int]        # best hypothesis (new tokens only)
    score: float             # length-normalized log-probability
    num_beams: int
    ttft_s: float


@dataclasses.dataclass
class GenerationStep:
    """One yield of ``generate_stepwise``: the tokens this pipeline round
    produced (one for plain decode, up to K+1 for an accepted speculative
    run). The final yield carries ``done=True`` plus the assembled
    ``GenerationResult``; its ``new_tokens`` is empty."""

    new_tokens: List[int]
    done: bool = False
    result: Optional["GenerationResult"] = None


@dataclasses.dataclass
class GenerationResult:
    tokens: List[int]
    ttft_s: float
    decode_times_s: List[float]
    stopped_by: str          # "eos" | "repeat" | "max_tokens"

    @property
    def decode_tokens_per_s(self) -> float:
        # TOKENS decoded over decode wall time, not len(decode_times_s): a
        # speculative round contributes ONE timing entry but up to K+1
        # tokens; counting entries would understate speculative throughput by
        # the acceptance factor. tokens[0] came from the prefill (TTFT).
        total = sum(self.decode_times_s)
        decoded = max(len(self.tokens) - 1, 0)
        return (decoded / total) if total > 0 else 0.0


class PipelineClient:
    """Drives generation across local stage0 + remote pipeline stages."""

    def __init__(
        self,
        cfg: ModelConfig,
        plan: StagePlan,
        stage0: StageExecutor,
        transport: Transport,
        registry: PlacementRegistry,
        *,
        use_module_routing: bool = False,
        route_by_latency: bool = False,
        use_push_chain: bool = False,
        total_blocks: Optional[int] = None,
        request_timeout: float = 60.0,
        settle_seconds: float = SETTLE_SECONDS,
        journal_max_entries: int = 256,
        seed: int = 0,
        model: Optional[str] = None,
        long_context_threshold: Optional[int] = None,
        metrics: Optional[MetricsRegistry] = None,
        route_cache_capacity: int = 64,
    ):
        self.cfg = cfg
        # Multi-model swarm: every discovery/coverage query is scoped to this
        # model name (the model-prefixed DHT keys of src/dht_utils.py:20-31).
        # None = single-model swarm, all records match.
        self.model = model
        self.plan = plan
        # The client-local stage-0 executor, or a zero-arg factory for it:
        # a session served whole by a full-span peer computes nothing
        # here, so a CLI client builds its stage-0 weights (and opens the
        # device) only when a classic route first needs them.
        self._stage0 = None if callable(stage0) else stage0
        self._stage0_factory = stage0 if callable(stage0) else None
        self._stage0_lock = threading.Lock()
        self.transport = transport
        self.registry = registry
        if route_by_latency and not use_module_routing:
            # The latency planner only runs inside module routing
            # (_compute_route -> _compute_module_route -> latency planner);
            # without this, --route_by_latency alone would silently fall back
            # to stage-index routing.
            logger.warning("route_by_latency implies module routing; "
                           "enabling use_module_routing")
            use_module_routing = True
        self.use_module_routing = use_module_routing
        self.route_by_latency = route_by_latency
        self.use_push_chain = use_push_chain
        self.total_blocks = total_blocks or cfg.num_layers
        self.request_timeout = request_timeout
        self.settle_seconds = settle_seconds
        self.journal_max_entries = journal_max_entries
        self.seed = seed
        # Prompts at/above this length route as kind="long" (preferring
        # engine=sp peers whose prefix cache shards across a mesh). None =
        # never classify by length.
        self.long_context_threshold = long_context_threshold

        # hop key -> session -> activation journal (src/rpc_transport.py:106)
        self.journal: Dict[str, Dict[str, List[JournalEntry]]] = {}
        # hop key -> peers that failed for that hop (src/rpc_transport.py:107-108)
        self.failed_peers: Dict[str, set] = {}
        # session -> every peer that ever held KV for it. A timed-out peer
        # the client failed over AWAY from is usually still alive and still
        # holding the session's arena lease; _end_session must release it
        # there too or each failover permanently shrinks that server's
        # advertised cache capacity.
        self._session_peers: Dict[str, set] = {}
        # session -> full deep-prompt tensor [total_blocks, pre, D]; sliced
        # per hop on every step AND on journal replay (a replacement peer
        # must rebuild the same prompt-injected hiddens).
        self._session_prompts: Dict[str, np.ndarray] = {}
        # Gateway-assigned tenant priority per live session (lower = more
        # urgent); stamped onto every StageRequest the session sends so
        # server task pools order contended work by tenant.
        self._session_priority: Dict[str, float] = {}
        # Route cache per session KIND:
        #   "plain"  — prefers engine=batched peers (one compiled step
        #              serves every concurrent session);
        #   "spec"   — speculative sessions: prefers batched peers too
        #              (draft verify coalesces in multi-token rounds) but
        #              must avoid sp peers, which refuse drafts;
        #   "long"   — prefers engine=sp peers (prefix KV sharded across a
        #              mesh: context beyond one device's budget);
        #   "exotic" — beam / training / anything the single-session
        #              engines refuse (batching.py forward checks) routes
        #              around them.
        # Keyed so kinds never evict each other's route. Capacity bounds the
        # affinity-keyed entries (one per distinct prompt-head digest —
        # unbounded in a long-lived client); swarm-scale tuning is a
        # constructor knob, evictions are counted.
        self.route_cache_capacity = int(route_cache_capacity)
        self._routes: Dict[str, List[Hop]] = {}
        # peer -> (rtt_s, measured_at): client-side ping cache for the
        # latency planner's first hop. Route recomputation runs on the
        # RECOVERY path, where serially re-pinging dead candidates (multi-
        # second timeouts each) would multiply failover latency.
        self._ping_cache: Dict[str, Tuple[float, float]] = {}
        self.ping_cache_ttl = 30.0

        # Telemetry: ONE owner of client metric state (replaces the ad-hoc
        # int/dict mirrors of RpcTransport.last_prefill_stage_times /
        # decode_stage_history, src/rpc_transport.py:98-103). The client
        # carries a private ALWAYS-ON registry by default — `recoveries` is
        # load-bearing API and must count regardless of the process-global
        # flag; pass the global registry (telemetry.get_registry()) to fold
        # client series into a process scrape.
        self.metrics = metrics if metrics is not None else \
            MetricsRegistry(enabled=True)
        self._m_ttft = _tm.get("client_ttft_seconds", self.metrics)
        self._m_step = _tm.get("client_step_seconds", self.metrics)
        self._m_stage_time = _tm.get("client_stage_time_seconds", self.metrics)
        self._m_retries = _tm.get("client_retries_total", self.metrics)
        self._m_recoveries = _tm.get("client_recoveries_total", self.metrics)
        self._m_generations = _tm.get("client_generations_total", self.metrics)
        self._m_tokens = _tm.get("client_tokens_generated_total", self.metrics)
        # Route-plan events go to the PROCESS-GLOBAL registry (scheduler
        # metric family, shared with the latency planner in
        # scheduling.routing) — they describe swarm behaviour, not this
        # client's private counters.
        self._m_route_plans = _tm.get("scheduler_route_plans_total")
        self._m_route_hops = _tm.get("scheduler_route_hops")
        self._m_deadline = _tm.get("client_deadline_expired_total",
                                   self.metrics)
        self._m_route_evictions = _tm.get(
            "client_route_cache_evictions_total", self.metrics)
        # Per-peer circuit breaker: bounds how often the recovery loop
        # re-dials a flapping peer (consecutive-failure threshold -> open
        # with exponential backoff + jitter -> half-open single probe ->
        # close). Seeded with the client seed so chaos runs reproduce.
        self.breaker = CircuitBreaker(seed=seed, metrics=self.metrics)
        # Last-REQUEST views kept for API compatibility (status displays and
        # tests read them); cumulative aggregates live in self.metrics.
        self.last_prefill_stage_times: Dict[str, float] = {}
        # Bounded: the old unbounded list leaked one dict per decode step for
        # the life of the client.
        self.decode_stage_history = deque(maxlen=512)

    @property
    def recoveries(self) -> int:
        """Successful failovers to a replacement server — a registry-backed
        view of ``client_recoveries_total`` (the old ad-hoc int)."""
        return int(self._m_recoveries.value)

    # ------------------------------------------------------------------
    # Routing
    # ------------------------------------------------------------------

    def _compute_route(self, kind: str = "plain",
                       min_context: Optional[int] = None,
                       affinity: Optional[str] = None) -> List[Hop]:
        if self.use_module_routing:
            return self._compute_module_route(kind, min_context)
        hops: List[Hop] = []
        for spec in self.plan.stages[1:]:
            key = f"stage{spec.index}"
            exclude = self.failed_peers.get(key, set())
            peer = self.registry.discover_stage(
                spec.index, exclude=tuple(exclude), model=self.model,
                prefer_engine={"plain": "batched", "spec": "batched",
                               "long": "sp"}.get(kind),
                avoid_engine=(SESSION_ONLY_ENGINES if kind == "exotic"
                              else ("sp",) if kind == "spec" else None),
                min_context=min_context, affinity=affinity)
            if peer is None:
                raise NoRouteError(f"no live server for {key}")
            hops.append(Hop(key, peer, spec.start, spec.end, spec.is_last))
        self._m_route_plans.labels(planner="stage").inc()
        self._m_route_hops.observe(len(hops))
        return hops

    def _ping_candidates(self, peer_ids: Sequence[str]) -> Dict[str, float]:
        """Concurrent pings with a freshness cache (ping_cache_ttl seconds).
        Unreachable peers are simply absent (the planner charges its default
        RTT); failed pings are not cached so a recovering peer is re-probed."""
        now = time.monotonic()
        out: Dict[str, float] = {}
        to_ping: List[str] = []
        for pid in peer_ids:
            cached = self._ping_cache.get(pid)
            if cached is not None and now - cached[1] < self.ping_cache_ttl:
                out[pid] = cached[0]
            else:
                to_ping.append(pid)
        if to_ping:
            from concurrent.futures import ThreadPoolExecutor

            with ThreadPoolExecutor(max_workers=min(8, len(to_ping))) as pool:
                for pid, rtt in zip(to_ping,
                                    pool.map(self.transport.ping, to_ping)):
                    if rtt is not None:
                        out[pid] = rtt
                        self._ping_cache[pid] = (rtt, now)
        return out

    def _compute_latency_route(self, kind: str = "plain",
                               min_context: Optional[int] = None) -> Optional[List[Hop]]:
        """Latency-aware module routing: Dijkstra over block coverage using
        server-published next-hop RTTs + the client's own first-hop pings
        (scheduling.routing; the upstream-Petals ping-aware route choice the
        greedy router approximates). Returns None when the planner finds no
        final-stage-terminated coverage — caller falls back to greedy."""
        from ..scheduling.routing import plan_min_latency_route

        start = self.plan.stages[0].end
        exclude = set()
        for peers in self.failed_peers.values():
            exclude |= peers
        records = self.registry.live_servers(model=self.model)
        if kind == "exotic":
            # Single-session engines refuse the exotic verbs — don't even
            # consider them (plain sessions keep them: the planner optimizes
            # latency, and engine preference is secondary there).
            records = _soft_filter(
                records, lambda r: r.engine not in SESSION_ONLY_ENGINES)
        elif kind == "spec":
            # Batched peers verify drafts; sp peers refuse them. A peer
            # advertising less context than the session needs would refuse
            # the prefill.
            records = _soft_filter(
                records,
                lambda r: r.engine != "sp" and (
                    min_context is None or r.max_context is None
                    or r.max_context >= min_context))
        elif min_context is not None:
            # sp peers advertising less context than this session needs
            # would refuse the prefill.
            records = _soft_filter(
                records,
                lambda r: (r.engine != "sp" or r.max_context is None
                           or r.max_context >= min_context))
        # Client-side pings for first-hop candidates only (the rest of the
        # route uses server-published RTTs). Pings run CONCURRENTLY and
        # recent measurements are reused — failover triggers a route refresh
        # exactly when candidates are likely dead, and serial multi-second
        # ping timeouts there would multiply recovery latency.
        cands = [rec.peer_id for rec in records
                 if rec.start_block <= start < rec.end_block
                 and rec.peer_id not in exclude]
        client_rtts = self._ping_candidates(cands)
        planned = plan_min_latency_route(
            records, start, self.total_blocks,
            client_rtts=client_rtts, exclude=tuple(exclude))
        if planned is not None and any(
                h.record.engine in SESSION_ONLY_ENGINES
                and (h.entry != h.record.start_block
                     or h.end != h.record.end_block)
                for h in planned):
            # Single-session engines serve their FULL span only
            # (batching.py:396-400); a sub-span hop through one would be
            # refused at call time. Re-plan without them rather than ship a
            # dead route.
            planned = plan_min_latency_route(
                [r for r in records if r.engine not in SESSION_ONLY_ENGINES],
                start, self.total_blocks,
                client_rtts=client_rtts, exclude=tuple(exclude))
        if planned is None:
            return None
        hops = [Hop(f"blocks{h.entry}", h.record.peer_id, h.entry, h.end,
                    h.end >= self.total_blocks)
                for h in planned]
        return hops

    def _compute_module_route(self, kind: str = "plain",
                              min_context: Optional[int] = None) -> List[Hop]:
        """Greedy block-coverage routing (``src/rpc_transport.py:393-493``):
        cover [stage0_end, total_blocks) hop by hop, each hop the candidate
        with max end_block (tie-break engine preference, then throughput),
        loop-guarded, final hop must serve the final stage."""
        if self.route_by_latency:
            hops = self._compute_latency_route(kind, min_context)
            if hops is not None:
                return hops
            logger.warning("latency planner found no route; "
                           "falling back to greedy coverage routing")
        start = self.plan.stages[0].end
        hops: List[Hop] = []
        covered = start
        while covered < self.total_blocks:
            key = f"blocks{covered}"
            exclude = self.failed_peers.get(key, set())
            cands = self.registry.discover_block(covered, exclude=tuple(exclude),
                                                 model=self.model)
            # The hop must START at `covered` or earlier; its span past
            # `covered` is what advances coverage.
            cands = [c for c in cands if c.end_block > covered]
            # Engine compatibility: single-session engines serve their FULL
            # span only and refuse the exotic verbs (batching.py:387-407).
            # Drop candidates this session could never call — softly, so a
            # swarm of only-unusable peers still fails with the clearer
            # retryable stage error rather than NoRouteError here.
            cands = _soft_filter(
                cands,
                lambda c: _engine_usable(c, kind,
                                         full_span=c.start_block == covered,
                                         min_context=min_context))
            if not cands:
                raise NoRouteError(f"no live server covers block {covered}")
            prefer = {"plain": "batched", "spec": "batched",
                      "long": "sp"}.get(kind)
            best = max(cands, key=lambda c: (
                c.end_block,
                c.engine == prefer,    # engine preference on equal coverage
                c.throughput))
            if best.end_block <= covered:  # loop guard, rpc_transport.py:459-461
                raise NoRouteError(f"route stuck at block {covered}")
            is_final = best.end_block >= self.total_blocks
            if is_final and not best.final_stage:
                raise NoRouteError(
                    f"last hop {best.peer_id} does not serve the final stage "
                    "(src/rpc_transport.py:463-491 verification)"
                )
            hops.append(Hop(key, best.peer_id, covered, best.end_block, is_final))
            covered = best.end_block
        self._m_route_plans.labels(planner="greedy").inc()
        self._m_route_hops.observe(len(hops))
        return hops

    def route(self, refresh: bool = False, kind: str = "plain",
              min_context: Optional[int] = None,
              affinity: Optional[str] = None) -> List[Hop]:
        """`affinity` (prompt-head digest) makes the replica choice a
        rendezvous hash so repeat/shared prompts from ANY client land on
        the peer whose prefix store is warm (registry._pick_newest). The
        route cache is keyed by it; distinct prompt heads are unbounded,
        so the cache evicts LEAST-RECENTLY-USED past a small cap (an
        in-flight session touches its key every step, so eviction can
        never yank a live generation's route — FIFO could, silently
        swapping a mid-session hop for a replica holding no KV)."""
        if self.use_module_routing:
            # The module-route planner ignores affinity (span-greedy pick
            # is already deterministic); keying the cache on it would turn
            # every distinct prompt head into a full recompute.
            affinity = None
        key = (kind, min_context, affinity)
        if refresh or key not in self._routes:
            while len(self._routes) >= self.route_cache_capacity:
                # Evict LRU among AFFINITY-CARRYING keys only. The
                # affinity=None entries are the per-(kind, min_context)
                # fallback routes — a bounded handful that every
                # non-affinity session shares — and evicting one to make
                # room for yet another one-off prompt-head digest forces a
                # full route recompute on the next plain step. Distinct
                # digests are what's unbounded; only they pay eviction.
                victim = next((k for k in self._routes if k[2] is not None),
                              None)
                if victim is None:
                    break  # all entries are exempt fallback routes
                self._routes.pop(victim)
                self._m_route_evictions.inc()
            self._routes[key] = self._compute_route(kind, min_context,
                                                    affinity)
        else:
            self._routes[key] = self._routes.pop(key)  # LRU touch
        return self._routes[key]

    # ------------------------------------------------------------------
    # Journal + recovery
    # ------------------------------------------------------------------

    def _journal_append(self, key: str, session_id: str, entry: JournalEntry) -> None:
        entries = self.journal.setdefault(key, {}).setdefault(session_id, [])
        entries.append(entry)
        if len(entries) > self.journal_max_entries:
            # Coalesce the oldest adjacent pair whose merged chunk is still
            # replayable (<= MAX_COALESCED_TOKENS — the executor's seq buckets
            # cap what one replay request may carry). If every pair is at the
            # cap the list grows past journal_max_entries, but is then bounded
            # by max_length / MAX_COALESCED_TOKENS + recent singles.
            for i in range(len(entries) - 1):
                a, b = entries[i], entries[i + 1]
                if a.seq_len + b.seq_len <= MAX_COALESCED_TOKENS:
                    entries[i:i + 2] = [_merge_entries(a, b)]
                    break

    def _replay(self, hop: Hop, session_id: str, sampling: SamplingParams,
                max_length: int) -> None:
        """Rebuild a replacement peer's KV by replaying the journal
        (``src/rpc_transport.py:670-712``): first chunk as prefill, the rest
        as is_replay decode chunks with cumulative cur_len."""
        entries = self.journal.get(hop.key, {}).get(session_id, [])
        tokens = sum(e.seq_len for e in entries)
        _ev.emit("replay_start", session_id=session_id, peer=hop.peer_id,
                 entries=len(entries), tokens=tokens)
        t0 = time.monotonic()
        for i, e in enumerate(entries):
            req = StageRequest(
                session_id=session_id,
                hidden=e.hidden,
                seq_len=e.seq_len,
                cur_len=e.cur_len,
                is_prefill=(i == 0),
                is_replay=True,
                max_length=max_length,
                sampling=sampling,
                start_block=hop.start_block,
                end_block=hop.end_block,
                hypo_ids=None if i == 0 else e.hypo_ids,
                prompts=self._hop_prompts(session_id, hop, e.cur_len),
            )
            self.transport.call(hop.peer_id, req, timeout=self.request_timeout)
        _ev.emit("replay_done", session_id=session_id, peer=hop.peer_id,
                 tokens=tokens, seconds=round(time.monotonic() - t0, 4))

    def _hop_prompts(self, session_id: str, hop: Hop, cur_len: int = 0):
        return self._span_prompts(session_id, hop.start_block,
                                  hop.end_block, cur_len)

    def _span_prompts(self, session_id: str, start: int, end: int,
                      cur_len: int = 0):
        """One span's slice of the session's deep prompts (rows are absolute
        block indices — each server gets exactly its span's blocks, the
        petals client-side prompt split). Returns None once the step sits
        entirely PAST the prompt region (cur_len >= pre_seq): the injection
        is an exact no-op there, and dropping the tensor keeps steady-state
        decode off the wire-heavy classic frame (it re-ships [span, pre, D]
        floats per hop) and back on the persistent-stream fast path. The
        slice stays a host numpy view — the transport encodes from host
        anyway, and the server does its own device put."""
        pr = self._session_prompts.get(session_id)
        if pr is None or cur_len >= pr.shape[1] or start >= end:
            return None
        return pr[start:end]

    def _deadline_budget(self, deadline_at: Optional[float],
                         session_id: str, *, trace_id=None,
                         peer: Optional[str] = None) -> Optional[float]:
        """Remaining end-to-end budget (seconds), or None when the session
        has no deadline. An EXPIRED budget raises the typed client error
        here — before any hop is dialed — with the catalogued
        ``deadline_expired`` event; the counterpart of the server-side
        ``deadline_rejected`` refusal."""
        if deadline_at is None:
            return None
        remaining = deadline_at - time.monotonic()
        if remaining <= 0.0:
            self._m_deadline.inc()
            _ev.emit("deadline_expired", session_id=session_id,
                     trace_id=trace_id, peer=peer,
                     over_s=round(-remaining, 6))
            raise DeadlineExceeded(
                f"session {session_id}: deadline exceeded "
                f"({-remaining:.3f}s past) before dialing "
                f"{peer or 'the next hop'}")
        return remaining

    def _call_with_recovery(self, hop: Hop, req: StageRequest) -> StageResponse:
        """3-attempt failover (``src/rpc_transport.py:587-668``), gated by
        the per-peer circuit breaker: an open breaker turns the dial into a
        synthetic retryable failure (failover to a replacement, no
        connection attempt), and only real observations feed the breaker's
        state machine."""
        last_exc: Optional[Exception] = None
        touched = self._session_peers.setdefault(req.session_id, set())
        for attempt in range(MAX_ATTEMPTS):
            touched.add(hop.peer_id)
            try:
                if not self.breaker.allow(hop.peer_id):
                    raise _BreakerOpen(
                        f"peer {hop.peer_id}: circuit breaker open")
                # The "socket" phase: one request/response turnaround on
                # the wire, per attempt (recovery machinery stays outside).
                with _get_profiler().phase("socket"):
                    resp = self.transport.call(hop.peer_id, req,
                                               timeout=self.request_timeout)
                self.breaker.record_success(hop.peer_id)
                return resp
            except DeadlineExceeded:
                # Terminal by design: the caller's budget is spent, and a
                # failover attempt can only spend more of it. Never counts
                # against the peer (it did the right thing by refusing).
                raise
            # Retryable taxonomy (runtime/errors.py, the same table
            # graftlint checks): connectivity faults + server-side session
            # loss (StageExecutionError — failover+replay rebuilds the KV).
            # Deliberately NOT the reference's broad RuntimeError/ValueError
            # net (src/rpc_transport.py:618): a deterministic client-side bug
            # would blacklist every healthy replica in turn.
            except _errors.retryable_types() as exc:
                if not isinstance(exc, _BreakerOpen):
                    # A skipped dial is not evidence about the peer. Breaker
                    # blame may differ from routing blame: a RELAYED hop's
                    # failure is usually its volunteer's (breaker_peer_id)
                    # — opening the hop's own breaker would blacklist every
                    # peer behind one dead relay.
                    self.breaker.record_failure(
                        _errors.breaker_blame(exc, hop.peer_id))
                last_exc = exc
                self._m_retries.inc()
                trace_id = (req.trace or {}).get("trace_id") \
                    if isinstance(req.trace, dict) else None
                _ev.emit("hop_retry", session_id=req.session_id,
                         trace_id=trace_id, hop=hop.key, peer=hop.peer_id,
                         attempt=attempt + 1,
                         error=f"{type(exc).__name__}: {exc}"[:200])
                _ev.emit("peer_failed", session_id=req.session_id,
                         trace_id=trace_id, hop=hop.key, peer=hop.peer_id,
                         reason=type(exc).__name__)
                failed = self.failed_peers.setdefault(hop.key, set())
                failed.add(hop.peer_id)
                logger.warning(
                    "hop %s peer %s failed (attempt %d/%d): %s",
                    hop.key, hop.peer_id, attempt + 1, MAX_ATTEMPTS, exc,
                )
                old_peer = hop.peer_id
                try:
                    replacement = self._rediscover(hop)
                except NoRouteError:
                    continue  # maybe a peer re-registers before we run out
                hop.peer_id = replacement
                self._m_recoveries.inc()
                _ev.emit("failover", session_id=req.session_id,
                         trace_id=trace_id, hop=hop.key, old_peer=old_peer,
                         new_peer=replacement)
                try:
                    self._replay(hop, req.session_id, req.sampling, req.max_length)
                except _errors.retryable_types() as replay_exc:
                    # Replacement died too: blacklist it and keep failing
                    # over. Permanent failures (e.g. DeadlineExceeded mid-
                    # replay) propagate — retrying cannot help them.
                    last_exc = replay_exc
                    failed.add(replacement)
                    continue
                if self.settle_seconds:
                    time.sleep(self.settle_seconds)
        raise RuntimeError(
            f"hop {hop.key}: all {MAX_ATTEMPTS} attempts failed"
        ) from last_exc

    def _rediscover(self, hop: Hop) -> str:
        peer = self._rediscover_excluding(
            hop, tuple(self.failed_peers.get(hop.key, set()))
        )
        if peer is None:
            # Every candidate is blacklisted. Failures are often transient
            # (the reference never un-marks a failed peer and can wedge a
            # long-lived client); give recently-failed peers another chance
            # rather than hard-failing with live servers present.
            _ev.emit("blacklist_amnesty", hop=hop.key,
                     cleared=len(self.failed_peers.get(hop.key, ())))
            self.failed_peers.get(hop.key, set()).clear()
            peer = self._rediscover_excluding(hop, ())
        if peer is None:
            raise NoRouteError(f"no replacement for {hop.key}")
        return peer

    def _rediscover_excluding(self, hop: Hop, exclude: Tuple[str, ...]) -> Optional[str]:
        if hop.key == BURST_HOP_KEY:
            # A burst session can only fail over onto another full-span
            # batched peer (burst requests need on-device sampling over the
            # whole model; batched engines DO accept replay since the burst
            # refactor — prefill + multi-token KV-rebuild chunks).
            return self._discover_burst_peer(exclude=exclude)
        # The replacement receives the session's REPLAY journal (is_replay +
        # multi-token chunks), which single-session engines refuse — avoid.
        if self.use_module_routing:
            cands = [
                c for c in self.registry.discover_block(hop.start_block, exclude=exclude,
                                                        model=self.model)
                # The replacement must cover the hop's exact span: downstream
                # hops already hold KV for their own spans.
                if c.start_block <= hop.start_block and c.end_block >= hop.end_block
                and (not hop.expect_token or c.final_stage)
            ]
            cands = _soft_filter(
                cands, lambda c: c.engine not in SESSION_ONLY_ENGINES)
            if not cands:
                return None
            return max(cands, key=lambda c: (c.end_block, c.throughput)).peer_id
        stage_index = int(hop.key.removeprefix("stage"))
        return self.registry.discover_stage(stage_index, exclude=exclude,
                                            model=self.model,
                                            avoid_engine=SESSION_ONLY_ENGINES)

    # ------------------------------------------------------------------
    # Pipeline walk
    # ------------------------------------------------------------------

    def _walk(self, hidden: jnp.ndarray, seq_len: int, cur_len: int,
              session_id: str, *, is_prefill: bool, max_length: int,
              sampling: Optional[SamplingParams] = None,
              generated: Sequence[int] = (), step_seed: int = 0,
              stage_times: Dict[str, float],
              hypo_ids: Optional[Tuple[int, ...]] = None,
              num_logprobs: int = 0,
              draft_tokens: Optional[Tuple[int, ...]] = None,
              start_from_position: Optional[int] = None,
              kind: str = "plain",
              min_context: Optional[int] = None,
              prefix_len: int = 0,
              affinity: Optional[str] = None,
              deadline_at: Optional[float] = None,
              trace_ctx=None) -> StageResponse:
        """Send the activation through every remote hop; return the final
        hop's response: a sampled token, (num_logprobs > 0, beam mode)
        per-row top-N candidates, or (draft_tokens set, speculative mode)
        the verified token run. ``kind`` is the SESSION's routing kind
        (decided once at generate/beam entry, not per step): an exotic
        session's prefill must already route around single-session engines,
        or its later beam/speculative steps land on a peer that refuses
        them."""
        sampling = sampling or SamplingParams()
        phase = "prefill" if is_prefill else "decode"
        # Deep-prompt sessions never push-chain: a relay would need the NEXT
        # hop's prompt slice, which only the client holds (petals' handler
        # likewise sets can_push = not has_prompts,
        # block_functions.py:233).
        if self.use_push_chain and session_id not in self._session_prompts:
            return self._walk_chain(
                hidden, seq_len, cur_len, session_id, is_prefill=is_prefill,
                max_length=max_length, sampling=sampling, generated=generated,
                step_seed=step_seed, stage_times=stage_times,
                draft_tokens=draft_tokens,
                start_from_position=start_from_position,
                deadline_at=deadline_at,
                trace_ctx=trace_ctx,
            )
        tracer = get_tracer()
        # One trace per pipeline step; callers that opened a step-level root
        # (the generate loop) pass it in so stage0 and every hop share the
        # trace_id, others get their own root here.
        own_root = trace_ctx is None
        root = trace_ctx if trace_ctx is not None else tracer.start_span(
            "pipeline_step", kind="client", session_id=session_id, phase=phase)
        cur = hidden
        try:
            for i, hop in enumerate(self.route(kind=kind,
                                               min_context=min_context,
                                               affinity=affinity)):
                wire_ctx = root.wire_context(hop=i) if root else None
                # Per-hop deadline stamp: the budget REMAINING right now —
                # earlier hops' service time has already been spent from it.
                # Expiry raises the typed client error before dialing.
                budget = self._deadline_budget(
                    deadline_at, session_id,
                    trace_id=root.trace_id if root else None,
                    peer=hop.peer_id)
                req = StageRequest(
                    session_id=session_id,
                    hidden=cur,
                    seq_len=seq_len,
                    cur_len=cur_len,
                    is_prefill=is_prefill,
                    max_length=max_length,
                    sampling=sampling,
                    generated_tokens=clip_generated(generated),
                    step_seed=step_seed,
                    start_block=hop.start_block,
                    end_block=hop.end_block,
                    hypo_ids=hypo_ids,
                    num_logprobs=num_logprobs,
                    draft_tokens=draft_tokens,
                    start_from_position=start_from_position,
                    prompts=self._hop_prompts(session_id, hop, cur_len),
                    prefix_len=prefix_len if is_prefill else 0,
                    trace=wire_ctx,
                    deadline_budget_s=budget,
                    priority=self._session_priority.get(session_id),
                )
                hop_span = tracer.start_span(
                    f"hop:{hop.key}", trace_id=root.trace_id,
                    parent_id=root.span_id, kind="client", peer=hop.peer_id,
                    phase=phase) if root else root
                t0 = time.monotonic()
                try:
                    resp = self._call_with_recovery(hop, req)
                except BaseException as exc:
                    hop_span.end(error=repr(exc))
                    raise
                dt = time.monotonic() - t0
                hop_span.end(server=resp.span)
                stage_times[hop.key] = dt
                self._m_stage_time.labels(hop=hop.key, phase=phase).observe(dt)
                # Journal AFTER success: replay then rebuilds exactly the
                # applied history and the failed in-flight step is retried
                # separately. (The reference appends BEFORE the call and
                # replays the full journal including the in-flight entry —
                # `rpc_transport.py:741` vs `:648-654` — re-applying the
                # current step; we fix that.)
                self._journal_append(
                    hop.key, session_id,
                    JournalEntry(np.asarray(cur), seq_len, cur_len,
                                 hypo_ids=hypo_ids),
                )
                if hop.expect_token:
                    if num_logprobs > 0:
                        if not resp.is_beam:
                            raise RuntimeError(
                                f"final hop {hop.key} returned no beam "
                                "candidates"
                            )
                    elif draft_tokens is not None:
                        if not resp.is_speculative:
                            raise RuntimeError(
                                f"final hop {hop.key} returned no verified "
                                "tokens"
                            )
                    elif not resp.is_token:
                        raise RuntimeError(
                            f"final hop {hop.key} returned no token")
                    return resp
                if resp.hidden is None:
                    raise RuntimeError(
                        f"hop {hop.key} returned no hidden states")
                cur = resp.hidden
            raise RuntimeError("route had no final hop")
        finally:
            if own_root:
                root.end()

    # ------------------------------------------------------------------
    # Push-chain walk (petals handler.py:320-350 server→server push): the
    # client makes ONE call per step; servers relay activations hop-to-hop
    # and the final token rides the relay chain back. The journal then holds
    # only stage0 outputs (key "chain") — recovery replays them through a
    # freshly-routed chain, rebuilding every hop's KV at once.
    # ------------------------------------------------------------------

    CHAIN_KEY = "chain"

    def _chain_request(self, hops: List[Hop], hidden, seq_len: int,
                       cur_len: int, session_id: str, *, is_prefill: bool,
                       is_replay: bool, max_length: int,
                       sampling: SamplingParams, generated: Sequence[int],
                       step_seed: int,
                       draft_tokens: Optional[Tuple[int, ...]] = None,
                       start_from_position: Optional[int] = None,
                       deadline_at: Optional[float] = None) -> StageRequest:
        nxt = []
        for h in hops[1:]:
            rec = self.registry.get(h.peer_id)
            entry = {
                "peer_id": h.peer_id,
                "address": getattr(rec, "address", None) if rec else None,
                "start_block": h.start_block,
                "end_block": h.end_block,
            }
            via = getattr(rec, "relay_via", None) if rec else None
            if via:
                # NAT'd next hop: the pushing server must dial its relay
                # VOLUNTEER and stamp relay_to (TcpStageServer._relay does,
                # keyed on relay_via) — the hop's own address is unreachable.
                rrec = self.registry.get(via)
                entry["relay_via"] = via
                entry["address"] = getattr(rrec, "address", None) \
                    if rrec else None
            nxt.append(entry)
        return StageRequest(
            session_id=session_id, hidden=hidden, seq_len=seq_len,
            cur_len=cur_len, is_prefill=is_prefill, is_replay=is_replay,
            max_length=max_length, sampling=sampling,
            generated_tokens=clip_generated(generated), step_seed=step_seed,
            start_block=hops[0].start_block, end_block=hops[0].end_block,
            next_servers=tuple(nxt),
            draft_tokens=draft_tokens,
            start_from_position=start_from_position,
            deadline_budget_s=self._deadline_budget(
                deadline_at, session_id, peer=hops[0].peer_id),
            priority=self._session_priority.get(session_id),
        )

    def _replay_chain(self, hops: List[Hop], session_id: str,
                      sampling: SamplingParams, max_length: int) -> None:
        entries = self.journal.get(self.CHAIN_KEY, {}).get(session_id, [])
        tokens = sum(e.seq_len for e in entries)
        _ev.emit("replay_start", session_id=session_id,
                 peer=hops[0].peer_id, entries=len(entries), tokens=tokens)
        t0 = time.monotonic()
        for i, e in enumerate(entries):
            req = self._chain_request(
                hops, jnp.asarray(e.hidden), e.seq_len, e.cur_len, session_id,
                is_prefill=(i == 0), is_replay=True, max_length=max_length,
                sampling=sampling, generated=(), step_seed=0,
            )
            self.transport.call(hops[0].peer_id, req,
                                timeout=self.request_timeout)
        _ev.emit("replay_done", session_id=session_id,
                 peer=hops[0].peer_id, tokens=tokens,
                 seconds=round(time.monotonic() - t0, 4))

    def _blame_chain_failure(self, hops: List[Hop], exc: Exception) -> None:
        """Blacklist the hop responsible for a chain failure and invalidate
        the cached route. Server-relayed errors carry the true origin peer;
        a bare client-side timeout has no attribution, so probe hop liveness
        to find the dead one (a hung host usually stops accepting
        connections) before defaulting to the entry hop."""
        blame = getattr(exc, "peer_id", None)
        if blame is None and isinstance(exc, TimeoutError):
            blame = next(
                (h.peer_id for h in hops
                 if not self.transport.alive(h.peer_id)), None,
            )
        blame = blame or hops[0].peer_id
        blamed_hop = next((h for h in hops if h.peer_id == blame), hops[0])
        self.failed_peers.setdefault(blamed_hop.key, set()).add(blame)
        _ev.emit("peer_failed", hop=blamed_hop.key, peer=blame,
                 reason=type(exc).__name__)
        self._routes.clear()  # recompute with the blacklist applied
        logger.warning("push chain failed at %s: %s", blame, exc)

    def _walk_chain(self, hidden, seq_len: int, cur_len: int, session_id: str,
                    *, is_prefill: bool, max_length: int,
                    sampling: SamplingParams, generated: Sequence[int],
                    step_seed: int,
                    stage_times: Dict[str, float],
                    draft_tokens: Optional[Tuple[int, ...]] = None,
                    start_from_position: Optional[int] = None,
                    deadline_at: Optional[float] = None,
                    trace_ctx=None) -> StageResponse:
        tracer = get_tracer()
        own_root = trace_ctx is None
        root = trace_ctx if trace_ctx is not None else tracer.start_span(
            "pipeline_step", kind="client", session_id=session_id,
            phase="prefill" if is_prefill else "decode")
        try:
            return self._walk_chain_traced(
                hidden, seq_len, cur_len, session_id, is_prefill=is_prefill,
                max_length=max_length, sampling=sampling, generated=generated,
                step_seed=step_seed, stage_times=stage_times,
                draft_tokens=draft_tokens,
                start_from_position=start_from_position,
                deadline_at=deadline_at, root=root)
        finally:
            if own_root:
                root.end()

    def _walk_chain_traced(self, hidden, seq_len: int, cur_len: int,
                           session_id: str, *, is_prefill: bool,
                           max_length: int, sampling: SamplingParams,
                           generated: Sequence[int], step_seed: int,
                           stage_times: Dict[str, float],
                           draft_tokens: Optional[Tuple[int, ...]],
                           start_from_position: Optional[int],
                           deadline_at: Optional[float] = None,
                           root=None) -> StageResponse:
        tracer = get_tracer()
        touched = self._session_peers.setdefault(session_id, set())
        last_exc: Optional[Exception] = None
        blacklist_cleared = False
        # Chain sessions are ALWAYS exotic-routed: every retry ships
        # is_replay=True (attempt > 0 below) and recovery replays the whole
        # journal through the chain — both refused by the single-session
        # engines, so a batched/sp-preferring chain could never recover from
        # a transient fault (it would blacklist healthy peers until attempts
        # ran out).
        for attempt in range(MAX_ATTEMPTS):
            try:
                hops = self.route(kind="exotic")
            except NoRouteError as exc:
                last_exc = exc
                if blacklist_cleared:
                    continue
                # Every candidate is blacklisted — transient failures must
                # not wedge the client forever (same amnesty as the per-hop
                # path's _rediscover, client.py _rediscover).
                blacklist_cleared = True
                _ev.emit("blacklist_amnesty", session_id=session_id,
                         hop=self.CHAIN_KEY,
                         cleared=sum(len(v)
                                     for v in self.failed_peers.values()))
                self.failed_peers.clear()
                self._routes.clear()
                continue
            touched.update(h.peer_id for h in hops)
            if not self.breaker.allow(hops[0].peer_id):
                # Entry hop's breaker is open: skipping the dial is a
                # retryable failure — blacklist it for this chain and
                # re-route (readmission comes from the breaker's half-open
                # probe, not from clearing the blacklist wholesale).
                last_exc = _BreakerOpen(
                    f"peer {hops[0].peer_id}: circuit breaker open")
                self._m_retries.inc()
                self.failed_peers.setdefault(
                    hops[0].key, set()).add(hops[0].peer_id)
                self._routes.clear()
                continue
            req = self._chain_request(
                hops, hidden, seq_len, cur_len, session_id,
                is_prefill=is_prefill, is_replay=attempt > 0,
                max_length=max_length, sampling=sampling, generated=generated,
                step_seed=step_seed, draft_tokens=draft_tokens,
                start_from_position=start_from_position,
                deadline_at=deadline_at,
            )
            req.trace = root.wire_context(hop=0) if root else None
            chain_span = tracer.start_span(
                "hop:chain", trace_id=root.trace_id, parent_id=root.span_id,
                kind="client", peer=hops[0].peer_id,
                chain_len=len(hops)) if root else root
            t0 = time.monotonic()
            try:
                resp = self.transport.call(
                    hops[0].peer_id, req,
                    # the chain spans len(hops) computes before responding
                    timeout=self.request_timeout * max(1, len(hops)),
                )
                self.breaker.record_success(hops[0].peer_id)
            except DeadlineExceeded:
                chain_span.end(error="deadline")
                raise  # terminal: retrying spends a budget already blown
            except _errors.retryable_types() as exc:
                # Breaker blame prefers the failing COMPONENT over the
                # routing-blamed hop (runtime/errors.py BLAME_BREAKER): a
                # PushChainError whose breaker_peer_id names a relay
                # volunteer opens the VOLUNTEER's breaker (the relayed peer
                # behind it may be perfectly healthy), while
                # _blame_chain_failure below still blacklists the hop so the
                # next route avoids it.
                self.breaker.record_failure(_errors.breaker_blame(
                    exc, getattr(exc, "peer_id", None) or hops[0].peer_id))
                chain_span.end(error=repr(exc))
                last_exc = exc
                self._m_retries.inc()
                _ev.emit("hop_retry", session_id=session_id,
                         trace_id=root.trace_id if root else None,
                         hop=self.CHAIN_KEY, peer=hops[0].peer_id,
                         attempt=attempt + 1,
                         error=f"{type(exc).__name__}: {exc}"[:200])
                self._blame_chain_failure(hops, exc)
                try:
                    new_hops = self.route(kind="exotic")
                    self._replay_chain(new_hops, session_id, sampling,
                                       max_length)
                except NoRouteError as rexc:
                    last_exc = rexc
                    continue
                except _errors.retryable_types() as rexc:
                    # A peer died DURING replay: blame it too so the next
                    # attempt routes around it instead of repeating the
                    # identical failing chain.
                    last_exc = rexc
                    self._blame_chain_failure(new_hops, rexc)
                    continue
                self._m_recoveries.inc()
                _ev.emit("failover", session_id=session_id,
                         trace_id=root.trace_id if root else None,
                         hop=self.CHAIN_KEY, old_peer=hops[0].peer_id,
                         new_peer=new_hops[0].peer_id)
                if self.settle_seconds:
                    time.sleep(self.settle_seconds)
                continue
            dt = time.monotonic() - t0
            chain_span.end(server=resp.span)
            stage_times[self.CHAIN_KEY] = dt
            self._m_stage_time.labels(
                hop=self.CHAIN_KEY,
                phase="prefill" if is_prefill else "decode").observe(dt)
            self._journal_append(
                self.CHAIN_KEY, session_id,
                JournalEntry(np.asarray(hidden), seq_len, cur_len),
            )
            if draft_tokens is not None:
                if not resp.is_speculative:
                    raise RuntimeError("push chain returned no verified tokens")
            elif not resp.is_token:
                raise RuntimeError("push chain returned no token "
                                   "(route must end at the final stage)")
            return resp
        raise RuntimeError(
            f"push chain: all {MAX_ATTEMPTS} attempts failed"
        ) from last_exc

    @property
    def stage0(self):
        with self._stage0_lock:
            if self._stage0_factory is not None:
                self._stage0 = self._stage0_factory()
                self._stage0_factory = None
            return self._stage0

    # ------------------------------------------------------------------
    # Generation (run_rank0, src/main.py:62-227)
    # ------------------------------------------------------------------

    def generate(
        self,
        prompt_ids: Sequence[int],
        max_new_tokens: int = 64,
        *,
        sampling: Optional[SamplingParams] = None,
        eos_token_id: Optional[int] = None,
        session_id: Optional[str] = None,
        max_length: Optional[int] = None,
        speculative_k: int = 0,
        draft_fn=None,
        deep_prompts=None,
        deadline_s: Optional[float] = None,
        burst: int = 0,
    ) -> GenerationResult:
        """``deep_prompts`` ([total_blocks, pre_seq, D]) enables
        inference-time deep prompt tuning: each step, every server injects
        its span's learned prompts at each block's entry (absolute
        positions < pre_seq), matching a monolithic forward with the same
        prompts (``petals/server/block_functions.py:57-65,171-226``). The
        session routes kind="exotic" — batched/sp engines refuse prompts.

        ``speculative_k > 0`` enables speculative decoding: per decode
        round the client drafts up to K tokens (``draft_fn(context, k)``,
        default n-gram prompt lookup — runtime.speculative), ships them as
        one multi-token step, and the final stage verifies — amortizing the
        per-token pipeline round trip the reference pays (its dominant
        latency, SURVEY.md §3.2). Greedy (temperature<=0) verification is
        token-identical to non-speculative greedy decoding; temperature>0
        uses rejection-sampling verification (accept draft i with prob
        p_i(d_i), resample the residual on reject), which preserves the
        sampling distribution exactly.

        ``deadline_s`` sets an end-to-end wall-clock budget for the WHOLE
        generation: each hop is stamped with the seconds remaining, servers
        refuse already-expired work, and an exhausted budget raises
        `DeadlineExceeded` (non-retryable) instead of burning retries on a
        response the caller has stopped waiting for."""
        result: Optional[GenerationResult] = None
        for step in self.generate_stepwise(
                prompt_ids, max_new_tokens, sampling=sampling,
                eos_token_id=eos_token_id, session_id=session_id,
                max_length=max_length, speculative_k=speculative_k,
                draft_fn=draft_fn, deep_prompts=deep_prompts,
                deadline_s=deadline_s, burst=burst):
            if step.done:
                result = step.result
        assert result is not None  # the generator's final yield carries it
        return result

    def generate_stepwise(
        self,
        prompt_ids: Sequence[int],
        max_new_tokens: int = 64,
        *,
        sampling: Optional[SamplingParams] = None,
        eos_token_id: Optional[int] = None,
        session_id: Optional[str] = None,
        max_length: Optional[int] = None,
        speculative_k: int = 0,
        draft_fn=None,
        deep_prompts=None,
        deadline_s: Optional[float] = None,
        deadline_at: Optional[float] = None,
        priority: Optional[float] = None,
        burst: int = 0,
    ) -> Iterator[GenerationStep]:
        """Incremental form of ``generate``: a generator yielding a
        ``GenerationStep`` after the prefill and after every decode round,
        so a caller (the serving gateway) can interleave MANY sessions one
        pipeline step at a time instead of running each back-to-back.
        Token output is identical to ``generate`` — the per-step sampling
        seed is ``self.seed + len(generated)``, purely session-local, so
        interleaving cannot change what any session emits.

        ``deadline_at`` is an ABSOLUTE ``time.monotonic()`` deadline
        (overrides ``deadline_s``): the gateway stamps it at admission so
        queue wait counts against the request's budget. ``priority`` is the
        gateway's tenant priority (lower = more urgent), stamped on every
        StageRequest this session sends. Session bookkeeping (KV leases,
        deep prompts, journal) is released when the generator finishes OR
        is closed early — abandoning it mid-stream cleans up via
        GeneratorExit.

        ``burst > 0`` asks a FULL-SPAN batched final-stage peer to run up
        to that many decode ticks per dispatch (one jitted ``lax.scan``
        with on-device sampling — see runtime.batching ``decode_burst``),
        yielding one GenerationStep per BURST instead of per token. The
        per-tick seed schedule is identical to the sequential path
        (``self.seed + len(generated)``), so tokens are bit-identical;
        when no burst-capable peer is live the session falls back to the
        classic per-step loop (a ``burst_fallback`` event records why)."""
        session_id = session_id or f"sess-{time.monotonic_ns():x}"
        if burst > 0 and (speculative_k > 0 or deep_prompts is not None):
            raise ValueError(
                "burst decode samples on-device and is incompatible with "
                "speculative drafting / deep prompts")
        if deep_prompts is not None:
            self._session_prompts[session_id] = np.asarray(deep_prompts)
        if priority is not None:
            self._session_priority[session_id] = float(priority)
        if deadline_at is None and deadline_s is not None:
            deadline_at = time.monotonic() + deadline_s
        _ev.emit("session_start", session_id=session_id,
                 prompt_len=len(prompt_ids), max_new_tokens=max_new_tokens)
        recoveries_before = self.recoveries
        tokens_out = 0
        # A plain session goes WHOLE to a full-span batched peer when one
        # is live — in N-token bursts with --burst, one token per round
        # trip without — and then needs no client-local stage 0.
        peer = None
        if burst > 0 or (
                speculative_k == 0 and deep_prompts is None
                and not (self.long_context_threshold is not None
                         and len(prompt_ids) >= self.long_context_threshold)):
            peer = self._discover_burst_peer()
        if burst > 0 and peer is None:
            _ev.emit("burst_fallback", session_id=session_id,
                     reason="no full-span batched peer is live")
        if peer is not None:
            steps = self._generate_steps_full_span(
                prompt_ids, max_new_tokens, sampling=sampling,
                eos_token_id=eos_token_id, session_id=session_id,
                max_length=max_length, peer=peer, burst=burst,
                deadline_at=deadline_at)
        else:
            steps = self._generate_steps(
                prompt_ids, max_new_tokens, sampling=sampling,
                eos_token_id=eos_token_id, session_id=session_id,
                max_length=max_length, speculative_k=speculative_k,
                draft_fn=draft_fn, deadline_at=deadline_at)
        try:
            for step in steps:
                tokens_out += len(step.new_tokens)
                yield step
        finally:
            # Error paths included: a failed or abandoned session must not
            # leak its deep-prompt tensor, KV leases, or journal entries.
            self._session_priority.pop(session_id, None)
            self._end_session(session_id)
            _ev.emit("session_end", session_id=session_id,
                     tokens=tokens_out or None,
                     recoveries=self.recoveries - recoveries_before)

    def _generate_steps(
        self,
        prompt_ids: Sequence[int],
        max_new_tokens: int,
        *,
        sampling: Optional[SamplingParams],
        eos_token_id: Optional[int],
        session_id: str,
        max_length: Optional[int],
        speculative_k: int,
        draft_fn,
        deadline_at: Optional[float] = None,
    ) -> Iterator[GenerationStep]:
        # The classic chain visits each stage's span ONCE a token.
        refuse_single_pass(self.cfg, "a route split over stages")
        sampling = sampling or SamplingParams()
        prompt_len = len(prompt_ids)
        dp = self._session_prompts.get(session_id)
        s0 = self.stage0.spec
        # Session kind is fixed at entry: a speculative session's PREFILL
        # must already land on a peer that will take its draft steps
        # (batched peers verify drafts in coalesced rounds; sp peers refuse
        # them); a plain session prefers batched peers; a long-context
        # session prefers sp peers (prefix KV sharded across their mesh).
        if dp is not None:
            kind = "exotic"  # single-session engines refuse deep prompts
        elif speculative_k > 0:
            kind = "spec"
        elif (self.long_context_threshold is not None
              and prompt_len >= self.long_context_threshold):
            kind = "long"
        else:
            kind = "plain"
        max_length = max_length or (
            prompt_len + max_new_tokens
            + (speculative_k if speculative_k > 0 else 0))
        # Prefix-cache-aware replica affinity: a digest of the prompt HEAD
        # (one store grain) steers replica choice via rendezvous hashing,
        # so shared-prefix prompts from any client land on the peer whose
        # store is warm. Exotic/long sessions route by capability instead.
        affinity = None
        if kind in ("plain", "spec"):
            import hashlib

            affinity = hashlib.sha1(
                np.asarray(prompt_ids[:64], np.int32).tobytes()).hexdigest()

        ids = jnp.asarray(np.asarray(prompt_ids, np.int32)[None, :])
        generated: List[int] = []
        stopped_by = "max_tokens"

        # ---- prefill (src/main.py:138-155) ----
        tracer = get_tracer()
        t0 = time.monotonic()
        root = tracer.start_span("pipeline_step", kind="client",
                                 session_id=session_id, phase="prefill")
        s0_span = tracer.start_span(
            "hop:stage0", trace_id=root.trace_id, parent_id=root.span_id,
            kind="client", phase="prefill",
            peer=getattr(self.stage0, "peer_id", "stage0")) if root else root
        s0_resp = self.stage0.forward(StageRequest(
            session_id=session_id, hidden=ids, seq_len=prompt_len, cur_len=0,
            is_prefill=True, max_length=max_length, sampling=sampling,
            prompts=self._span_prompts(session_id, s0.start, s0.end, 0),
            prefix_len=prompt_len,
        ))
        s0_span.end()
        times: Dict[str, float] = {}
        try:
            resp = self._walk(
                s0_resp.hidden, prompt_len, 0, session_id,
                is_prefill=True, max_length=max_length, sampling=sampling,
                generated=generated, step_seed=self.seed, stage_times=times,
                kind=kind, min_context=max_length, prefix_len=prompt_len,
                affinity=affinity, deadline_at=deadline_at, trace_ctx=root,
            )
        finally:
            root.end()
        ttft = time.monotonic() - t0
        self._m_ttft.observe(ttft)
        self.last_prefill_stage_times = times
        generated.append(resp.token_id)
        yield GenerationStep(new_tokens=[int(resp.token_id)])

        # ---- decode loop (src/main.py:164-211) ----
        # ONE loop serves both modes: a plain decode step is the degenerate
        # speculative round with zero drafts (k=0 never drafts, never sends
        # start_from_position — byte-identical requests to the pre-speculative
        # protocol).
        decode_times: List[float] = []
        cur_len = prompt_len
        if draft_fn is None and speculative_k > 0:
            from .speculative import ngram_draft as draft_fn
        context = [int(t) for t in prompt_ids] + generated
        while len(generated) < max_new_tokens:
            if eos_token_id is not None and generated[-1] == eos_token_id:
                stopped_by = "eos"
                break
            if len(generated) >= REPEAT_STOP and len(
                set(generated[-REPEAT_STOP:])
            ) == 1:
                stopped_by = "repeat"
                break
            t0 = time.monotonic()
            drafts = (tuple(draft_fn(context, speculative_k))
                      if speculative_k > 0 else ())
            # start_from_position rides every SPECULATIVE step (stage0's
            # local cache too): it truncates the previous round's rejected
            # overhang before this round appends.
            spos = cur_len if speculative_k > 0 else None
            step_ids = jnp.asarray([[generated[-1], *drafts]], jnp.int32)
            t_in = 1 + len(drafts)
            step_span = tracer.start_span(
                "pipeline_step", kind="client", session_id=session_id,
                phase="decode", step=len(generated))
            try:
                s0_resp = self.stage0.forward(StageRequest(
                    session_id=session_id, hidden=step_ids, seq_len=t_in,
                    cur_len=cur_len, is_prefill=False, max_length=max_length,
                    sampling=sampling, start_from_position=spos,
                    prompts=self._span_prompts(session_id, s0.start, s0.end,
                                               cur_len),
                ))
                times: Dict[str, float] = {}
                resp = self._walk(
                    s0_resp.hidden, t_in, cur_len, session_id,
                    is_prefill=False, max_length=max_length, sampling=sampling,
                    generated=generated, step_seed=self.seed + len(generated),
                    stage_times=times,
                    draft_tokens=drafts if drafts else None,
                    start_from_position=spos,
                    kind=kind, min_context=max_length, affinity=affinity,
                    deadline_at=deadline_at, trace_ctx=step_span,
                )
            finally:
                step_span.end()
            accepted = list(resp.tokens) if drafts else [resp.token_id]
            if drafts:
                # Shrink the round's journal entries to the accepted prefix:
                # replay must rebuild only VALID KV positions.
                self._amend_speculative_journal(session_id, len(accepted))
            dt = time.monotonic() - t0
            decode_times.append(dt)
            self._m_step.observe(dt)
            self._m_tokens.inc(len(accepted))
            self.decode_stage_history.append(times)
            cur_len += len(accepted)   # [g_last] + n_acc drafts consumed
            # Stop conditions are checked PER TOKEN inside the accepted run:
            # a round may overshoot the EOS / 5×-repeat point, and the output
            # must match single-token decoding exactly.
            n_before = len(generated)
            stop = None
            for tok in accepted:
                if len(generated) >= max_new_tokens:
                    break
                generated.append(int(tok))
                context.append(int(tok))
                if eos_token_id is not None and tok == eos_token_id:
                    stop = "eos"
                    break
                if len(generated) >= REPEAT_STOP and len(
                    set(generated[-REPEAT_STOP:])
                ) == 1:
                    stop = "repeat"
                    break
            yield GenerationStep(new_tokens=generated[n_before:])
            if stop is not None:
                stopped_by = stop
                break

        self._m_generations.inc()
        yield GenerationStep(new_tokens=[], done=True,
                             result=GenerationResult(
                                 tokens=generated, ttft_s=ttft,
                                 decode_times_s=decode_times,
                                 stopped_by=stopped_by))

    def _discover_burst_peer(self, exclude: Tuple[str, ...] = ()) -> Optional[str]:
        """A live batched FINAL-stage peer covering the whole model — the
        only server shape that can run a burst (on-device sampling feeds
        the next tick's embedding, so the scan needs blocks 0..total plus
        the head in one process). Highest advertised throughput wins."""
        cands = [
            r for r in self.registry.live_servers(model=self.model)
            if r.engine == "batched" and r.final_stage
            and r.start_block <= 0 and r.end_block >= self.total_blocks
            and r.peer_id not in exclude
            and getattr(r, "state", "online") == "online"
        ]
        if not cands:
            return None
        return max(cands, key=lambda r: r.throughput).peer_id

    def _generate_steps_full_span(
        self,
        prompt_ids: Sequence[int],
        max_new_tokens: int,
        *,
        sampling: Optional[SamplingParams],
        eos_token_id: Optional[int],
        session_id: str,
        max_length: Optional[int],
        peer: str,
        burst: int,
        deadline_at: Optional[float] = None,
    ) -> Iterator[GenerationStep]:
        """Full-span counterpart of ``_generate_steps``: the whole session
        runs on ONE full-span batched peer (``peer``), raw token ids in,
        sampled tokens out — the client computes nothing. With ``burst``
        each decode round ships a ``burst_len`` request the server answers
        with up to N tokens from a single jitted multi-tick dispatch;
        with ``burst == 0`` each round is one per-step decode returning one
        token (same seeds, same stop rules: identical ids). The client's
        per-token stop scan stays authoritative (the device mirrors it
        only to stop WRITING); the journal records one entry per round —
        the tokens whose KV the round wrote — so failover replay rebuilds
        a replacement peer across round boundaries exactly."""
        sampling = sampling or SamplingParams()
        prompt_len = len(prompt_ids)
        max_length = max_length or (prompt_len + max_new_tokens)
        hop = Hop(key=BURST_HOP_KEY, peer_id=peer, start_block=0,
                  end_block=self.total_blocks, expect_token=True)
        generated: List[int] = []
        stopped_by = "max_tokens"

        # ---- prefill: raw prompt ids straight to the full-span peer ----
        t0 = time.monotonic()
        ids = np.asarray(prompt_ids, np.int32)[None, :]
        resp = self._call_with_recovery(hop, StageRequest(
            session_id=session_id, hidden=ids,
            seq_len=prompt_len, cur_len=0, is_prefill=True,
            max_length=max_length, sampling=sampling, step_seed=self.seed,
            start_block=hop.start_block, end_block=hop.end_block,
            prefix_len=prompt_len,
            deadline_budget_s=self._deadline_budget(
                deadline_at, session_id, peer=hop.peer_id),
            priority=self._session_priority.get(session_id),
        ))
        if not resp.is_token:
            raise RuntimeError(
                f"full-span peer {hop.peer_id} returned no prefill token")
        self._journal_append(hop.key, session_id,
                             JournalEntry(ids, prompt_len, 0))
        ttft = time.monotonic() - t0
        self._m_ttft.observe(ttft)
        generated.append(int(resp.token_id))
        yield GenerationStep(new_tokens=[generated[-1]])

        # ---- decode loop: one burst, or one token, per round ----
        decode_times: List[float] = []
        cur_len = prompt_len
        while len(generated) < max_new_tokens:
            # Host stop rules FIRST, same order as the sequential loop —
            # the burst's last emitted token may be an EOS/repeat the
            # device could not act on (stops only gate the NEXT tick).
            if eos_token_id is not None and generated[-1] == eos_token_id:
                stopped_by = "eos"
                break
            if len(generated) >= REPEAT_STOP and len(
                set(generated[-REPEAT_STOP:])
            ) == 1:
                stopped_by = "repeat"
                break
            t0 = time.monotonic()
            resp = self._call_with_recovery(hop, StageRequest(
                session_id=session_id,
                hidden=np.asarray([[generated[-1]]], np.int32),
                seq_len=1, cur_len=cur_len, is_prefill=False,
                max_length=max_length, sampling=sampling,
                generated_tokens=clip_generated(generated),
                step_seed=self.seed + len(generated),
                start_block=hop.start_block, end_block=hop.end_block,
                burst_len=burst,
                burst_budget=min(burst, max_new_tokens - len(generated)),
                eos_token_id=eos_token_id if burst else None,
                deadline_budget_s=self._deadline_budget(
                    deadline_at, session_id, peer=hop.peer_id),
                priority=self._session_priority.get(session_id),
            ))
            if burst and not resp.is_burst:
                raise RuntimeError(
                    f"burst peer {hop.peer_id} returned no token block")
            if not burst and not resp.is_token:
                raise RuntimeError(
                    f"full-span peer {hop.peer_id} returned no token")
            toks = (list(resp.burst_tokens) if burst
                    else [int(resp.token_id)])
            # Journal the round's KV footprint: the carried-in token plus
            # every emitted token except the last (whose KV the device has
            # not written — it is the NEXT round's carry).
            self._journal_append(hop.key, session_id, JournalEntry(
                np.asarray([[generated[-1], *toks[:-1]]], np.int32),
                len(toks), cur_len))
            dt = time.monotonic() - t0
            decode_times.append(dt)
            self._m_step.observe(dt)
            self._m_tokens.inc(len(toks))
            cur_len += len(toks)
            # Per-token truncation scan, identical to the sequential loop:
            # the device may legally overshoot the host's stop point by
            # ticks it could not see (cap mid-window) — never emit those.
            n_before = len(generated)
            stop = None
            for tok in toks:
                if len(generated) >= max_new_tokens:
                    break
                generated.append(int(tok))
                if eos_token_id is not None and tok == eos_token_id:
                    stop = "eos"
                    break
                if len(generated) >= REPEAT_STOP and len(
                    set(generated[-REPEAT_STOP:])
                ) == 1:
                    stop = "repeat"
                    break
            yield GenerationStep(new_tokens=generated[n_before:])
            if stop is not None:
                stopped_by = stop
                break

        self._m_generations.inc()
        yield GenerationStep(new_tokens=[], done=True,
                             result=GenerationResult(
                                 tokens=generated, ttft_s=ttft,
                                 decode_times_s=decode_times,
                                 stopped_by=stopped_by))

    def _amend_speculative_journal(self, session_id: str, keep: int) -> None:
        """Truncate the just-journaled speculative entries to the accepted
        prefix (`keep` = n_accepted + 1 positions: the last real token plus
        the accepted drafts). Rejected positions must never be replayed into
        a replacement peer — contiguity is preserved because the next round's
        cur_len advances by exactly `keep`."""
        keys = ([self.CHAIN_KEY] if self.use_push_chain
                else [hop.key for hops in self._routes.values()
                      for hop in hops])
        for key in keys:
            entries = self.journal.get(key, {}).get(session_id)
            if entries:
                e = entries[-1]
                if e.seq_len > keep:
                    entries[-1] = JournalEntry(
                        e.hidden[:, :keep], keep, e.cur_len, e.hypo_ids)

    # ------------------------------------------------------------------
    # Beam search (client-side bookkeeping; servers reorder KV by hypo_ids —
    # petals backend.py:154-158 — and the final stage returns top-N logprobs)
    # ------------------------------------------------------------------

    def beam_search(
        self,
        prompt_ids: Sequence[int],
        max_new_tokens: int = 64,
        num_beams: int = 4,
        *,
        length_penalty: float = 1.0,
        eos_token_id: Optional[int] = None,
        session_id: Optional[str] = None,
        max_length: Optional[int] = None,
    ) -> "BeamResult":
        """Distributed beam search. The session holds num_beams KV rows on
        every stage; each step ships hypo_ids so servers reorder their rows
        to match the surviving hypotheses before computing. The prompt is
        prefilled ONCE at batch 1 — the first decode step's hypo_ids
        ``(0,)*num_beams`` expands every stage's KV to num_beams rows, so no
        stage ever runs the (num_beams-1)× redundant identical prefill."""
        if self.use_push_chain:
            raise ValueError("beam search uses the per-hop walk; disable "
                             "use_push_chain")
        session_id = session_id or f"beam-{time.monotonic_ns():x}"
        prompt_len = len(prompt_ids)
        max_length = max_length or (prompt_len + max_new_tokens)
        nb = num_beams
        topn = 2 * nb  # candidate pool per row (HF convention)

        ids = jnp.asarray(np.asarray(prompt_ids, np.int32))[None, :]
        t0 = time.monotonic()
        s0_resp = self.stage0.forward(StageRequest(
            session_id=session_id, hidden=ids, seq_len=prompt_len, cur_len=0,
            is_prefill=True, max_length=max_length,
        ))
        times: Dict[str, float] = {}
        resp = self._walk(
            s0_resp.hidden, prompt_len, 0, session_id, is_prefill=True,
            max_length=max_length, num_logprobs=topn, stage_times=times,
            kind="exotic",
        )
        ttft = time.monotonic() - t0
        self._m_ttft.observe(ttft)
        self.last_prefill_stage_times = times

        def norm(score: float, length: int) -> float:
            return score / (max(length, 1) ** length_penalty)

        # All prefill rows are identical: seed the beams from row 0, applying
        # the same EOS policy as every later step (an EOS first token is a
        # finished 1-token hypothesis, not a live beam).
        beams: List[List[int]] = []
        scores: List[float] = []
        finished: List[Tuple[float, List[int]]] = []
        for tok, lp in zip(resp.top_tokens[0], resp.top_logprobs[0]):
            if eos_token_id is not None and tok == eos_token_id:
                finished.append((norm(float(lp), 1), [int(tok)]))
                continue
            beams.append([int(tok)])
            scores.append(float(lp))
            if len(beams) == nb:
                break
        # The prefill left ONE KV row; the first decode step's (0,)*nb
        # "reorder" expands it to nb beam rows on every stage.
        identity = tuple(range(nb))
        parents = (0,) * nb
        cur_len = prompt_len

        for _ in range(1, max_new_tokens):
            # Identity reorders carry no information; normalizing them to
            # None keeps journal entries coalescible without composition.
            hypo = None if parents == identity else parents
            step_ids = jnp.asarray(
                np.asarray([b[-1] for b in beams], np.int32)[:, None]
            )
            s0_resp = self.stage0.forward(StageRequest(
                session_id=session_id, hidden=step_ids, seq_len=1,
                cur_len=cur_len, is_prefill=False, max_length=max_length,
                hypo_ids=hypo,
            ))
            times = {}
            resp = self._walk(
                s0_resp.hidden, 1, cur_len, session_id,
                is_prefill=False, max_length=max_length, num_logprobs=topn,
                hypo_ids=hypo, stage_times=times, kind="exotic",
            )
            self.decode_stage_history.append(times)
            cur_len += 1

            cands = []
            for i in range(nb):
                for tok, lp in zip(resp.top_tokens[i], resp.top_logprobs[i]):
                    cands.append((scores[i] + float(lp), i, int(tok)))
            cands.sort(key=lambda c: c[0], reverse=True)

            new_beams, new_scores, new_parents = [], [], []
            for score, parent, tok in cands:
                if eos_token_id is not None and tok == eos_token_id:
                    finished.append(
                        (norm(score, len(beams[parent]) + 1),
                         beams[parent] + [tok])
                    )
                    continue
                new_beams.append(beams[parent] + [tok])
                new_scores.append(score)
                new_parents.append(parent)
                if len(new_beams) == nb:
                    break
            beams, scores, parents = new_beams, new_scores, tuple(new_parents)

            if finished and len(finished) >= nb:
                best_live = norm(max(scores), len(beams[0]))
                if max(f[0] for f in finished) >= best_live:
                    break

        for score, beam in zip(scores, beams):
            finished.append((norm(score, len(beam)), beam))
        finished.sort(key=lambda f: f[0], reverse=True)
        self._end_session(session_id)
        return BeamResult(tokens=finished[0][1], score=finished[0][0],
                          num_beams=nb, ttft_s=ttft)

    def _end_session(self, session_id: str) -> None:
        with self._stage0_lock:
            stage0 = self._stage0
        if stage0 is not None:           # never built: nothing to drop
            stage0.drop_session(session_id)
        self._session_prompts.pop(session_id, None)
        # Release the KV lease on every peer that ever held it (best-effort):
        # current route hops PLUS peers abandoned by failover — without this,
        # each generation (or failover) permanently consumes arena budget.
        peers = set(self._session_peers.pop(session_id, ()))
        for hops in self._routes.values():
            peers.update(hop.peer_id for hop in hops)
        for peer_id in peers:
            try:
                self.transport.end_session(peer_id, session_id)
            except Exception:  # a dead peer's lease dies with the peer
                pass
        for sessions in self.journal.values():
            sessions.pop(session_id, None)


def make_server_record(peer_id: str, spec: StageSpec, *, throughput: float = 1.0,
                       cache_tokens_left: Optional[int] = None,
                       model: Optional[str] = None,
                       engine: str = "session") -> ServerRecord:
    """Registry record for a fixed-split stage server (the triple DHT publish
    of ``src/main.py:656-697`` collapsed into one record)."""
    return ServerRecord(
        peer_id=peer_id,
        start_block=spec.start,
        end_block=spec.end,
        throughput=throughput,
        final_stage=spec.is_last,
        stage_index=spec.index,
        cache_tokens_left=cache_tokens_left,
        model=model,
        engine=engine,
    )
