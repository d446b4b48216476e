"""Stage server lifecycle: fixed-split and elastic (load-balancing) modes.

TPU-native counterpart of the reference's server orchestration layer:

  * fixed mode (``src/main.py:243-278,426-555``): serve a statically assigned
    span; register on the placement registry with a TTL and refresh the
    heartbeat every TTL/3;
  * elastic mode (``src/main.py:281-423,558-772`` + vendored
    ``petals/server/server.py:328-384``): scan coverage, run
    `choose_best_blocks` (rule 1) to pick a span, build the stage executor for
    it, probe throughput, serve, and periodically — after a RANDOMIZED delay
    in [0, 2·mean_period), so simultaneous checks don't dogpile
    (``src/main.py:710-744``, ``petals/server/server.py:403-411``) — run
    `should_choose_other_blocks` (rule 2) and re-span when the swarm would
    improve past balance_quality.

Threading model: all state transitions are exposed as synchronous tick
methods (`heartbeat_once`, `maybe_rebalance`) so tests drive them
deterministically — the in-process analogue of the reference's
sleep-loop threads, which are also provided (`start`/`stop`) for real
deployments.
"""

from __future__ import annotations

import logging
import random
import threading
import time
from typing import Any, Callable, Dict, Optional, Sequence

import numpy as np

from ..models.config import ModelConfig
from ..models.partition import ROLE_LAST, ROLE_SEGMENT, StageSpec
from ..scheduling import load_balancing as lb
from ..scheduling.registry import (
    PlacementRegistry,
    ServerRecord,
    ServerState,
)
from ..scheduling.throughput import get_server_throughput
from ..telemetry import catalog as _tm
from ..telemetry import events as _ev
from .executor import StageExecutor
from .transport import LocalTransport, Transport

logger = logging.getLogger(__name__)

Params = Dict[str, Any]
ParamsProvider = Callable[[StageSpec], Params]

# How many likely next-hop peers a server pings per heartbeat
# (petals/server/server.py:760-767 pings the servers of its successor block).
MAX_PINGED_NEXT_SERVERS = 5


def measure_next_server_rtts(
    registry: PlacementRegistry,
    ping: Callable[[ServerRecord], Optional[float]],
    peer_id: str,
    end_block: int,
    max_peers: int = MAX_PINGED_NEXT_SERVERS,
    budget_s: Optional[float] = None,
    model: Optional[str] = None,
) -> Dict[str, float]:
    """Ping the live servers able to serve ``end_block`` (this server's likely
    next hops) and return {peer_id: rtt_seconds}. Unreachable peers are
    omitted — absence, not infinity, so the route planner applies its default
    penalty instead of hard-excluding a peer that merely dropped one ping.
    ``budget_s`` caps the whole sweep (checked between pings): sweeps run
    inside heartbeat loops, and a pile-up of timing-out pings must not
    stretch the inter-refresh gap past the registry TTL."""
    cands = [
        r for r in registry.live_servers(model=model)
        if r.peer_id != peer_id
        and r.start_block <= end_block < r.end_block
    ]
    cands.sort(key=lambda r: r.timestamp, reverse=True)
    deadline = None if budget_s is None else time.monotonic() + budget_s
    rtts: Dict[str, float] = {}
    for rec in cands[:max_peers]:
        if deadline is not None and time.monotonic() >= deadline:
            break
        rtt = ping(rec)
        if rtt is not None:
            rtts[rec.peer_id] = rtt
    return rtts


def derive_num_blocks(
    cfg: ModelConfig,
    *,
    dtype_bytes: int = 2,
    quant: str = "none",
    attn_cache_bytes: int = 1 << 30,
    device=None,
    headroom_fraction: float = 0.15,
    tp: int = 1,
) -> Optional[int]:
    """Server auto-capacity: how many blocks fit THIS device's free memory
    after the KV arena and an activation-headroom reserve — the reference's
    ``_choose_num_blocks`` (``petals/server/server.py:275-326``), which
    budgets weights + attention cache + headroom out of free GPU memory when
    ``--num_blocks`` is omitted.

    Reads ``device.memory_stats()`` (real HBM numbers on TPU; a TPU that
    publishes none is an error). Returns None when the backend publishes no
    byte limit (host CPU) — the caller falls back to its topology
    heuristic, mirroring the reference's behavior on devices it cannot
    introspect."""
    import jax

    from ..models.quant import choose_num_blocks

    device = device or jax.devices()[0]
    stats = getattr(device, "memory_stats", lambda: None)() or {}
    limit = stats.get("bytes_limit")
    if not limit:
        if getattr(device, "platform", None) == "tpu":
            # libtpu publishes bytes_limit (16909336064 on a v5e); a TPU
            # without it is a broken runtime, not something to guess around.
            raise RuntimeError(
                f"{device} publishes no memory_stats()['bytes_limit']; "
                "pass --num_blocks")
        return None
    free = max(0, int(limit) - int(stats.get("bytes_in_use", 0) or 0))
    from ..models.quant import block_bytes

    # TP shards each block's weights AND its KV arena share over tp devices,
    # so the per-DEVICE cost divides by tp (the reference's TP-aware sizing,
    # petals/server/server.py:280-293) — an N-chip host serves ~N× blocks.
    tp = max(int(tp), 1)
    usable = int(free * (1.0 - headroom_fraction)) - attn_cache_bytes // tp
    per = max(block_bytes(cfg, dtype_bytes, quant) // tp, 1)
    if usable < per:
        # The reference raises when even one block does not fit
        # (server.py:275-326); choose_num_blocks floors at 1, which here
        # would log a "budget-checked" count and then OOM at startup.
        raise RuntimeError(
            f"device memory cannot fit one {quant or 'full'}-precision "
            f"block: free={free / 2**30:.2f} GiB, KV arena="
            f"{attn_cache_bytes / 2**30:.2f} GiB, block="
            f"{per / 2**30:.2f} GiB (pass --num_blocks to override, or "
            "shrink the arena / use --quant)")
    # free*tp is per-device math folded into choose_num_blocks' total-budget
    # form: (tp*free*(1-r) - attn) / block == (free*(1-r) - attn/tp) / (block/tp).
    n = choose_num_blocks(
        cfg, free * tp, dtype_bytes=dtype_bytes, quant=quant,
        attn_cache_bytes=attn_cache_bytes,
        reserve_fraction=headroom_fraction,
    )
    logger.info(
        "auto num_blocks=%d (free=%.2f GiB of %.2f GiB per device, tp=%d, "
        "arena=%.2f GiB, quant=%s, %.0f%% headroom)", n, free / 2**30,
        int(limit) / 2**30, tp, attn_cache_bytes / 2**30, quant,
        headroom_fraction * 100)
    return n


def _pinger_from_transport(
    transport,
) -> Optional[Callable[[ServerRecord], Optional[float]]]:
    """A pinger built on the transport's `ping`, or None when the transport
    never overrode the base method (base returns None = unsupported) — so
    servers on ping-less transports publish no RTT table at all instead of
    eternally-empty sweeps."""
    tping = getattr(type(transport), "ping", None)
    if tping is None or tping is Transport.ping:
        return None
    return lambda rec: transport.ping(rec.peer_id)


class ElasticStageServer:
    """One elastic server: owns an executor for its current span and the
    registry records advertising it.

    `params_provider(spec)` returns the parameter shard for a span — backed by
    `slice_stage_params` over in-memory params, or by a per-span checkpoint
    loader (the per-block fetch style of ``petals/server/from_pretrained.py``).
    """

    def __init__(
        self,
        peer_id: str,
        cfg: ModelConfig,
        params_provider: ParamsProvider,
        registry: PlacementRegistry,
        transport: LocalTransport,
        *,
        num_blocks: int,
        total_blocks: Optional[int] = None,
        min_block: int = 0,
        balance_quality: float = 0.75,
        mean_balance_check_period: float = 120.0,
        objective: str = lb.WEAKEST,
        bandwidth_mbps: Optional[float] = None,
        probe_throughput: bool = False,
        rng: Optional[random.Random] = None,
        executor_kwargs: Optional[dict] = None,
        advertise_address: Optional[str] = None,
        warmup: bool = False,
        pinger: Optional[Callable[[ServerRecord], Optional[float]]] = None,
        model: Optional[str] = None,
    ):
        self.peer_id = peer_id
        # Model name scoping every record this server publishes and every
        # swarm query it makes (multi-model registry — src/dht_utils.py:20-31).
        self.model = model
        self.cfg = cfg
        self.params_provider = params_provider
        self.registry = registry
        self.transport = transport
        self.num_blocks = num_blocks
        self.total_blocks = total_blocks or cfg.num_layers
        self.min_block = min_block
        self.balance_quality = balance_quality
        self.mean_balance_check_period = mean_balance_check_period
        self.objective = objective
        self.bandwidth_mbps = bandwidth_mbps
        self.probe_throughput = probe_throughput
        # Extra StageExecutor knobs (offload, chunk budget, ...) applied to
        # every span (re)load — the elastic server rebuilds its executor on
        # rebalance, so these must persist across spans.
        self.executor_kwargs = dict(executor_kwargs or {})
        # Network deployments: the data-plane address to publish in records
        # (None for in-process transports) and whether to pre-compile the hot
        # step shapes on every span (re)load before going ONLINE.
        self.advertise_address = advertise_address
        self.warmup = warmup
        # Seeded default: an unseeded fallback makes rebalance jitter (and
        # thus span layout) run-unique, breaking token-identical soak reruns.
        self._rng = rng or random.Random(0)
        self._np_rng = np.random.default_rng(self._rng.randrange(2**31))

        # RTT probe to a peer; defaults to the transport's ping when the
        # transport actually implements one (LocalTransport / TcpTransport),
        # else disabled. TCP serve mode injects a registry-resolving
        # TcpTransport pinger.
        self._pinger = (pinger if pinger is not None
                        else _pinger_from_transport(transport))
        self.next_server_rtts: Dict[str, float] = {}

        self.executor: Optional[StageExecutor] = None
        self.spec: Optional[StageSpec] = None
        self.throughput: float = 1.0
        self.rebalances: int = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # ------------------------------------------------------------------
    # Span lifecycle
    # ------------------------------------------------------------------

    def _spec_for(self, start: int, end: int) -> StageSpec:
        role = ROLE_LAST if end >= self.total_blocks else ROLE_SEGMENT
        return StageSpec(index=start, role=role, start=start, end=end)

    def choose_span(self) -> StageSpec:
        """Rule 1 over the current live swarm (excluding self)."""
        records = [r for r in self.registry.live_servers(model=self.model)
                   if r.peer_id != self.peer_id]
        blocks = lb.choose_best_blocks(
            self.num_blocks, records, total_blocks=self.total_blocks,
            min_block=self.min_block, objective=self.objective,
        )
        return self._spec_for(blocks[0], blocks[-1] + 1)

    def load_span(self, spec: StageSpec) -> None:
        """(Re)build the executor for a span and advertise it.

        Announce-then-serve ordering mirrors the reference: JOINING is
        published first so concurrent joiners see the claim
        (``petals/server/server.py:468-481``), flipped ONLINE once the
        executor is ready."""
        self.registry.register(ServerRecord(
            peer_id=self.peer_id, start_block=spec.start, end_block=spec.end,
            throughput=self.throughput, state=ServerState.JOINING,
            final_stage=spec.is_last, model=self.model,
        ))
        params = self.params_provider(spec)
        self.executor = StageExecutor(self.cfg, spec, params,
                                      peer_id=self.peer_id,
                                      **self.executor_kwargs)
        if self.warmup:
            self.executor.warmup()
        self.spec = spec
        self.transport.add_peer(self.peer_id, self.executor)
        if self.probe_throughput:
            self.throughput = self._probe()
        self.registry.register(self._record())
        _ev.emit("server_join", peer=self.peer_id,
                 start_block=spec.start, end_block=spec.end)
        logger.info("%s serving blocks [%d, %d) throughput=%.2f",
                    self.peer_id, spec.start, spec.end, self.throughput)

    def _record(self) -> ServerRecord:
        assert self.spec is not None
        return ServerRecord(
            peer_id=self.peer_id,
            start_block=self.spec.start,
            end_block=self.spec.end,
            throughput=self.throughput,
            state=ServerState.ONLINE,
            final_stage=self.spec.is_last,
            cache_tokens_left=(
                self.executor.arena.tokens_left() if self.executor else None
            ),
            address=self.advertise_address,
            next_server_rtts=self._published_rtts(),
            model=self.model,
        )

    def _probe(self) -> float:
        """Self-benchmark: timed batch-1 seq-1 forward through the span
        (``src/main.py:394-403`` -> ``throughput_measurement.py:193``)."""
        import jax.numpy as jnp

        from .messages import StageRequest

        assert self.executor is not None and self.spec is not None
        d = self.cfg.hidden_size
        probe_session = f"__probe__{self.peer_id}"
        n = [0]

        def step():
            n[0] += 1
            sid = f"{probe_session}-{n[0]}"
            self.executor.forward(StageRequest(
                session_id=sid,
                hidden=jnp.zeros((1, 1, d), jnp.float32),
                seq_len=1, cur_len=0, is_prefill=True, max_length=8,
            ))
            self.executor.drop_session(sid)

        return get_server_throughput(
            step, self.cfg.hidden_size, bandwidth_mbps=self.bandwidth_mbps,
            num_blocks=self.spec.num_layers,
        )

    # ------------------------------------------------------------------
    # Ticks (deterministic test surface)
    # ------------------------------------------------------------------

    def start_serving(self) -> None:
        self.load_span(self.choose_span())

    def heartbeat_once(self) -> None:
        """TTL refresh + throughput/cache gossip (``src/main.py:529-537``).

        If the record already expired (missed beats — GC pause, suspend), it
        is RE-CREATED: the reference's heartbeat is a full DHT store each
        time, so a server self-heals back into the swarm; a refresh-only
        heartbeat would leave it serving but invisible forever."""
        if self.spec is None:
            return
        # TTL refresh FIRST, carrying the PREVIOUS beat's RTTs: a slow ping
        # sweep must never delay the refresh past record expiry. Staleness is
        # bounded by one beat (TTL/3); the sweep itself is budgeted (TTL/6)
        # so the inter-refresh gap stays well under the TTL even when every
        # ping times out.
        if not self.registry.heartbeat(
            self.peer_id, throughput=self.throughput,
            cache_tokens_left=(
                self.executor.arena.tokens_left() if self.executor else None
            ),
            next_server_rtts=self._published_rtts(),
        ):
            self.registry.register(self._record())
            _ev.emit("server_rejoin", peer=self.peer_id)
        _tm.get("server_heartbeats_total").inc()
        self.ping_next_servers()

    def _published_rtts(self) -> Optional[Dict[str, float]]:
        """What to advertise: None when pinging is unsupported or there is no
        next hop (nothing to say — the registry treats None as 'no update');
        otherwise the latest sweep AS IS, because an EMPTY sweep must be
        published to retract stale RTTs after links degrade."""
        if (self._pinger is None or self.spec is None or self.spec.is_last
                or self.spec.end >= self.total_blocks):
            return None
        return dict(self.next_server_rtts)

    def ping_next_servers(self) -> Dict[str, float]:
        """Measure RTT to likely next-hop peers (the announcer's
        ``_ping_next_servers``, ``petals/server/server.py:760-767``). Final
        stages have no next hop; a server without a pinger publishes none."""
        if (self.spec is None or self.spec.is_last or self._pinger is None
                or self.spec.end >= self.total_blocks):
            self.next_server_rtts = {}
        else:
            self.next_server_rtts = measure_next_server_rtts(
                self.registry, self._pinger, self.peer_id, self.spec.end,
                budget_s=self.registry.ttl / 6.0, model=self.model)
        return self.next_server_rtts

    def maybe_rebalance(self) -> bool:
        """Rule 2; on True, tear down and re-span (``src/main.py:405-416``).
        Returns whether a re-span happened."""
        if self.spec is None:
            return False
        records = self.registry.live_servers(model=self.model)
        if not lb.should_choose_other_blocks(
            self.peer_id, records, total_blocks=self.total_blocks,
            balance_quality=self.balance_quality, min_block=self.min_block,
            objective=self.objective, rng=self._np_rng,
        ):
            return False
        logger.info("%s rebalancing away from [%d, %d)",
                    self.peer_id, self.spec.start, self.spec.end)
        old_spec = self.spec
        _ev.emit("rebalance_decision", peer=self.peer_id,
                 from_start=old_spec.start, from_end=old_spec.end)
        t0 = time.monotonic()
        self.shutdown(deregister=True)
        try:
            self.start_serving()
        except Exception as exc:
            # Failed mid-re-span (e.g. the params provider's checkpoint fetch):
            # restore the old span rather than stranding a torn-down server.
            logger.exception("%s: re-span failed, restoring [%d, %d)",
                             self.peer_id, old_spec.start, old_spec.end)
            _ev.emit("rebalance_failed", peer=self.peer_id,
                     error=f"{type(exc).__name__}: {exc}"[:200])
            self.load_span(old_spec)
            return False
        self.rebalances += 1
        _tm.get("server_rebalances_total").inc()
        assert self.spec is not None
        _ev.emit("rebalance_done", peer=self.peer_id,
                 start_block=self.spec.start, end_block=self.spec.end,
                 seconds=round(time.monotonic() - t0, 4))
        return True

    def next_check_delay(self) -> float:
        """Randomized rebalance-check delay in [0, 2·mean_period)
        (``src/main.py:710-744``)."""
        return self._rng.random() * 2.0 * self.mean_balance_check_period

    def shutdown(self, deregister: bool = True) -> None:
        self.transport.remove_peer(self.peer_id)
        if deregister:
            self.registry.unregister(self.peer_id)
        else:
            self.registry.set_state(self.peer_id, ServerState.OFFLINE)
        _ev.emit("server_leave", peer=self.peer_id)
        self.executor = None
        self.spec = None

    # ------------------------------------------------------------------
    # Background loop (deployment surface)
    # ------------------------------------------------------------------

    def start(self) -> None:
        """Serve + heartbeat + randomized rebalance checks in a daemon thread."""
        self.start_serving()
        self._stop.clear()

        def loop():
            next_check = self.next_check_delay()
            elapsed = 0.0
            beat = self.registry.ttl / 3.0
            while not self._stop.wait(beat):
                # One transient failure must not kill the daemon (the
                # reference wraps its heartbeat body too, src/main.py:529-535).
                try:
                    self.heartbeat_once()
                    elapsed += beat
                    if elapsed >= next_check:
                        self.maybe_rebalance()
                        elapsed, next_check = 0.0, self.next_check_delay()
                except Exception:
                    logger.exception("%s: serve-loop tick failed; continuing",
                                     self.peer_id)

        self._thread = threading.Thread(target=loop, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
        self.shutdown(deregister=True)


class FixedStageServer:
    """Fixed-split server: a statically assigned span + heartbeat
    (``src/main.py:243-278``). Thin compared to the elastic server — the span
    never changes; stage_index routing is used by fixed-mode clients."""

    def __init__(
        self,
        peer_id: str,
        cfg: ModelConfig,
        spec: StageSpec,
        params: Params,
        registry: PlacementRegistry,
        transport: LocalTransport,
        *,
        throughput: float = 1.0,
        executor_kwargs: Optional[dict] = None,
        total_blocks: Optional[int] = None,
        pinger: Optional[Callable[[ServerRecord], Optional[float]]] = None,
        model: Optional[str] = None,
    ):
        self.peer_id = peer_id
        self.model = model
        self.spec = spec
        self.registry = registry
        self.transport = transport
        self.throughput = throughput
        self.total_blocks = total_blocks or cfg.num_layers
        self._pinger = (pinger if pinger is not None
                        else _pinger_from_transport(transport))
        self.next_server_rtts: Dict[str, float] = {}
        self.executor = StageExecutor(cfg, spec, params, peer_id=peer_id,
                                      **(executor_kwargs or {}))

    def _record(self) -> ServerRecord:
        return ServerRecord(
            peer_id=self.peer_id, start_block=self.spec.start,
            end_block=self.spec.end, throughput=self.throughput,
            state=ServerState.ONLINE, final_stage=self.spec.is_last,
            stage_index=self.spec.index,
            next_server_rtts=self._published_rtts(),
            model=self.model,
        )

    def start_serving(self) -> None:
        self.transport.add_peer(self.peer_id, self.executor)
        self.registry.register(self._record())
        _ev.emit("server_join", peer=self.peer_id,
                 start_block=self.spec.start, end_block=self.spec.end)

    def _published_rtts(self) -> Optional[Dict[str, float]]:
        # See ElasticStageServer._published_rtts: None = nothing to say,
        # {} = retract stale measurements.
        if (self._pinger is None or self.spec.is_last
                or self.spec.end >= self.total_blocks):
            return None
        return dict(self.next_server_rtts)

    def ping_next_servers(self) -> Dict[str, float]:
        if (self.spec.is_last or self._pinger is None
                or self.spec.end >= self.total_blocks):
            self.next_server_rtts = {}
        else:
            self.next_server_rtts = measure_next_server_rtts(
                self.registry, self._pinger, self.peer_id, self.spec.end,
                budget_s=self.registry.ttl / 6.0, model=self.model)
        return self.next_server_rtts

    def heartbeat_once(self) -> None:
        # Refresh first, measure after (see ElasticStageServer.heartbeat_once).
        if not self.registry.heartbeat(
            self.peer_id, throughput=self.throughput,
            cache_tokens_left=self.executor.arena.tokens_left(),
            next_server_rtts=self._published_rtts(),
        ):
            self.registry.register(self._record())  # self-heal after expiry
            _ev.emit("server_rejoin", peer=self.peer_id)
        _tm.get("server_heartbeats_total").inc()
        self.ping_next_servers()

    def shutdown(self) -> None:
        self.transport.remove_peer(self.peer_id)
        self.registry.unregister(self.peer_id)
        _ev.emit("server_leave", peer=self.peer_id)
