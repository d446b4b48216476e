"""TCP data plane + registry service — the multi-host transport.

Replaces the reference's hivemind stack (Go libp2p daemon + protobuf
``ExpertRequest``/``ExpertResponse`` + msgpack metadata sidecar + Kademlia
DHT; SURVEY.md §2.3/§5.8) with a dependency-free framed protocol:

  frame = MAGIC(4) | header_len(u32) | header JSON | payload | crc32c(u32)

The header carries the verb + the request metadata (exactly the reference's
metadata schema: session_id, seq_len, cur_len, is_prefill, is_replay,
max_length, sampling knobs, generated_tokens[-50:], block range — Appendix B
of SURVEY.md); the payload is the raw activation tensor, fp32 or wire-bf16
(the reference ships fp16 — same halved-payload tradeoff), converted by the
native codec (C++ via ctypes, numpy fallback) and integrity-checked with
CRC-32C (TCP's 16-bit checksum is weak at multi-MB payloads on WAN links).

Components:
  * `TcpStageServer` — serves one `StageExecutor` (verbs: forward,
    end_session, info — `info` mirrors Petals' ``rpc_info``,
    ``petals/server/handler.py:575-592``);
  * `TcpTransport` — the client side of `runtime.transport.Transport`;
    resolves peer addresses from registry records, keeps one persistent
    connection per peer, maps socket errors onto the retryable taxonomy;
  * `RegistryServer`/`RemoteRegistry` — the control plane: a tiny JSON-RPC
    registry every process points at (register/heartbeat/list), replacing
    the Kademlia DHT for discovery + liveness. TTL expiry runs server-side.

The elastic/fault-tolerance machinery (journal replay, failover, LB) is
transport-agnostic and works unchanged on top of this.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import socket
import socketserver
import struct
import threading
import time
from typing import Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from .. import native
from ..ops.sampling import SamplingParams
from ..scheduling.gossip import GossipNode
from ..scheduling.registry import (
    PlacementRegistry,
    ServerRecord,
    dict_to_rec,
    rec_to_dict,
)
from ..telemetry import catalog as _tm
from ..telemetry import events as _ev
from ..telemetry import exposition as _texp
from ..telemetry import get_registry as _get_metrics_registry
from ..telemetry import get_tracer
from ..telemetry.profiling import get_profiler as _get_profiler
from ..telemetry.profiling import stats_digest as _prof_digest
from . import errors as _errors
from .executor import StageExecutionError, StageExecutor
from .faults import SITE_KINDS, FaultPlan, FaultSocket
from .messages import BackwardRequest, StageRequest, StageResponse
from .task_pool import StageRuntime, TaskRejected
from .transport import (
    DeadlineExceeded,
    PeerUnavailable,
    PushChainError,
    Transport,
)

logger = logging.getLogger(__name__)

MAGIC = b"MPT1"
MAX_FRAME = 1 << 30
# Payloads beyond this are STREAMED as per-chunk-CRC'd segments (the
# reference splits at DEFAULT_MAX_MSG_SIZE, src/rpc_transport.py:551-562):
# progressive transfer with bounded sender memory (no giant concat copy),
# early corruption detection, and no hard 1 GiB payload ceiling.
CHUNK_SIZE = 64 * 1024 * 1024
MAX_PAYLOAD = 8 << 30          # 8 GiB sanity cap on a chunked payload
# CRC-valid bytes a chunked sender must commit before the receiver trusts
# the header-declared total enough to preallocate the full buffer. The
# effective threshold scales with the declared total (see _recv_frame), so a
# hostile sender's memory amplification is bounded by PREALLOC_AMP regardless
# of how large a total it declares.
PREALLOC_COMMIT = 128 * 1024 * 1024
PREALLOC_AMP = 8


@_errors.register
class WireError(ConnectionError):
    """Malformed or corrupted frame (retryable via its ConnectionError
    ancestor's catalog row: corruption fails closed and replays)."""


# ---------------------------------------------------------------------------
# Framing
# ---------------------------------------------------------------------------

def _send_frame(sock: socket.socket, header: dict, payload: bytes = b"") -> None:
    if len(payload) > CHUNK_SIZE:
        # Chunked transfer: the base frame carries an empty payload and a
        # "chunked" descriptor; the chunks follow as [len | bytes | crc32c]
        # segments. Each chunk is integrity-checked independently, so a
        # corrupt segment of a multi-GB activation is caught after one
        # chunk, not after the whole transfer.
        header = dict(header,
                      chunked={"total": len(payload), "chunk": CHUNK_SIZE})
        hdr = json.dumps(header).encode()
        sock.sendall(MAGIC + struct.pack("<I", len(hdr)) + hdr
                     + struct.pack("<I", 0) + struct.pack("<I", native.crc32c(b"")))
        mv = memoryview(payload)
        for off in range(0, len(payload), CHUNK_SIZE):
            chunk = bytes(mv[off:off + CHUNK_SIZE])
            sock.sendall(struct.pack("<I", len(chunk)))
            sock.sendall(chunk)
            sock.sendall(struct.pack("<I", native.crc32c(chunk)))
        return
    hdr = json.dumps(header).encode()
    crc = native.crc32c(payload)
    sock.sendall(
        MAGIC + struct.pack("<I", len(hdr)) + hdr
        + struct.pack("<I", len(payload)) + payload + struct.pack("<I", crc)
    )


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("peer closed connection")
        buf.extend(chunk)
    return bytes(buf)


def _recv_frame(sock: socket.socket) -> Tuple[dict, bytes]:
    # Returns bytes for ordinary frames; a reassembled chunked payload may be
    # a bytearray (bytes-like) to avoid a multi-GiB defensive copy.
    magic = _recv_exact(sock, 4)
    if magic != MAGIC:
        raise WireError(f"bad magic {magic!r}")
    (hlen,) = struct.unpack("<I", _recv_exact(sock, 4))
    if hlen > MAX_FRAME:
        raise WireError(f"oversized header {hlen}")
    try:
        header = json.loads(_recv_exact(sock, hlen))
    except ValueError as exc:
        # Corrupted-but-magic-valid header: classify as a wire fault so every
        # caller's ConnectionError taxonomy (drop + failover) applies, instead
        # of a JSONDecodeError escaping alive()/call() and leaving the
        # desynced socket pooled.
        raise WireError(f"undecodable header: {exc}") from exc
    (plen,) = struct.unpack("<I", _recv_exact(sock, 4))
    if plen > MAX_FRAME:
        raise WireError(f"oversized payload {plen}")
    payload = _recv_exact(sock, plen)
    (crc,) = struct.unpack("<I", _recv_exact(sock, 4))
    if crc != native.crc32c(payload):
        raise WireError("payload checksum mismatch")
    ch = header.get("chunked")
    if ch:
        total = int(ch["total"])
        if not 0 <= total <= MAX_PAYLOAD:
            raise WireError(f"oversized chunked payload {total}")
        # Preallocating the header-declared total up front would let a
        # hostile 100-byte frame force a MAX_PAYLOAD-sized allocation before
        # committing a single chunk byte (remote OOM), so the full buffer is
        # only allocated once the sender has committed PREALLOC_COMMIT bytes
        # of CRC-valid data; until then chunks accumulate in a list. Writing
        # the tail in place (no trailing bytes(buf) copy) keeps peak memory
        # at ~total instead of ~2x total for multi-GiB payloads.
        chunks: list = []
        buf: Optional[bytearray] = None
        off = 0
        while off < total:
            (clen,) = struct.unpack("<I", _recv_exact(sock, 4))
            if clen == 0 or clen > MAX_FRAME or off + clen > total:
                raise WireError(f"bad chunk length {clen} at offset {off}")
            chunk = _recv_exact(sock, clen)
            (ccrc,) = struct.unpack("<I", _recv_exact(sock, 4))
            if ccrc != native.crc32c(chunk):
                raise WireError(f"chunk checksum mismatch at offset {off}")
            if buf is not None:
                buf[off:off + clen] = chunk
            else:
                chunks.append(chunk)
                if off + clen >= min(total, max(PREALLOC_COMMIT,
                                                total // PREALLOC_AMP)):
                    buf = bytearray(total)
                    pos = 0
                    for c in chunks:
                        buf[pos:pos + len(c)] = c
                        pos += len(c)
                    chunks = []
            off += clen
        # No trailing copy of the preallocated buffer: every consumer
        # (np.frombuffer, socket.sendall, slicing in _decode_tensors) takes
        # any bytes-like object, and bytes(buf) would briefly double memory
        # at the exact payload sizes this path exists to support.
        payload = b"".join(chunks) if buf is None else buf
        # The reassembled payload replaces the (empty) chunked one — drop the
        # descriptor so a relayed re-send of this header re-derives framing
        # from the actual payload size instead of replaying a stale one.
        header.pop("chunked", None)
    return header, payload


def _encode_tensor(arr: np.ndarray, wire_dtype: str) -> Tuple[dict, bytes]:
    meta = {"shape": list(arr.shape)}
    if arr.dtype == np.int32:
        meta["dtype"] = "int32"
        return meta, np.ascontiguousarray(arr).tobytes()
    if wire_dtype == "bf16":
        meta["dtype"] = "bf16"
        return meta, native.fp32_to_bf16_bytes(np.asarray(arr, np.float32))
    meta["dtype"] = "f32"
    return meta, np.ascontiguousarray(arr, np.float32).tobytes()


def _decode_tensor(meta: dict, payload: bytes) -> np.ndarray:
    shape = tuple(meta["shape"])
    if meta["dtype"] == "int32":
        return np.frombuffer(payload, np.int32).reshape(shape)
    if meta["dtype"] == "bf16":
        return native.bf16_bytes_to_fp32(payload, shape)
    return np.frombuffer(payload, np.float32).reshape(shape).copy()


def _encode_tensors(arrs, wire_dtype) -> Tuple[list, bytes]:
    """Pack several tensors into one payload; each meta gains 'nbytes'.

    ``wire_dtype`` may be one string (uniform) or a PER-TENSOR list — the
    petals handler's schema-driven per-tensor compression choice
    (``petals/server/handler.py:411-432``): e.g. activations ride bf16
    while learned prompts / gradients in the same payload stay f32. The
    decode side needs no flag — every meta already records its own dtype.
    """
    if isinstance(wire_dtype, str):
        wire_dtype = [wire_dtype] * len(arrs)
    if len(wire_dtype) != len(arrs):
        raise WireError(
            f"{len(wire_dtype)} wire dtypes for {len(arrs)} tensors")
    metas, chunks = [], []
    for arr, wd in zip(arrs, wire_dtype):
        meta, body = _encode_tensor(np.asarray(arr), wd)
        meta["nbytes"] = len(body)
        metas.append(meta)
        chunks.append(body)
    return metas, b"".join(chunks)


def _decode_tensors(metas: list, payload: bytes) -> list:
    out, off = [], 0
    for meta in metas:
        n = meta["nbytes"]
        out.append(_decode_tensor(meta, payload[off:off + n]))
        off += n
    return out


def _request_header(req: StageRequest, tensor_meta: dict,
                    model: Optional[str] = None,
                    prompts_meta: Optional[dict] = None) -> dict:
    hdr = {
        "verb": "forward",
        "session_id": req.session_id,
        "seq_len": req.seq_len,
        "cur_len": req.cur_len,
        "is_prefill": req.is_prefill,
        "is_replay": req.is_replay,
        "max_length": req.max_length,
        "temperature": req.sampling.temperature,
        "top_p": req.sampling.top_p,
        "top_k": req.sampling.top_k,
        "repetition_penalty": req.sampling.repetition_penalty,
        "generated_tokens": list(req.generated_tokens),
        "step_seed": req.step_seed,
        "start_block": req.start_block,
        "end_block": req.end_block,
        "next_servers": list(req.next_servers),
        "hypo_ids": None if req.hypo_ids is None else list(req.hypo_ids),
        "num_logprobs": req.num_logprobs,
        "start_from_position": req.start_from_position,
        "draft_tokens": (None if req.draft_tokens is None
                         else list(req.draft_tokens)),
        "tensor": tensor_meta,
    }
    if req.prefix_len:
        # Prompt-prefix sharing marker (runtime.prefix_cache); absent for
        # the common case so legacy peers see byte-identical headers.
        hdr["prefix_len"] = req.prefix_len
    if req.trace is not None:
        # Trace context (telemetry.tracing): absent unless the client runs
        # with tracing on, so legacy peers see byte-identical headers.
        hdr["trace"] = req.trace
    if req.deadline_budget_s is not None:
        # End-to-end deadline budget (seconds remaining at send time);
        # absent unless the caller set a deadline, so legacy peers see
        # byte-identical headers.
        hdr["deadline_budget_s"] = req.deadline_budget_s
    if req.priority is not None:
        # Gateway-assigned tenant priority (lower = more urgent); absent
        # unless a serving gateway stamped one, so legacy peers see
        # byte-identical headers.
        hdr["priority"] = req.priority
    if req.burst_len:
        # Burst decode (runtime.batching burst engine): absent on the
        # classic per-tick path, so legacy peers see byte-identical
        # headers.
        hdr["burst_len"] = req.burst_len
        hdr["burst_budget"] = req.burst_budget
    if req.eos_token_id is not None:
        hdr["eos_token_id"] = req.eos_token_id
    # Model identity echo: the data-plane counterpart of the reference's
    # model-prefixed DHT keys (src/dht_utils.py:20-31). A mis-routed request
    # (wrong model's server) must fail loudly, not produce garbage activations.
    if model is not None:
        hdr["model"] = model
    # Inference-time deep prompts ride as a second payload tensor (the
    # petals handler's optional prompts input, block_functions.py:171-226).
    if prompts_meta is not None:
        hdr["prompts_tensor"] = prompts_meta
    return hdr


def _stage_input(arr: np.ndarray):
    """A decoded frame's tensor as a stage takes it. Token ids (an INTEGER
    tensor: the first stage's input, a burst request's one token, a rider's
    prompt) stay the host array the frame decoded to: the engines read ids
    on the host (`batching._burst_entry` packs them into the round's one
    upload; a prefill uploads its prompt once, padded), so an upload here
    would be read straight back under the adapter's lock with the chip
    idle. Hidden states entering a later stage (a FLOAT tensor) go up
    here, off the lock. The rule is the tensor's dtype, nothing else."""
    if np.issubdtype(arr.dtype, np.integer):
        return arr
    return jnp.asarray(arr)


def _header_to_request(h: dict, payload: bytes) -> StageRequest:
    pr = None
    if h.get("prompts_tensor") is not None:
        arr, pr = _decode_tensors([h["tensor"], h["prompts_tensor"]], payload)
        pr = jnp.asarray(pr)
    else:
        arr = _decode_tensor(h["tensor"], payload)
    return StageRequest(
        session_id=h["session_id"],
        hidden=_stage_input(arr),
        seq_len=h["seq_len"],
        cur_len=h["cur_len"],
        is_prefill=h["is_prefill"],
        is_replay=h.get("is_replay", False),
        max_length=h["max_length"],
        sampling=SamplingParams(
            temperature=h["temperature"], top_p=h["top_p"], top_k=h["top_k"],
            repetition_penalty=h["repetition_penalty"],
        ),
        generated_tokens=tuple(h.get("generated_tokens", ())),
        step_seed=h.get("step_seed", 0),
        start_block=h.get("start_block"),
        end_block=h.get("end_block"),
        next_servers=tuple(h.get("next_servers", ())),
        hypo_ids=(None if h.get("hypo_ids") is None
                  else tuple(h["hypo_ids"])),
        num_logprobs=h.get("num_logprobs", 0),
        start_from_position=h.get("start_from_position"),
        draft_tokens=(None if h.get("draft_tokens") is None
                      else tuple(h["draft_tokens"])),
        model=h.get("model"),
        prompts=pr,
        prefix_len=h.get("prefix_len", 0),
        trace=h.get("trace"),
        deadline_budget_s=h.get("deadline_budget_s"),
        priority=h.get("priority"),
        burst_len=h.get("burst_len", 0),
        burst_budget=h.get("burst_budget", 0),
        eos_token_id=h.get("eos_token_id"),
    )


def _trace_id(req: StageRequest) -> Optional[str]:
    """Trace id riding the request's wire trace context, if any — lets
    flight-recorder events on both sides of a hop correlate with the
    client's distributed trace."""
    trace = getattr(req, "trace", None)
    if isinstance(trace, dict):
        tid = trace.get("trace_id")
        return str(tid) if tid is not None else None
    return None


# ---------------------------------------------------------------------------
# Framed-protocol server base
# ---------------------------------------------------------------------------

class _FramedTcpServer:
    """Threaded TCP server speaking the framed protocol; subclasses implement
    per-frame handling via `_dispatch(sock, header, payload)`.

    `stop()` severs established connections, not just the listener — a
    stopped server must look dead to clients (the failover path depends on
    it). Connections are tracked in `process_request`, which runs on the
    accept-loop thread, so every connection accepted before `shutdown()`
    returns is in the set — no handler-thread startup race.
    """

    def __init__(self, host: str, port: int):
        # Per connection thread: ``t``, the monotonic instant its last
        # frame was read off the socket (`TcpStageServer._run_forward`
        # hands it to the request: ``server_request_leg_seconds``).
        self._arrival = threading.local()
        active_lock = threading.Lock()
        active: set = set()
        self._active_lock, self._active = active_lock, active
        # Chaos layer (runtime.faults). `fault_plan` is the injection hook:
        # None (the default) keeps the serving path on the raw socket with a
        # single attribute read per frame — zero overhead. A plan is armed
        # either in-process (tests) or over the wire via the `fault` admin
        # verb, which is refused unless the operator opted in with
        # `allow_fault_injection` (--allow_fault_injection).
        self.fault_plan: Optional[FaultPlan] = None
        self.fault_side = "server"
        self.allow_fault_injection = False
        outer = self

        class Handler(socketserver.BaseRequestHandler):
            def handle(self):
                sock = self.request
                while True:
                    try:
                        header, payload = _recv_frame(sock)
                    except (ConnectionError, OSError):
                        return
                    outer._arrival.t = time.monotonic()
                    plan = outer.fault_plan
                    if plan is not None:
                        if not isinstance(sock, FaultSocket):
                            # Arm send-side faults for this connection. The
                            # wrapper hashes/compares as the raw socket, so
                            # per-connection state keyed on the dispatch sock
                            # (stream registries) survives the upgrade and
                            # `_on_connection_closed(raw)` still matches.
                            sock = FaultSocket(self.request, plan,
                                               side=outer.fault_side)
                        sock.ctx_verb = header.get("verb")
                        sock.ctx_session = header.get("session_id")
                        rule = plan.fire(
                            "dispatch", ("accept_hang", "delay"),
                            side=outer.fault_side, verb=sock.ctx_verb,
                            session=sock.ctx_session)
                        if rule is not None:
                            time.sleep(rule.delay_s)
                            if rule.kind == "accept_hang":
                                # Swallow the request: the client sees a
                                # stalled-then-dead connection, never a reply.
                                return
                    try:
                        outer._dispatch(sock, header, payload)
                    except (ConnectionError, OSError):
                        return
                    except Exception as exc:  # report, keep serving
                        logger.exception("request failed")
                        try:
                            _send_frame(sock,
                                        {"verb": "error", "message": str(exc)})
                        except OSError:
                            return

        class Server(socketserver.ThreadingTCPServer):
            daemon_threads = True
            allow_reuse_address = True

            def process_request(self, request, client_address):
                with active_lock:
                    active.add(request)
                super().process_request(request, client_address)

            def shutdown_request(self, request):
                with active_lock:
                    active.discard(request)
                outer._on_connection_closed(request)
                super().shutdown_request(request)

        self._server = Server((host, port), Handler)
        self.address = "%s:%d" % self._server.server_address
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._server.serve_forever, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._server.shutdown()
        self._server.server_close()
        with self._active_lock:
            active = list(self._active)
        for sock in active:
            # shutdown() only: socketserver's shutdown_request closes the fd
            # once the handler thread returns; closing here too would race
            # fd reuse with threads still blocked in recv().
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass

    def _dispatch(self, sock, header: dict, payload: bytes) -> None:
        raise NotImplementedError

    def _on_connection_closed(self, sock) -> None:
        """Hook: a connection's handler finished (socket about to close)."""

    def _fault_admin(self, header: dict) -> dict:
        """The `fault` admin verb: install/clear/inspect this process's
        FaultPlan over the wire. Refused unless the operator started the
        process with fault injection allowed — a production swarm must not
        accept chaos from any client that can dial it."""
        if not self.allow_fault_injection:
            return {"verb": "error",
                    "message": "fault injection disabled "
                               "(start with --allow_fault_injection)"}
        action = header.get("action", "install")
        if action == "clear":
            self.fault_plan = None
            return {"verb": "ok", "installed": False}
        if action == "report":
            plan = self.fault_plan
            return {"verb": "fault_report",
                    "installed": plan is not None,
                    "firings": [] if plan is None else plan.report()}
        self.fault_plan = FaultPlan.from_dict(header.get("plan") or {})
        return {"verb": "ok", "installed": True,
                "rules": len(self.fault_plan.rules)}


# ---------------------------------------------------------------------------
# Stage server
# ---------------------------------------------------------------------------

class RequestLog:
    """Structured per-request records (the reference's ``_log_request``,
    ``petals/server/handler.py:549-573``, which logs
    ``method(blocks=a:b, remote_peer=...xxxxxx)`` per RPC — exceeded here:
    every record carries verb, session, peer address, request size,
    duration, and outcome, goes to the ``...request_log`` logger as a
    greppable key=value line, AND lands in a bounded ring surfaced by the
    ``info`` verb so an operator can ask a live server for its recent
    traffic without log access)."""

    def __init__(self, capacity: int = 256, name: str = "request_log"):
        from collections import deque

        self._ring = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._logger = logging.getLogger(f"{__name__}.{name}")

    def record(self, verb: str, *, session: Optional[str] = None,
               peer: str = "?", tokens: Optional[int] = None,
               cur: Optional[int] = None, dur_ms: Optional[float] = None,
               outcome: str = "ok", detail: Optional[str] = None,
               **fields) -> None:
        rec = {"t": time.time(), "verb": verb, "peer": peer,
               "outcome": outcome}
        if session is not None:
            rec["session"] = session
        if tokens is not None:
            rec["tokens"] = int(tokens)
        if cur is not None:
            rec["cur"] = int(cur)
        if dur_ms is not None:
            rec["dur_ms"] = round(float(dur_ms), 2)
        if detail:
            rec["detail"] = str(detail)[:200]
        rec.update({k: v for k, v in fields.items() if v is not None})
        with self._lock:
            self._ring.append(rec)
        line = " ".join(f"{k}={v}" for k, v in rec.items() if k != "t")
        if outcome != "ok":
            self._logger.warning(line)
        elif verb == "forward":
            # steady-state decode steps must not flood serving logs
            self._logger.debug(line)
        else:
            self._logger.info(line)

    def tail(self, n: int = 20) -> list:
        with self._lock:
            return list(self._ring)[-n:]


class TcpStageServer(_FramedTcpServer):
    """Serves one StageExecutor over TCP (the ``StageConnectionHandler``
    role, ``src/rpc_handler.py:43``).

    With a `StageRuntime`, each connection's handler thread submits compute
    to the prioritized pools and blocks on the future — one compute thread
    owns the chip while N handler threads own the sockets, the reference's
    handlers→Runtime split (``petals/server/server.py:557-671``) without the
    process boundary. Without one, compute runs on the handler thread
    (single-client deployments)."""

    # Relay circuit lease (seconds): an attached NAT'd peer must re-attach
    # (its heartbeat loop does, idempotently) within this window or the
    # volunteer reclaims the slot — a dead relayed peer never pins capacity.
    RELAY_CIRCUIT_TTL = 90.0

    def __init__(self, executor: Optional[StageExecutor],
                 host: str = "127.0.0.1",
                 port: int = 0, wire_dtype: str = "bf16",
                 runtime: Optional["StageRuntime"] = None,
                 compute_timeout: float = 120.0,
                 owns_runtime: bool = True,
                 peer_id: Optional[str] = None,
                 model: Optional[str] = None,
                 allow_fault_injection: bool = False,
                 gossip: Optional[GossipNode] = None,
                 relay_capacity: int = 0):
        # May be swapped at runtime (elastic servers re-span in place) or
        # None during a re-span window — requests then get a retryable
        # stage error and clients fail over / retry.
        self.executor = executor
        # Decentralized control plane: when a GossipNode is attached this
        # server also answers the registry service's verbs from its mirror
        # (any-peer bootstrap) and the `gossip` anti-entropy verb — see
        # _gossip_dispatch. None (the default) keeps the server data-plane
        # only, exactly the pre-gossip behavior.
        self.gossip = gossip
        # Stable identity independent of the (swappable) executor: error
        # frames must carry a real peer id even mid-re-span, or push-chain
        # clients blacklist a placeholder and never route around us.
        self.peer_id = peer_id or (executor.peer_id if executor else None)
        # Which model this server's weights belong to. Tagged requests from a
        # different model are rejected before touching the executor — the
        # data-plane enforcement of the registry's model scoping (ADVICE r2:
        # _model_ok alone cannot stop a mis-constructed client from shipping
        # model-A activations into model-B blocks).
        self.model = model
        self.wire_dtype = wire_dtype
        self.runtime = runtime
        self.compute_timeout = compute_timeout
        # addr -> (socket, per-connection send/recv lock)
        self._relay_conns: Dict[str, tuple] = {}
        self._relay_lock = threading.Lock()
        # NAT relay volunteering (petals/server/reachability.py): how many
        # unreachable peers this server will forward for (0 = not a
        # volunteer; attaches beyond capacity are shed with an error frame).
        # _relay_targets maps an attached peer_id -> (its relay-dialable
        # address, circuit expiry). Circuits are leases: the relayed peer
        # re-attaches on its heartbeat cadence, so a dead peer's slot frees
        # itself and capacity is never permanently consumed.
        self.relay_capacity = int(relay_capacity)
        self._relay_targets: Dict[str, tuple] = {}
        # Persistent inference streams (petals handler.py:132-308): per
        # CONNECTION, session_id -> stream state (metadata shipped once at
        # stream_open; steady-state steps carry only deltas). Keyed by the
        # connection's socket object; cleaned up when the connection dies.
        self._streams: Dict[object, Dict[str, dict]] = {}
        self._streams_lock = threading.Lock()
        self.stream_opens = 0      # observability: full-metadata (re)opens
        self.stream_steps = 0      # observability: delta-only steps
        # Structured per-request records (_log_request parity; the ring's
        # tail rides the info verb).
        self.request_log = RequestLog()
        # Several stage servers on one host may SHARE one runtime (one chip,
        # one compute thread): only the owner may start/stop it, otherwise an
        # elastic teardown of server A would kill server B's compute.
        self.owns_runtime = owns_runtime
        super().__init__(host, port)
        # After super().__init__ (which defaults it off): opt-in gate for
        # the `fault` admin verb (runtime.faults chaos layer).
        self.allow_fault_injection = allow_fault_injection

    def _compute(self, kind: str, fn, *args, size: int = 1,
                 timeout: Optional[float] = None,
                 priority: Optional[float] = None):
        budget = (self.compute_timeout if timeout is None
                  else min(timeout, self.compute_timeout))
        if self.runtime is None:
            return fn(*args)
        kwargs = {} if priority is None else {"priority": priority}
        return self.runtime.call(kind, fn, *args, size=size, timeout=budget,
                                 **kwargs)

    def _relay(self, nxt: dict, nreq: StageRequest) -> Tuple[dict, bytes]:
        """Send a push-chain request to the next hop, return its raw response
        frame for verbatim upstream relay. Connections are pooled per address
        (decode pushes one small tensor per token — a fresh TCP connect per
        step would add an RTT per hop per token, cancelling the feature's
        point on WAN links); a stale pooled socket gets one reconnect."""
        addr = nxt.get("address")
        if not addr:
            raise ConnectionError(f"no address for push target {nxt}")
        arr = np.asarray(nreq.hidden)
        meta, body = _encode_tensor(arr, self.wire_dtype)
        # Propagate the ORIGINATING client's tag when it has one — an
        # untagged legacy hop relaying with only self.model (None) would
        # strip the tag from the rest of the chain.
        hdr = _request_header(
            nreq, meta,
            model=(nreq.model if nreq.model is not None else self.model))
        if nxt.get("relay_via"):
            # NAT'd next hop: `addr` is its relay VOLUNTEER's address (the
            # route planner resolved it); relay_to tells the volunteer which
            # attached circuit this frame is for.
            hdr["relay_to"] = nxt.get("peer_id")
        # The downstream response covers the REST of the chain's computes.
        timeout = self.compute_timeout * (1 + len(nreq.next_servers))
        for fresh in (False, True):
            sock, lock = self._relay_sock(addr, fresh)
            try:
                # Per-connection lock: concurrent handler threads relaying to
                # the same next hop must not interleave frames on one socket.
                with lock:
                    sock.settimeout(timeout)
                    _send_frame(sock, hdr, body)
                    return _recv_frame(sock)
            except (ConnectionError, OSError):
                self._drop_relay(addr, sock)
                if fresh:
                    raise
        raise ConnectionError("unreachable")  # pragma: no cover

    def _relay_sock(self, addr: str, fresh: bool):
        """`fresh` only runs after `_drop_relay` removed the failed socket, so
        ANY pooled entry seen here is a newer reconnect (possibly another
        thread's) and always usable — never displace it (the other thread may
        be mid-frame on it, and nothing would ever close the displaced
        socket)."""
        del fresh  # retry safety comes from _drop_relay, not a forced redial
        with self._relay_lock:
            entry = self._relay_conns.get(addr)
        if entry is not None:
            return entry
        # Connect OUTSIDE the pool lock (a slow/unresponsive host must not
        # stall relays to every other address for the connect timeout).
        host, port = addr.rsplit(":", 1)
        sock = socket.create_connection((host, int(port)), timeout=5.0)
        new_entry = (sock, threading.Lock())
        with self._relay_lock:
            existing = self._relay_conns.get(addr)
            if existing is not None:
                winner = existing  # concurrent thread reconnected first
            else:
                self._relay_conns[addr] = new_entry
                winner = new_entry
        if winner is not new_entry:
            try:
                sock.close()
            except OSError:
                pass
        return winner

    def _drop_relay(self, addr: str, sock: socket.socket) -> None:
        with self._relay_lock:
            entry = self._relay_conns.get(addr)
            if entry is not None and entry[0] is sock:
                del self._relay_conns[addr]
        try:
            sock.close()
        except OSError:
            pass

    # ------------------------------------------------------------------
    # NAT relay volunteering (petals/server/reachability.py)
    # ------------------------------------------------------------------

    def _prune_relay_targets_locked(self, now: float) -> None:
        expired = [p for p, (_, exp) in self._relay_targets.items()
                   if now >= exp]
        for p in expired:
            del self._relay_targets[p]

    def _relay_attach(self, sock, header: dict) -> None:
        """Open (or refresh) a relay circuit for an unreachable peer. The
        peer sends the address the VOLUNTEER can dial it at — typically its
        bind address, reachable from inside the NAT while its advertised
        address is not. Saturated volunteers shed with an error frame so the
        attacher moves on to the next candidate."""
        peer = header.get("peer_id")
        addr = header.get("address")
        if not peer or not addr:
            _send_frame(sock, {"verb": "error",
                               "message": "relay_attach needs peer_id "
                                          "and address"})
            return
        now = time.monotonic()
        with self._relay_lock:
            self._prune_relay_targets_locked(now)
            if (peer not in self._relay_targets
                    and len(self._relay_targets) >= self.relay_capacity):
                active = len(self._relay_targets)
                saturated = True
            else:
                self._relay_targets[peer] = (addr,
                                             now + self.RELAY_CIRCUIT_TTL)
                active = len(self._relay_targets)
                saturated = False
        _tm.get("relay_active_circuits").set(active)
        if saturated:
            _send_frame(sock, {"verb": "error", "relay_saturated": True,
                               "peer": self.peer_id or "?",
                               "message": f"relay at capacity "
                                          f"({active}/{self.relay_capacity})"})
            return
        _send_frame(sock, {"verb": "ok", "peer": self.peer_id or "?",
                           "active": active,
                           "capacity": self.relay_capacity,
                           "ttl": self.RELAY_CIRCUIT_TTL})

    def _relay_forward(self, sock, target: str, header: dict,
                       payload: bytes) -> None:
        """Forward a client frame verbatim to attached peer `target` over the
        pooled `_relay_conns` circuit and relay the response frame back.
        Failures answer with the push-chain error shape: `peer`=target keeps
        the CLIENT's routing blame on the unreachable hop, while the circuit
        breaker opens only where `breaker_peer` says the fault actually is."""
        verb = header.get("verb")
        session = header.get("session_id")
        m_fwd = _tm.get("relay_forwarded_total")
        plan = self.fault_plan
        if plan is not None:
            rule = plan.fire("relay", SITE_KINDS["relay"],
                             side=self.fault_side, peer=target, verb=verb,
                             session=session)
            if rule is not None:
                if rule.kind == "relay_stall":
                    time.sleep(rule.delay_s)
                else:  # relay_drop: the volunteer eats the frame
                    m_fwd.labels(outcome="drop").inc()
                    _ev.emit("relay_forward_error", session_id=session,
                             relay=self.peer_id or "?", peer=target,
                             verb=verb, error="relay_drop (injected)")
                    _send_frame(sock, {
                        "verb": "error", "kind": "push", "peer": target,
                        "breaker_peer": self.peer_id or "?",
                        "message": f"relay dropped frame for {target} "
                                   f"(injected)"})
                    return
        now = time.monotonic()
        with self._relay_lock:
            self._prune_relay_targets_locked(now)
            entry = self._relay_targets.get(target)
            active = len(self._relay_targets)
        _tm.get("relay_active_circuits").set(active)
        if entry is None:
            # No circuit: the peer never attached here (stale record) or its
            # lease lapsed (it stopped heartbeating — probably dead). Either
            # way the TARGET is the unhealthy component, not this volunteer.
            m_fwd.labels(outcome="no_circuit").inc()
            _ev.emit("relay_forward_error", session_id=session,
                     relay=self.peer_id or "?", peer=target, verb=verb,
                     error="no circuit")
            _send_frame(sock, {
                "verb": "error", "kind": "push", "peer": target,
                "message": f"no relay circuit for {target}"})
            return
        addr = entry[0]
        # The relayed peer's compute is on the far side of this forward;
        # budget like a push hop (chained verbs carry their own chain).
        timeout = self.compute_timeout * (
            1 + len(header.get("next_servers") or ()))
        for fresh in (False, True):
            fsock = None
            try:
                fsock, lock = self._relay_sock(addr, fresh)
                with lock:
                    fsock.settimeout(timeout)
                    _send_frame(fsock, header, payload)
                    rh, rp = _recv_frame(fsock)
                break
            except (ConnectionError, OSError, socket.timeout) as exc:
                if fsock is not None:
                    self._drop_relay(addr, fsock)
                if fresh:
                    m_fwd.labels(outcome="error").inc()
                    _ev.emit("relay_forward_error", session_id=session,
                             relay=self.peer_id or "?", peer=target,
                             verb=verb, error=str(exc)[:200])
                    _send_frame(sock, {
                        "verb": "error", "kind": "push", "peer": target,
                        "message": f"relay to {target} failed: {exc}"})
                    return
        m_fwd.labels(outcome="ok").inc()
        _send_frame(sock, rh, rp)

    def start(self) -> None:
        super().start()
        if self.runtime is not None and self.owns_runtime:
            self.runtime.start()
        if self.executor is not None:
            logger.info("stage server %s on %s (span [%d, %d))",
                        self.executor.peer_id, self.address,
                        self.executor.spec.start, self.executor.spec.end)

    def stop(self) -> None:
        super().stop()
        if self.runtime is not None and self.owns_runtime:
            self.runtime.stop()
        with self._relay_lock:
            conns, self._relay_conns = dict(self._relay_conns), {}
        for sock, _ in conns.values():
            try:
                sock.close()
            except OSError:
                pass

    def _gossip_dispatch(self, sock, header: dict) -> None:
        """Serve the decentralized control plane from this server's
        GossipNode: the `gossip` anti-entropy verb, plus the registry
        service's register/heartbeat/unregister/list with RegistryServer's
        exact response shapes — `RemoteRegistry` pointed at THIS address
        works unmodified (any-peer bootstrap)."""
        node = self.gossip
        verb = header.get("verb")
        if verb == "gossip":
            plan = self.fault_plan
            if plan is not None:
                rule = plan.fire("gossip", SITE_KINDS["gossip"],
                                 side=self.fault_side,
                                 peer=header.get("peer_id"), verb=verb)
                if rule is not None:
                    if rule.kind == "gossip_drop":
                        # Swallow the frame: the initiator's round dies
                        # (read timeout) and anti-entropy rides a later
                        # round — which the soak proves still converges.
                        return
                    # duplicate: merge the delta twice — idempotent.
                    node.merge(header.get("entries") or ())
            merged = node.merge(header.get("entries") or ())
            resp = {"verb": "gossip", "peer_id": self.peer_id,
                    "merged": merged}
            digest = header.get("digest")
            if digest is not None:
                # Round opener: answer with OUR digest and the entries the
                # initiator's digest shows it lacks (digest-then-delta).
                resp["digest"] = node.digest()
                resp["entries"] = node.delta_for(digest)
                _tm.get("gossip_rounds_total").labels(role="responder").inc()
            _send_frame(sock, resp)
            return
        _tm.get("gossip_mirror_requests_total").labels(verb=verb).inc()
        if verb == "register":
            node.publish(dict(header["record"]))
            _send_frame(sock, {"verb": "ok", "ttl": node.ttl})
            return
        if verb == "heartbeat":
            ok = node.apply_heartbeat(
                header["peer_id"], throughput=header.get("throughput"),
                cache_tokens_left=header.get("cache_tokens_left"),
                next_server_rtts=header.get("next_server_rtts"))
            _send_frame(sock, {"verb": "ok", "known": ok, "ttl": node.ttl})
            return
        if verb == "unregister":
            node.apply_unregister(header["peer_id"])
            _send_frame(sock, {"verb": "ok"})
            return
        # list — a client discovering through us instead of a seed.
        now = time.monotonic()
        records = [dict(_rec_to_dict(r),
                        age_s=max(0.0, now - r.timestamp))
                   for r in node.live_servers()]
        _ev.emit("gossip_served_discovery", peer=self.peer_id,
                 records=len(records))
        _send_frame(sock, {"verb": "records", "ttl": node.ttl,
                           "records": records})

    def _dispatch(self, sock, header: dict, payload: bytes) -> None:
        verb = header.get("verb")
        relay_to = header.pop("relay_to", None)
        if relay_to is not None:
            # We are this frame's relay VOLUNTEER, not its destination:
            # forward it verbatim (minus the routing key) over the pooled
            # circuit to the attached NAT'd peer and stream the response
            # back. Runs before every other verb — any verb can be relayed —
            # and needs no executor (a pure volunteer serves no blocks).
            self._relay_forward(sock, relay_to, header, payload)
            return
        if verb == "relay_attach":
            # Circuit setup from an unreachable peer. Executor-less on
            # purpose: volunteering is a socket-plane capability.
            self._relay_attach(sock, header)
            return
        if verb == "reach_check":
            # Socket-only probe — needs no executor, so a re-spanning server
            # still answers reachability votes for its peers.
            self._reach_check(sock, header)
            return
        if verb == "metrics":
            # Prometheus-text scrape of this PROCESS's registry. Needs no
            # executor (a re-spanning server still answers scrapes); empty
            # output when telemetry is disabled — the scrape itself never
            # enables collection.
            _send_frame(sock, {
                "verb": "metrics",
                "text": _texp.render(_get_metrics_registry()),
            })
            return
        if verb == "dump-events":
            # Flight-recorder scrape: this PROCESS's event ring as JSONL,
            # with the metrics snapshot embedded, exactly what a crash dump
            # would have written. Executor-less for the same reason as
            # `metrics`; empty event stream when the recorder is disabled.
            _send_frame(sock, {
                "verb": "events",
                "lines": _ev.get_recorder().render_jsonl(
                    registry=_get_metrics_registry()),
            })
            return
        if verb == "fault":
            # Chaos-layer admin (runtime.faults): install/clear/report this
            # server's FaultPlan. Executor-less (a re-spanning server still
            # takes plans) and gated by allow_fault_injection.
            _send_frame(sock, self._fault_admin(header))
            return
        if verb == "swarm-stats":
            # Swarm-top scrape: this process's own stats digest plus every
            # live gossip record it holds (verbatim, so piggybacked per-peer
            # "stats" digests ride along). Executor-less and registry-free:
            # dialing ANY live server yields a whole-swarm view even with
            # every seed registry dead.
            _send_frame(sock, {
                "verb": "swarm-stats",
                "peer_id": self.peer_id or "?",
                "self": _prof_digest(),
                "records": (self.gossip.live_records()
                            if self.gossip is not None else []),
            })
            return
        if self.gossip is not None and verb in (
                "gossip", "register", "heartbeat", "unregister", "list"):
            # Control-plane mirror: executor-less on purpose — a
            # re-spanning server must keep gossiping and keep serving
            # discovery, or the control plane would flap exactly when the
            # swarm is reorganizing.
            self._gossip_dispatch(sock, header)
            return
        # Snapshot: the elastic rebalance thread may null/swap self.executor
        # at any moment; every later access in this request must see ONE
        # consistent executor (a mid-request swap would otherwise surface as
        # an AttributeError in a kind-less — non-retryable — error frame).
        ex = self.executor
        if ex is None:
            _send_frame(sock, {"verb": "error", "kind": "stage",
                               "peer": self.peer_id or "?",
                               "message": "server is re-spanning"})
            return
        req_model = header.get("model")
        if (req_model is not None and self.model is not None
                and req_model != self.model):
            # kind="stage" puts this in the client's retryable taxonomy: it
            # blacklists this peer and re-discovers (correctly) scoped peers.
            _send_frame(sock, {"verb": "error", "kind": "stage",
                               "peer": self.peer_id or "?",
                               "model_mismatch": True,
                               "message": f"model mismatch: request is for "
                                          f"{req_model!r}, server holds "
                                          f"{self.model!r}"})
            return
        if verb == "stream_open":
            self._stream_open(sock, header)
            return
        if verb == "step":
            self._stream_step(sock, ex, header, payload)
            return
        if verb == "forward":
            # The request's way in on this connection's thread, as the span
            # stage.request on the profiler's clock: the frame (read a few
            # checks ago) made a request, its tensor decoded and uploaded.
            with _get_profiler().span("request",
                                      session=header.get("session_id")):
                req = _header_to_request(header, payload)
            self._run_forward(sock, ex, req,
                              resp_wire_dtype=header.get("wire_dtype"))
        elif verb in ("train_forward", "backward"):
            self._train_verbs(sock, ex, verb, header, payload)
        elif verb == "end_session":
            # Drop the session's stream state too, or metadata + the 50-token
            # window would accumulate per ended session on long-lived client
            # connections until the socket closes.
            with self._streams_lock:
                self._streams.get(sock, {}).pop(header["session_id"], None)
            # Through the runtime's compute thread, NOT inline: freeing the
            # arena handle while a timed-out forward for the same session is
            # still stepping its KV buffers would null them mid-step and
            # corrupt the arena's byte accounting.
            try:
                self._compute("inference", ex.drop_session,
                              header["session_id"])
            except (StageExecutionError, TaskRejected, TimeoutError) as exc:
                self.request_log.record("end_session",
                                        session=header["session_id"],
                                        outcome="stage_error",
                                        detail=str(exc))
                _send_frame(sock, {"verb": "error", "message": str(exc),
                                   "kind": "stage"})
                return
            self.request_log.record("end_session",
                                    session=header["session_id"])
            _send_frame(sock, {"verb": "ok"})
        elif verb == "info":
            spec = ex.spec
            frame = {
                "verb": "info", "peer_id": ex.peer_id,
                "start_block": spec.start, "end_block": spec.end,
                "cache_tokens_left": ex.arena.tokens_left(),
                "requests_served": ex.requests_served,
                "engine": getattr(ex, "engine", "session"),
                "version": 1,
                # Capability flags for mixed-version swarms (the data-plane
                # guard is the client's no-grad_lora check in finetune).
                "lora": True,
            }
            # Batched engines expose their coalescing effectiveness (rounds
            # executed vs requests served) for tests + ops introspection.
            steps = getattr(getattr(ex, "inner", None), "decode_steps", None)
            if steps is not None:
                frame["decode_steps"] = steps
            store = (getattr(ex, "prefix_store", None)
                     or getattr(getattr(ex, "inner", None),
                                "prefix_store", None))
            if store is not None:
                frame["prefix_cache"] = store.stats()
            # Structured recent-request tail (_log_request parity): the
            # operator's first question about a misbehaving server is "what
            # has it been serving" — answerable over the wire.
            frame["recent_requests"] = self.request_log.tail(20)
            # One-line telemetry aggregate (steps/s, p50/p95 step latency,
            # cache hit rate) for --mode status; None-valued fields when
            # telemetry is off or no traffic has been observed yet.
            frame["telemetry"] = _texp.summary(_get_metrics_registry())
            _send_frame(sock, frame)
        else:
            _send_frame(sock, {"verb": "error",
                               "message": f"unknown verb {verb!r}"})

    # ------------------------------------------------------------------
    # Persistent inference streams (petals/server/handler.py:132-308)
    # ------------------------------------------------------------------

    def _on_connection_closed(self, sock) -> None:
        with self._streams_lock:
            self._streams.pop(sock, None)

    def _stream_open(self, sock, header: dict) -> None:
        """Register a session stream on THIS connection: the full request
        metadata (sampling, block range, route, recent-token window) ships
        once; subsequent `step` frames carry only per-step deltas. Re-opening
        an existing session replaces its metadata (the client does this when
        sampling params or the route change)."""
        sid = header["session_id"]
        state = {
            "max_length": header.get("max_length", 0),
            "sampling": SamplingParams(
                temperature=header.get("temperature", 0.7),
                top_p=header.get("top_p", 0.9),
                top_k=header.get("top_k", 50),
                repetition_penalty=header.get("repetition_penalty", 1.5),
            ),
            "start_block": header.get("start_block"),
            "end_block": header.get("end_block"),
            "model": header.get("model"),
            "next_servers": tuple(header.get("next_servers", ())),
            # Server-maintained recent-token window: seeded here, then
            # appended with every token THIS server samples for the session
            # — steady-state steps never re-ship it.
            "generated": list(header.get("generated_tokens", ()))[-50:],
            # Per-step compute timeout + absolute session deadline
            # (petals handler.py per-step timeout / session max duration).
            "step_timeout": header.get("step_timeout"),
            "deadline": (time.monotonic() + header["deadline_s"]
                         if header.get("deadline_s") else None),
            # Negotiated response precision for this session (absent ->
            # the server's default).
            "wire_dtype": header.get("wire_dtype"),
        }
        with self._streams_lock:
            self._streams.setdefault(sock, {})[sid] = state
            self.stream_opens += 1
        _send_frame(sock, {"verb": "ok", "session_id": sid})

    def _stream_step(self, sock, ex, header: dict, payload: bytes) -> None:
        sid = header["session_id"]
        with self._streams_lock:
            state = self._streams.get(sock, {}).get(sid)
            self.stream_steps += 1
        if state is None:
            # stream_closed/reason let the transport distinguish a repairable
            # desync (re-open + resend transparently) from policy refusals.
            _send_frame(sock, {"verb": "error", "kind": "stage",
                               "peer": self.peer_id or "?",
                               "stream_closed": True, "reason": "no_stream",
                               "message": f"session {sid}: step without "
                                          "stream_open on this connection"})
            return
        if state["deadline"] is not None and time.monotonic() > state["deadline"]:
            # Session outlived its declared budget: free the cache and
            # refuse — the stream analogue of petals' session expiry.
            with self._streams_lock:
                self._streams.get(sock, {}).pop(sid, None)
            try:
                self._compute("inference", ex.drop_session, sid)
            except Exception:
                pass
            _send_frame(sock, {"verb": "error", "kind": "stage",
                               "peer": self.peer_id or "?",
                               "stream_closed": True, "reason": "deadline",
                               "message": f"session {sid}: deadline exceeded"})
            return
        # stage.request, as for the `forward` verb (`_dispatch`)
        with _get_profiler().span("request", session=sid):
            req = StageRequest(
                session_id=sid,
                hidden=_stage_input(
                    _decode_tensor(header["tensor"], payload)),
                seq_len=header["seq_len"],
                cur_len=header["cur_len"],
                is_prefill=header.get("is_prefill", False),
                max_length=state["max_length"],
                sampling=state["sampling"],
                generated_tokens=tuple(state["generated"]),
                step_seed=header.get("step_seed", 0),
                start_block=state["start_block"],
                end_block=state["end_block"],
                model=state["model"],
                next_servers=state["next_servers"],
                start_from_position=header.get("start_from_position"),
                prefix_len=header.get("prefix_len", 0),
                trace=header.get("trace"),
                deadline_budget_s=header.get("deadline_budget_s"),
                priority=header.get("priority"),
            )
        self._run_forward(sock, ex, req, stream=state,
                          step_timeout=state["step_timeout"])

    def _run_forward(self, sock, ex, req: StageRequest, stream: dict = None,
                     step_timeout: Optional[float] = None,
                     resp_wire_dtype: Optional[str] = None) -> None:
        t_req = time.monotonic()
        # When this thread read the request's frame (`handle`); a caller
        # that is no connection thread has no such instant.
        req.t_recv = getattr(self._arrival, "t", 0.0)
        if resp_wire_dtype is None and stream is not None:
            resp_wire_dtype = stream.get("wire_dtype")
        resp_wire_dtype = resp_wire_dtype or self.wire_dtype
        # Serving-boundary telemetry: THIS is where a request's server-side
        # step latency is defined (queue wait through response encode), so
        # the step histogram/token counters live here, not in the executor.
        phase = "prefill" if req.is_prefill else "decode"
        m_requests = _tm.get("server_requests_total")
        span = get_tracer().span_from_wire(
            req.trace, "server_forward", kind="server",
            peer=ex.peer_id, phase=phase)

        def _log(outcome, detail=None):
            try:
                peer = "%s:%s" % sock.getpeername()[:2]
            except OSError:
                peer = "?"
            self.request_log.record(
                "prefill" if req.is_prefill else "forward",
                session=req.session_id, peer=peer, tokens=req.seq_len,
                cur=req.cur_len,
                dur_ms=(time.monotonic() - t_req) * 1e3,
                outcome=outcome, detail=detail,
                span=f"[{req.start_block},{req.end_block})",
                replay=int(req.is_replay) or None)

        if req.deadline_budget_s is not None:
            # End-to-end deadline budget: the first hop that observes an
            # exhausted budget refuses the work — computing tokens the
            # caller already gave up on wastes the swarm's scarce resource
            # (and on a push chain would waste EVERY downstream hop too).
            remaining = req.deadline_budget_s - (time.monotonic() - t_req)
            if remaining <= 0.0:
                _log("deadline", f"budget {req.deadline_budget_s:.3f}s")
                m_requests.labels(outcome="error").inc()
                _tm.get("server_deadline_rejected_total").inc()
                _ev.emit("deadline_rejected", session_id=req.session_id,
                         trace_id=_trace_id(req), peer=ex.peer_id,
                         budget_s=req.deadline_budget_s,
                         waited_s=round(time.monotonic() - t_req, 6))
                span.end(error="deadline")
                _send_frame(sock, {
                    "verb": "error", "kind": "stage", "peer": ex.peer_id,
                    "deadline_expired": True,
                    "message": f"deadline budget exhausted "
                               f"({req.deadline_budget_s:.3f}s remaining "
                               f"on arrival)"})
                return
            # Cap the compute wait by what's left of the caller's deadline:
            # a queue stall past the budget surfaces as a stage timeout
            # instead of a reply nobody is waiting for.
            step_timeout = (remaining if step_timeout is None
                            else min(step_timeout, remaining))

        t_compute = time.monotonic()
        try:
            resp = self._compute("inference", ex.forward, req,
                                 size=req.seq_len, timeout=step_timeout,
                                 priority=req.priority)
        # All three map to kind="stage": the client converts that to
        # StageExecutionError, which is in its retryable taxonomy
        # (client.py failover) — a crashed generation helps nobody.
        # TimeoutError must be caught here explicitly: on py>=3.11 it is
        # an OSError subclass, and the outer handler's socket-error catch
        # would otherwise silently drop the connection.
        except (StageExecutionError, TaskRejected) as exc:
            _log("stage_error", str(exc))
            m_requests.labels(outcome="error").inc()
            _ev.emit("stage_error", session_id=req.session_id,
                     trace_id=_trace_id(req), peer=ex.peer_id,
                     phase=phase, error=str(exc)[:200])
            span.end(error=repr(exc))
            if isinstance(exc, TaskRejected) and exc.permanent:
                # Oversized work can never succeed on a retry or a
                # replacement peer — a typed, non-retryable refusal keeps
                # the client from burning its retry budget (and its
                # circuit breaker) on it.
                _send_frame(sock, {"verb": "error", "message": str(exc),
                                   "kind": "stage", "task_rejected": True,
                                   "peer": ex.peer_id})
                return
            _send_frame(sock, {"verb": "error", "message": str(exc),
                               "kind": "stage",
                               "peer": ex.peer_id})
            return
        except TimeoutError:
            budget = (step_timeout if step_timeout is not None
                      else self.compute_timeout)
            _log("timeout")
            m_requests.labels(outcome="timeout").inc()
            _ev.emit("stage_timeout", session_id=req.session_id,
                     trace_id=_trace_id(req), peer=ex.peer_id,
                     phase=phase, budget_s=budget)
            span.end(error="timeout")
            _send_frame(sock, {"verb": "error", "kind": "stage",
                               "peer": ex.peer_id,
                               "message": f"stage compute timed out after "
                                          f"{budget:.0f}s"})
            return
        # End the server span at compute completion (its to_wire summary
        # rides the response so the CLIENT records both sides of the hop).
        # queue_s here is the pre-dispatch wait at this boundary (deadline
        # checks); pool queueing is inside _compute and charges to compute.
        _get_profiler().observe("server", time.monotonic() - t_req)
        span.set(cache_len=resp.cache_len,
                 queue_s=max(0.0, t_compute - t_req)).end()
        wire_span = span.to_wire() if req.trace is not None else None
        # The reply's way out on this connection's thread, `_compute`
        # returned -> frame written, as the span stage.reply.
        with _get_profiler().span("reply", session=req.session_id):
            if getattr(resp, "is_burst", False):
                frame = {
                    "verb": "burst", "session_id": resp.session_id,
                    "tokens": list(resp.burst_tokens),
                    "stop": resp.burst_stop,
                    "cache_len": resp.cache_len,
                }
                if wire_span is not None:
                    frame["span"] = wire_span
                _send_frame(sock, frame)
            elif resp.is_token:
                if stream is not None and resp.token_id is not None:
                    # Maintain the stream's server-side recent-token window
                    # (the client never re-ships it on the stream path).
                    stream["generated"].append(int(resp.token_id))
                    del stream["generated"][:-50]
                frame = {
                    "verb": "token", "session_id": resp.session_id,
                    "token_id": resp.token_id, "cache_len": resp.cache_len,
                }
                if resp.token_ids is not None:   # batch>1 per-row sampling
                    frame["token_ids"] = list(resp.token_ids)
                if wire_span is not None:
                    frame["span"] = wire_span
                _send_frame(sock, frame)
            elif resp.is_speculative:
                frame = {
                    "verb": "spec", "session_id": resp.session_id,
                    "tokens": list(resp.tokens),
                    "n_accepted": resp.n_accepted,
                    "cache_len": resp.cache_len,
                }
                if wire_span is not None:
                    frame["span"] = wire_span
                _send_frame(sock, frame)
            elif resp.is_beam:
                frame = {
                    "verb": "beam", "session_id": resp.session_id,
                    "cache_len": resp.cache_len,
                    "top_tokens": [list(r) for r in resp.top_tokens],
                    "top_logprobs": [list(r) for r in resp.top_logprobs],
                }
                if wire_span is not None:
                    frame["span"] = wire_span
                _send_frame(sock, frame)
            elif req.next_servers:
                # Push chain (petals handler.py:320-350): ship our output
                # straight to the next hop and relay its final response back
                # upstream — the client sees ONE round trip per step.
                nxt = req.next_servers[0]
                nreq = dataclasses.replace(
                    req,
                    hidden=resp.hidden,
                    start_block=nxt.get("start_block"),
                    end_block=nxt.get("end_block"),
                    next_servers=tuple(req.next_servers[1:]),
                )
                if req.deadline_budget_s is not None:
                    # Forward the REMAINING budget: this hop's service time
                    # has already been spent from the caller's deadline, and
                    # the next hop must judge expiry against what's actually
                    # left.
                    nreq = dataclasses.replace(
                        nreq,
                        deadline_budget_s=(req.deadline_budget_s
                                           - (time.monotonic() - t_req)))
                try:
                    rh, rp = self._relay(nxt, nreq)
                except (ConnectionError, OSError, TimeoutError) as exc:
                    m_requests.labels(outcome="error").inc()
                    err = {
                        "verb": "error", "kind": "push",
                        "peer": nxt.get("peer_id", "?"),
                        "message": (f"push to {nxt.get('peer_id')} failed: "
                                    f"{exc}"),
                    }
                    if nxt.get("relay_via"):
                        # The dial that failed was to the next hop's relay
                        # VOLUNTEER, not the hop itself: blame the hop for
                        # routing (`peer` — the client routes around it) but
                        # the volunteer for the circuit breaker, so one dead
                        # relay doesn't blacklist every peer behind it.
                        err["breaker_peer"] = nxt.get("relay_via")
                    _send_frame(sock, err)
                    return
                if stream is not None and rh.get("verb") == "token" and (
                        rh.get("token_id") is not None):
                    # Push chain on a stream: the token was sampled
                    # DOWNSTREAM and only relays through us — append it to
                    # this stream's window too, or the final stage's
                    # repetition penalty would run against the window as of
                    # stream_open forever.
                    stream["generated"].append(int(rh["token_id"]))
                    del stream["generated"][:-50]
                _send_frame(sock, rh, rp)
            else:
                arr = np.asarray(resp.hidden)
                meta, body = _encode_tensor(arr, resp_wire_dtype)
                frame = {
                    "verb": "hidden", "session_id": resp.session_id,
                    "cache_len": resp.cache_len, "tensor": meta,
                }
                if wire_span is not None:
                    frame["span"] = wire_span
                _send_frame(sock, frame, body)
        # Structured per-request record (petals _log_request,
        # handler.py:549-573 parity, exceeded: RequestLog also keeps the
        # bounded ring the info verb surfaces, and errors are recorded at
        # the failure sites above). Logged AFTER the response is
        # encoded+sent: JAX dispatch is async, so only then has the device
        # work for hidden-returning stages actually materialized — dur_ms
        # covers real compute, not dispatch. Decode-ok records go to the
        # logger at DEBUG so steady-state serving doesn't flood logs.
        _tm.get("server_step_latency_seconds").labels(
            phase=phase).observe(time.monotonic() - t_req)
        _tm.get("server_tokens_total").labels(phase=phase).inc(req.seq_len)
        m_requests.labels(outcome="ok").inc()
        if resp.t_done:
            # A batched round's reply that says the session asks again:
            # its way out, from the round's results on the host.
            _tm.get("server_reply_leg_seconds").observe(
                time.monotonic() - resp.t_done)
        _log("ok")

    def _train_verbs(self, sock, ex, verb: str, header: dict,
                     payload: bytes) -> None:
        # QoS via the pool kinds: inference outranks both training verbs
        # (DummyTaskPrioritizer semantics, petals/server/task_prioritizer.py).
        tensors = _decode_tensors(header["tensors"], payload)
        try:
            # LoRA adapters trail the frame; peel them off by manifest
            # length (header-driven — the positional prompts convention
            # predates it, so has_prompts falls back to arity for legacy
            # clients). Inside the try: a malformed manifest must come
            # back as a clean stage error, not a connection-level one the
            # client misreads as a dead peer.
            manifest = header.get("lora_manifest")
            lora = None
            if manifest:
                from ..models.lora import lora_from_list

                try:
                    lora = lora_from_list(manifest, tensors[-len(manifest):])
                except ValueError as exc:
                    raise StageExecutionError(str(exc)) from exc
                tensors = tensors[:-len(manifest)]
            lora_scale = float(header.get("lora_scale", 1.0))
            base = 1 if verb == "train_forward" else 2
            has_prompts = header.get("has_prompts", len(tensors) > base)
            if verb == "train_forward":
                req = StageRequest(
                    session_id=header["session_id"],
                    hidden=jnp.asarray(tensors[0]),
                    seq_len=header["seq_len"], cur_len=0, is_prefill=False,
                    max_length=0, train=True,
                    prompts=(jnp.asarray(tensors[1])
                             if has_prompts else None),
                    lora=lora, lora_scale=lora_scale,
                    start_block=header.get("start_block"),
                    end_block=header.get("end_block"),
                )
                resp = self._compute("forward", ex.train_forward,
                                     req, size=req.seq_len)
                arr = np.asarray(resp.hidden)
                meta, body = _encode_tensor(arr, self.wire_dtype)
                _send_frame(sock, {
                    "verb": "hidden", "session_id": resp.session_id,
                    "cache_len": 0, "tensor": meta,
                }, body)
            else:
                breq = BackwardRequest(
                    session_id=header["session_id"],
                    hidden=jnp.asarray(tensors[0]),
                    grad_output=jnp.asarray(tensors[1]),
                    seq_len=header["seq_len"],
                    prompts=(jnp.asarray(tensors[2])
                             if has_prompts else None),
                    lora=lora, lora_scale=lora_scale,
                    start_block=header.get("start_block"),
                    end_block=header.get("end_block"),
                )
                bresp = self._compute("backward", ex.backward,
                                      breq, size=breq.seq_len)
                arrs = [np.asarray(bresp.grad_input)]
                if bresp.grad_prompts is not None:
                    arrs.append(np.asarray(bresp.grad_prompts))
                hdr_out = {"verb": "grads", "session_id": bresp.session_id}
                if bresp.grad_lora:
                    from ..models.lora import lora_to_list

                    gmanifest, garrs = lora_to_list(bresp.grad_lora)
                    hdr_out["lora_manifest"] = gmanifest
                    arrs += [np.asarray(a) for a in garrs]
                metas, body = _encode_tensors(arrs, "f32")
                hdr_out["tensors"] = metas
                _send_frame(sock, hdr_out, body)
        except (StageExecutionError, TaskRejected) as exc:
            hdr_err = {"verb": "error", "message": str(exc), "kind": "stage"}
            if isinstance(exc, TaskRejected) and exc.permanent:
                hdr_err["task_rejected"] = True
            _send_frame(sock, hdr_err)
        except TimeoutError:
            _send_frame(sock, {"verb": "error", "kind": "stage",
                               "message": f"stage compute timed out after "
                                          f"{self.compute_timeout:.0f}s"})

    def _reach_check(self, sock, header: dict) -> None:
        """ReachabilityProtocol.rpc_check (petals reachability.py:86-164):
        "can YOU dial this address?" — peers answer for each other so a
        booting server can learn whether its advertised address is
        reachable from the outside before publishing it."""
        target = header.get("target", "")
        ok = False
        try:
            host, port = target.rsplit(":", 1)
            with socket.create_connection((host, int(port)), timeout=3.0) as s:
                _send_frame(s, {"verb": "info"})
                hdr, _ = _recv_frame(s)
                # A re-spanning peer answers with a stage-error frame — it
                # is still REACHABLE (the probe is about connectivity).
                ok = hdr.get("verb") in ("info", "error")
        except (ConnectionError, OSError, ValueError):
            ok = False
        _send_frame(sock, {"verb": "reach_check", "target": target,
                           "ok": ok})


# ---------------------------------------------------------------------------
# Client transport
# ---------------------------------------------------------------------------

class TcpTransport(Transport):
    """Client-side transport resolving peers via registry `address` fields."""

    def __init__(self, registry, wire_dtype: str = "bf16",
                 connect_timeout: float = 5.0, use_streams: bool = True,
                 step_timeout: Optional[float] = None,
                 session_deadline_s: Optional[float] = None,
                 model: Optional[str] = None):
        self.registry = registry
        # Echoed in every request so a mis-routed peer (different model)
        # rejects instead of computing garbage; None = untagged legacy client.
        self.model = model
        self.wire_dtype = wire_dtype
        self.connect_timeout = connect_timeout
        # Persistent per-session streams (metadata once, deltas per step).
        # step_timeout/session_deadline_s are DECLARED to the server at
        # stream_open: the server enforces them (per-step compute budget,
        # absolute session lifetime) — petals handler.py:132-195 semantics.
        self.use_streams = use_streams
        self.step_timeout = step_timeout
        self.session_deadline_s = session_deadline_s
        self._conns: Dict[str, socket.socket] = {}
        # (peer_id, session_id) -> {"snap", "sock", "window", "returns_tokens"}
        self._streams: Dict[Tuple[str, str], dict] = {}
        # peer_id -> relay volunteer's peer_id when the peer is NAT'd
        # (record carries relay_via); refreshed by _addr at dial time. The
        # pool key stays the TARGET peer: each relayed peer gets its own
        # socket to the volunteer, preserving per-peer stream semantics.
        self._via_relay: Dict[str, Optional[str]] = {}
        self._lock = threading.Lock()
        # Chaos layer (runtime.faults): client-side injection hook. None
        # (default) keeps dial/send on raw sockets; arm via set_fault_plan.
        self.fault_plan: Optional[FaultPlan] = None
        # peer_id -> cached `info` reply (None = probe failed; fail open).
        # Capability gating for mixed-version swarms — see _capabilities.
        self._peer_caps: Dict[str, Optional[dict]] = {}
        # Wire telemetry (global registry; no-op unless enabled). Byte
        # counters cover tensor payloads, not frame/header overhead —
        # consistent with LocalTransport's accounting.
        self._m_calls = _tm.get("transport_calls_total")
        self._m_sent = _tm.get("transport_bytes_sent_total")
        self._m_recv = _tm.get("transport_bytes_received_total")
        self._m_rtt = _tm.get("transport_rtt_seconds")

    def _tagged(self, hdr: dict) -> dict:
        """Stamp the client's model identity on an outgoing request header.
        EVERY request-frame builder must route through this (or pass
        model= to _request_header) so the 'tagged requests fail loudly on
        mis-routed peers' invariant is structural, not per-call-site."""
        if self.model is not None:
            hdr["model"] = self.model
        return hdr

    def _addr(self, peer_id: str) -> Tuple[str, int]:
        rec = self.registry.get(peer_id)
        if rec is None or not rec.address:
            raise PeerUnavailable(f"no address for peer {peer_id}")
        addr = rec.address
        via = getattr(rec, "relay_via", None)
        if via:
            # NAT'd peer: its own address is unreachable by construction —
            # dial its relay VOLUNTEER instead and let _send stamp frames
            # with relay_to so the volunteer forwards them verbatim.
            rrec = self.registry.get(via)
            if rrec is None or not rrec.address:
                raise PeerUnavailable(
                    f"no address for relay {via} of peer {peer_id}")
            addr = rrec.address
        with self._lock:
            self._via_relay[peer_id] = via
        host, port = addr.rsplit(":", 1)
        return host, int(port)

    def _send(self, peer_id: str, sock, hdr: dict, body: bytes = b"") -> None:
        """Single choke point for request frames to `peer_id`: a peer served
        through a relay volunteer (we dialed the volunteer in _addr) gets
        every frame stamped with relay_to, whatever the verb — the relay
        data plane is verb-transparent by construction."""
        with self._lock:
            via = self._via_relay.get(peer_id)
        if via:
            hdr["relay_to"] = peer_id
        _send_frame(sock, hdr, body)

    def _connect(self, peer_id: str) -> socket.socket:
        with self._lock:
            sock = self._conns.get(peer_id)
        if sock is not None:
            return sock
        plan = self.fault_plan
        if plan is not None and plan.fire(
                "connect", SITE_KINDS["connect"], side="client",
                peer=peer_id) is not None:
            # Injected dial refusal: surfaces through the transport's normal
            # unreachable mapping so recovery/breaker paths see the real
            # taxonomy, not a synthetic one.
            raise PeerUnavailable(
                f"cannot reach {peer_id}: connection refused (injected)")
        host, port = self._addr(peer_id)
        with self._lock:
            via = self._via_relay.get(peer_id)
        try:
            sock = socket.create_connection((host, port),
                                            timeout=self.connect_timeout)
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        except OSError as exc:
            err = PeerUnavailable(
                f"cannot reach {peer_id} at {host}:{port}: {exc}")
            if via:
                # The socket we failed to open was the relay VOLUNTEER's:
                # breaker blame goes to it, while routing blame (peer_id on
                # the raised error) stays on the unreachable hop — one dead
                # relay must not blacklist every peer behind it.
                err.breaker_peer_id = via
            raise err
        if plan is not None:
            sock = FaultSocket(sock, plan, side="client", peer=peer_id)
        with self._lock:
            self._conns[peer_id] = sock
        return sock

    def _drop(self, peer_id: str) -> None:
        with self._lock:
            sock = self._conns.pop(peer_id, None)
            # Streams live on the dropped connection: forget them so the next
            # step re-opens (full metadata) on the fresh socket.
            for key in [k for k in self._streams if k[0] == peer_id]:
                del self._streams[key]
        if sock is not None:
            try:
                sock.close()
            except OSError:
                pass

    def _unavailable(self, peer_id: str, exc: Exception) -> PeerUnavailable:
        """Wrap a socket-level failure on `peer_id`'s connection. For a
        relayed peer the socket belongs to the relay VOLUNTEER, so breaker
        blame (breaker_peer_id) goes to the volunteer while routing blame
        (the error's peer) stays on the hop — the relay-aware split the
        client's recovery path keys on."""
        err = PeerUnavailable(f"peer {peer_id} connection failed: {exc}")
        with self._lock:
            via = self._via_relay.get(peer_id)
        if via:
            err.breaker_peer_id = via
        return err

    def _note_relay_failure(self, peer_id: str, request: StageRequest,
                            error: Exception) -> None:
        """Flight-recorder marker for a failed exchange with a peer reached
        THROUGH a volunteer — doctor's failure chains key on this to tell a
        relay loss from an ordinary peer death."""
        with self._lock:
            via = self._via_relay.get(peer_id)
        if via:
            _ev.emit("relay_forward_error", session_id=request.session_id,
                     trace_id=_trace_id(request), relay=via, peer=peer_id,
                     verb="step" if self._streamable(request) else "forward",
                     error=str(error)[:200])

    def alive(self, peer_id: str) -> bool:
        """Real liveness probe, not just registry presence: dial the peer and
        exchange an `info` round trip on a short deadline. A host whose
        compute wedged still answers (info is served inline by the handler
        thread); a hung/partitioned HOST does not — which is exactly the case
        the push-chain blame heuristic needs to distinguish."""
        try:
            self.info(peer_id, timeout=3.0)
            return True
        except (PeerUnavailable, TimeoutError, ConnectionError, OSError):
            return False

    def ping(self, peer_id: str) -> Optional[float]:
        """Real wire RTT: time one `info` round trip (a fresh exchange on the
        pooled connection — dial cost is paid once, so steady-state pings
        measure the link, not the handshake)."""
        try:
            t0 = time.perf_counter()
            self.info(peer_id, timeout=3.0)
            rtt = time.perf_counter() - t0
            self._m_rtt.observe(rtt)
            return rtt
        except (PeerUnavailable, TimeoutError, ConnectionError, OSError):
            return None

    def _streamable(self, request: StageRequest) -> bool:
        """Plain prefill/decode rides the persistent stream; every exotic
        request shape (train, beam, speculative, replay) uses the classic
        full-metadata frame."""
        return (self.use_streams and not request.train
                and request.hypo_ids is None and request.num_logprobs == 0
                and request.draft_tokens is None and not request.is_replay
                and request.prompts is None and not request.burst_len)

    def _capabilities(self, peer_id: str) -> Optional[dict]:
        """The peer's cached `info` reply (capability flags: version, lora,
        ...), probed once per peer. FAIL OPEN: an unreachable or erroring
        probe caches None so capability gating skips rather than adding a
        second failure mode to the call path — only a SUCCESSFUL info reply
        that lacks a capability blocks a call."""
        with self._lock:
            if peer_id in self._peer_caps:
                return self._peer_caps[peer_id]
        try:
            caps: Optional[dict] = self.info(peer_id)
            if not isinstance(caps, dict) or caps.get("verb") != "info":
                caps = None
        except (PeerUnavailable, TimeoutError, ConnectionError, OSError,
                WireError):
            caps = None
        with self._lock:
            self._peer_caps[peer_id] = caps
        return caps

    def call(self, peer_id: str, request: StageRequest,
             timeout: Optional[float] = None) -> StageResponse:
        if request.train and request.lora:
            # Mixed-version swarms: a pre-LoRA server would silently drop
            # the adapters from the frame tail (unknown header keys) and
            # train the base span instead — reject BEFORE shipping, with an
            # error naming the peer and the fix. StageExecutionError keeps
            # it in the retryable taxonomy, so the trainer fails over to a
            # replica that does advertise the capability.
            caps = self._capabilities(peer_id)
            if caps is not None and not caps.get("lora"):
                exc = StageExecutionError(
                    f"peer {peer_id} (info version "
                    f"{caps.get('version', 0)}) does not advertise LoRA "
                    f"support; upgrade that server or detach the adapters "
                    f"for this span")
                exc.peer_id = peer_id
                raise exc
        if self._streamable(request):
            return self._call_stream(peer_id, request, timeout)
        try:
            sock = self._connect(peer_id)
        except PeerUnavailable as exc:
            self._note_relay_failure(peer_id, request, exc)
            raise
        if self.fault_plan is not None and isinstance(sock, FaultSocket):
            sock.ctx_verb = "train_forward" if request.train else "forward"
            sock.ctx_session = request.session_id
        self._m_calls.labels(
            verb="train" if request.train else "forward").inc()
        try:
            sock.settimeout(timeout)
            if request.train:
                arrs = [np.asarray(request.hidden)]
                # Per-tensor schema (petals handler.py:411-432): the
                # activation rides the session wire dtype; learned PROMPTS
                # and LoRA adapters stay f32 — they are trainable
                # parameters, and bf16-rounding them on every step would
                # quantize the tuning signal itself.
                wds = [self.wire_dtype]
                if request.prompts is not None:
                    arrs.append(np.asarray(request.prompts))
                    wds.append("f32")
                hdr = {
                    "verb": "train_forward",
                    "session_id": request.session_id,
                    "seq_len": request.seq_len,
                    "start_block": request.start_block,
                    "end_block": request.end_block,
                    "has_prompts": request.prompts is not None,
                }
                if request.lora:
                    from ..models.lora import lora_to_list

                    manifest, lora_arrs = lora_to_list(request.lora)
                    hdr["lora_manifest"] = manifest
                    hdr["lora_scale"] = float(request.lora_scale)
                    arrs += [np.asarray(a) for a in lora_arrs]
                    wds += ["f32"] * len(lora_arrs)
                metas, body = _encode_tensors(arrs, wds)
                hdr["tensors"] = metas
                self._send(peer_id, sock, self._tagged(hdr), body)
            elif request.prompts is not None:
                # Deep-prompt inference step: prompts ride as a second
                # payload tensor (classic frame — never streamed/pushed,
                # matching petals' can_push = not has_prompts). Per-tensor
                # schema: activation at the session wire dtype, prompts f32.
                metas, body = _encode_tensors(
                    [np.asarray(request.hidden), np.asarray(request.prompts)],
                    [self.wire_dtype, "f32"])
                hdr = _request_header(request, metas[0],
                                      prompts_meta=metas[1])
                hdr["wire_dtype"] = self.wire_dtype
                self._send(peer_id, sock, self._tagged(hdr), body)
            else:
                arr = np.asarray(request.hidden)
                meta, body = _encode_tensor(arr, self.wire_dtype)
                hdr = _request_header(request, meta)
                # Per-session wire negotiation (reference parity: its
                # schema carries a per-tensor compression choice,
                # petals/server/handler.py:411-432): the client asks the
                # server to encode RESPONSES at the client's precision —
                # an f32 client keeps exact activations from a
                # bf16-default server.
                hdr["wire_dtype"] = self.wire_dtype
                self._send(peer_id, sock, self._tagged(hdr), body)
            self._m_sent.inc(len(body))
            header, payload = _recv_frame(sock)
            self._m_recv.inc(len(payload))
        except socket.timeout as exc:
            self._drop(peer_id)
            _ev.emit("transport_timeout", session_id=request.session_id,
                     trace_id=_trace_id(request), peer=peer_id)
            raise TimeoutError(f"peer {peer_id} timed out") from exc
        except (ConnectionError, OSError) as exc:
            self._drop(peer_id)
            self._note_relay_failure(peer_id, request, exc)
            _ev.emit("transport_error", session_id=request.session_id,
                     trace_id=_trace_id(request), peer=peer_id,
                     error=str(exc)[:200])
            raise self._unavailable(peer_id, exc)
        return self._parse_response(peer_id, header, payload)

    def _call_stream(self, peer_id: str, request: StageRequest,
                     timeout: Optional[float] = None) -> StageResponse:
        """Persistent-stream fast path (petals handler.py:132-308): session
        metadata ships once per (peer, connection) in `stream_open`; steady-
        state steps carry only {cur_len, seq_len, seed} + the tensor. The
        transport mirrors the server's recent-token window (the server
        appends every token it returns on the stream) and re-ships it inline
        only when the client's window diverges — e.g. the first step back on
        a peer after tokens were sampled elsewhere during failover."""
        key = (peer_id, request.session_id)
        snap = (request.sampling.temperature, request.sampling.top_p,
                request.sampling.top_k, request.sampling.repetition_penalty,
                request.max_length, request.start_block, request.end_block,
                tuple(json.dumps(n, sort_keys=True)
                      for n in request.next_servers))
        try:
            sock = self._connect(peer_id)
        except PeerUnavailable as exc:
            self._note_relay_failure(peer_id, request, exc)
            raise
        if self.fault_plan is not None and isinstance(sock, FaultSocket):
            sock.ctx_verb = "step"
            sock.ctx_session = request.session_id
        try:
            sock.settimeout(timeout)
            with self._lock:
                st = self._streams.get(key)
                stale = st is None or st["snap"] != snap or st["sock"] is not sock
            if stale:
                open_hdr = {
                    "verb": "stream_open",
                    "session_id": request.session_id,
                    "max_length": request.max_length,
                    "temperature": request.sampling.temperature,
                    "top_p": request.sampling.top_p,
                    "top_k": request.sampling.top_k,
                    "repetition_penalty": request.sampling.repetition_penalty,
                    "generated_tokens": list(request.generated_tokens),
                    "start_block": request.start_block,
                    "end_block": request.end_block,
                    "next_servers": list(request.next_servers),
                    "step_timeout": self.step_timeout,
                    "deadline_s": self.session_deadline_s,
                    "wire_dtype": self.wire_dtype,
                }
                self._send(peer_id, sock, self._tagged(open_hdr))
                h, _ = _recv_frame(sock)
                if h.get("verb") != "ok":
                    self._parse_response(peer_id, h, b"")  # raises
                    raise WireError(f"bad stream_open reply {h.get('verb')!r}")
                st = {"snap": snap, "sock": sock,
                      "window": list(request.generated_tokens)[-50:],
                      "returns_tokens": None}
                with self._lock:
                    self._streams[key] = st
            hdr = {
                "verb": "step",
                "session_id": request.session_id,
                "seq_len": request.seq_len,
                "cur_len": request.cur_len,
                "step_seed": request.step_seed,
            }
            if request.is_prefill:
                hdr["is_prefill"] = True
                if request.prefix_len:
                    hdr["prefix_len"] = request.prefix_len
            if request.start_from_position is not None:
                hdr["start_from_position"] = request.start_from_position
            if request.trace is not None:
                hdr["trace"] = request.trace
            if request.deadline_budget_s is not None:
                hdr["deadline_budget_s"] = request.deadline_budget_s
            if request.priority is not None:
                hdr["priority"] = request.priority
            if st["returns_tokens"] and (
                    st["window"] != list(request.generated_tokens)[-50:]):
                # Window drifted (tokens were produced off-stream): re-seed
                # the server's copy inline rather than re-opening.
                st["window"] = list(request.generated_tokens)[-50:]
                # Inline override uses stream_open semantics server-side:
                # cheapest correct fix is a re-open carrying the window.
                with self._lock:
                    self._streams.pop(key, None)
                return self._call_stream(peer_id, request, timeout)
            arr = np.asarray(request.hidden)
            meta, body = _encode_tensor(arr, self.wire_dtype)
            hdr["tensor"] = meta
            self._m_calls.labels(verb="step").inc()
            self._send(peer_id, sock, hdr, body)
            self._m_sent.inc(len(body))
            header, payload = _recv_frame(sock)
            self._m_recv.inc(len(payload))
        except socket.timeout as exc:
            self._drop(peer_id)
            _ev.emit("transport_timeout", session_id=request.session_id,
                     trace_id=_trace_id(request), peer=peer_id)
            raise TimeoutError(f"peer {peer_id} timed out") from exc
        except (ConnectionError, OSError) as exc:
            self._drop(peer_id)
            self._note_relay_failure(peer_id, request, exc)
            _ev.emit("transport_error", session_id=request.session_id,
                     trace_id=_trace_id(request), peer=peer_id,
                     error=str(exc)[:200])
            raise self._unavailable(peer_id, exc)
        try:
            resp = self._parse_response(peer_id, header, payload)
        except StageExecutionError:
            if header.get("stream_closed"):
                # Server no longer holds this stream (deadline, restart, or
                # connection churn). Forget ours; a pure desync is repaired
                # transparently by ONE re-open + resend, policy refusals
                # (deadline) propagate into the client's failover taxonomy.
                with self._lock:
                    self._streams.pop(key, None)
                if header.get("reason") == "no_stream":
                    return self._call_stream(peer_id, request, timeout)
            raise
        if resp.token_id is not None:
            st["returns_tokens"] = True
            st["window"].append(int(resp.token_id))
            del st["window"][:-50]
        elif resp.hidden is not None and st["returns_tokens"] is None:
            st["returns_tokens"] = False
        return resp

    def _parse_response(self, peer_id: str, header: dict,
                        payload: bytes) -> StageResponse:
        verb = header.get("verb")
        # Server-side span summary (telemetry.tracing): present only when
        # the request carried a trace context.
        span = header.get("span")
        if verb == "spec":
            return StageResponse(
                session_id=header["session_id"],
                tokens=tuple(header["tokens"]),
                n_accepted=header["n_accepted"],
                cache_len=header["cache_len"],
                span=span,
            )
        if verb == "burst":
            return StageResponse(
                session_id=header["session_id"],
                burst_tokens=tuple(header["tokens"]),
                burst_stop=header.get("stop"),
                cache_len=header["cache_len"],
                span=span,
            )
        if verb == "token":
            ids = header.get("token_ids")
            return StageResponse(
                session_id=header["session_id"],
                token_id=header["token_id"],
                token_ids=None if ids is None else tuple(ids),
                cache_len=header["cache_len"],
                span=span,
            )
        if verb == "beam":
            return StageResponse(
                session_id=header["session_id"],
                cache_len=header["cache_len"],
                top_tokens=tuple(tuple(r) for r in header["top_tokens"]),
                top_logprobs=tuple(tuple(r) for r in header["top_logprobs"]),
                span=span,
            )
        if verb == "hidden":
            return StageResponse(
                session_id=header["session_id"],
                hidden=_stage_input(
                    _decode_tensor(header["tensor"], payload)),
                cache_len=header["cache_len"],
                span=span,
            )
        if verb == "error":
            # Wire markers -> typed exceptions via the ONE catalog
            # (runtime/errors.py from_wire): terminal flags
            # (deadline_expired, task_rejected) before the kind=
            # discriminators they ride on, push frames carrying the
            # relay-aware breaker_peer blame split.
            raise _errors.from_wire(header, peer_id)
        raise WireError(f"unexpected response verb {verb!r}")

    def backward(self, peer_id: str, request: "BackwardRequest",
                 timeout: Optional[float] = None) -> "BackwardResponse":
        from .messages import BackwardResponse

        sock = self._connect(peer_id)
        try:
            sock.settimeout(timeout)
            # Gradients ride the wire fp32: bf16's 8 mantissa bits compound
            # across hops (the reference compresses activations, never grads —
            # petals/server/handler.py:496-520 uses the schema dtype).
            arrs = [np.asarray(request.hidden), np.asarray(request.grad_output)]
            if request.prompts is not None:
                arrs.append(np.asarray(request.prompts))
            hdr = {
                "verb": "backward",
                "session_id": request.session_id,
                "seq_len": request.seq_len,
                "start_block": request.start_block,
                "end_block": request.end_block,
                "has_prompts": request.prompts is not None,
            }
            if request.lora:
                from ..models.lora import lora_to_list

                manifest, lora_arrs = lora_to_list(request.lora)
                hdr["lora_manifest"] = manifest
                hdr["lora_scale"] = float(request.lora_scale)
                arrs += [np.asarray(a) for a in lora_arrs]
            metas, body = _encode_tensors(arrs, "f32")
            hdr["tensors"] = metas
            self._send(peer_id, sock, self._tagged(hdr), body)
            header, payload = _recv_frame(sock)
        except socket.timeout as exc:
            self._drop(peer_id)
            raise TimeoutError(f"peer {peer_id} timed out") from exc
        except (ConnectionError, OSError) as exc:
            self._drop(peer_id)
            raise PeerUnavailable(f"peer {peer_id} connection failed: {exc}")
        if header.get("verb") == "grads":
            tensors = _decode_tensors(header["tensors"], payload)
            n_lora = len(header.get("lora_manifest", ()))
            grad_lora = None
            if n_lora:
                from ..models.lora import lora_from_list

                grad_lora = lora_from_list(header["lora_manifest"],
                                           tensors[-n_lora:])
                tensors = tensors[:-n_lora]
            return BackwardResponse(
                session_id=header["session_id"],
                grad_input=jnp.asarray(tensors[0]),
                grad_prompts=(jnp.asarray(tensors[1])
                              if len(tensors) > 1 else None),
                grad_lora=grad_lora,
            )
        if header.get("verb") == "error":
            # Same catalog mapping as the forward path: before this the
            # backward parser dropped the task_rejected flag, so a PERMANENT
            # rejection surfaced as a retryable StageExecutionError and the
            # trainer burned its retry budget on oversized work.
            raise _errors.from_wire(header, peer_id)
        raise WireError(f"unexpected response verb {header.get('verb')!r}")

    def end_session(self, peer_id: str, session_id: str) -> None:
        with self._lock:
            self._streams.pop((peer_id, session_id), None)
        try:
            sock = self._connect(peer_id)
            sock.settimeout(self.connect_timeout)
            self._send(peer_id, sock,
                       {"verb": "end_session", "session_id": session_id})
            _recv_frame(sock)
        except (PeerUnavailable, TimeoutError, ConnectionError, OSError):
            self._drop(peer_id)

    def info(self, peer_id: str, timeout: float = 5.0) -> dict:
        sock = self._connect(peer_id)
        try:
            sock.settimeout(timeout)
            self._send(peer_id, sock, {"verb": "info"})
            header, _ = _recv_frame(sock)
            return header
        except (ConnectionError, OSError) as exc:
            self._drop(peer_id)
            raise PeerUnavailable(f"peer {peer_id}: {exc}")

    def metrics_text(self, peer_id: str, timeout: float = 5.0) -> str:
        """Prometheus-text scrape of a peer's process registry (the
        ``metrics`` verb). Empty string when the peer runs telemetry off."""
        sock = self._connect(peer_id)
        try:
            sock.settimeout(timeout)
            self._send(peer_id, sock, {"verb": "metrics"})
            header, _ = _recv_frame(sock)
        except (ConnectionError, OSError) as exc:
            self._drop(peer_id)
            raise PeerUnavailable(f"peer {peer_id}: {exc}")
        if header.get("verb") != "metrics":
            raise WireError(
                f"unexpected response verb {header.get('verb')!r}")
        return header.get("text", "")

    def events_text(self, peer_id: str, timeout: float = 5.0) -> str:
        """Flight-recorder scrape of a peer's event ring as JSONL (the
        ``dump-events`` verb) — what ``--mode doctor`` ingests from LIVE
        servers. Meta line only when the peer's recorder is disabled."""
        sock = self._connect(peer_id)
        try:
            sock.settimeout(timeout)
            self._send(peer_id, sock, {"verb": "dump-events"})
            header, _ = _recv_frame(sock)
        except (ConnectionError, OSError) as exc:
            self._drop(peer_id)
            raise PeerUnavailable(f"peer {peer_id}: {exc}")
        if header.get("verb") != "events":
            raise WireError(
                f"unexpected response verb {header.get('verb')!r}")
        return header.get("lines", "")

    def swarm_stats(self, peer_id: str, timeout: float = 5.0) -> dict:
        """One peer's swarm view (the ``swarm-stats`` verb): its own stats
        digest under ``"self"`` plus every live gossip record it holds
        under ``"records"`` — the input for ``--mode top``."""
        sock = self._connect(peer_id)
        try:
            sock.settimeout(timeout)
            self._send(peer_id, sock, {"verb": "swarm-stats"})
            header, _ = _recv_frame(sock)
        except (ConnectionError, OSError) as exc:
            self._drop(peer_id)
            raise PeerUnavailable(f"peer {peer_id}: {exc}")
        if header.get("verb") != "swarm-stats":
            raise WireError(
                f"unexpected response verb {header.get('verb')!r}")
        return header

    # -- chaos layer (runtime.faults) -----------------------------------

    def set_fault_plan(self, plan: Optional[FaultPlan]) -> None:
        """Arm (or with None, clear) a FaultPlan on THIS transport's own
        dial/send path. Drops pooled connections so socket wrapping always
        matches the armed state — a cleared plan must not keep firing
        through wrappers left on old sockets."""
        self.close()
        self.fault_plan = plan

    def _fault_rpc(self, peer_id: str, header: dict,
                   timeout: float = 5.0) -> dict:
        sock = self._connect(peer_id)
        try:
            sock.settimeout(timeout)
            self._send(peer_id, sock, header)
            h, _ = _recv_frame(sock)
        except (ConnectionError, OSError) as exc:
            self._drop(peer_id)
            raise PeerUnavailable(f"peer {peer_id}: {exc}")
        if h.get("verb") == "error":
            raise RuntimeError(f"peer {peer_id}: {h.get('message')}")
        return h

    def install_fault_plan(self, peer_id: str,
                           plan: Optional[FaultPlan]) -> dict:
        """Install (or with None, clear) a FaultPlan on a REMOTE peer via
        the `fault` admin verb. The peer refuses unless it was started with
        fault injection allowed (--allow_fault_injection)."""
        if plan is None:
            return self._fault_rpc(peer_id,
                                   {"verb": "fault", "action": "clear"})
        return self._fault_rpc(peer_id,
                               {"verb": "fault", "plan": plan.to_dict()})

    def fault_report(self, peer_id: str) -> list:
        """The remote peer's fault-firing log (list of dicts): what its
        armed plan actually injected, in order — the chaos soak diffs this
        against the doctor's reconstructed failure chains."""
        return self._fault_rpc(
            peer_id, {"verb": "fault", "action": "report"}).get("firings", [])

    def reach_check(self, peer_id: str, target: str,
                    timeout: float = 8.0) -> bool:
        """Ask `peer_id` whether IT can dial `target` ("host:port") — the
        client side of the reach_check verb (petals ReachabilityProtocol
        rpc_check, reachability.py:136-150)."""
        sock = self._connect(peer_id)
        try:
            sock.settimeout(timeout)
            self._send(peer_id, sock,
                       {"verb": "reach_check", "target": target})
            header, _ = _recv_frame(sock)
            return bool(header.get("ok"))
        except (ConnectionError, OSError) as exc:
            self._drop(peer_id)
            raise PeerUnavailable(f"peer {peer_id}: {exc}")

    def relay_attach(self, peer_id: str, my_peer_id: str, my_address: str,
                     timeout: float = 5.0) -> dict:
        """Ask volunteer `peer_id` to forward for us: open (or refresh — the
        verb is an idempotent lease renewal) a relay circuit mapping
        `my_peer_id` -> `my_address`. The address must be one the VOLUNTEER
        can dial (our bind address, inside the NAT) — by definition not the
        advertised one that failed the reachability vote. Raises
        PeerUnavailable when the volunteer sheds (saturated) or is gone, so
        the picker moves on to the next candidate."""
        sock = self._connect(peer_id)
        try:
            sock.settimeout(timeout)
            self._send(peer_id, sock, {"verb": "relay_attach",
                                       "peer_id": my_peer_id,
                                       "address": my_address})
            header, _ = _recv_frame(sock)
        except (ConnectionError, OSError) as exc:
            self._drop(peer_id)
            raise PeerUnavailable(f"peer {peer_id}: {exc}")
        if header.get("verb") != "ok":
            raise PeerUnavailable(
                f"relay {peer_id} refused attach: {header.get('message')}")
        return header

    def close(self) -> None:
        with self._lock:
            conns, self._conns = dict(self._conns), {}
        for sock in conns.values():
            try:
                sock.close()
            except OSError:
                pass


def check_direct_reachability(transport: TcpTransport, registry,
                              my_address: str, max_peers: int = 5,
                              threshold: float = 0.5) -> Optional[bool]:
    """Am I directly reachable at `my_address`? Ask up to `max_peers` live
    peers to dial it back; >= `threshold` of the answers saying yes means
    direct (petals ``check_direct_reachability``, reachability.py:55-78 —
    same >=50%-of-<=5-peers rule). Returns None when no peer answered (a
    single-server swarm cannot decide). A booting elastic server uses this
    to validate its advertised address before publishing it (the reference's
    public-maddr filtering, src/main.py:492-509)."""
    votes = []
    for rec in registry.live_servers():
        if len(votes) >= max_peers:
            break
        if not getattr(rec, "address", None) or rec.address == my_address:
            continue
        try:
            votes.append(transport.reach_check(rec.peer_id, my_address))
        except (PeerUnavailable, TimeoutError, ConnectionError, OSError):
            continue
    if not votes:
        return None
    return sum(votes) / len(votes) >= threshold


def attach_via_relay(transport: TcpTransport, registry, my_peer_id: str,
                     my_address: str, exclude=()) -> Optional[dict]:
    """Pick a relay volunteer and attach to it (petals' relay fallback after
    a failed reachability vote). Candidates are live peers that advertise
    relay capacity and are not themselves relayed — relaying through a
    relayed peer would chain circuits. Tried most-spare-capacity first; a
    saturated volunteer sheds with an error frame and the next candidate is
    tried, so load spreads by construction. Returns the volunteer's ok frame
    with ``"relay"`` = its peer_id, or None when nobody volunteers (the
    caller stays unregistered and retries on its heartbeat cadence)."""
    skip = set(exclude) | {my_peer_id}
    cands = [r for r in registry.live_servers()
             if r.peer_id not in skip
             and getattr(r, "address", None)
             and (getattr(r, "relay_capacity", None) or 0) > 0
             and not getattr(r, "relay_via", None)]
    cands.sort(key=lambda r: -(r.relay_capacity or 0))
    for rec in cands:
        try:
            ok = transport.relay_attach(rec.peer_id, my_peer_id, my_address)
        except (PeerUnavailable, TimeoutError, ConnectionError, OSError,
                WireError):
            continue
        ok["relay"] = rec.peer_id
        return ok
    return None


# ---------------------------------------------------------------------------
# Registry service (control plane)
# ---------------------------------------------------------------------------

# Record wire schema now lives beside the dataclass (scheduling.registry) so
# the gossip mirrors serialize identically; these aliases keep this module's
# historical private names working.
_rec_to_dict = rec_to_dict
_dict_to_rec = dict_to_rec


def gossip_exchange(node: GossipNode, address: str,
                    timeout: float = 5.0) -> Tuple[int, int]:
    """One digest-then-delta anti-entropy round with the stage server at
    `address` (initiator side; the responder is `_gossip_dispatch`).

      1. ship our digest; the peer answers with ITS digest plus the
         entries our digest shows we lack — merge them;
      2. ship back the entries the peer's digest shows IT lacks (skipped
         when it already has everything).

    Returns (entries_sent, entries_merged). Connection errors propagate —
    the gossip loop treats a dead peer as this round's loss, nothing more.
    """
    host, port = address.rsplit(":", 1)
    sock = socket.create_connection((host, int(port)), timeout=timeout)
    try:
        sock.settimeout(timeout)
        _send_frame(sock, {"verb": "gossip", "peer_id": node.peer_id,
                           "digest": node.digest()})
        resp, _ = _recv_frame(sock)
        if resp.get("verb") != "gossip":
            raise ConnectionError(
                f"peer at {address} does not gossip: "
                f"{resp.get('message', resp.get('verb'))!r}")
        merged = node.merge(resp.get("entries") or ())
        delta = node.delta_for(resp.get("digest") or {})
        if delta:
            _send_frame(sock, {"verb": "gossip", "peer_id": node.peer_id,
                               "entries": delta})
            _recv_frame(sock)      # ack ({"verb": "gossip", "merged": n})
        _tm.get("gossip_rounds_total").labels(role="initiator").inc()
        _ev.emit("gossip_round", peer=address, sent=len(delta),
                 merged=merged)
        return len(delta), merged
    finally:
        try:
            sock.close()
        except OSError:
            pass


class RegistryServer(_FramedTcpServer):
    """JSON-over-TCP registry service backed by a PlacementRegistry."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 ttl: float = 45.0, allow_fault_injection: bool = False):
        self.registry = PlacementRegistry(ttl=ttl)
        super().__init__(host, port)
        self.fault_side = "registry"
        self.allow_fault_injection = allow_fault_injection

    def _dispatch(self, sock, header: dict, payload: bytes) -> None:
        del payload
        plan = self.fault_plan
        if plan is not None:
            # Control-plane chaos beyond the generic dispatch hooks (which
            # already cover accept_hang/delay for side="registry"):
            #   duplicate      — process the verb TWICE, reply once
            #                    (at-least-once delivery; the registry's
            #                    verbs are idempotent, which this proves);
            #   stale_registry — rewind every record's freshness before
            #                    answering (a lagging/partitioned view).
            rule = plan.fire("registry", SITE_KINDS["registry"],
                             side="registry", verb=header.get("verb"))
            if rule is not None:
                if rule.kind == "duplicate":
                    self._handle_verb(header)
                else:
                    self.registry.age_records(rule.age_s)
        _send_frame(sock, self._handle_verb(header))

    def _handle_verb(self, h: dict) -> dict:
        verb = h.get("verb")
        if verb == "fault":
            return self._fault_admin(h)
        if verb == "register":
            self.registry.register(_dict_to_rec(h["record"]))
            # The server's TTL rides every write response so peers pace
            # their heartbeats off the REAL expiry policy, not a client-side
            # default (a --ttl mismatch would make records expire between
            # heartbeats and flap the whole swarm).
            return {"verb": "ok", "ttl": self.registry.ttl}
        if verb == "heartbeat":
            ok = self.registry.heartbeat(
                h["peer_id"], throughput=h.get("throughput"),
                cache_tokens_left=h.get("cache_tokens_left"),
                next_server_rtts=h.get("next_server_rtts"))
            return {"verb": "ok", "known": ok, "ttl": self.registry.ttl}
        if verb == "unregister":
            self.registry.unregister(h["peer_id"])
            return {"verb": "ok"}
        if verb == "list":
            # age_s rides along so clients can reconstruct freshness ordering:
            # raw `timestamp` is time.monotonic(), meaningless across hosts.
            now = time.monotonic()
            return {"verb": "records", "ttl": self.registry.ttl,
                    "records": [dict(_rec_to_dict(r),
                                     age_s=max(0.0, now - r.timestamp))
                                for r in self.registry.live_servers()]}
        return {"verb": "error", "message": f"unknown verb {verb!r}"}


class RemoteRegistry:
    """Client for RegistryServer with the PlacementRegistry query surface.

    Queries fetch the full live-record list and evaluate locally — the same
    read-everything pattern as the reference's ``get_remote_module_infos``
    DHT scan (``src/dht_utils.py:147-242``). Fine at mini-Petals swarm sizes.

    HA (VERDICT r3 item 6 — the registry replaced a DHT with no single
    point of failure, ``src/dht_utils.py:34-242``): ``address`` may be a
    COMMA-SEPARATED list of registries (a primary + standbys, each an
    independent ``--mode registry`` process; no registry-to-registry sync
    exists or is needed).

      * WRITES (register/heartbeat/unregister) broadcast to every address
        and succeed if ANY registry took them — so a standby holds live
        records the moment servers heartbeat, and a NEW server can join
        while the primary is down. A restarted-empty registry answers
        heartbeat known=false, and every server's heartbeat loop already
        re-registers on that — the standby self-populates within one beat.
      * READS (list) try addresses round-robin from the last-good one; if
        ALL registries are down, the last fetched records serve as a STALE
        CACHE with natural TTL grace (each record's restored timestamp
        ages out through PlacementRegistry's normal expiry), so pinned
        routes and discovery keep working across a registry outage shorter
        than the TTL.
    """

    def __init__(self, address: str, timeout: float = 5.0,
                 rng: Optional["np.random.Generator"] = None,
                 peers_cache: Optional[str] = None):
        self._addrs = []
        for part in str(address).split(","):
            part = part.strip()
            if not part:
                continue
            host, port = part.rsplit(":", 1)
            self._addrs.append((host, int(port)))
        if not self._addrs:
            raise ValueError(f"no registry address in {address!r}")
        self.timeout = timeout
        self._socks: List[Optional[socket.socket]] = [None] * len(self._addrs)
        self._read_idx = 0          # last-good registry for reads
        # Per-registry connect backoff: a firewalled/partitioned standby
        # must not add a full connect timeout to EVERY write (all traffic
        # shares self._lock) — after a failure the address is skipped until
        # the backoff expires, except as a last resort when nothing else
        # answers.
        self.down_backoff_s = 4 * timeout
        self._down_until = [0.0] * len(self._addrs)
        self._lock = threading.Lock()
        import random as _random

        self._local = PlacementRegistry(rng=_random.Random(0))
        self._have_snapshot = False
        self._stale_since: Optional[float] = None
        self._seeds_down_since: Optional[float] = None
        self.ttl = self._local.ttl
        # Last-known-peers bootstrap cache (--peers_cache): stage-server
        # addresses from the last good snapshot, persisted to disk so a
        # FRESHLY STARTED client (no snapshot yet) can still bootstrap off
        # a live stage server's gossip mirror after total seed loss.
        self.peers_cache = peers_cache
        self._cached_peer_addrs: List[str] = self._load_peers_cache()
        # Buffered registrations (one per peer): a register issued while
        # every registry is down must not be silently dropped — it flushes
        # on the first successful reconnect (see _rpc_one_locked).
        self._pending_register: Dict[str, dict] = {}

    def _rpc_one_locked(self, i: int, header: dict) -> dict:
        """One request/response against registry i (caller holds the lock).
        A failure on a REUSED connection retries once on a fresh one — a
        restarted registry leaves the old persistent socket half-open, and
        that stale-socket error must not read as 'registry down'."""
        for attempt in (0, 1):
            fresh = self._socks[i] is None
            try:
                if fresh:
                    self._socks[i] = socket.create_connection(
                        self._addrs[i], timeout=self.timeout)
                _send_frame(self._socks[i], header)
                resp, _ = _recv_frame(self._socks[i])
                self._down_until[i] = 0.0
                if self._pending_register and header.get("verb") != "register":
                    self._flush_pending_locked(i)
                return resp
            except (ConnectionError, OSError):
                if self._socks[i] is not None:
                    try:
                        self._socks[i].close()
                    finally:
                        self._socks[i] = None
                if fresh or attempt:
                    self._down_until[i] = time.monotonic() + self.down_backoff_s
                    raise
        raise AssertionError("unreachable")

    def _flush_pending_locked(self, i: int) -> None:
        """Replay buffered registrations into registry `i` (just proven
        reachable; caller holds the lock and the live socket). A failure
        mid-flush leaves the remainder buffered for the next success."""
        for peer in list(self._pending_register):
            rec = self._pending_register[peer]
            try:
                _send_frame(self._socks[i], {"verb": "register",
                                             "record": rec})
                resp, _ = _recv_frame(self._socks[i])
            except (ConnectionError, OSError):
                return
            self._pending_register.pop(peer, None)
            self._sync_ttl(resp)
            logger.info("flushed buffered registration of %s to %s:%d",
                        peer, *self._addrs[i])

    def _up_order(self, start: int = 0) -> List[int]:
        """Registry indices, not-in-backoff first (rotated from `start`),
        backed-off ones last — tried only as a last resort."""
        now = time.monotonic()
        idxs = [(start + k) % len(self._addrs)
                for k in range(len(self._addrs))]
        return ([i for i in idxs if self._down_until[i] <= now]
                + [i for i in idxs if self._down_until[i] > now])

    def _rpc(self, header: dict) -> dict:
        """READ path: first registry that answers, round-robin from the
        last good one (backed-off addresses tried last). Raises only when
        every registry is down."""
        with self._lock:
            last_exc: Optional[Exception] = None
            for i in self._up_order(self._read_idx):
                try:
                    resp = self._rpc_one_locked(i, header)
                    self._read_idx = i
                    return resp
                except (ConnectionError, OSError) as exc:
                    last_exc = exc
            raise last_exc  # type: ignore[misc]

    def _rpc_all(self, header: dict) -> List[dict]:
        """WRITE path: broadcast to every non-backed-off registry; succeeds
        if ANY took it (a dead standby must not fail serving, nor cost a
        connect timeout on every write). Backed-off registries are retried
        only when nothing else answered."""
        with self._lock:
            now = time.monotonic()
            resps, last_exc = [], None
            skipped = []
            for i in range(len(self._addrs)):
                if self._down_until[i] > now:
                    skipped.append(i)
                    continue
                try:
                    resps.append(self._rpc_one_locked(i, header))
                except (ConnectionError, OSError) as exc:
                    last_exc = exc
            if not resps:
                for i in skipped:        # last resort: try backed-off ones
                    try:
                        resps.append(self._rpc_one_locked(i, header))
                    except (ConnectionError, OSError) as exc:
                        last_exc = exc
            if not resps:
                raise last_exc  # type: ignore[misc]
            return resps

    # -- write path ---------------------------------------------------------

    def _sync_ttl(self, resp: dict) -> None:
        if resp.get("ttl"):
            self.ttl = float(resp["ttl"])

    def register(self, record: ServerRecord, ttl: Optional[float] = None) -> None:
        del ttl  # server-side TTL policy
        rec = _rec_to_dict(record)
        try:
            resps = self._rpc_all({"verb": "register", "record": rec})
        except (ConnectionError, OSError):
            # Every registry is down: buffer the LAST record per peer and
            # flush on the first successful reconnect — without this, a
            # registration issued during an outage silently vanished until
            # the heartbeat loop's known=false repair, and a peer that
            # never heartbeats (a client-issued set_state) stayed lost.
            with self._lock:
                self._pending_register[record.peer_id] = rec
            logger.warning(
                "register(%s): every registry unreachable; buffered for "
                "flush on reconnect", record.peer_id)
            return
        with self._lock:
            self._pending_register.pop(record.peer_id, None)
        for resp in resps:
            self._sync_ttl(resp)

    def heartbeat(self, peer_id: str, throughput: Optional[float] = None,
                  cache_tokens_left: Optional[int] = None,
                  next_server_rtts: Optional[Dict[str, float]] = None) -> bool:
        resps = self._rpc_all({"verb": "heartbeat", "peer_id": peer_id,
                               "throughput": throughput,
                               "cache_tokens_left": cache_tokens_left,
                               "next_server_rtts": next_server_rtts})
        for resp in resps:
            self._sync_ttl(resp)
        # known = AND over the registries that answered: if ANY reachable
        # registry forgot us (restart, fresh standby), the caller's
        # re-register broadcast refreshes all of them.
        return all(bool(r.get("known")) for r in resps)

    def unregister(self, peer_id: str) -> None:
        self._rpc_all({"verb": "unregister", "peer_id": peer_id})

    def set_state(self, peer_id: str, state: str) -> None:
        rec = self.get(peer_id)
        if rec is not None:
            rec.state = state
            self.register(rec)

    # -- read path (local evaluation over fetched records) ------------------

    def _refresh(self) -> None:
        source = "seed"
        try:
            resp = self._rpc({"verb": "list"})
        except (ConnectionError, OSError):
            if self._seeds_down_since is None:
                self._seeds_down_since = time.monotonic()
                _ev.emit("registry_unreachable", registries=len(self._addrs))
                logger.warning(
                    "all %d registry seed%s unreachable",
                    len(self._addrs),
                    " is" if len(self._addrs) == 1 else "s are")
            # ANY-PEER BOOTSTRAP: every seed registry is down, but the
            # stage servers gossip the placement records among themselves —
            # any live one answers `list` from its mirror. Candidates come
            # from the current snapshot and from the on-disk peers cache
            # (so even a freshly restarted client survives total seed loss).
            resp = self._fallback_list()
            source = "mirror"
            if resp is None:
                if not self._have_snapshot:
                    raise
                # STALE-CACHE GRACE: every registry AND every known stage
                # server is unreachable, but we hold a previous snapshot
                # whose records age out through the normal TTL — keep
                # serving it so discovery and pinned-route repair survive
                # an outage shorter than the TTL.
                _tm.get("client_registry_stale_reads_total").inc()
                if self._stale_since is None:
                    self._stale_since = time.monotonic()
                    _ev.emit("registry_stale_serve",
                             registries=len(self._addrs))
                    logger.warning(
                        "no registry and no live stage server reachable; "
                        "serving the cached record snapshot under TTL "
                        "grace")
                return
        now = time.monotonic()
        if source == "seed":
            if self._seeds_down_since is not None:
                _ev.emit("registry_recovered", source="seed",
                         stale_s=round(now - self._seeds_down_since, 3))
                logger.info("registry seeds reachable again")
            self._seeds_down_since = None
        elif self._stale_since is not None:
            # A mirror answered after a stale-serving window: fresh records
            # again, though the seeds are still gone (that window stays
            # open until a seed read succeeds).
            _ev.emit("registry_recovered", source="mirror",
                     stale_s=round(now - self._stale_since, 3))
        self._stale_since = None
        self._sync_ttl(resp)
        import random as _random

        # The snapshot's records must expire on the SERVER's TTL policy —
        # that is what bounds the stale-cache grace when every registry
        # later goes down.
        fresh = PlacementRegistry(ttl=self.ttl, rng=_random.Random(0))
        now = time.monotonic()
        for d in resp.get("records", []):
            rec = _dict_to_rec(d)
            fresh.register(rec)
            # Restore true freshness from the server-reported age (register()
            # stamps "now"): newest-first ordering in discovery and next-hop
            # ping candidate selection depends on it — and the expiry must
            # follow, or the stale-cache grace would serve an already-aged
            # record for up to ~2x TTL after its last heartbeat.
            rec.timestamp = now - float(d.get("age_s") or 0.0)
            rec.expires_at = rec.timestamp + fresh.ttl
        self._local = fresh
        self._have_snapshot = True
        self._save_peers_cache()

    # -- any-peer bootstrap (gossip mirrors + peers cache) -------------------

    def _fallback_list(self) -> Optional[dict]:
        """`list` served by ANY live stage server's gossip mirror: tried in
        order over the snapshot's stage addresses then the on-disk peers
        cache. None when nobody answered (pure pre-gossip outage)."""
        for addr in self._fallback_candidates():
            try:
                host, port = addr.rsplit(":", 1)
                sock = socket.create_connection((host, int(port)),
                                                timeout=self.timeout)
                try:
                    sock.settimeout(self.timeout)
                    _send_frame(sock, {"verb": "list"})
                    resp, _ = _recv_frame(sock)
                finally:
                    sock.close()
            except (ConnectionError, OSError, ValueError):
                continue
            if resp.get("verb") != "records":
                # A stage server without a gossip mirror answers an error
                # frame — not a discovery source, keep looking.
                continue
            _tm.get("client_registry_fallback_reads_total").inc()
            _ev.emit("gossip_fallback", address=addr,
                     records=len(resp.get("records") or ()))
            logger.warning(
                "registry reads served by stage server %s (gossip mirror)",
                addr)
            return resp
        return None

    def _fallback_candidates(self) -> List[str]:
        seeds = {"%s:%d" % a for a in self._addrs}
        seen, out = set(seeds), []
        for r in self._local.live_servers():
            a = getattr(r, "address", None)
            if a and a not in seen:
                seen.add(a)
                out.append(a)
        for a in self._cached_peer_addrs:
            if a and a not in seen:
                seen.add(a)
                out.append(a)
        return out

    def _load_peers_cache(self) -> List[str]:
        if not self.peers_cache:
            return []
        try:
            with open(self.peers_cache, "r", encoding="utf-8") as fh:
                data = json.load(fh)
            return [str(a) for a in data.get("addresses", [])]
        except (OSError, ValueError):
            return []

    def _save_peers_cache(self) -> None:
        """Persist the snapshot's stage-server addresses (atomic rename) so
        a fresh client process can bootstrap with every seed dead."""
        addrs = []
        for r in self._local.live_servers():
            a = getattr(r, "address", None)
            if a and a not in addrs:
                addrs.append(a)
        self._cached_peer_addrs = addrs
        if not self.peers_cache or not addrs:
            return
        try:
            tmp = f"{self.peers_cache}.tmp"
            with open(tmp, "w", encoding="utf-8") as fh:
                json.dump({"addresses": addrs, "saved_wall": time.time()}, fh)
            import os

            os.replace(tmp, self.peers_cache)
        except OSError:
            logger.debug("could not write peers cache %s", self.peers_cache,
                         exc_info=True)

    def stale_info(self) -> dict:
        """The current outage windows, for --mode status and operators:
        `seeds_down_s` since every seed stopped answering (0 = healthy),
        `stale_s` since reads fell back to the STALE snapshot (0 = reads
        are fresh, possibly via a gossip mirror)."""
        now = time.monotonic()
        sd, st = self._seeds_down_since, self._stale_since
        return {"seeds_down": sd is not None,
                "seeds_down_s": 0.0 if sd is None else now - sd,
                "stale": st is not None,
                "stale_s": 0.0 if st is None else now - st}

    def live_servers(self, model=None):
        self._refresh()
        return self._local.live_servers(model=model)

    def get(self, peer_id: str):
        self._refresh()
        return self._local.get(peer_id)

    def discover_stage(self, stage_index: int, exclude=(), model=None,
                       prefer_engine=None, avoid_engine=None,
                       min_context=None, affinity=None):
        self._refresh()
        return self._local.discover_stage(stage_index, exclude, model=model,
                                          prefer_engine=prefer_engine,
                                          avoid_engine=avoid_engine,
                                          min_context=min_context,
                                          affinity=affinity)

    def discover_block(self, block: int, exclude=(), model=None):
        self._refresh()
        return self._local.discover_block(block, exclude, model=model)

    def coverage(self, total_blocks: int, model=None):
        self._refresh()
        return self._local.coverage(total_blocks, model=model)
