"""Stage request/response schema — the in-process mirror of the wire protocol.

Semantically mirrors the reference's ``ExpertRequest``/``ExpertResponse``
protobufs + msgpack metadata sidecar (SURVEY.md Appendix B;
``src/rpc_transport.py:725-734,788-798`` and ``src/rpc_handler.py:301-325``):

  request:  {session_id, seq_len, cur_len, is_prefill, is_replay, max_length,
             temperature, top_p, top_k, repetition_penalty,
             generated_tokens[-50:]} + one hidden tensor [B, T, D]
  response (intermediate): hidden tensor [B, T, D]
  response (final): token_id

The reference ships sampling params and the recent-token window in metadata on
EVERY step so the final server can sample statelessly — we keep that property:
it is exactly what makes failover to a replacement final stage work without
migrating sampler state.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax.numpy as jnp

from ..ops.sampling import SamplingParams


@dataclasses.dataclass
class StageRequest:
    """One hop's worth of work for a pipeline stage."""

    session_id: str
    hidden: jnp.ndarray            # [B, T, D] activation entering the span
    seq_len: int                   # number of REAL (unpadded) tokens in hidden
    cur_len: int                   # tokens already in this session before this step
    is_prefill: bool
    max_length: int                # session KV admission limit
    is_replay: bool = False        # replaying journal into a replacement peer
    sampling: SamplingParams = dataclasses.field(default_factory=SamplingParams)
    generated_tokens: Tuple[int, ...] = ()   # last <=50, for repetition penalty
    step_seed: int = 0             # deterministic per-step sampling seed
    # Block sub-range to execute, absolute indices. None = the server's whole
    # span. This is the uid-chain of the Petals protocol
    # (``petals/server/handler.py:522-530``): elastic placement produces
    # OVERLAPPING spans, and a hop must run exactly the blocks the route
    # assigned it, not everything it has loaded.
    start_block: Optional[int] = None
    end_block: Optional[int] = None
    # Fine-tuning forward (the vendored ``rpc_forward`` training path,
    # ``petals/server/block_functions.py:32-81``): stateless cache-free span
    # forward of the BLOCKS only (no head/sampling), with optional deep
    # prompts added into the first positions of each block's input.
    train: bool = False
    # Deep prompts, [span_layers, pre_seq, D]. train=True: the rpc_forward
    # training injection above. train=False: INFERENCE-time deep prompt
    # tuning (``petals/server/block_functions.py:171-226``) — every step,
    # each block of the span adds its prompt at absolute positions <
    # pre_seq before computing (executor._get_prompt_step).
    prompts: Optional[jnp.ndarray] = None
    # Client-owned LoRA adapters for the span (models.lora; train=True
    # only): {"wq": {"a": [span, D, r], "b": [span, r, O]}, ...}. The
    # server merges W + lora_scale * a @ b functionally per step and
    # returns adapter grads — stateless, like the prompt slices.
    lora: Optional[dict] = None
    lora_scale: float = 1.0
    # Session rewind (the ``start_from_position`` of petals
    # ``handler.py:163-168`` / ``block_functions.py:163-168``): before this
    # step, shrink the session's valid KV prefix to this position — the
    # client is re-generating from an earlier point (interactive edit /
    # speculative rollback). Must satisfy 0 <= pos <= current cache_len and
    # equal cur_len.
    start_from_position: Optional[int] = None
    # Beam search (petals ``backend.py:154-158`` hypo_ids semantics):
    # hypo_ids[i] = which existing KV row hypothesis i continues from; the
    # server reorders the session's cache BEFORE the step. num_logprobs > 0
    # asks the final stage for per-row top-N (token, logprob) pairs instead
    # of a sampled token — the client runs the beam bookkeeping.
    hypo_ids: Optional[Tuple[int, ...]] = None
    num_logprobs: int = 0
    # Speculative decoding (no reference counterpart — a TPU-build extension
    # that attacks the reference's dominant cost, one WAN round trip per
    # token): ``hidden`` carries 1 + K positions — the last accepted token
    # followed by K client-drafted tokens — and ``draft_tokens`` holds those
    # K draft ids. Intermediate stages treat it as a normal multi-token step;
    # the FINAL stage greedily verifies (accept while draft[i] ==
    # argmax(logits[i])), rewinds its own KV past the rejected tail, and
    # returns the accepted tokens plus one correction/bonus token.
    draft_tokens: Optional[Tuple[int, ...]] = None
    # Model identity as declared by the ORIGINATING client (the data-plane
    # mirror of the reference's model-prefixed DHT keys). Servers reject
    # mismatches and relays propagate the original tag — an untagged legacy
    # hop must not strip the client's tag from the rest of the chain.
    model: Optional[str] = None
    # Push-chain route (the ``next_servers`` metadata of Petals'
    # server→server push, ``petals/server/handler.py:320-350``): the hops
    # AFTER this one. A server that produced hidden output forwards it
    # directly to next_servers[0] (relaying the eventual final response back
    # up) instead of bouncing through the client — one client round trip per
    # step instead of one per hop. Entries: {peer_id, address?, start_block,
    # end_block}. A NAT'd hop's entry additionally carries relay_via (its
    # volunteer's peer_id) with address OVERRIDDEN to the volunteer's — the
    # pushing server dials the volunteer and stamps relay_to, exactly like
    # a client would, and push-chain error frames for that hop split
    # routing blame (peer) from breaker blame (breaker_peer).
    next_servers: Tuple[dict, ...] = ()
    # Prompt-prefix sharing (runtime.prefix_cache; no reference
    # counterpart): on a PREFILL, the client marks the leading prefix_len
    # tokens as shareable across sessions. A server running a prefix store
    # may then skip the span forward for those rows (content-addressed hit)
    # and registers them on a miss. 0 = no sharing; servers without a store
    # ignore the field, so clients annotate unconditionally.
    prefix_len: int = 0
    # Trace context (telemetry.tracing, Dapper-style):
    # {"trace_id": <16 hex>, "parent": <client hop span_id>, "hop": <int>}.
    # None = tracing off / legacy client; servers must treat it as opaque
    # pass-through (push-chain relays propagate it unchanged so every hop of
    # a chain lands in the same trace).
    trace: Optional[dict] = None
    # End-to-end deadline budget: seconds REMAINING when this request left
    # its sender. The client stamps the remaining budget per hop (and the
    # push-chain relay re-stamps it minus its own service time), so any hop
    # observing an exhausted budget rejects instead of computing tokens the
    # caller already gave up on (typed DeadlineExceeded client-side; a
    # ``deadline_rejected`` event server-side). None = no deadline (default;
    # the pre-deadline wire format, headers stay byte-identical).
    deadline_budget_s: Optional[float] = None
    # Tenant priority assigned by the serving gateway (serving.gateway):
    # lower is MORE urgent, fed into the server task pool's prioritizer so
    # a heavy tenant's steps queue behind a light tenant's on a contended
    # stage. None = no gateway (default; headers stay byte-identical).
    priority: Optional[float] = None
    # Burst decode (continuous-batching serving core): ask a full-span
    # batched final stage to run up to ``burst_len`` decode ticks in ONE
    # jitted dispatch, sampling on-device with the session-local seed
    # schedule (PRNGKey(step_seed + i) for tick i) so tokens stay
    # bit-identical to the sequential path. ``hidden`` carries the single
    # last accepted token id as a [1, 1] int array; the response is a
    # ``burst_tokens`` block. ``burst_budget`` caps the EMITTED tokens
    # below burst_len (the session's remaining allowance) without forcing
    # a second jit compile for the final partial burst. 0 = classic
    # per-tick decode (default; headers stay byte-identical).
    burst_len: int = 0
    burst_budget: int = 0
    # End-of-sequence token the DEVICE must stop at mid-burst (mirrors the
    # client's host-side stop rule so emitted counts match). None = no eos
    # stop (the classic path never ships one).
    eos_token_id: Optional[int] = None
    # HOST ONLY, never on the wire (no header is built from it): the
    # monotonic instant the serving boundary read this request's frame off
    # its socket, for ``server_request_leg_seconds``. 0.0 on every path
    # that has no such instant (in-process transports, relays, a gateway).
    t_recv: float = 0.0


@dataclasses.dataclass
class BackwardRequest:
    """``rpc_backward`` (``petals/server/handler.py:434-488``): the server
    re-forwards its span from the supplied input (activations are NOT stored
    server-side between training steps) and returns input/prompt grads."""

    session_id: str
    hidden: jnp.ndarray            # [B, T, D] span INPUT (what forward consumed)
    grad_output: jnp.ndarray       # [B, T, D] dL/d(span output)
    seq_len: int                   # REAL tokens in hidden/grad_output
    prompts: Optional[jnp.ndarray] = None   # [span_layers, pre_seq, D]
    start_block: Optional[int] = None
    end_block: Optional[int] = None
    # LoRA adapters, same layout/semantics as StageRequest.lora — the
    # backward re-forwards with them merged and returns their grads.
    lora: Optional[dict] = None
    lora_scale: float = 1.0


@dataclasses.dataclass
class BackwardResponse:
    session_id: str
    grad_input: jnp.ndarray                   # [B, T, D]
    grad_prompts: Optional[jnp.ndarray] = None  # [span_layers, pre_seq, D]
    grad_lora: Optional[dict] = None            # same tree shape as lora


@dataclasses.dataclass
class StageResponse:
    """What a stage returns: hidden states (intermediate) or a token (final)."""

    session_id: str
    hidden: Optional[jnp.ndarray] = None   # [B, T, D]
    token_id: Optional[int] = None
    # Batch>1 plain sampling: one token per batch row (token_id mirrors row 0
    # for back-compat). None for batch-1 responses.
    token_ids: Optional[Tuple[int, ...]] = None
    cache_len: int = 0                     # server-side KV length after the step
    # Beam mode (request.num_logprobs > 0): per batch row, the top-N
    # continuation candidates from the final stage's logits.
    top_tokens: Optional[Tuple[Tuple[int, ...], ...]] = None     # [B][N]
    top_logprobs: Optional[Tuple[Tuple[float, ...], ...]] = None  # [B][N]
    # Speculative mode (request.draft_tokens set): the verified output —
    # n_accepted accepted drafts followed by one correction/bonus token
    # (len == n_accepted + 1). cache_len reflects the final stage's KV AFTER
    # rewinding past the rejected tail.
    tokens: Optional[Tuple[int, ...]] = None
    n_accepted: Optional[int] = None
    # Burst mode (request.burst_len > 0): the tokens EMITTED by one burst
    # dispatch (<= burst_len; device-side stop rules truncate), plus why
    # the burst ended early: None (budget/burst boundary), "eos", or
    # "repeat". cache_len reflects the KV length after all emitted ticks.
    burst_tokens: Optional[Tuple[int, ...]] = None
    burst_stop: Optional[str] = None
    # Server-side span summary for the request's trace (telemetry.tracing
    # Span.to_wire()): the serving peer's own wall-clock start/end plus attrs
    # (peer id, blocks). None when the request carried no trace. On a push
    # chain the relayed final response keeps the FINAL hop's span — each
    # intermediate hop still records its span into its local tracer.
    span: Optional[dict] = None
    # HOST ONLY, never on the wire (no frame is built from it): the
    # monotonic instant the batched round that produced this response had
    # its results on the host, set where the reply says the session asks
    # for the next round; the serving boundary observes
    # ``server_reply_leg_seconds`` from it. 0.0 on every other path.
    t_done: float = 0.0

    @property
    def is_token(self) -> bool:
        return self.token_id is not None

    @property
    def is_speculative(self) -> bool:
        return self.tokens is not None

    @property
    def is_beam(self) -> bool:
        return self.top_tokens is not None

    @property
    def is_burst(self) -> bool:
        return self.burst_tokens is not None


def clip_generated(tokens: Sequence[int], window: int = 50) -> Tuple[int, ...]:
    """The reference sends only the last 50 generated tokens
    (``src/rpc_transport.py:788-798``)."""
    return tuple(int(t) for t in tokens[-window:])
