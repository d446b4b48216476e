"""Per-stage executor: the server-side compute path.

TPU-native counterpart of the reference's ``StageConnectionHandler._run_forward``
(``src/rpc_handler.py:149-325``): manage per-session KV, run the stage's layer
span, and either return the next hidden states (intermediate stage) or sample a
token (final stage — sampling happens ON the final server, with the sampling
params and recent-token window taken from request metadata each step).

Replay semantics preserved exactly (``src/rpc_handler.py:176-202``):
  * prefill clears any existing session cache;
  * decode with no cached session and ``is_replay=True`` is treated as a
    prefill chunk (a replacement server rebuilding its KV from the journal);
  * decode with no cached session and no replay flag is a hard error.

XLA-specific design (no reference counterpart — it re-traces per request):
  * the stage step is one jitted function per (cache_bucket, seq_bucket) pair;
    real sequence lengths are padded up to a small set of buckets so an elastic
    server sees a handful of compiles, then pure replay;
  * right-padded prefill is safe end-to-end: padded queries only produce
    garbage OUTPUT rows (discarded here before returning), and padded cache
    rows sit at positions the causal mask hides until a later real token
    overwrites them;
  * KV buffers live in a fixed-budget `KVArena` (admission control before
    dispatch — inside jit the cache write clamps rather than raises).
"""

from __future__ import annotations

import logging
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.config import ModelConfig, refuse_single_pass
from ..models.partition import (
    ROLE_FULL,
    ROLE_LAST,
    ROLE_SEGMENT,
    ROLE_STAGE0,
    StageSpec,
    stage_forward,
)
from ..ops.sampling import RECENT_WINDOW, row_keys, sample_tokens
from ..models.transformer import stack_forward_train
from ..telemetry import events as _ev
from ..utils.platform import engine_donation
from .errors import register as _catalog
from .kv_cache import AllocationFailed, KVArena, KVHandle, round_to_bucket
from .messages import (
    BackwardRequest,
    BackwardResponse,
    StageRequest,
    StageResponse,
)

logger = logging.getLogger(__name__)

SEQ_BUCKETS = (1, 8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096, 8192)


@_catalog
class StageExecutionError(RuntimeError):
    """Server-side hard error (maps to the RuntimeError raised at
    ``src/rpc_handler.py:198-202`` for decode-without-cache)."""


_PREFIX_CHAIN_JIT = None


def _apply_prefix_chain(k, v, segs_k, segs_v):
    """Write a prefix-cache chain's KV segments (each [L, B, G, H, Dh])
    into the leading rows of the session caches in ONE program. Lists are
    pytrees, so jit re-specializes per chain length — stable per shared
    prompt. The fresh arena lease is donated (platform-gated like the
    engines — utils.platform.engine_donation) so a hit updates the
    bucket-sized buffers in place instead of duplicating them.

    Built LAZILY on first use: evaluating engine_donation at module import
    would initialize the JAX backend as an import side effect — breaking
    dcn.initialize's must-run-first contract and freezing the donation
    decision before a CPU fallback could flip it."""
    global _PREFIX_CHAIN_JIT
    if _PREFIX_CHAIN_JIT is None:
        @partial(jax.jit, donate_argnums=engine_donation(0, 1))
        def fn(k, v, segs_k, segs_v):
            zeros = (0,) * k.ndim
            kc = (segs_k[0] if len(segs_k) == 1
                  else jnp.concatenate(segs_k, axis=2))
            vc = (segs_v[0] if len(segs_v) == 1
                  else jnp.concatenate(segs_v, axis=2))
            return (jax.lax.dynamic_update_slice(k, kc, zeros),
                    jax.lax.dynamic_update_slice(v, vc, zeros))

        _PREFIX_CHAIN_JIT = fn
    return _PREFIX_CHAIN_JIT(k, v, segs_k, segs_v)


def verify_drafts_from_logits(
    logits2d: jnp.ndarray, req: StageRequest
) -> "tuple[tuple[int, ...], int]":
    """Final-stage speculative verification over one session's logits.

    logits2d: [T, V] for the T = K+1 positions [last_accepted, d_1..d_K];
    logits2d[i] predicts the token AFTER consuming position i. Returns
    (tokens, n_accepted) with len(tokens) == n_accepted + 1 (accepted run
    plus one correction/bonus token). Shared by the per-session executor
    and the batched adapter so both engines verify identically.

    Greedy (temperature<=0): accept while d_{i+1} == argmax(logits[i]) —
    token-identical to non-speculative greedy decoding
    (``src/rpc_handler.py:334-335`` applies greedy before penalties).
    Sampled (temperature>0): rejection-sampling verification
    (ops.sampling.speculative_verify) — accept draft i with probability
    p_i(d_i), resample the residual on reject — which preserves the
    sampling distribution exactly."""
    drafts = np.asarray(req.draft_tokens, np.int64)
    k = int(drafts.shape[0])
    if not req.sampling.greedy:
        from ..ops.sampling import speculative_verify

        recent = np.zeros((RECENT_WINDOW,), np.int32)
        n = min(len(req.generated_tokens), RECENT_WINDOW)
        if n:
            recent[:n] = np.asarray(req.generated_tokens[-n:], np.int32)
        sp = req.sampling
        toks, n_acc = speculative_verify(
            jax.random.PRNGKey(req.step_seed),
            logits2d.astype(jnp.float32),
            [int(d) for d in drafts], recent, n,
            sp.temperature, sp.top_p, sp.top_k, sp.repetition_penalty)
        return tuple(int(t) for t in toks), int(n_acc)
    preds = np.asarray(jnp.argmax(logits2d, axis=-1))  # [T]
    n_acc = 0
    while n_acc < k and int(preds[n_acc]) == int(drafts[n_acc]):
        n_acc += 1
    return tuple(int(t) for t in preds[: n_acc + 1]), n_acc


def _sample_rows(logits: jnp.ndarray, t_real: int, req: StageRequest) -> np.ndarray:
    """Final-stage sampling from the last REAL token's logits, PER BATCH ROW,
    using the metadata-shipped params + recent window
    (``src/rpc_handler.py:268-307``). logits: [B, T, V] -> int32 [B].

    Each row samples from its own logits with a row-decorrelated fold of the
    step seed (row 0 keeps the unfolded key, so batch-1 output is bit-
    identical to the historical single-row path). The recent-token window is
    session-scoped metadata and therefore shared across rows — matching the
    reference, whose generated-token window is likewise per-session
    (``src/rpc_transport.py:788-798``)."""
    last = logits[:, t_real - 1]  # [B, V] fp32 (lm_head upcasts)
    b = last.shape[0]
    recent = np.zeros((RECENT_WINDOW,), np.int32)
    n = min(len(req.generated_tokens), RECENT_WINDOW)
    if n:
        recent[:n] = np.asarray(req.generated_tokens[-n:], np.int32)
    sp = req.sampling
    base = jax.random.PRNGKey(req.step_seed)
    args = (
        jnp.asarray(recent),
        jnp.asarray(n, jnp.int32),
        jnp.asarray(sp.temperature, jnp.float32),
        jnp.asarray(sp.top_p, jnp.float32),
        jnp.asarray(sp.top_k, jnp.int32),
        jnp.asarray(sp.repetition_penalty, jnp.float32),
    )
    return np.asarray(sample_tokens(row_keys(base, b), last, *args))


def _sample_last(logits: jnp.ndarray, t_real: int, req: StageRequest) -> int:
    """Batch-1 convenience wrapper over `_sample_rows` (the batched adapter's
    per-slot rows are [1, T, V])."""
    return int(_sample_rows(logits, t_real, req)[0])


class StageExecutor:
    """One pipeline stage's compute engine (one 'server' in reference terms)."""

    def __init__(
        self,
        cfg: ModelConfig,
        spec: StageSpec,
        params: Dict[str, Any],
        arena: Optional[KVArena] = None,
        *,
        max_cache_bytes: int = 1 << 30,
        cache_dtype=jnp.float32,
        peer_id: str = "local",
        debug_activation_checks: bool = False,
        max_chunk_bytes: int = 256 * 1024 * 1024,
        offload: bool = False,
        keep_layers_resident: int = 0,
        tp_mesh: Optional["jax.sharding.Mesh"] = None,
        tp_axis: str = "tp",
        prefix_cache_bytes: int = 0,
    ):
        refuse_single_pass(cfg, "the per-session executor")
        self.cfg = cfg
        self.spec = spec
        self.params = params
        self.peer_id = peer_id
        # Tensor parallelism INSIDE the serving path (the reference wraps
        # every serving block in TP, petals/server/backend.py:43): params are
        # megatron-sharded over the local ('tp',) mesh, the step runs through
        # parallel.tensor_parallel's shard_map, and the session KV shards
        # over kv heads. Protocol-invisible: requests/responses are
        # replicated at the boundary.
        self.tp_mesh = tp_mesh
        self.tp_axis = tp_axis
        if tp_mesh is not None:
            from ..parallel.tensor_parallel import (
                shard_stage_params,
                validate_tp,
            )

            if offload:
                raise ValueError(
                    "tensor parallelism and host offload are mutually "
                    "exclusive on one executor (a TP span is HBM-resident "
                    "by design)")
            validate_tp(cfg, tp_mesh.shape[tp_axis])
            self.params = params = shard_stage_params(
                cfg, params, tp_mesh, tp_axis)
        # Prefill chunk budget (petals ``backend.py:129-143``
        # max_chunk_size_bytes): long prefills run as several bounded chunks
        # over the same session cache instead of one huge activation.
        self.max_chunk_bytes = max_chunk_bytes
        # Host-offload layer streaming (the reference's --use_cpu_offload /
        # --keep_layers_on_gpu, component 6): span weights live in host
        # memory and stream through HBM one layer at a time.
        self.offload = offload
        self.keep_layers_resident = max(keep_layers_resident, 0)
        if offload:
            # Pin the executor's own copy to HOST first, so the runner's
            # streamed layers alias host arrays and the only device-resident
            # weights are the pinned prefix + embed/norm/head. Without this,
            # self.params (and each cached sub_params slice) would keep the
            # full span alive in HBM — defeating the offload entirely.
            host = jax.devices("cpu")[0]
            self.params = jax.tree.map(
                lambda a: jax.device_put(a, host), params)
            params = self.params
        if tp_mesh is None and not offload:
            # Engine-side fused-QKV layout (one projection matmul per
            # layer; bitwise-identical — models/transformer.fuse_qkv_params).
            # TP keeps the canonical split (its shard boundaries must align
            # per-projection); offload keeps it (host-streaming layer trees
            # are keyed to the stored layout).
            from ..models.transformer import fuse_qkv_params

            self.params = params = fuse_qkv_params(params)
        self.cache_dtype = jnp.dtype(cache_dtype)
        kv_sharding = None
        tp_degree = 1
        if tp_mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            kv_sharding = NamedSharding(tp_mesh, P(None, None, None, tp_axis))
            tp_degree = tp_mesh.shape[tp_axis]
        self.arena = arena or KVArena(
            num_layers=max(spec.num_layers, 1),
            num_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.head_dim,
            max_bytes=max_cache_bytes,
            dtype=cache_dtype,
            sharding=kv_sharding,
            bytes_divisor=tp_degree,
        )
        self.debug_activation_checks = debug_activation_checks
        self.requests_served = 0
        # Prompt-prefix KV reuse (runtime.prefix_cache): > 0 enables a
        # bounded content-addressed store; repeat prefills copy cached KV
        # rows instead of recomputing the span for the shared prefix.
        self.prefix_store = None
        if prefix_cache_bytes > 0:
            from .prefix_cache import PrefixStore

            self.prefix_store = PrefixStore(prefix_cache_bytes)

        # Sub-span execution units, keyed by relative layer range (a, b). A
        # request may cover only part of the loaded span (the uid-chain of
        # petals/server/handler.py:522-530): elastic placement yields
        # OVERLAPPING server spans, and running the full span on a hidden
        # state that already passed some of its blocks silently corrupts the
        # output. The route assigns each hop an exact range; we execute
        # exactly that. Each entry holds (sub_spec, sub_params, jitted step);
        # jax.jit then caches one executable per (seq_bucket, cache_bucket)
        # input-shape pair — the bucket padding below bounds how many shapes
        # it ever sees.
        self._subspans: Dict[tuple, tuple] = {}
        # (a, b) -> prompt-injecting step callable (deep-prompt requests
        # only; kept separate so every _subspans entry stays a 3-tuple).
        self._prompt_steps: Dict[tuple, Any] = {}
        self._get_subspan(0, spec.num_layers)

    def _get_subspan(self, a: int, b: int):
        key = (a, b)
        entry = self._subspans.get(key)
        if entry is not None:
            return entry
        spec = self.spec
        if a == 0 and b == spec.num_layers:
            sub_spec, sub_params = spec, self.params
        else:
            first = spec.is_first and a == 0
            last = spec.is_last and b == spec.num_layers
            role = (ROLE_FULL if first and last else ROLE_STAGE0 if first
                    else ROLE_LAST if last else ROLE_SEGMENT)
            sub_spec = StageSpec(spec.index, role, spec.start + a, spec.start + b)
            sub_params = {}
            if "layers" in self.params:
                sub_params["layers"] = jax.tree.map(
                    lambda x: x[a:b], self.params["layers"]
                )
            if first and "embed" in self.params:
                sub_params["embed"] = self.params["embed"]
            if last:
                for k in ("final_norm", "lm_head"):
                    if k in self.params:
                        sub_params[k] = self.params[k]
                if self.cfg.tie_word_embeddings and "embed" in self.params:
                    sub_params.setdefault("embed", {})
                    sub_params["embed"] = {**sub_params["embed"],
                                           "wte": self.params["embed"]["wte"]}

        cfg = self.cfg

        if self.offload:
            from .offload import OffloadedSpanRunner

            step = OffloadedSpanRunner(
                cfg, sub_spec, sub_params,
                keep_resident=self.keep_layers_resident,
            )
        elif self.tp_mesh is not None:
            from ..parallel.tensor_parallel import make_tp_stage_fn

            step = make_tp_stage_fn(
                cfg, sub_spec, self.tp_mesh, self.tp_axis,
                donate_cache=bool(engine_donation(0)),
            )(sub_params)
        else:
            @partial(jax.jit, donate_argnums=engine_donation(2, 3))
            def step(params, x, k_cache, v_cache, cache_len):
                return stage_forward(cfg, sub_spec, params, x, k_cache,
                                     v_cache, cache_len)

        entry = (sub_spec, sub_params, step)
        self._subspans[key] = entry
        return entry

    def _get_prompt_step(self, a: int, b: int):
        """Step for inference requests carrying DEEP PROMPTS
        (``petals/server/block_functions.py:57-65,171-226``): same math as
        the plain subspan step plus a per-layer prompt injection at each
        block's entry, on EVERY engine (plain jit, offload, tp). Cached
        separately — the plain hot path keeps its prompt-free signature
        (and donation) untouched; jit re-specializes per prompts shape."""
        key = (a, b)
        entry = self._prompt_steps.get(key)
        if entry is not None:
            return entry
        sub_spec, sub_params, plain_step = self._get_subspan(a, b)
        cfg = self.cfg

        if self.offload:
            # OffloadedSpanRunner takes prompts as a trailing optional arg.
            step = plain_step
        elif self.tp_mesh is not None:
            from ..parallel.tensor_parallel import make_tp_stage_fn

            step = make_tp_stage_fn(
                cfg, sub_spec, self.tp_mesh, self.tp_axis,
                donate_cache=bool(engine_donation(0)), with_prompts=True,
            )(sub_params)
        else:
            @partial(jax.jit, donate_argnums=engine_donation(2, 3))
            def step(params, x, k_cache, v_cache, cache_len, prompts):
                return stage_forward(cfg, sub_spec, params, x, k_cache,
                                     v_cache, cache_len, prompts=prompts)

        self._prompt_steps[key] = step
        return step

    def _resolve_range(self, req: StageRequest) -> tuple:
        """Absolute request block range -> relative (a, b) within the span."""
        a = 0 if req.start_block is None else req.start_block - self.spec.start
        b = (self.spec.num_layers if req.end_block is None
             else req.end_block - self.spec.start)
        if not (0 <= a < b <= max(self.spec.num_layers, 1)):
            raise StageExecutionError(
                f"requested blocks [{req.start_block},{req.end_block}) outside "
                f"served span [{self.spec.start},{self.spec.end})"
            )
        return a, b

    # ------------------------------------------------------------------
    # Session / cache management (mirrors rpc_handler session semantics)
    # ------------------------------------------------------------------

    def _allocate(self, req: StageRequest, num_layers: int, batch: int) -> KVHandle:
        """Arena lease as a STAGE error: a full arena is peer-local state —
        surfacing it as StageExecutionError puts it in the client's retryable
        taxonomy, so the session fails over to a replica with free memory
        instead of crashing the generation."""
        try:
            handle = self.arena.allocate(req.session_id, req.max_length,
                                         num_layers=num_layers, batch=batch)
        except AllocationFailed as exc:
            raise StageExecutionError(str(exc)) from exc
        _ev.emit("server_session_open", session_id=req.session_id,
                 peer=self.peer_id, max_length=req.max_length,
                 replay=req.is_replay)
        return handle

    def _session_cache(self, req: StageRequest, num_layers: int,
                       batch: int = 1) -> KVHandle:
        handle = self.arena.get(req.session_id)
        if req.is_prefill:
            # Prefill (re)starts the session: clear existing cache
            # (src/rpc_handler.py:180-182).
            if handle is not None:
                self.arena.free(req.session_id)
            handle = self._allocate(req, num_layers, batch)
        elif handle is None:
            if req.is_replay:
                # Replacement server rebuilding KV from the client's journal:
                # treat the first replayed decode as a prefill
                # (src/rpc_handler.py:187-196).
                handle = self._allocate(req, num_layers, batch)
            else:
                raise StageExecutionError(
                    f"session {req.session_id}: decode step without KV cache "
                    "and not a replay (src/rpc_handler.py:198-202 semantics)"
                )
        if (not req.is_prefill and handle.cache_len != req.cur_len
                and not req.is_replay and req.start_from_position is None):
            # The reference logs and proceeds with the server's own count
            # (src/rpc_handler.py:206-225). A rewinding step (cur_len ==
            # start_from_position < cache_len) is NOT a mismatch — forward()
            # adopts the client's position via handle.rewind.
            logger.warning(
                "session %s: past-len mismatch client=%d server=%d; "
                "trusting server", req.session_id, req.cur_len, handle.cache_len,
            )
        return handle

    # ------------------------------------------------------------------
    # Forward
    # ------------------------------------------------------------------

    def forward(self, req: StageRequest) -> StageResponse:
        """Run one step of this stage for one session."""
        a, b = self._resolve_range(req)
        sub_spec, sub_params, step = self._get_subspan(a, b)

        prompts = None
        if req.prompts is not None:
            # Inference-time deep prompt tuning (petals
            # block_functions.py:171-226): inject the client's learned
            # per-block prompts at every block entry, every step.
            prompts = jnp.asarray(req.prompts)
            if prompts.ndim != 3 or prompts.shape[0] != b - a:
                raise StageExecutionError(
                    f"prompts shape {tuple(prompts.shape)} does not cover "
                    f"the requested {b - a} blocks (want [span, pre, D])"
                )
            step = self._get_prompt_step(a, b)

        x = jnp.asarray(req.hidden)
        # stage0 consumes int token ids [B, T]; later stages float hidden
        # [B, T, D] (uniform signature, src/llama_partition.py:99-137).
        want_ndim = 2 if sub_spec.is_first else 3
        if x.ndim != want_ndim:
            raise StageExecutionError(
                f"stage {self.spec.index} expects ndim={want_ndim}, got {x.shape}"
            )
        handle = self._session_cache(req, num_layers=max(b - a, 1),
                                     batch=x.shape[0])
        if handle.k is not None and handle.k.shape[0] != max(b - a, 1):
            raise StageExecutionError(
                f"session {req.session_id} was allocated for "
                f"{handle.k.shape[0]} layers but the request covers {b - a} "
                "(a route must use a stable block range per hop)"
            )
        if req.start_from_position is not None and not req.is_prefill:
            # Session rewind (petals handler.py:163-168): shrink the valid KV
            # prefix before this step — the client restarts generation from an
            # earlier position.
            try:
                handle.rewind(req.start_from_position)
            except ValueError as exc:
                raise StageExecutionError(str(exc)) from exc
        if req.hypo_ids is not None and not req.is_prefill:
            # Beam reorder BEFORE the step (petals backend.py:154-158):
            # hypothesis i continues from old KV row hypo_ids[i]. May also
            # GROW the batch (e.g. hypo_ids=(0,)*nb expands a batch-1 prefill
            # into nb beam rows) — re-lease the arena bytes first.
            ids_np = np.asarray(req.hypo_ids, np.int64)
            if ids_np.shape[0] != x.shape[0]:
                raise StageExecutionError(
                    f"hypo_ids has {ids_np.shape[0]} rows, batch is {x.shape[0]}"
                )
            old_batch = handle.k.shape[1]
            # jnp.take clamps out-of-range indices — that would silently
            # continue a hypothesis from the wrong KV row, so check here.
            if ids_np.size and (ids_np.min() < 0 or ids_np.max() >= old_batch):
                raise StageExecutionError(
                    f"hypo_ids {tuple(req.hypo_ids)} out of range for KV "
                    f"batch {old_batch}"
                )
            if x.shape[0] != old_batch:
                try:
                    self.arena.resize_batch(req.session_id, x.shape[0])
                except AllocationFailed as exc:
                    # Same taxonomy as _allocate: let the client fail over to
                    # a replica whose arena can hold the expanded batch.
                    raise StageExecutionError(str(exc)) from exc
            ids = jnp.asarray(ids_np, jnp.int32)
            handle.k = jnp.take(handle.k, ids, axis=1)
            handle.v = jnp.take(handle.v, ids, axis=1)
        if handle.k is not None and handle.k.shape[1] != x.shape[0]:
            raise StageExecutionError(
                f"session {req.session_id} holds KV for batch "
                f"{handle.k.shape[1]}, request batch is {x.shape[0]}"
            )
        t_real = req.seq_len
        handle.admit(t_real)

        t = x.shape[1]
        if t != t_real:
            raise StageExecutionError(f"seq_len {t_real} != tensor T {t}")

        # Prompt-prefix reuse (runtime.prefix_cache): on a prefill whose
        # leading grains were served before THROUGH THESE BLOCKS, copy the
        # cached KV segments into the fresh arena lease and compute only the
        # remainder. The rolling chain digest gives longest-shared-prefix
        # matching at grain granularity — two prompts sharing a system
        # preamble reuse its grains with no annotation of where it ends.
        # The shareable region is clamped to t_real - 1 so the final stage
        # always has a computed row to sample from. Exotic shapes (deep
        # prompts, beam reorder, drafts) skip the path — their step
        # semantics aren't a pure function of the prefix.
        pfx_skip = 0
        pfx_outs: list = []
        pfx_register: list = []  # (key, grain_start, grain_end) to register
        if (self.prefix_store is not None and req.is_prefill
                and req.prefix_len > 0 and prompts is None
                and req.hypo_ids is None and req.draft_tokens is None
                and handle.k is not None):
            from .prefix_cache import chain_digests

            grain = self.prefix_store.grain
            n_grains = min(req.prefix_len, t_real - 1) // grain
            if n_grains > 0:
                coords = (self.spec.start + a, self.spec.start + b,
                          x.shape[0], str(x.dtype), str(self.cache_dtype),
                          req.model)
                # Digest from the HOST-side request buffer when the wire
                # already delivered one — hashing the device copy would pay
                # a D2H transfer + sync on every store-enabled prefill,
                # misses included.
                src = (req.hidden if isinstance(req.hidden, np.ndarray)
                       else x)
                xp = np.asarray(src[:, :n_grains * grain])
                blocks = [
                    np.ascontiguousarray(xp[:, g * grain:(g + 1) * grain])
                    .tobytes() for g in range(n_grains)]
                keys = chain_digests(blocks, coords)
                chain = self.prefix_store.lookup_chain(
                    keys, need_out=not sub_spec.is_last)
                if chain:
                    # ONE dispatch applies the whole chain (concat + both
                    # cache writes inside one jitted program — jit
                    # specializes per chain length, which is stable for a
                    # given shared prompt). Eager per-grain updates would
                    # cost a device round trip each.
                    handle.k, handle.v = _apply_prefix_chain(
                        handle.k, handle.v,
                        [e.k for e in chain], [e.v for e in chain])
                    pfx_outs = [e.out for e in chain if e.out is not None]
                    pfx_skip = len(chain) * grain
                    handle.advance(pfx_skip)
                pfx_register = [
                    (keys[g], g * grain, (g + 1) * grain)
                    for g in range(len(chain), n_grains)]

        # Chunked prefill (petals backend.py:129-143): split an oversized
        # request into byte-bounded chunks over the same session cache. The
        # numerics are identical (each chunk attends causally to everything
        # already written); what the bound buys is peak activation memory —
        # and prefills longer than the largest jit seq bucket become possible
        # at all. Intermediate stages concatenate chunk outputs (the next
        # stage needs every token's hidden state); the final stage samples
        # from the LAST chunk's logits only.
        chunk = self._max_chunk_tokens(x.shape[0])
        outs = []
        off = pfx_skip
        while off < t_real:
            n = min(chunk, t_real - off)
            xc = jax.lax.slice_in_dim(x, off, off + n, axis=1)
            outs.append(self._dispatch_chunk(step, sub_params, xc, handle, n,
                                             prompts=prompts))
            off += n
        self.requests_served += 1

        if pfx_register:
            # Register the grains the chain lookup didn't cover. KV rows
            # come from the arena lease (already written by the chunk
            # loop); intermediate stages also keep the output rows they'd
            # need to forward on a future hit. Slicing copies — entries
            # must outlive this session's arena buffers.
            full = None
            if not sub_spec.is_last:
                full = (outs[0] if len(outs) == 1
                        else jnp.concatenate(outs, axis=1))
                outs = [full]
            for key, g0, g1 in pfx_register:
                out_rows = (None if full is None
                            else full[:, g0 - pfx_skip:g1 - pfx_skip])
                self.prefix_store.put(key, handle.k[:, :, g0:g1],
                                      handle.v[:, :, g0:g1], out_rows)

        if sub_spec.is_last:
            if req.draft_tokens is not None:
                return self._verify_drafts(req, outs, handle)
            out = outs[-1]  # chunk outputs are trimmed; sample from its tail
            if req.num_logprobs > 0:
                # Beam mode: per-row top-N candidates, raw log-softmax (beam
                # search scores, no sampling).
                last = out[:, -1].astype(jnp.float32)  # [B, V]
                logp = jax.nn.log_softmax(last, axis=-1)
                vals, idx = jax.lax.top_k(logp, req.num_logprobs)
                return StageResponse(
                    session_id=req.session_id, cache_len=handle.cache_len,
                    top_tokens=tuple(tuple(int(t) for t in row)
                                     for row in np.asarray(idx)),
                    top_logprobs=tuple(tuple(float(v) for v in row)
                                       for row in np.asarray(vals)),
                )
            row_tokens = _sample_rows(out, out.shape[1], req)
            return StageResponse(
                session_id=req.session_id, token_id=int(row_tokens[0]),
                token_ids=(tuple(int(t) for t in row_tokens)
                           if row_tokens.shape[0] > 1 else None),
                cache_len=handle.cache_len,
            )
        out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
        if pfx_outs:
            # Hit: the next hop needs every token's hidden state — prepend
            # the stored prefix segments' outputs to the computed suffix.
            out = jnp.concatenate([*pfx_outs, out], axis=1)
        if self.debug_activation_checks:
            # Activation-explosion guard (src/rpc_handler.py:316-319). Opt-in:
            # the float() forces a host sync per hop per token, which would
            # serialize the decode hot path if always on.
            max_abs = float(jnp.max(jnp.abs(out)))
            if max_abs > 100.0:
                logger.warning(
                    "session %s stage %d: activation explosion |x|=%.1f",
                    req.session_id, self.spec.index, max_abs,
                )
        return StageResponse(
            session_id=req.session_id, hidden=out, cache_len=handle.cache_len
        )

    def _max_chunk_tokens(self, batch: int) -> int:
        """Tokens per prefill chunk: the byte budget over the per-token
        activation footprint (batch x hidden x fp32 x span layers — the
        attention-memory estimate of petals ``backend.py:146-152``), capped
        at the largest jit seq bucket and floored at one bucket."""
        per_token = batch * self.cfg.hidden_size * 4 * max(self.spec.num_layers, 1)
        est = self.max_chunk_bytes // max(per_token, 1)
        est = max(16, min(int(est), SEQ_BUCKETS[-1]))
        # Align DOWN to a jit seq bucket: a chunk size strictly between
        # buckets would pad every full chunk up to the next bucket — up to
        # ~2x wasted attention/MLP work per chunk.
        return max(b for b in SEQ_BUCKETS if b <= est)

    def _dispatch_chunk(self, step, sub_params, x: jnp.ndarray,
                        handle: KVHandle, n: int,
                        prompts: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        """Run ONE bucket-padded jitted step of n real tokens against the
        session cache; advances the cache and returns the TRIMMED output.
        Bucket-padded tail positions may receive a deep-prompt injection
        too (absolute index < pre_seq); harmless — their output rows are
        trimmed here and their KV rows sit past cache_len until a real
        token overwrites them."""
        tb = round_to_bucket(n, SEQ_BUCKETS)
        if handle.cache_len + tb > handle.bucket_len:
            # Padding would make the jitted dynamic_update_slice clamp its
            # start index (writing garbage over the newest real rows). Fall
            # back to the exact length — one extra compile at the tail of a
            # session beats silent cache corruption.
            tb = n
        if tb != n:
            pad = ((0, 0), (0, tb - n)) + (((0, 0),) if x.ndim == 3 else ())
            x = jnp.pad(x, pad)
        cache_len = jnp.asarray(handle.cache_len, jnp.int32)
        if prompts is None:
            out, handle.k, handle.v = step(
                sub_params, x, handle.k, handle.v, cache_len
            )
        else:
            out, handle.k, handle.v = step(
                sub_params, x, handle.k, handle.v, cache_len, prompts
            )
        handle.advance(n)
        return out[:, :n]

    def _verify_drafts(self, req: StageRequest, outs, handle: KVHandle) -> StageResponse:
        """Speculative verification on the final stage.

        The request's T = 1 + K positions are [last_accepted, d_1..d_K];
        logits[i] predict the token AFTER consuming position i. Returns the
        accepted run plus one correction/bonus token, and REWINDS this
        stage's own KV past the rejected tail so the session is immediately
        consistent here; upstream stages drop their overhang via the next
        request's ``start_from_position`` (rewind semantics of petals
        handler.py:163-168, reused as speculative rollback).

        Greedy (temperature<=0): accept while d_{i+1} == argmax(logits[i]) —
        token-identical to non-speculative greedy decoding
        (``src/rpc_handler.py:334-335`` applies greedy before penalties).
        Sampled (temperature>0): rejection-sampling verification
        (ops.sampling.speculative_verify) — accept draft i with probability
        p_i(d_i), resample the residual on reject — which preserves the
        sampling distribution exactly, so temperature>0 gets the same
        round-trip amortization.
        """
        k = len(req.draft_tokens)
        t_real = req.seq_len
        if t_real != k + 1:
            raise StageExecutionError(
                f"speculative step carries {t_real} positions for {k} drafts "
                "(want K+1)"
            )
        logits = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
        tokens, n_acc = verify_drafts_from_logits(logits[0], req)
        # Rewind our own cache: positions for rejected drafts are garbage.
        valid = req.cur_len + n_acc + 1
        try:
            handle.rewind(valid)
        except ValueError as exc:  # pragma: no cover - defensive
            raise StageExecutionError(str(exc)) from exc
        return StageResponse(
            session_id=req.session_id,
            tokens=tokens,
            n_accepted=n_acc,
            cache_len=handle.cache_len,
        )

    # ------------------------------------------------------------------
    # Fine-tuning path (vendored rpc_forward/rpc_backward training surface,
    # petals/server/handler.py:352-488, block_functions.py:32-141)
    # ------------------------------------------------------------------

    def _train_fns(self, a: int, b: int):
        """Jitted (forward, backward) for blocks [a, b) of the loaded span.
        Stateless: no KV, no session; frozen span weights; grads flow to
        inputs (+ prompts and LoRA adapters — jit re-specializes per
        prompts/lora shape/None; lora_scale is static per compile)."""
        key = ("train", a, b)
        entry = self._subspans.get(key)
        if entry is not None:
            return entry
        cfg = self.cfg
        if a == 0 and b == self.spec.num_layers:
            layers = self.params["layers"]  # no duplicate HBM copy
        else:
            layers = jax.tree.map(lambda x: x[a:b], self.params["layers"])

        def f(x, prompts, lora, lora_scale):
            from ..models.lora import merge_lora

            bsz, t, _ = x.shape
            positions = jnp.broadcast_to(
                jnp.arange(t, dtype=jnp.int32)[None, :], (bsz, t)
            )
            return stack_forward_train(
                cfg, merge_lora(cfg, layers, lora, lora_scale), x, positions,
                prompts=prompts)

        fwd = jax.jit(f, static_argnums=3)

        @partial(jax.jit, static_argnums=3)
        def bwd(x, prompts, lora, lora_scale, grad_out):
            _, vjp = jax.vjp(
                lambda x_, p_, l_: f(x_, p_, l_, lora_scale),
                x, prompts, lora)
            return vjp(grad_out.astype(x.dtype))

        entry = (fwd, bwd)
        self._subspans[key] = entry
        return entry

    def _train_args(self, req) -> tuple:
        """Shared validation/padding for train_forward and backward."""
        a, b = self._resolve_range(req)
        x = jnp.asarray(req.hidden)
        if x.ndim != 3:
            raise StageExecutionError(
                f"training forward expects hidden [B, T, D], got {x.shape}"
            )
        if x.shape[1] != req.seq_len:
            raise StageExecutionError(
                f"seq_len {req.seq_len} != tensor T {x.shape[1]}"
            )
        prompts = None if req.prompts is None else jnp.asarray(req.prompts)
        if prompts is not None and prompts.shape[0] != b - a:
            raise StageExecutionError(
                f"prompts cover {prompts.shape[0]} layers, request spans {b - a}"
            )
        lora = req.lora
        if lora:
            attn = self.params["layers"].get("attn", {})
            from ..models.quant import is_quantized

            if is_quantized(attn):
                # merge_lora adds deltas to the stored weights, which for a
                # --quant span are packed QuantizedTensors (dequantized only
                # inside the layer scan) — fail as a clean stage error, not
                # a TypeError the client misreads as a dead peer.
                raise StageExecutionError(
                    "LoRA training is unsupported on a quantized span "
                    "(serve this span unquantized to fine-tune against it)")
            for t, ab in lora.items():
                if t not in attn and not (
                        "wqkv" in attn and t in ("wq", "wk", "wv")):
                    raise StageExecutionError(
                        f"LoRA target {t!r} not in this span's attn params")
                for leaf in ("a", "b"):
                    arr = ab.get(leaf)
                    if arr is None or arr.shape[0] != b - a:
                        raise StageExecutionError(
                            f"LoRA {t}/{leaf} covers "
                            f"{None if arr is None else arr.shape[0]} layers, "
                            f"request spans {b - a}")
        else:
            lora = None
        return a, b, x, prompts, lora

    def train_forward(self, req: StageRequest) -> StageResponse:
        """Cache-free span forward of the BLOCKS only (no head/sampling) —
        the training rpc_forward. Sequence padded to the shared buckets so an
        epoch of varying lengths stays within a handful of compiles."""
        a, b, x, prompts, lora = self._train_args(req)
        fwd, _ = self._train_fns(a, b)
        t_real = req.seq_len
        tb = round_to_bucket(t_real, SEQ_BUCKETS)
        if tb != t_real:
            x = jnp.pad(x, ((0, 0), (0, tb - t_real), (0, 0)))
        out = fwd(x, prompts, lora, float(req.lora_scale))
        self.requests_served += 1
        return StageResponse(
            session_id=req.session_id, hidden=out[:, :t_real], cache_len=0
        )

    def backward(self, req: BackwardRequest) -> BackwardResponse:
        """Re-forward blocks [a, b) from the supplied input and return
        (grad_input, grad_prompts). Activations are recomputed, never stored
        between training RPCs — same contract as the reference's
        ``run_rpc_backward`` re-forward (block_functions.py:106-124)."""
        a, b, x, prompts, lora = self._train_args(req)
        g = jnp.asarray(req.grad_output)
        if g.shape != x.shape:
            raise StageExecutionError(
                f"grad_output shape {g.shape} != hidden shape {x.shape}"
            )
        _, bwd = self._train_fns(a, b)
        t_real = req.seq_len
        tb = round_to_bucket(t_real, SEQ_BUCKETS)
        if tb != t_real:
            pad = ((0, 0), (0, tb - t_real), (0, 0))
            x = jnp.pad(x, pad)
            g = jnp.pad(g, pad)  # zero cotangents on padding
        gx, gp, gl = bwd(x, prompts, lora, float(req.lora_scale), g)
        self.requests_served += 1
        return BackwardResponse(
            session_id=req.session_id,
            grad_input=gx[:, :t_real],
            grad_prompts=gp,
            grad_lora=gl,
        )

    # ------------------------------------------------------------------
    # Lifecycle
    # ------------------------------------------------------------------

    def warmup(self, seq_buckets=(16, 8, 1), max_length: int = 128) -> None:
        """Pre-compile the common (seq bucket, cache bucket) step shapes so
        the first real request doesn't pay 30-120s of XLA compile inside the
        client's RPC deadline (a fresh server's first prefill would
        otherwise read as a dead peer and trigger spurious failover)."""
        b = 1
        cur = 0
        for i, t in enumerate(seq_buckets):
            if self.spec.is_first:
                x = jnp.zeros((b, t), jnp.int32)
            else:
                x = jnp.zeros((b, t, self.cfg.hidden_size), jnp.float32)
            try:
                self.forward(StageRequest(
                    session_id="__warmup__", hidden=x, seq_len=t,
                    cur_len=cur, is_prefill=(i == 0),
                    max_length=max_length))
                cur += t
            except Exception as exc:  # warmup must never kill a server
                logger.warning("warmup step (T=%d) failed: %s", t, exc)
        self.drop_session("__warmup__")

    def drop_session(self, session_id: str) -> None:
        if self.arena.get(session_id) is not None:
            _ev.emit("server_session_closed", session_id=session_id,
                     peer=self.peer_id)
        self.arena.free(session_id)

    def session_len(self, session_id: str) -> Optional[int]:
        h = self.arena.get(session_id)
        return None if h is None else h.cache_len
