"""CLI entry point — the reference's flag surface on the TPU-native runtime.

Mirrors ``src/main.py:775-838`` (argparse, role dispatch) with the stages
re-homed: on a TPU host the whole pipeline lives in one process, so
``--stage N`` processes become execution MODES:

  * ``--mode local``  — in-process cluster: fixed-split or load-balancing
    stage servers + the pipeline client, one generation end-to-end. This is
    also the ``scripts/run_all.py`` role (component 17): the reference
    spawned 4 subprocesses and scraped their logs; here the same topology is
    constructed directly.
  * ``--mode fused``  — the ICI hot path: all stages in one jitted program
    on a ("stage"[, "tp"]) device mesh (microbatched pipelined decode).
  * ``--mode oracle`` — unpartitioned single-device generation
    (``scripts/single_gpu_check.py``, component 19): the correctness/speed
    baseline with identical sampling.

Model weights: ``--checkpoint`` loads a local HF checkpoint directory via
transformers (offline; no downloads — zero-egress environments). Without a
checkpoint, weights are random-initialized from the ``--model`` preset, which
still exercises every runtime path. Tokenization uses the checkpoint's
tokenizer when available, else a UTF-8 byte fallback so the CLI always runs.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import random
import re
import sys
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .models import full_forward, get_config, init_kv_cache, init_params
from .models.config import ModelConfig
from .models.partition import StagePlan, parse_splits, slice_stage_params
from .native import codec_name
from .ops.sampling import SamplingParams
from .runtime.client import PipelineClient, make_server_record
from .runtime.executor import StageExecutor
from .runtime.server import ElasticStageServer
from .runtime.transport import LocalTransport
from .scheduling.registry import PlacementRegistry
from .utils.platform import compile_cache_dir, device_line, start_backend

logger = logging.getLogger("mini_petals_tpu")


def _emit(*parts, **kwargs) -> None:
    """CLI output boundary: every user-facing stdout line in this module
    goes through here (scripts/check_no_bare_print.py enforces it).
    Diagnostics belong on a logger; _emit is for the REPORT a mode exists
    to print — generation text, status tables, scrape output."""
    print(*parts, **kwargs)  # noqa: T201 — the one sanctioned print

# Random init as ONE jitted program: eager, every leaf's RNG ops compile one
# by one (53 s for gpt2-xl on the v5e) and hold float32 copies of whole
# stacks. Every role loads through it, so they all hold the same weights.
_init_params_jit = jax.jit(init_params, static_argnums=(1, 2))


# float16 runs as bfloat16: TPUs have no fp16 compute path (load_model warns).
_DTYPE_MAP = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
              "float16": jnp.bfloat16}


# ---------------------------------------------------------------------------
# Tokenizer (checkpoint tokenizer, else byte-level fallback)
# ---------------------------------------------------------------------------

class ByteTokenizer:
    """UTF-8 byte fallback: token id = byte value. Keeps the CLI runnable
    with random-init models in zero-egress environments."""

    eos_token_id: Optional[int] = None

    def encode(self, text: str) -> List[int]:
        return list(text.encode("utf-8"))

    def decode(self, ids: Sequence[int]) -> str:
        return bytes(int(i) % 256 for i in ids).decode("utf-8", errors="replace")


def load_tokenizer(checkpoint: Optional[str]):
    if checkpoint:
        try:
            from transformers import AutoTokenizer

            return AutoTokenizer.from_pretrained(checkpoint, local_files_only=True)
        except Exception as exc:
            logger.warning("tokenizer load failed (%s); using byte fallback", exc)
    return ByteTokenizer()


_STORES: dict = {}


def _remote_store(args):
    """Memoized RemoteShardStore for an http(s):// --checkpoint (one cache
    + one LRU state per process, shared by load_model and _stage_params)."""
    from .models.remote_store import RemoteShardStore

    key = (args.checkpoint, args.weight_cache_dir)
    store = _STORES.get(key)
    if store is None:
        cache = args.weight_cache_dir or os.path.join(
            os.path.expanduser("~"), ".cache", "mini_petals_tpu",
            re.sub(r"[^A-Za-z0-9._-]+", "_", args.checkpoint))
        store = RemoteShardStore(
            args.checkpoint, cache,
            max_cache_bytes=args.weight_cache_bytes)
        _STORES[key] = store
    return store


def _is_remote(checkpoint) -> bool:
    return bool(checkpoint) and checkpoint.startswith(("http://", "https://"))


def _preset_config(args) -> ModelConfig:
    """The --model preset, depth cut to --num_layers when given (every
    width stays as published; random-init only)."""
    import dataclasses

    cfg = get_config(args.model)
    if args.num_layers:
        if not 0 < args.num_layers <= cfg.num_layers:
            raise SystemExit(
                f"--num_layers {args.num_layers} outside 1..{cfg.num_layers} "
                f"for {args.model}")
        cfg = dataclasses.replace(cfg, num_layers=args.num_layers)
    return cfg


def load_config(args) -> ModelConfig:
    """The model's config WITHOUT its weights: what the client and gateway
    need at start-up (their stage-0 weights load on first use)."""
    if args.checkpoint:
        from .models.hf_import import config_from_checkpoint

        return config_from_checkpoint(
            _remote_store(args).fetch_config() if _is_remote(args.checkpoint)
            else args.checkpoint)
    return _preset_config(args)


def _refuse_unheld_state(args, cfg: ModelConfig) -> None:
    """Where a weight-holding mode CHOOSES its engine, before a weight is
    made: the one predicate on per-session state
    (`models.config.single_pass_unsupported`) asked of the engine the
    arguments name. The full-span batched server (``serve --stage 0
    --batched``) holds every kind of state; the in-program oracle runs a
    looped stack and nothing else; every other engine keeps one K/V row a
    position and one pass a token. A client or gateway is host-side and
    meets the same predicate where a route makes it build stage 0
    (`runtime.client`); the engines' own constructors ask it too."""
    from .models.config import single_pass_unsupported

    if args.mode == "serve" and args.stage == 0 and args.batched:
        what = None
        own_state = cfg.eva_window or cfg.kv_lora_rank
        if own_state and args.prefix_cache_mb:
            what = "the prefix cache (a stored prefix is a slice of rows)"
        elif own_state and getattr(args, "speculative_k", 0):
            what = ("speculative verify (a block of draft rows may cross "
                    "a window's edge, and a selection is made for one "
                    "query row a slot)")
    elif args.mode == "serve":
        what = ("a stage server over part of the stack" if args.batched
                else "the per-session executor")
    elif args.mode == "oracle" and not (cfg.eva_window or cfg.kv_lora_rank):
        what = None
    else:
        what = f"--mode {args.mode}"
    reason = what and single_pass_unsupported(cfg, what)
    if reason:
        raise SystemExit(reason)


def load_model(args) -> Tuple[ModelConfig, dict]:
    if args.checkpoint and args.num_layers:
        raise SystemExit("--num_layers cuts a random-init preset; a "
                         "--checkpoint serves the depth it was saved with")
    if args.dtype == "float16":
        # TPUs have no fp16 compute path; bf16 differs numerically (8-bit
        # exponent / 7-bit mantissa vs 5/10) so an fp16 baseline will not
        # reproduce bit-for-bit.
        logger.warning("--dtype float16 runs as bfloat16 on TPU")
    dtype = _DTYPE_MAP[args.dtype]
    if _is_remote(args.checkpoint):
        from .models.hf_import import config_from_checkpoint

        store = _remote_store(args)
        cfg = config_from_checkpoint(store.fetch_config())
        if args.mode in ("local", "serve", "client", "gateway"):
            # Per-span streaming (petals from_pretrained.py:81-128): params
            # stay None; each serving role later fetches just the shards
            # covering ITS span (store.load_stage via _stage_params).
            return cfg, None
        # oracle/fused/etc. need the FULL tree up front: fetch every shard,
        # then stream-convert from the cache like a local checkpoint.
        from .models.partition import ROLE_FULL, StageSpec

        full = StageSpec(0, ROLE_FULL, 0, cfg.num_layers)
        return cfg, store.load_stage(cfg, full, dtype=dtype)
    if args.checkpoint:
        if args.mode in ("local", "serve", "client", "gateway"):
            from .models.hf_import import config_from_checkpoint

            has_st = (os.path.exists(os.path.join(
                args.checkpoint, "model.safetensors.index.json"))
                or os.path.exists(os.path.join(args.checkpoint,
                                               "model.safetensors")))
            if has_st:
                # Per-stage weight streaming (petals from_pretrained.py:
                # 81-128): stage servers read only their span's shards; the
                # full model is never materialized (run_local/run_serve/
                # run_client load per-stage when params is None).
                return config_from_checkpoint(args.checkpoint), None
        import torch
        from transformers import AutoModelForCausalLM

        from .models.hf_import import config_from_hf, convert_state_dict

        torch.manual_seed(0)
        hf = AutoModelForCausalLM.from_pretrained(
            args.checkpoint, local_files_only=True, torch_dtype=torch.float32
        )
        cfg = config_from_hf(hf.config)
        params = convert_state_dict(cfg, hf.state_dict(), dtype=np.float32)
        if dtype != jnp.float32:
            # Float leaves only: the gemma2 per-layer "window" leaf is
            # int32 position arithmetic (see convert_state_dict).
            params = jax.tree.map(
                lambda x: (x.astype(dtype)
                           if jnp.issubdtype(x.dtype, jnp.floating) else x),
                params)
        return cfg, params
    cfg = _preset_config(args)
    logger.info("no --checkpoint: random-initializing %s (%d layers)",
                args.model, cfg.num_layers)
    return cfg, _init_params_jit(jax.random.PRNGKey(args.seed), cfg, dtype)


# ---------------------------------------------------------------------------
# Modes
# ---------------------------------------------------------------------------

def _model_id(args) -> str:
    """Registry-scoping model id: --model_name, defaulting to the --model
    preset. The ONE place the fallback rule lives — every record publish and
    client query must agree or the swarm silently splits per model id."""
    return args.model_name or args.model


def _client_metrics(args):
    """Under ``--telemetry`` the client folds its series into the
    process-global registry (one ``--mode metrics`` scrape shows client +
    server families); otherwise it keeps its default private registry."""
    if getattr(args, "telemetry", False):
        from . import telemetry

        return telemetry.get_registry()
    return None


def run_local(args, cfg: ModelConfig, params) -> int:
    """In-process cluster: servers (fixed or LB) + client, one generation."""
    splits = parse_splits(args.splits) if args.splits else None
    if splits is None:
        plan = StagePlan.even(cfg.num_layers, 4)
    else:
        plan = StagePlan.from_splits(cfg.num_layers, splits)

    transport = LocalTransport()
    registry = PlacementRegistry(rng=random.Random(args.seed))
    provider = lambda spec: _stage_params(args, cfg, params, spec)  # noqa: E731

    if args.use_load_balancing:
        min_block = plan.stages[0].end
        num_blocks = args.num_blocks or max(
            1, (cfg.num_layers - min_block) // max(plan.num_stages - 1, 1))
        for i in range(args.num_servers):
            ElasticStageServer(
                f"server-{i}", cfg, provider, registry, transport,
                executor_kwargs={
                    "offload": args.use_cpu_offload,
                    "keep_layers_resident": args.keep_layers_on_gpu,
                    "prefix_cache_bytes": args.prefix_cache_mb << 20,
                },
                num_blocks=num_blocks,
                total_blocks=args.total_blocks or cfg.num_layers,
                min_block=min_block,
                balance_quality=args.balance_quality,
                mean_balance_check_period=args.mean_balance_check_period,
                bandwidth_mbps=args.network_bandwidth_mbps,
                rng=random.Random(args.seed + i),
                model=_model_id(args),
            ).start_serving()
    else:
        for spec in plan.stages[1:]:
            peer = f"server-stage{spec.index}"
            ex = StageExecutor(
                cfg, spec, provider(spec), peer_id=peer,
                offload=args.use_cpu_offload,
                keep_layers_resident=args.keep_layers_on_gpu,
                prefix_cache_bytes=args.prefix_cache_mb << 20,
            )
            transport.add_peer(peer, ex)
            registry.register(make_server_record(
                peer, spec, model=_model_id(args)))

    stage0 = StageExecutor(cfg, plan.stages[0], provider(plan.stages[0]),
                           peer_id="client-local",
                           prefix_cache_bytes=args.prefix_cache_mb << 20)
    client = PipelineClient(
        cfg, plan, stage0, transport, registry,
        use_module_routing=bool(args.use_load_balancing),
        route_by_latency=args.route_by_latency,
        total_blocks=args.total_blocks or cfg.num_layers,
        request_timeout=args.request_timeout,
        seed=args.seed,
        model=_model_id(args),
        metrics=_client_metrics(args),
    )
    return _generate_and_report(args, client.generate, cfg)


def _maybe_lora(args, cfg, params, start=None, end=None):
    """Apply ``--lora``: fold saved adapter deltas (a fine-tune's
    ``export_lora`` .npz) into the attention weights at LOAD time —
    serving a tuned model needs no runtime adapter support, and the merge
    runs BEFORE quantization so int8/nf4 weights include the deltas.
    start/end select the span's slice (stage serving); None = full model
    (oracle/fused)."""
    path = getattr(args, "lora", None)
    if not path or "layers" not in params:
        return params
    from .models.lora import load_lora, merge_lora, slice_lora

    cached = _maybe_lora._cache.get(path)
    if cached is None:
        # Load once per process: elastic re-spans and multi-stage local
        # mode call _stage_params repeatedly.
        cached = _maybe_lora._cache[path] = load_lora(path)
    tree, scale = cached
    # Validate the FULL adapter depth BEFORE slicing — a wrong-model
    # adapter could slice to exactly a span's width and silently merge
    # deltas from the wrong layers on every non-final stage.
    for t, ab in tree.items():
        if ab["a"].shape[0] != cfg.num_layers:
            raise SystemExit(
                f"--lora: adapter {t!r} covers {ab['a'].shape[0]} layers, "
                f"the model has {cfg.num_layers} (adapter trained for a "
                "different model?)")
    if start is not None:
        tree = slice_lora(tree, start, end)
    return {**params,
            "layers": merge_lora(cfg, params["layers"], tree, scale)}


_maybe_lora._cache = {}


def _maybe_quantize(args, params, tp: int = 1):
    """Apply ``--quant`` weight-only quantization (int8 the throughput
    mode, nf4 the capacity mode — docs/PERFORMANCE.md): QuantizedTensor/
    NF4Tensor leaves ride the layer trees and dequantize per layer inside
    the scans; embed/head stay full precision. Rejected with tp > 1 on
    the fused path: the megatron sharding tables key on leaf names that
    quantized pytree nodes hide, so the q/s leaves would replicate over
    tp and the closing psum would scale every projection by tp — the same
    silent corruption the TP stage engine guards against
    (parallel/tensor_parallel.py shard tables)."""
    if getattr(args, "quant", "none") == "none":
        return params
    if tp > 1:
        raise SystemExit(
            "--quant is not supported with --tp > 1 on the fused/ring "
            "path (quantized leaves cannot be megatron-sharded; run "
            "tp=1, or serve full-precision TP)")
    from .models.quant import quantize_params

    return quantize_params(params, args.quant)


def run_fused(args, cfg: ModelConfig, params) -> int:
    """Fused ICI pipeline generation (microbatch=1 stream for the CLI), or
    — with ``--ring_sessions G`` — G concurrent generations on the
    multi-session ring-decode schedule (every stage advances a different
    session each tick; see parallel.ring_decode)."""
    from .parallel.pipeline import IciPipeline

    num_stages = args.num_stages or max(1, min(len(jax.devices()) // args.tp, 4))
    while cfg.num_layers % num_stages:
        num_stages -= 1
    params = _maybe_quantize(args, _maybe_lora(args, cfg, params),
                             tp=args.tp)
    if getattr(args, "ring_sessions", 0) > 1:
        return _run_fused_ring(args, cfg, params, num_stages)
    pipe = IciPipeline.build(cfg, params, num_stages=num_stages,
                             num_micro=1, tp=args.tp)
    logger.info("fused pipeline: %d stages x tp=%d on %s",
                num_stages, args.tp, pipe.mesh.devices.ravel())

    def generate(prompt_ids, max_new_tokens, sampling, eos_token_id=None,
                 **_kw):
        from .ops.sampling import (
            make_recent_buffer,
            push_recent,
            sample_token_jit,
            sampling_scalars,
        )
        from .runtime.client import GenerationResult

        sp_args = sampling_scalars(sampling.temperature, sampling.top_p,
                                   sampling.top_k,
                                   sampling.repetition_penalty)
        recent, nvalid = make_recent_buffer()

        def pick(logits_last, step):
            # Full reference sampler (jitted — one executable for every
            # knob config), oracle key schedule PRNGKey(seed + step) —
            # single-session fused output matches --mode oracle.
            nonlocal recent, nvalid
            if sampling.greedy:
                return int(jnp.argmax(logits_last))
            tok = sample_token_jit(jax.random.PRNGKey(args.seed + step),
                                   logits_last.astype(jnp.float32),
                                   recent, nvalid, *sp_args)
            recent, nvalid = push_recent(recent, nvalid, tok)
            return int(tok)

        max_len = len(prompt_ids) + max_new_tokens + 1
        kv_dtype = pipe.embed["wte"].dtype
        k, v = pipe.init_kv(1, max(128, max_len), dtype=kv_dtype)
        ids = jnp.asarray(np.asarray(prompt_ids, np.int32)[None, None, :])
        t0 = time.monotonic()
        logits, k, v = pipe.forward(ids, k, v, jnp.int32(0))
        tok = pick(logits[0, 0, -1], 0)
        ttft = time.monotonic() - t0
        tokens = [tok]
        cur = len(prompt_ids)
        decode_times = []
        stopped = "max_tokens"
        for step_i in range(1, max_new_tokens):
            if eos_token_id is not None and tokens[-1] == eos_token_id:
                stopped = "eos"
                break
            if len(tokens) >= 5 and len(set(tokens[-5:])) == 1:
                stopped = "repeat"
                break
            t0 = time.monotonic()
            step = jnp.asarray([[[tokens[-1]]]], jnp.int32)
            logits, k, v = pipe.forward(step, k, v, jnp.int32(cur))
            tokens.append(pick(logits[0, 0, -1], step_i))
            decode_times.append(time.monotonic() - t0)
            cur += 1
        return GenerationResult(tokens=tokens, ttft_s=ttft,
                                decode_times_s=decode_times, stopped_by=stopped)

    return _generate_and_report(args, generate, cfg,
                                supports_speculative=False)


def run_oracle(args, cfg: ModelConfig, params) -> int:
    """Single-device unpartitioned generation (scripts/single_gpu_check.py).

    Both greedy and sampled decoding ride the fused multi-step engine
    (runtime.fused_decode): whole chunks run as ONE compiled program with
    stop conditions checked between chunks — the CUDA-graph replay the
    reference's oracle lacks. The sampled path folds the full reference
    sampler into the scan with the SAME per-step key schedule as the old
    per-token loop, so outputs are bit-identical to it. ``--quant`` serves
    int8/nf4 weights, dequantized per layer inside the scan."""
    params = _maybe_quantize(args, _maybe_lora(args, cfg, params))

    def _drive_chunks(prompt_ids, max_new_tokens, eos_token_id, *,
                      prefill_first_token, run_chunk, chunk):
        """Shared chunked-generation driver for both fused engines.

        ``prefill_first_token(ids, kc, vc) -> (tok0, kc, vc)`` consumes the
        prompt and produces the first token (greedy argmax or key-schedule
        step 0 of the sampler); ``run_chunk(last_tok, cur, n, kc, vc, step)
        -> (got_tokens, kc, vc)`` runs n fused steps (``step`` = PRNG
        schedule index of the chunk's first token; the greedy engine ignores
        it). Stop conditions are re-checked PER TOKEN inside each chunk —
        the fused program may overshoot an EOS/repeat point and the trimmed
        output must match per-token decoding exactly — and each chunk's
        FULL wall time amortizes over the KEPT tokens so reported tokens/s
        doesn't inflate on overshoot."""
        from .runtime.client import GenerationResult

        max_len = max(128, len(prompt_ids) + max_new_tokens + 1)
        kc, vc = init_kv_cache(cfg, cfg.num_layers, 1, max_len,
                               dtype=params["embed"]["wte"].dtype)
        ids = jnp.asarray(np.asarray(prompt_ids, np.int32)[None, :])
        t0 = time.monotonic()
        tok0, kc, vc = prefill_first_token(ids, kc, vc)
        tokens = [int(tok0)]
        ttft = time.monotonic() - t0
        cur = len(prompt_ids)
        step = 1                      # PRNG schedule index: seed + step
        decode_times: List[float] = []
        stopped = "max_tokens"
        while len(tokens) < max_new_tokens and stopped == "max_tokens":
            if eos_token_id is not None and tokens[-1] == eos_token_id:
                stopped = "eos"
                break
            if len(tokens) >= 5 and len(set(tokens[-5:])) == 1:
                stopped = "repeat"
                break
            n = min(chunk, max_new_tokens - len(tokens))
            t0 = time.monotonic()
            got, kc, vc = run_chunk(tokens[-1], cur, n, kc, vc, step)
            dt = time.monotonic() - t0
            kept = 0
            for tok in got:
                tokens.append(int(tok))
                cur += 1
                step += 1
                kept += 1
                if eos_token_id is not None and int(tok) == eos_token_id:
                    stopped = "eos"
                    break
                if len(tokens) >= 5 and len(set(tokens[-5:])) == 1:
                    stopped = "repeat"
                    break
            decode_times.extend([dt / max(kept, 1)] * kept)
        return GenerationResult(
            tokens=tokens[:max_new_tokens], ttft_s=ttft,
            decode_times_s=decode_times[:max(len(tokens) - 1, 0)],
            stopped_by=stopped)

    def generate(prompt_ids, max_new_tokens, sampling, eos_token_id=None,
                 **_kw):
        chunk = min(max_new_tokens, 32)
        if sampling.greedy:
            from .runtime.fused_decode import make_fused_decode

            fn = make_fused_decode(cfg, chunk, 1, exact_head=True)

            def prefill_first(ids, kc, vc):
                logits, kc, vc = full_forward(cfg, params, ids, kc, vc,
                                              jnp.int32(0))
                return int(jnp.argmax(logits[0, -1])), kc, vc

            def run_chunk(last, cur, n, kc, vc, step):
                toks, kc, vc = fn(params, jnp.asarray([last], jnp.int32),
                                  kc, vc, jnp.int32(cur), jnp.int32(n))
                return [int(t) for t in np.asarray(toks[:n, 0])], kc, vc

            return _drive_chunks(prompt_ids, max_new_tokens, eos_token_id,
                                 prefill_first_token=prefill_first,
                                 run_chunk=run_chunk, chunk=chunk)

        from .ops.sampling import (
            make_recent_buffer,
            push_recent,
            sample_token_jit,
            sampling_scalars,
        )
        from .runtime.fused_decode import make_fused_sample_decode

        fn = make_fused_sample_decode(cfg, chunk)
        sp_args = sampling_scalars(sampling.temperature, sampling.top_p,
                                   sampling.top_k,
                                   sampling.repetition_penalty)
        state = {"recent": None, "nvalid": None}

        def prefill_first(ids, kc, vc):
            logits, kc, vc = full_forward(cfg, params, ids, kc, vc,
                                          jnp.int32(0))
            recent, nvalid = make_recent_buffer()
            # First token: key schedule step 0 (same as the per-token loop).
            tok = sample_token_jit(jax.random.PRNGKey(args.seed),
                                   logits[0, -1], recent, nvalid, *sp_args)
            state["recent"], state["nvalid"] = push_recent(recent, nvalid,
                                                           tok)
            return int(tok), kc, vc

        def run_chunk(last, cur, n, kc, vc, step):
            toks, kc, vc, state["recent"], state["nvalid"] = fn(
                params, jnp.asarray(last, jnp.int32), kc, vc,
                jnp.int32(cur), jnp.int32(n),
                jnp.int32(args.seed + step), state["recent"],
                state["nvalid"], *sp_args)
            return [int(t) for t in np.asarray(toks[:n])], kc, vc

        return _drive_chunks(prompt_ids, max_new_tokens, eos_token_id,
                             prefill_first_token=prefill_first,
                             run_chunk=run_chunk, chunk=chunk)

    return _generate_and_report(args, generate, cfg,
                                supports_speculative=False)


def _run_fused_ring(args, cfg: ModelConfig, params, num_stages: int) -> int:
    """`--mode fused --ring_sessions G`: serve G concurrent prompts
    ('||'-separated in --prompt; a single prompt is replicated) with the
    bubble-free rotation schedule. Each session prefills its own length
    via the masked single-group prefill, then all G decode together — one
    sampled token per tick in steady state instead of one per S ticks.
    temperature > 0 runs the FULL reference sampler inside the rotation
    (per-session recent windows, the oracle's PRNGKey(seed + i) schedule —
    each session's text matches --mode oracle for its prompt); greedy
    otherwise. --speculative_k composes with both: greedy output stays
    token-identical to the plain ring for any draft quality; sampled +
    speculative preserves the sampling DISTRIBUTION exactly (rejection
    sampling) but uses a per-round key schedule, so the text differs from
    the non-speculative run at the same seed (logged below)."""
    from .parallel.pipeline import IciPipeline
    from .parallel.ring_decode import RingDecoder, make_ring_prefill_group

    G = args.ring_sessions
    if G < num_stages:
        raise SystemExit(
            f"--ring_sessions {G} < pipeline stages {num_stages}: the "
            "rotation needs at least one session per stage "
            "(use --num_stages to shrink the pipeline)")
    tokenizer = load_tokenizer(_remote_store(args).cache_dir
                               if _is_remote(args.checkpoint)
                               else args.checkpoint)
    prompts = [p for p in args.prompt.split("||") if p.strip()] or ["hi"]
    orig = len(prompts)  # cycle over the USER's prompts, not the grown list
    while len(prompts) < G:
        prompts.append(prompts[len(prompts) % orig])
    prompts = prompts[:G]
    prompt_ids = [[i % cfg.vocab_size for i in tokenizer.encode(p)]
                  for p in prompts]
    eos = getattr(tokenizer, "eos_token_id", None)
    sampled = args.temperature > 0

    spec_k = getattr(args, "speculative_k", 0) or 0
    if spec_k and sampled:
        logger.warning(
            "sampled + speculative ring: rejection-sampling verification "
            "preserves the sampling distribution exactly, but the per-round "
            "key schedule differs from the per-token one — text will not "
            "bitwise-match the same seed without --speculative_k")
    pipe = IciPipeline.build(cfg, params, num_stages=num_stages,
                             num_micro=G, tp=args.tp)
    logger.info("ring decode: %d sessions over %d stages x tp=%d (%s%s)",
                G, num_stages, args.tp,
                "sampled" if sampled else "greedy",
                f", speculative_k={spec_k}" if spec_k else "")
    chunk = 16
    if spec_k:
        from .parallel.ring_decode import make_ring_spec_round

        round_fn = make_ring_spec_round(pipe, spec_k)
    else:
        rd = RingDecoder.build(pipe, max_steps=chunk, sampled=sampled)
    prefill_one = make_ring_prefill_group(pipe, return_logits=sampled)
    # chunk-1 (or one spec round) of overshoot headroom: a session finishing
    # mid-chunk still has its (discarded) extra steps' KV writes in-bounds.
    max_len = (max(len(p) for p in prompt_ids) + args.max_new_tokens
               + max(chunk, spec_k + 1))
    k, v = pipe.init_kv(1, max(128, max_len), dtype=pipe.embed["wte"].dtype)

    from .ops.sampling import (
        RECENT_WINDOW,
        push_recent,
        sample_token_jit,
        sampling_scalars,
    )

    sp_scalars = sampling_scalars(args.temperature, args.top_p, args.top_k,
                                  args.repetition_penalty)
    recent = jnp.zeros((G, 1, RECENT_WINDOW), jnp.int32)
    nvalid = jnp.zeros((G, 1), jnp.int32)

    t0 = time.monotonic()
    lens = np.zeros((G,), np.int32)
    tok0 = np.zeros((G, 1), np.int32)
    for g, ids_g in enumerate(prompt_ids):
        first, k, v = prefill_one(jnp.asarray([ids_g], jnp.int32), k, v, g)
        if sampled:
            # Key-schedule step 0 on the prefill logits (run_oracle parity).
            tok = sample_token_jit(jax.random.PRNGKey(args.seed),
                                   first[0], recent[g, 0], nvalid[g, 0],
                                   *sp_scalars)
            r2, n2 = push_recent(recent[g, 0], nvalid[g, 0], tok)
            recent = recent.at[g, 0].set(r2)
            nvalid = nvalid.at[g, 0].set(n2)
            tok0[g] = int(tok)
        else:
            tok0[g] = np.asarray(first)
        lens[g] = len(ids_g)
    ttft = time.monotonic() - t0

    sessions = [[int(tok0[g, 0])] for g in range(G)]
    done = [False] * G
    cur_tok = jnp.asarray(tok0)
    lens_j = jnp.asarray(lens)
    sp_vecs = dict(
        temps=jnp.full((G,), args.temperature, jnp.float32),
        top_ps=jnp.full((G,), args.top_p, jnp.float32),
        top_ks=jnp.full((G,), args.top_k, jnp.int32),
        reps=jnp.full((G,), args.repetition_penalty, jnp.float32))
    steps_done = 1      # PRNG schedule index: prefill token was step 0
    t0 = time.monotonic()
    # Count only tokens harvested INSIDE the decode loop: the first token
    # per session came from prefill (its cost sits in TTFT, not here).
    produced = 0
    rounds = accepted = 0

    def _harvest(g, run) -> None:
        """Append tokens to session g with per-token stop checks."""
        nonlocal produced
        for t in run:
            if done[g] or len(sessions[g]) >= args.max_new_tokens:
                done[g] = True
                return
            t = int(t)
            sessions[g].append(t)
            produced += 1
            if eos is not None and t == eos:
                done[g] = True
            elif (len(sessions[g]) >= 5
                  and len(set(sessions[g][-5:])) == 1):
                done[g] = True

    if spec_k:
        # Ring x speculative: each round every session consumes its last
        # token + K client-drafted tokens; the last stage verifies
        # in-program (greedy chain or rejection sampling), yielding 1..K+1
        # tokens per session per pipeline traversal. Greedy output is
        # token-identical to the plain ring regardless of draft quality.
        from .runtime.speculative import ngram_draft

        contexts = [list(prompt_ids[g]) + list(sessions[g])
                    for g in range(G)]
        lens_np = lens.copy()
        while True:
            act = [g for g in range(G)
                   if not done[g] and len(sessions[g]) < args.max_new_tokens]
            if not act:
                break
            tokens_in = np.zeros((G, 1, spec_k + 1), np.int32)
            for g in range(G):
                tokens_in[g, 0, 0] = sessions[g][-1]
                drafts = (list(ngram_draft(contexts[g], spec_k))
                          if not done[g] else [])
                for i in range(spec_k):   # short draft runs pad with 0 — a
                    # pad is just a (probably wrong) draft; verification
                    # keeps the output exact either way.
                    tokens_in[g, 0, 1 + i] = (drafts[i] if i < len(drafts)
                                              else 0)
            seed_base = np.asarray(
                [args.seed + len(sessions[g]) for g in range(G)], np.int32)
            toks, nacc, k, v, recent, nvalid = round_fn(
                tokens_in, k, v, lens_np, seed_base=seed_base,
                recent=recent, nvalid=nvalid, **sp_vecs)
            toks, nacc = np.asarray(toks), np.asarray(nacc)
            rounds += 1
            for g in act:
                na = int(nacc[g, 0])
                accepted += na
                run = toks[g, 0, : na + 1].tolist()
                lens_np[g] += na + 1
                _harvest(g, run)
                contexts[g] = list(prompt_ids[g]) + list(sessions[g])
    else:
        while True:
            act = [g for g in range(G)
                   if not done[g] and len(sessions[g]) < args.max_new_tokens]
            if not act:
                break
            n = max(1, min(chunk, max(args.max_new_tokens - len(sessions[g])
                                      for g in act)))
            if sampled:
                toks, k, v, recent, nvalid = rd.decode_sampled(
                    cur_tok, k, v, lens_j, n,
                    seed_base=jnp.full((G,), args.seed + steps_done,
                                       jnp.int32),
                    recent=recent, nvalid=nvalid, **sp_vecs)
            else:
                toks, k, v = rd.decode(cur_tok, k, v, lens_j, n)
            steps_done += n
            toks = np.asarray(toks[:n])
            for g in range(G):
                _harvest(g, toks[:, g, 0])
            cur_tok = jnp.asarray(toks[n - 1])
            lens_j = lens_j + n
    decode_s = time.monotonic() - t0

    for g, toks_g in enumerate(sessions):
        text = tokenizer.decode(toks_g[:args.max_new_tokens])
        _emit(f"\n=== Session {g} ({len(toks_g[:args.max_new_tokens])} "
              f"tokens) ===\n{text}")
    _emit(f"\nTTFT (all {G} prefills): {ttft:.3f}s")
    rate = produced / decode_s if decode_s > 0 else 0.0
    _emit(f"Decode: {decode_s:.3f}s total, {rate:.2f} tokens/s aggregate "
          f"across {G} sessions (decode-loop tokens only; each session's "
          f"first token comes from prefill)")
    if spec_k and rounds:
        _emit(f"Speculative: {rounds} rounds, "
              f"{accepted / (rounds * len(sessions)):.2f} drafts accepted "
              f"per session-round (of {spec_k})")
    return 0


def _generate_and_report(args, generate_fn, cfg: ModelConfig,
                         supports_speculative: bool = True) -> int:
    # Remote checkpoints: the tokenizer files were fetched into the local
    # cache by fetch_config — load from there, not the URL.
    tokenizer = load_tokenizer(_remote_store(args).cache_dir
                               if _is_remote(args.checkpoint)
                               else args.checkpoint)
    prompt_ids = tokenizer.encode(args.prompt)
    prompt_ids = [i % cfg.vocab_size for i in prompt_ids]
    sampling = SamplingParams(
        temperature=args.temperature, top_p=args.top_p, top_k=args.top_k,
        repetition_penalty=args.repetition_penalty,
    )
    eos = getattr(tokenizer, "eos_token_id", None)

    kw = {}
    if getattr(args, "speculative_k", 0):
        if supports_speculative:
            kw["speculative_k"] = args.speculative_k
        else:
            logger.warning("--speculative_k is ignored in --mode %s "
                           "(pipeline-client modes only)", args.mode)
    if getattr(args, "deadline_s", None):
        if supports_speculative:  # same gate: pipeline-client modes only
            kw["deadline_s"] = args.deadline_s
        else:
            logger.warning("--deadline_s is ignored in --mode %s "
                           "(pipeline-client modes only)", args.mode)
    if getattr(args, "burst", 0):
        if supports_speculative:  # same gate: pipeline-client modes only
            kw["burst"] = args.burst
        else:
            logger.warning("--burst is ignored in --mode %s "
                           "(pipeline-client modes only)", args.mode)
    res = generate_fn(prompt_ids, args.max_new_tokens, sampling=sampling,
                      eos_token_id=eos, **kw)
    text = tokenizer.decode(res.tokens)
    # The reference's closing report (src/main.py:213-225): TTFT, decode
    # time, tokens/s.
    _emit(f"\n=== Generation ({len(res.tokens)} tokens, "
          f"stopped by {res.stopped_by}) ===")
    _emit(text)
    # The ids themselves: the byte tokenizer's text is lossy, and parity
    # checks (chip_smoke.py) compare tokens.
    _emit(f"IDS {json.dumps([int(t) for t in res.tokens])}")
    _emit(f"\nTTFT: {res.ttft_s:.3f}s")
    total_decode = sum(res.decode_times_s)
    _emit(f"Decode: {total_decode:.3f}s total, "
          f"{res.decode_tokens_per_s:.2f} tokens/s")
    return 0


# ---------------------------------------------------------------------------
# Network modes: REAL multi-process swarm over TCP (reference --stage N
# servers + DHT, src/main.py:243-278,426-555 — registry service instead of
# Kademlia, framed TCP instead of libp2p). One process per role:
#   --mode registry : control-plane service (the DHT bootstrap node role)
#   --mode serve    : one stage server (--stage N picks the span)
#   --mode client   : stage-0 client driving the remote pipeline
# ---------------------------------------------------------------------------

def _stage_params(args, cfg: ModelConfig, params, spec):
    """Stage weights for a serving role: streamed from a safetensors
    checkpoint when possible, sliced from the loaded tree otherwise, then
    optionally block-quantized (--quant int8, V9 parity)."""
    if params is None:
        if _is_remote(args.checkpoint):
            sp = _remote_store(args).load_stage(
                cfg, spec, dtype=_DTYPE_MAP[args.dtype])
        else:
            from .models.hf_import import load_stage_checkpoint

            sp = load_stage_checkpoint(args.checkpoint, cfg, spec,
                                       dtype=_DTYPE_MAP[args.dtype])
    else:
        sp = slice_stage_params(cfg, params, spec)
    sp = _maybe_lora(args, cfg, sp, spec.start, spec.end)
    # Stage-server TP + quant is guarded downstream (the TP engine's shard
    # tables reject quantized leaves loudly), so no tp check here.
    return _maybe_quantize(args, sp)


def run_registry(args, cfg: ModelConfig, params) -> int:
    del cfg, params
    from .runtime.net import RegistryServer

    srv = RegistryServer(host=args.host, port=args.registry_port,
                         ttl=args.ttl,
                         allow_fault_injection=args.allow_fault_injection)
    srv.start()
    # Machine-readable handshake line (the reference printed the DHT maddr
    # for run_all.py to scrape, src/main.py:449-465).
    _emit(f"REGISTRY_ADDR={srv.address}", flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        srv.stop()
    return 0


def _serve_tp_mesh(args):
    """Local ('tp',) mesh for --mode serve --tp N: one server process using
    N chips for its stage (the reference wraps every serving block in TP,
    petals/server/backend.py:43). None when tp <= 1."""
    if args.tp <= 1:
        return None
    from jax.sharding import Mesh

    devs = jax.devices()
    if len(devs) < args.tp:
        raise SystemExit(
            f"--tp {args.tp} needs {args.tp} local devices, found {len(devs)}")
    return Mesh(np.asarray(devs[:args.tp]), ("tp",))


def _fused_stage_params(args, cfg: ModelConfig, params, spec):
    """`_stage_params` in the batched engine's fused layout
    (`fuse_qkv_params`), for a server that builds ONE engine from it: the
    q, k, v and gate, up stacks that the fused copies replace are freed
    here, where the tree's life is decided, before the engine allocates its
    cache stacks. Left alive they stay resident beside the copies for the
    process's life: 3.4 GB of ouro-2.6b's 5.3, on a chip that the weights
    and 8 x 512 rows of a 192-layer cache fill to 11.8 GB (peak 16.49 of
    16.91 GB without this, 13.06 with it: PERF.md section 4)."""
    from .models.transformer import fuse_qkv_params

    staged = _stage_params(args, cfg, params, spec)
    fused = fuse_qkv_params(staged)
    kept = {id(x) for x in jax.tree.leaves(fused)}
    for leaf in jax.tree.leaves(staged):
        if isinstance(leaf, jax.Array) and id(leaf) not in kept:
            leaf.delete()
    return fused


def run_serve(args, cfg: ModelConfig, params) -> int:
    import os

    from .runtime.executor import StageExecutor as _SE
    from .runtime.net import RemoteRegistry, TcpStageServer

    if args.use_load_balancing:
        return _run_serve_elastic(args, cfg, params)
    splits = parse_splits(args.splits) if args.splits else None
    plan = (StagePlan.from_splits(cfg.num_layers, splits) if splits
            else StagePlan.even(cfg.num_layers, 4))
    if args.stage == 0:
        # Full-span server: the only shape that can run burst decode —
        # on-device sampling feeds each tick's token straight back into
        # the embedding, so the scan needs blocks 0..L plus the head in
        # one process. Classic stage 0 runs inside the client, so this
        # shape is --batched-only; --splits is ignored for the span.
        if not args.batched:
            raise SystemExit(
                "--stage 0 serves the FULL model span and requires "
                "--batched (the burst-capable continuous-batching engine); "
                "classic stage 0 runs inside the client")
        spec = StagePlan.even(cfg.num_layers, 1).stages[0]
    elif not 1 <= args.stage < plan.num_stages:
        raise SystemExit(
            f"--stage must be 1..{plan.num_stages - 1} for serve mode "
            "(stage 0 runs inside the client; --stage 0 --batched serves "
            "the full span for --burst)")
    else:
        spec = plan.stages[args.stage]

    registry = RemoteRegistry(args.registry_addr, peers_cache=args.peers_cache)
    peer_id = args.peer_id or f"stage{args.stage}-{os.getpid()}"
    if args.sp_zigzag and args.sp <= 1:
        raise SystemExit("--sp_zigzag requires --sp N > 1 (it is a layout "
                         "for the sequence-parallel engine)")
    if args.sp > 1 and (args.batched or args.tp > 1 or args.use_cpu_offload):
        raise SystemExit("--sp does not compose with --batched/--tp/"
                         "--use_cpu_offload on one server")
    if args.prefix_cache_mb and args.sp > 1:
        raise SystemExit(
            "--prefix_cache_mb does not compose with --sp (the sp engine "
            "shards one session's prefix KV across the mesh; a shared "
            "store would need per-device segment sharding) — serve "
            "session or batched replicas with it instead")
    if args.sp > 1:
        # Sequence-parallel long-context engine: ONE session at a time, its
        # prefix KV sharded along T over the local ('sp',) mesh.
        from jax.sharding import Mesh as _Mesh

        from .parallel.sp_stage import SpStageRunner
        from .runtime.sp_serve import SpStageAdapter

        devs = jax.devices()
        if len(devs) < args.sp:
            raise SystemExit(f"--sp {args.sp} needs {args.sp} local devices, "
                             f"found {len(devs)}")
        mesh = _Mesh(np.asarray(devs[:args.sp]), ("sp",))
        runner = SpStageRunner(cfg, spec,
                               _stage_params(args, cfg, params, spec), mesh,
                               dtype=_DTYPE_MAP[args.dtype],
                               zigzag=args.sp_zigzag)
        # max_context default (8192/chip + tail) is the ADAPTER's policy.
        ex = SpStageAdapter(runner, peer_id=peer_id,
                            max_context=args.max_context)
    elif args.batched:
        # Continuous-batching engine behind the same TCP protocol: plain
        # sessions coalesce into shared rounds; exotic verbs get a retryable
        # refusal and clients route them to per-session replicas. Compute
        # runs inline on handler threads (NOT through a single-threaded
        # StageRuntime) — the adapter's round window IS the scheduler.
        if args.use_cpu_offload or args.keep_layers_on_gpu:
            raise SystemExit(
                "--batched keeps its span resident in HBM (the batched step "
                "reads every layer every round); host offload is a "
                "per-session-executor feature — drop --use_cpu_offload/"
                "--keep_layers_on_gpu or serve without --batched")
        if args.tp > 1:
            raise SystemExit("--batched does not compose with --tp yet; "
                             "serve per-session (--tp N) or batched (--batched)")
        from .runtime.batching import BatchedStageExecutor, BatchingStageAdapter

        kv_dtype = (jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32)
        engine = BatchedStageExecutor(
            cfg, spec, _fused_stage_params(args, cfg, params, spec),
            slots=args.slots, max_len=args.max_session_len, dtype=kv_dtype,
            prefix_cache_bytes=args.prefix_cache_mb << 20,
            model=_model_id(args))
        ex = BatchingStageAdapter(engine, peer_id=peer_id)
    else:
        ex = _SE(cfg, spec, _stage_params(args, cfg, params, spec),
                 peer_id=peer_id,
                 offload=args.use_cpu_offload,
                 keep_layers_resident=args.keep_layers_on_gpu,
                 tp_mesh=_serve_tp_mesh(args),
                 prefix_cache_bytes=args.prefix_cache_mb << 20)
    logger.info("warming up stage %d (pre-compiling step shapes)", args.stage)
    if args.batched:
        # Warm the K+1-wide batched decode step and/or the N-tick burst
        # program too, so neither compiles inside the round leader's lock.
        ex.warmup(speculative_k=getattr(args, "speculative_k", 0),
                  burst=getattr(args, "burst", 0))
    else:
        ex.warmup()
    if args.quant != "none":
        # Which quantized matmul sites the warm-up traced, and where each
        # ran (Pallas kernel or XLA) — read by chip_smoke.py.
        from .ops import quant_kernel_report

        _emit("KERNELS " + json.dumps(quant_kernel_report()), flush=True)
    # Per-session executors serialize compute through the prioritized
    # runtime (one compute thread owns the chip; N handler threads own the
    # sockets — the reference's handlers→Runtime split). The batched engine
    # must NOT be serialized: concurrent handler calls are how its round
    # window coalesces, and its own lock + round leadership guard the chip.
    from .runtime.task_pool import StageRuntime

    # The batched engine must NOT be serialized (concurrent handler calls
    # are how its round window coalesces); the sp adapter serializes itself
    # with its own lock (one session owns the mesh anyway).
    runtime = (None if (args.batched or args.sp > 1)
               else StageRuntime(high_water=args.queue_high_water,
                                 low_water=args.queue_low_water))
    # Decentralized control plane: every serve process embeds a gossip
    # mirror of the placement records, so the swarm survives losing EVERY
    # dedicated registry (seeds become bootstrap-only, like DHT initial
    # peers). The server answers register/heartbeat/list itself and runs
    # anti-entropy exchanges piggybacked on the heartbeat cadence.
    from .scheduling.gossip import GossipLoop, GossipNode
    from .scheduling.registry import rec_to_dict as _r2d

    gnode = GossipNode(peer_id, ttl=registry.ttl,
                       rng=random.Random(args.seed + os.getpid()))
    srv = TcpStageServer(ex, host=args.host, port=args.rpc_port,
                         wire_dtype=args.wire_dtype, model=_model_id(args),
                         runtime=runtime,
                         allow_fault_injection=args.allow_fault_injection,
                         gossip=gnode,
                         relay_capacity=args.relay_capacity)
    srv.start()
    # --public_ip overrides the advertised address (the reference's
    # public-maddr-only advertising, component 21 / src/main.py:492-509).
    advert = (f"{args.public_ip}:{srv.address.rsplit(':', 1)[1]}"
              if args.public_ip else srv.address)
    gnode.self_address = advert
    rec = make_server_record(ex.peer_id, spec,
                             model=_model_id(args),
                             engine=getattr(ex, "engine", "session"))
    rec.max_context = getattr(ex, "max_context", None)
    rec.address = advert
    if args.relay_capacity > 0:
        rec.relay_capacity = args.relay_capacity
    # Next-hop RTT probe + relay attach share one transport: a TcpTransport
    # resolves peers via the registry, so both hit the real data-plane wire.
    from .runtime.net import TcpTransport as _TT
    from .runtime.net import attach_via_relay as _attach_relay
    from .runtime.net import check_direct_reachability as _reach
    from .telemetry import events as _events

    ping_tx = _TT(registry, wire_dtype=args.wire_dtype)
    # Dial-back reachability vote (petals/server/reachability.py): ask live
    # peers to dial `advert` back. An explicit False verdict means we are
    # NAT'd — attach to a volunteer and advertise relay_via so clients
    # route through it; None (nobody answered / first server in the swarm)
    # is treated as reachable. The registration below then replicates
    # relay_via through gossip like any other record field.
    if _reach(ping_tx, registry, advert) is False:
        got = _attach_relay(ping_tx, registry, ex.peer_id, srv.address)
        if got is None:
            _emit("WARNING: dial-back vote says this server is unreachable "
                  "and no relay volunteer accepted an attach — clients "
                  "will not be able to reach it (start a peer with "
                  "--relay_capacity N or fix --public_ip)", flush=True)
        else:
            rec.relay_via = got["relay"]
            # Advertise the relayed throughput through the same model the
            # planner trusts: with step=None get_server_throughput returns
            # the network-only estimate, so the relayed/direct ratio is
            # exactly the RELAY_PENALTY discount (petals' use_relay wiring).
            from .scheduling.throughput import get_server_throughput as _gst
            nb = max(1, spec.end - spec.start)
            direct_rps = _gst(None, cfg.hidden_size, num_blocks=nb)
            relayed_rps = _gst(None, cfg.hidden_size, use_relay=True,
                               num_blocks=nb)
            rec.throughput = rec.throughput * (relayed_rps / direct_rps)
            _events.emit("relay_attach", peer=ex.peer_id,
                         relay=rec.relay_via, address=srv.address)
            _emit(f"RELAY: serving via volunteer {rec.relay_via} "
                  f"(dial-back vote failed for {advert})", flush=True)
    registry.register(rec)
    gnode.publish(_r2d(rec))

    from .runtime.net import gossip_exchange as _gx

    def _seed_peers():
        # Seed the gossip peer set from whatever discovery still works —
        # the seed registry while it's up, the mirror/stale snapshot after.
        return [r.address for r in registry.live_servers() if r.address]

    from .telemetry.profiling import stats_digest as _stats_digest

    def _own_rec_with_stats():
        # Piggyback this server's live stats digest on the gossip record:
        # dict_to_rec ignores unknown keys, so the "stats" extra propagates
        # swarm-wide verbatim and --mode top reads it from ANY live mirror.
        d = _r2d(rec)
        d["stats"] = _stats_digest()
        return d

    gloop = GossipLoop(gnode, _gx, record_fn=_own_rec_with_stats,
                       extra_peers_fn=_seed_peers)
    gloop.start()
    _emit(f"SERVING stage={args.stage} span=[{spec.start},{spec.end}) "
          f"addr={advert} peer={ex.peer_id} {device_line()} "
          f"codec={codec_name()}", flush=True)
    # Next-hop RTT probe (petals/server/server.py:760-767) reuses ping_tx.
    from .runtime.server import measure_next_server_rtts as _rtts

    try:
        # Heartbeat every TTL/3 (src/main.py:529-537); re-register if the
        # registry restarted and forgot us.
        rtts = None
        while True:
            time.sleep(registry.ttl / 3.0)
            try:
                # Refresh first with last beat's RTTs, then measure — a slow
                # ping sweep must not delay the TTL refresh past expiry.
                rec.next_server_rtts = rtts
                if not registry.heartbeat(
                        ex.peer_id,
                        cache_tokens_left=ex.arena.tokens_left(),
                        next_server_rtts=rtts):
                    registry.register(rec)
                if rec.relay_via is not None:
                    # Relay circuits are leases: re-attach every beat to
                    # refresh ours (idempotent on the volunteer). If the
                    # volunteer died, pick a replacement and re-advertise —
                    # clients meanwhile hit the failover/replay path.
                    from .runtime.net import PeerUnavailable as _PU
                    try:
                        ping_tx.relay_attach(rec.relay_via, ex.peer_id,
                                             srv.address)
                    except (_PU, TimeoutError, ConnectionError, OSError):
                        got = _attach_relay(ping_tx, registry, ex.peer_id,
                                            srv.address,
                                            exclude=(rec.relay_via,))
                        if got is not None:
                            rec.relay_via = got["relay"]
                            _events.emit("relay_attach", peer=ex.peer_id,
                                         relay=rec.relay_via,
                                         address=srv.address)
                            registry.register(rec)
                            gnode.publish(_r2d(rec))
                # {} is published as-is: it RETRACTS stale RTTs (None would
                # mean "no update" and pin dead-link measurements forever).
                rtts = (None if spec.is_last else _rtts(
                    registry, lambda r: ping_tx.ping(r.peer_id),
                    ex.peer_id, spec.end,
                    budget_s=registry.ttl / 6.0,
                    model=_model_id(args)))
            except (ConnectionError, OSError) as exc:
                logger.warning("heartbeat failed: %s", exc)
    except KeyboardInterrupt:
        pass
    finally:
        gloop.stop()
        try:
            registry.unregister(ex.peer_id)
        except Exception:
            pass
        gnode.apply_unregister(ex.peer_id)
        srv.stop()
    return 0


def _run_serve_elastic(args, cfg: ModelConfig, params) -> int:
    """Elastic (load-balancing) stage server over TCP: the span is CHOSEN
    from live swarm coverage (rule 1), re-chosen on imbalance (rule 2), and
    the executor is swapped in place on the listening socket — the
    reference's LB servers were network servers too
    (src/main.py:281-423,558-772)."""
    import os

    from .runtime.net import RemoteRegistry, TcpStageServer
    from .runtime.server import ElasticStageServer

    peer = args.peer_id or f"lb-{os.getpid()}"
    registry = RemoteRegistry(args.registry_addr, peers_cache=args.peers_cache)
    # Serialize compute through the prioritized runtime: elastic servers see
    # whatever concurrency the swarm sends them, and concurrent per-session
    # forwards on one executor are not a supported dispatch pattern.
    from .runtime.task_pool import StageRuntime
    from .scheduling.gossip import GossipLoop, GossipNode

    gnode = GossipNode(peer, ttl=registry.ttl,
                       rng=random.Random(args.seed + os.getpid()))
    srv = TcpStageServer(None, host=args.host, port=args.rpc_port,
                         wire_dtype=args.wire_dtype, peer_id=peer,
                         model=_model_id(args), runtime=StageRuntime(),
                         allow_fault_injection=args.allow_fault_injection,
                         gossip=gnode)
    srv.start()
    advert = (f"{args.public_ip}:{srv.address.rsplit(':', 1)[1]}"
              if args.public_ip else srv.address)
    gnode.self_address = advert

    class _Membership:
        """LocalTransport's membership surface, backed by the live TCP
        socket: add_peer swaps the served executor, remove_peer blanks it
        (requests during a re-span get a retryable stage error)."""

        def add_peer(self, peer_id, executor):
            srv.executor = executor

        def remove_peer(self, peer_id):
            srv.executor = None

    splits = parse_splits(args.splits) if args.splits else None
    min_block = splits[0] if splits else 0  # client-local prefix floor
    total = args.total_blocks or cfg.num_layers
    num_blocks = args.num_blocks
    if num_blocks is None:
        # No --num_blocks: derive capacity from the REAL device memory
        # (weights + KV arena + headroom, petals server.py:275-326), falling
        # back to the even-thirds topology heuristic when the backend
        # publishes no byte limit (host CPU).
        from .runtime.server import derive_num_blocks

        num_blocks = derive_num_blocks(
            cfg, dtype_bytes=jnp.dtype(_DTYPE_MAP[args.dtype]).itemsize,
            quant=args.quant, tp=args.tp)
        if num_blocks is not None:
            num_blocks = min(num_blocks, max(total - min_block, 1))
    num_blocks = num_blocks or max(1, (total - min_block) // 3)
    from .runtime.net import TcpTransport as _TT

    ping_tx = _TT(registry, wire_dtype=args.wire_dtype)
    es = ElasticStageServer(
        peer, cfg, lambda spec: _stage_params(args, cfg, params, spec),
        registry, _Membership(),
        pinger=lambda rec: ping_tx.ping(rec.peer_id),
        num_blocks=num_blocks, total_blocks=total, min_block=min_block,
        balance_quality=args.balance_quality,
        mean_balance_check_period=args.mean_balance_check_period,
        bandwidth_mbps=args.network_bandwidth_mbps,
        executor_kwargs={"offload": args.use_cpu_offload,
                         "keep_layers_resident": args.keep_layers_on_gpu,
                         "tp_mesh": _serve_tp_mesh(args),
                         "prefix_cache_bytes": args.prefix_cache_mb << 20},
        advertise_address=advert, warmup=True,
        rng=random.Random(args.seed + os.getpid()),
        model=_model_id(args),
    )
    es.start()
    _emit(f"SERVING elastic span=[{es.spec.start},{es.spec.end}) "
          f"addr={advert} peer={peer} {device_line()} "
          f"codec={codec_name()}", flush=True)

    from .runtime.net import gossip_exchange as _gx
    from .scheduling.registry import rec_to_dict as _r2d

    from .telemetry.profiling import stats_digest as _stats_digest

    def _own_record():
        # During a re-span the spec is momentarily unset; skip that beat.
        if es.spec is None:
            return None
        d = _r2d(es._record())
        d["stats"] = _stats_digest()
        return d

    gloop = GossipLoop(
        gnode, _gx, record_fn=_own_record,
        extra_peers_fn=lambda: [r.address for r in registry.live_servers()
                                if r.address])
    gloop.start()
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        gloop.stop()
        es.stop()
        gnode.apply_unregister(peer)
        srv.stop()
    return 0


def _lazy_stage0(args, cfg: ModelConfig, plan: StagePlan,
                 peer_ids: Sequence[str]) -> list:
    """One zero-arg stage-0 factory per peer id, sharing one weight load.

    The client and gateway are host-side roles until a classic route
    starts after block 0: a session served whole by a full-span peer (every
    --burst session, and plain per-step ones while such a peer is live)
    computes nothing locally, so start-up loads no weights and opens no
    device. The first classic route builds stage 0 — that process then
    owns a chip, and says which on its STAGE0 line."""
    import threading

    lock = threading.Lock()
    loaded: list = []

    def weights():
        with lock:
            if not loaded:
                loaded.append(load_model(args)[1])
            return loaded[0]

    def factory(peer_id: str):
        def build() -> StageExecutor:
            spec = plan.stages[0]
            ex = StageExecutor(cfg, spec,
                               _stage_params(args, cfg, weights(), spec),
                               peer_id=peer_id)
            _emit(f"STAGE0 span=[{spec.start},{spec.end}) peer={peer_id} "
                  f"{device_line()}", flush=True)
            return ex
        return build

    return [factory(p) for p in peer_ids]


def run_client(args, cfg: ModelConfig, params) -> int:
    from .runtime.net import RemoteRegistry, TcpTransport

    del params  # stage-0 weights load on first use (_lazy_stage0)
    splits = parse_splits(args.splits) if args.splits else None
    plan = (StagePlan.from_splits(cfg.num_layers, splits) if splits
            else StagePlan.even(cfg.num_layers, 4))
    registry = RemoteRegistry(args.registry_addr, peers_cache=args.peers_cache)
    transport = TcpTransport(registry, wire_dtype=args.wire_dtype,
                             model=_model_id(args))
    stage0, = _lazy_stage0(args, cfg, plan, ["client-local"])
    client = PipelineClient(
        cfg, plan, stage0, transport, registry,
        use_module_routing=bool(args.use_load_balancing),
        route_by_latency=args.route_by_latency,
        total_blocks=args.total_blocks or cfg.num_layers,
        request_timeout=args.request_timeout,
        seed=args.seed,
        model=_model_id(args),
        long_context_threshold=args.long_context_threshold,
        metrics=_client_metrics(args),
    )
    try:
        return _generate_and_report(args, client.generate, cfg)
    finally:
        transport.close()


def _load_tenants_config(raw: Optional[str]):
    """Parse --tenants: inline JSON (starts with '{') or a file path;
    omitted means one 'default' tenant with the library defaults."""
    from .serving import parse_tenants_config

    raw = raw or '{"default": {}}'
    if not raw.lstrip().startswith("{"):
        with open(raw) as f:
            raw = f.read()
    return parse_tenants_config(json.loads(raw))


def run_gateway(args, cfg: ModelConfig, params) -> int:
    """--mode gateway: the multi-tenant serving front door. Owns one or
    more PipelineClients against the swarm at --registry_addr and serves
    the framed-TCP `submit` verb (docs/SERVING.md)."""
    from .runtime.net import RemoteRegistry, TcpTransport
    from .serving import GatewayServer

    del params  # stage-0 weights load on first use (_lazy_stage0)
    tenants, max_queue_depth, max_active = _load_tenants_config(args.tenants)
    splits = parse_splits(args.splits) if args.splits else None
    plan = (StagePlan.from_splits(cfg.num_layers, splits) if splits
            else StagePlan.even(cfg.num_layers, 4))
    registry = RemoteRegistry(args.registry_addr,
                              peers_cache=args.peers_cache)
    transports = []
    clients = []
    n_clients = max(1, args.gateway_clients)
    stage0s = _lazy_stage0(args, cfg, plan,
                           [f"gateway-local-{i}" for i in range(n_clients)])
    for stage0 in stage0s:
        tx = TcpTransport(registry, wire_dtype=args.wire_dtype,
                          model=_model_id(args))
        transports.append(tx)
        clients.append(PipelineClient(
            cfg, plan, stage0, tx, registry,
            use_module_routing=bool(args.use_load_balancing),
            route_by_latency=args.route_by_latency,
            total_blocks=args.total_blocks or cfg.num_layers,
            request_timeout=args.request_timeout,
            seed=args.seed,
            model=_model_id(args),
            long_context_threshold=args.long_context_threshold,
            metrics=_client_metrics(args),
        ))
    gw = GatewayServer(clients, tenants, host=args.host,
                       port=args.rpc_port,
                       max_queue_depth=max_queue_depth,
                       max_active=max_active,
                       allow_fault_injection=args.allow_fault_injection,
                       burst=args.burst)
    gw.start()
    # Host-side until a classic route builds stage 0 (which then prints
    # its own STAGE0 line with the device it opened).
    _emit(f"GATEWAY addr={gw.address} tenants={','.join(sorted(tenants))} "
          f"clients={len(clients)} max_queue_depth={max_queue_depth} "
          f"max_active={max_active} burst={args.burst} stage0=on-first-use "
          f"codec={codec_name()}", flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        gw.stop()
        for tx in transports:
            tx.close()
    return 0


def run_submit(args) -> int:
    """--mode submit: fire --submit_requests requests at --gateway_addr as
    --tenant. No model weights load here — the gateway's swarm owns the
    model; only the tokenizer (prompt encoding) is needed."""
    from .serving import GatewaySubmitClient, Overloaded

    cfg = get_config(args.model)
    tokenizer = load_tokenizer(_remote_store(args).cache_dir
                               if _is_remote(args.checkpoint)
                               else args.checkpoint)
    prompt_ids = [i % cfg.vocab_size for i in tokenizer.encode(args.prompt)]
    client = GatewaySubmitClient(args.gateway_addr)
    shed = 0
    for i in range(args.submit_requests):
        t0 = time.perf_counter()
        try:
            res = client.submit(
                args.tenant, prompt_ids, args.max_new_tokens,
                temperature=args.temperature, top_p=args.top_p,
                top_k=args.top_k,
                repetition_penalty=args.repetition_penalty,
                deadline_s=args.deadline_s,
                timeout=args.request_timeout)
        except Overloaded as exc:
            shed += 1
            _emit(f"[{i}] SHED ({exc.reason}): retry after "
                  f"{exc.retry_after_s:.3f}s -- {exc}")
            continue
        dt = time.perf_counter() - t0
        _emit(f"[{i}] {len(res['tokens'])} tokens in {dt:.2f}s "
              f"(ttft={res['ttft_s'] or 0:.3f}s "
              f"queue_wait={res['queue_wait_s'] or 0:.3f}s "
              f"stopped_by={res['stopped_by']}): "
              f"{tokenizer.decode(res['tokens'])!r}")
    # Shedding is the gateway doing its job; only all-shed is a failure.
    return 1 if shed == args.submit_requests else 0


# ---------------------------------------------------------------------------
# Chaos soak (--mode chaos): deterministic fault injection against the REAL
# TCP data plane. Two generations with the same seed and prompt — one clean,
# one under a seeded FaultPlan covering every side of the swarm — must emit
# IDENTICAL tokens (recovery is exactly-once), and the doctor must
# reconstruct every injected failure from the flight-recorder rings.
# ---------------------------------------------------------------------------

def chaos_soak(cfg, params, *, prompt_ids, max_new_tokens=10, seed=0,
               splits=None, wire_dtype="f32", request_timeout=30.0,
               registry_addr=None, sampling=None, deadline_probe=True,
               stage_params=None) -> dict:
    """Run the chaos soak and return a verdict dict (``ok``, ``problems``,
    ``kinds_fired``, token lists, chain stats).

    ``registry_addr=None`` boots a self-contained swarm in-process — real
    TCP sockets, every role fault-armable. Passing an address instead
    ATTACHES to an externally launched swarm (scripts/chaos_swarm.py: one
    OS process per role, all started with --allow_fault_injection
    --telemetry) and scrapes the servers' event rings over the wire."""
    import collections as _collections
    import os as _os

    from .runtime.client import DeadlineExceeded
    from .runtime.executor import StageExecutor as _SE
    from .runtime.faults import FaultPlan, default_chaos_rules
    from .runtime.net import (RegistryServer, RemoteRegistry, TcpStageServer,
                              TcpTransport)
    from .runtime.task_pool import StageRuntime
    from .telemetry import doctor as _doc
    from .telemetry import events as _events

    # The soak IS a diagnostic: record regardless of --telemetry so the
    # doctor cross-check below always has a local stream to read.
    _events.get_recorder().enable()
    if sampling is None:
        # Greedy keeps the token-equality oracle independent of sampling
        # RNG bookkeeping; seeded-sampling parity under failover is already
        # pinned by the recovery tests.
        sampling = SamplingParams(temperature=0.0)
    if stage_params is None:
        stage_params = lambda spec: slice_stage_params(cfg, params, spec)  # noqa: E731
    plan = (StagePlan.from_splits(cfg.num_layers, splits) if splits
            else StagePlan.even(cfg.num_layers, 4))

    attach = registry_addr is not None
    reg_server = None
    servers = []
    problems: List[str] = []
    result: dict = {"attach": attach, "seed": seed}
    try:
        if not attach:
            reg_server = RegistryServer(host="127.0.0.1", port=0,
                                        allow_fault_injection=True)
            reg_server.start()
            registry_addr = reg_server.address
        reg = RemoteRegistry(registry_addr)
        if not attach:
            for spec in plan.stages[1:]:
                ex = _SE(cfg, spec, stage_params(spec),
                         peer_id=f"chaos-s{spec.index}")
                srv = TcpStageServer(ex, host="127.0.0.1", port=0,
                                     wire_dtype=wire_dtype,
                                     runtime=StageRuntime(),
                                     allow_fault_injection=True)
                srv.start()
                rec = make_server_record(ex.peer_id, spec)
                rec.address = srv.address
                reg.register(rec)
                servers.append(srv)
        ex0 = _SE(cfg, plan.stages[0], stage_params(plan.stages[0]),
                  peer_id="chaos-client")

        def _client(tx):
            # settle_seconds=0: recovery sleeps would dominate a soak whose
            # faults are all deterministic one-shots.
            return PipelineClient(cfg, plan, ex0, tx, reg,
                                  request_timeout=request_timeout,
                                  settle_seconds=0.0, seed=seed)

        # --- clean reference run: nothing armed anywhere ---
        tx1 = TcpTransport(reg, wire_dtype=wire_dtype)
        try:
            clean = _client(tx1).generate(
                list(prompt_ids), max_new_tokens, sampling=sampling,
                session_id="chaos-clean")
        finally:
            tx1.close()

        # --- arm every side of the swarm with one seeded plan ---
        recs = sorted(reg.live_servers(),
                      key=lambda r: (r.start_block, r.peer_id))
        peer_ids = [r.peer_id for r in recs]
        rules = default_chaos_rules(peer_ids, seed=seed)
        client_plan = FaultPlan([r for r in rules if r.side == "client"],
                                seed=seed)
        server_rules = [r for r in rules if r.side == "server"]
        reg_rules = [r for r in rules if r.side == "registry"]
        # Admin traffic goes over a transport that is NEVER armed — an
        # armed transport's own frames would consume fault-rule matches.
        admin = TcpTransport(reg, wire_dtype=wire_dtype)
        for pid in peer_ids:
            admin.install_fault_plan(pid, FaultPlan(server_rules, seed=seed))
        reg._rpc({"verb": "fault",
                  "plan": FaultPlan(reg_rules, seed=seed).to_dict()})
        # Deterministic control-plane traffic: two heartbeats trip the
        # `duplicate` rule (times=2) and two list calls walk `stale_registry`
        # past nth=2 — the data-plane run alone need not send either verb.
        for _ in range(2):
            reg.heartbeat(peer_ids[0])
            reg.live_servers()

        # --- chaos run: same seed, same prompt, every plan armed ---
        tx2 = TcpTransport(reg, wire_dtype=wire_dtype)
        tx2.set_fault_plan(client_plan)
        try:
            chaos = _client(tx2).generate(
                list(prompt_ids), max_new_tokens, sampling=sampling,
                session_id="chaos-faulty")
        finally:
            tx2.set_fault_plan(None)  # drops pooled conns too
            tx2.close()
        result["tokens_clean"] = list(clean.tokens)
        result["tokens_chaos"] = list(chaos.tokens)
        if list(clean.tokens) != list(chaos.tokens):
            problems.append(
                f"token divergence under faults: clean={list(clean.tokens)} "
                f"chaos={list(chaos.tokens)}")

        # --- deadline probe: an expired budget is a TYPED client error ---
        if deadline_probe:
            tx3 = TcpTransport(reg, wire_dtype=wire_dtype)
            try:
                _client(tx3).generate(list(prompt_ids), 2, sampling=sampling,
                                      session_id="chaos-deadline",
                                      deadline_s=1e-6)
                problems.append(
                    "deadline_s=1e-6 generation finished instead of raising "
                    "DeadlineExceeded")
            except DeadlineExceeded:
                result["deadline_probe"] = "raised DeadlineExceeded"
            finally:
                tx3.close()

        # --- collect firing reports, then disarm for whoever runs next ---
        client_firings = list(client_plan.report())
        server_firings: List[dict] = []
        for pid in peer_ids:
            server_firings += admin.fault_report(pid)
        reg_firings = list(reg._rpc(
            {"verb": "fault", "action": "report"}).get("firings", []))
        for pid in peer_ids:
            admin.install_fault_plan(pid, None)
        reg._rpc({"verb": "fault", "action": "clear"})

        all_firings = client_firings + server_firings + reg_firings
        fired = _collections.Counter(f["kind"] for f in all_firings)
        result["kinds_fired"] = sorted(fired)
        result["firings"] = dict(fired)
        if len(fired) < 5:
            problems.append(
                f"only {len(fired)} distinct fault kinds fired "
                f"({sorted(fired)}); the soak must cover >= 5")

        # --- doctor cross-check: every injection must be reconstructable
        # from the flight-recorder rings as part of a failure chain ---
        streams = [{"meta": {"pid": _os.getpid()},
                    "events": [ev.to_dict()
                               for ev in _events.get_recorder().events()]}]
        if attach:
            streams += _doc.scrape_events(admin, peer_ids)
        timeline = _doc.merge_timeline(streams)
        chains = _doc.failure_chains(timeline)
        in_chains = _collections.Counter(
            ev.get("fields", {}).get("kind")
            for ch in chains for ev in ch["events"]
            if ev.get("event") == "fault_injected")
        # Attach mode cannot read the registry process's ring (no
        # dump-events verb there) — hold the doctor to what it CAN see.
        accountable = client_firings + server_firings + (
            [] if attach else reg_firings)
        for kind, n in _collections.Counter(
                f["kind"] for f in accountable).items():
            if in_chains.get(kind, 0) < n:
                problems.append(
                    f"doctor chains account for {in_chains.get(kind, 0)}/{n} "
                    f"'{kind}' injections")
        fault_chains = [ch for ch in chains
                        if any(ev.get("event") == "fault_injected"
                               for ev in ch["events"])]
        result["chains"] = len(chains)
        result["fault_chains"] = len(fault_chains)
        if not any("chaos-faulty" in ch["sessions"] for ch in fault_chains):
            problems.append(
                "no failure chain correlates an injected fault with the "
                "chaos session (expected session 'chaos-faulty')")
        admin.close()
    finally:
        for srv in servers:
            srv.stop()
        if reg_server is not None:
            reg_server.stop()
    result["problems"] = problems
    result["ok"] = not problems
    return result


def registry_loss_soak(cfg, params, *, prompt_ids, max_new_tokens=8, seed=0,
                       splits=None, wire_dtype="f32", request_timeout=30.0,
                       peers_cache=None, gossip_interval_s=0.25,
                       sampling=None, stage_params=None) -> dict:
    """Total-registry-loss survival drill (the tentpole's acceptance
    scenario): boot a primary+standby registry and a gossiping stage swarm
    in-process, kill BOTH registries deterministically mid-generation, and
    require

      * the in-flight generation to finish with tokens IDENTICAL to a
        clean run (the data plane never depended on the seeds);
      * a FRESH client — empty snapshot, seeds dead — to bootstrap through
        a live stage server's gossip mirror (via the --peers_cache file)
        and generate the same tokens;
      * a restarted seed to be re-adopted (``registry_recovered``), and the
        doctor to reconstruct the whole outage as one failure chain:
        registries lost -> gossip-served discovery -> seeds restored.
    """
    import tempfile as _tempfile

    from .runtime.executor import StageExecutor as _SE
    from .runtime.net import (RegistryServer, RemoteRegistry, TcpStageServer,
                              TcpTransport, gossip_exchange)
    from .runtime.task_pool import StageRuntime
    from .scheduling.gossip import GossipLoop, GossipNode
    from .scheduling.registry import rec_to_dict as _r2d
    from .telemetry import doctor as _doc
    from .telemetry import events as _events

    _events.get_recorder().enable()
    if sampling is None:
        sampling = SamplingParams(temperature=0.0)
    if stage_params is None:
        stage_params = lambda spec: slice_stage_params(cfg, params, spec)  # noqa: E731
    plan = (StagePlan.from_splits(cfg.num_layers, splits) if splits
            else StagePlan.even(cfg.num_layers, 4))
    if peers_cache is None:
        fd, peers_cache = _tempfile.mkstemp(prefix="peers_cache_",
                                            suffix=".json")
        os.close(fd)

    problems: List[str] = []
    result: dict = {"seed": seed, "peers_cache": peers_cache}
    registries: List[RegistryServer] = []
    servers: List[TcpStageServer] = []
    loops: List[GossipLoop] = []
    transports: List[TcpTransport] = []
    try:
        # --- seeds: a primary + one standby, both about to die ---
        for _ in range(2):
            rs = RegistryServer(host="127.0.0.1", port=0)
            rs.start()
            registries.append(rs)
        seed_addrs = ",".join(rs.address for rs in registries)
        reg = RemoteRegistry(seed_addrs, timeout=2.0,
                             peers_cache=peers_cache)

        # --- gossiping stage swarm (every server embeds a mirror) ---
        gnodes: List[GossipNode] = []
        own_recs: List = []
        for spec in plan.stages[1:]:
            ex = _SE(cfg, spec, stage_params(spec),
                     peer_id=f"rloss-s{spec.index}")
            gnode = GossipNode(ex.peer_id,
                               rng=random.Random(seed + spec.index))
            srv = TcpStageServer(ex, host="127.0.0.1", port=0,
                                 wire_dtype=wire_dtype,
                                 runtime=StageRuntime(), gossip=gnode)
            srv.start()
            gnode.self_address = srv.address
            rec = make_server_record(ex.peer_id, spec)
            rec.address = srv.address
            reg.register(rec)
            gnode.publish(_r2d(rec))
            servers.append(srv)
            gnodes.append(gnode)
            own_recs.append(rec)
        all_addrs = [s.address for s in servers]
        for gnode, rec in zip(gnodes, own_recs):
            loop = GossipLoop(gnode, gossip_exchange,
                              record_fn=lambda r=rec: _r2d(r),
                              extra_peers_fn=lambda: list(all_addrs),
                              interval_s=gossip_interval_s)
            loop.start()
            loops.append(loop)
        # Anti-entropy must have replicated the FULL live set everywhere
        # before the seeds die, or a mirror could serve a partial swarm.
        deadline = time.monotonic() + 30.0
        want = len(servers)
        while (any(n.live_count() < want for n in gnodes)
               and time.monotonic() < deadline):
            time.sleep(0.05)
        if any(n.live_count() < want for n in gnodes):
            problems.append(
                "gossip never converged: mirror live counts "
                f"{[n.live_count() for n in gnodes]} < {want}")

        ex0 = _SE(cfg, plan.stages[0], stage_params(plan.stages[0]),
                  peer_id="rloss-client")

        def _client(tx, stage0, registry):
            return PipelineClient(cfg, plan, stage0, tx, registry,
                                  request_timeout=request_timeout,
                                  settle_seconds=0.0, seed=seed)

        # --- clean reference run (also warms the peers cache) ---
        tx1 = TcpTransport(reg, wire_dtype=wire_dtype)
        transports.append(tx1)
        clean = _client(tx1, ex0, reg).generate(
            list(prompt_ids), max_new_tokens, sampling=sampling,
            session_id="rloss-clean")
        result["tokens_clean"] = list(clean.tokens)

        # --- chaos run: the 2nd stage-0 forward kills EVERY seed ---
        class _KillSwitch:
            """Stage-0 proxy that trips `kill` after the Nth forward: the
            registry massacre lands DETERMINISTICALLY mid-generation
            (after prefill, before the decode steps finish)."""

            def __init__(self, inner, after_n, kill):
                self._inner, self._after, self._kill = inner, after_n, kill
                self.calls = 0

            def forward(self, req):
                out = self._inner.forward(req)
                self.calls += 1
                if self.calls == self._after:
                    self._kill()
                return out

            def __getattr__(self, name):
                return getattr(self._inner, name)

        def _kill_seeds():
            for rs in registries:
                try:
                    rs.stop()
                except Exception:
                    pass

        tx2 = TcpTransport(reg, wire_dtype=wire_dtype)
        transports.append(tx2)
        chaos = _client(tx2, _KillSwitch(ex0, 2, _kill_seeds), reg).generate(
            list(prompt_ids), max_new_tokens, sampling=sampling,
            session_id="rloss-chaos")
        result["tokens_chaos"] = list(chaos.tokens)
        if list(clean.tokens) != list(chaos.tokens):
            problems.append(
                "token divergence across the registry massacre: "
                f"clean={list(clean.tokens)} chaos={list(chaos.tokens)}")

        # --- the WARM client's next read must be mirror-served ---
        recs = reg.live_servers()
        if len(recs) < want:
            problems.append(
                f"warm client saw {len(recs)}/{want} servers after seed "
                "loss (gossip fallback should have served the full set)")

        # --- fresh client: no snapshot, dead seeds, only the cache file ---
        reg2 = RemoteRegistry(seed_addrs, timeout=2.0,
                              peers_cache=peers_cache)
        boot = reg2.live_servers()
        result["bootstrap_records"] = len(boot)
        if len(boot) < want:
            problems.append(
                f"fresh client bootstrapped {len(boot)}/{want} records "
                "from the gossip mirrors")
        tx3 = TcpTransport(reg2, wire_dtype=wire_dtype)
        transports.append(tx3)
        fresh = _client(tx3, ex0, reg2).generate(
            list(prompt_ids), max_new_tokens, sampling=sampling,
            session_id="rloss-bootstrap")
        result["tokens_bootstrap"] = list(fresh.tokens)
        if list(clean.tokens) != list(fresh.tokens):
            problems.append(
                "registry-less bootstrap diverged: "
                f"clean={list(clean.tokens)} fresh={list(fresh.tokens)}")

        # --- restore a seed: the swarm must re-adopt it ---
        primary_port = int(registries[0].address.rsplit(":", 1)[1])
        restored = RegistryServer(host="127.0.0.1", port=primary_port)
        restored.start()
        registries.append(restored)
        for rec in own_recs:
            reg2.register(rec)      # the serve heartbeat loop's re-register
        back = reg2.live_servers()
        if len(back) < want:
            problems.append(
                f"restored seed served {len(back)}/{want} records")

        # --- doctor: the outage must read as ONE failure chain ---
        streams = [{"meta": {"pid": os.getpid()},
                    "events": [ev.to_dict()
                               for ev in _events.get_recorder().events()]}]
        chains = _doc.failure_chains(_doc.merge_timeline(streams))
        result["chains"] = len(chains)
        ok_chain = False
        for ch in chains:
            names = {ev.get("event") for ev in ch["events"]}
            if ("registry_unreachable" in names
                    and ({"gossip_fallback", "gossip_served_discovery"}
                         & names)
                    and "registry_recovered" in names):
                ok_chain = True
        if not ok_chain:
            problems.append(
                "doctor chains do not reconstruct the outage (want one "
                "chain with registry_unreachable + gossip-served "
                "discovery + registry_recovered)")
    finally:
        for loop in loops:
            loop.stop()
        for tx in transports:
            try:
                tx.close()
            except Exception:
                pass
        for srv in servers:
            srv.stop()
        for rs in registries:
            try:
                rs.stop()
            except Exception:
                pass
    result["problems"] = problems
    result["ok"] = not problems
    return result


def relay_break_soak(cfg, params, *, prompt_ids, max_new_tokens=8, seed=0,
                     splits=None, wire_dtype="f32", request_timeout=30.0,
                     kill_after=2, sampling=None, stage_params=None) -> dict:
    """Relay-death survival drill (--mode chaos --chaos_scenario relay_break).

    Boots an in-process swarm where the FINAL stage server is NAT'd by
    construction: it advertises an address nothing can dial (a closed local
    port) and serves only through a relay volunteer. Two executor-less
    volunteers stand by; the higher-capacity one wins the attach. The drill:

      * clean run THROUGH the relay (the reference tokens — proving the
        relayed data path is bit-identical to begin with);
      * chaos run: the Nth stage-0 forward stops the active volunteer
        mid-generation and re-attaches the NAT'd server to the standby
        (exactly what its heartbeat re-pick does, compressed in time);
      * the generation must finish with IDENTICAL tokens — the client's
        normal failover/replay path re-resolves the hop through the new
        volunteer;
      * the circuit breaker must blame the dead VOLUNTEER, not the relayed
        peer (one dead relay must not blacklist every peer behind it);
      * the doctor must reconstruct the incident as one failure chain:
        relay lost -> failover -> replay.
    """
    from .runtime.executor import StageExecutor as _SE
    from .runtime.net import (RegistryServer, RemoteRegistry, TcpStageServer,
                              TcpTransport, attach_via_relay)
    from .runtime.task_pool import StageRuntime
    from .telemetry import doctor as _doc
    from .telemetry import events as _events

    _events.get_recorder().enable()
    if sampling is None:
        sampling = SamplingParams(temperature=0.0)
    if stage_params is None:
        stage_params = lambda spec: slice_stage_params(cfg, params, spec)  # noqa: E731
    plan = (StagePlan.from_splits(cfg.num_layers, splits) if splits
            else StagePlan.even(cfg.num_layers, 4))

    problems: List[str] = []
    result: dict = {"seed": seed}
    registries: List[RegistryServer] = []
    servers: List[TcpStageServer] = []
    transports: List[TcpTransport] = []
    try:
        rs = RegistryServer(host="127.0.0.1", port=0)
        rs.start()
        registries.append(rs)
        reg = RemoteRegistry(rs.address, timeout=2.0)

        # --- two relay volunteers: pure forwarders, no stage span. Their
        # records carry an EMPTY span (never routed stage traffic) plus
        # relay_capacity, exactly what attach_via_relay's picker keys on;
        # v1's larger capacity makes it the deterministic first choice. ---
        from .scheduling.registry import ServerRecord as _SR

        vols = {}
        for vid, cap in (("relay-v1", 4), ("relay-v2", 2)):
            vsrv = TcpStageServer(None, host="127.0.0.1", port=0,
                                  wire_dtype=wire_dtype, peer_id=vid,
                                  relay_capacity=cap)
            vsrv.start()
            vrec = _SR(peer_id=vid, start_block=0, end_block=0,
                       address=vsrv.address, relay_capacity=cap)
            reg.register(vrec)
            servers.append(vsrv)
            vols[vid] = vsrv

        # --- stage swarm; the FINAL stage is the NAT'd server ---
        nat_spec = plan.stages[-1]
        nat_rec = None
        nat_srv = None
        for spec in plan.stages[1:]:
            ex = _SE(cfg, spec, stage_params(spec),
                     peer_id=f"rbreak-s{spec.index}")
            srv = TcpStageServer(ex, host="127.0.0.1", port=0,
                                 wire_dtype=wire_dtype,
                                 runtime=StageRuntime())
            srv.start()
            rec = make_server_record(ex.peer_id, spec)
            if spec is nat_spec:
                # Advertise a closed port: any DIRECT dial fails instantly,
                # so a passing run proves every frame rode the relay.
                rec.address = "127.0.0.1:9"
                nat_rec, nat_srv = rec, srv
            else:
                rec.address = srv.address
            reg.register(rec)
            servers.append(srv)

        # --- the NAT'd server attaches (run_serve's post-vote path) ---
        atx = TcpTransport(reg, wire_dtype=wire_dtype)
        transports.append(atx)
        got = attach_via_relay(atx, reg, nat_rec.peer_id, nat_srv.address)
        if got is None or got["relay"] != "relay-v1":
            problems.append(f"attach picked {got and got['relay']}, "
                            "want relay-v1 (highest spare capacity)")
            result["problems"] = problems
            result["ok"] = False
            return result
        nat_rec.relay_via = got["relay"]
        _events.emit("relay_attach", peer=nat_rec.peer_id,
                     relay=nat_rec.relay_via, address=nat_srv.address)
        reg.register(nat_rec)

        ex0 = _SE(cfg, plan.stages[0], stage_params(plan.stages[0]),
                  peer_id="rbreak-client")

        def _client(tx, stage0):
            return PipelineClient(cfg, plan, stage0, tx, reg,
                                  request_timeout=request_timeout,
                                  settle_seconds=0.0, seed=seed)

        # --- clean reference run, THROUGH the relay ---
        tx1 = TcpTransport(reg, wire_dtype=wire_dtype)
        transports.append(tx1)
        clean = _client(tx1, ex0).generate(
            list(prompt_ids), max_new_tokens, sampling=sampling,
            session_id="rbreak-clean")
        result["tokens_clean"] = list(clean.tokens)

        # --- chaos run: Nth stage-0 forward kills the active volunteer ---
        class _KillSwitch:
            """Stage-0 proxy that trips `kill` after the Nth forward, so the
            relay dies DETERMINISTICALLY mid-generation (after prefill,
            before the decode steps finish)."""

            def __init__(self, inner, after_n, kill):
                self._inner, self._after, self._kill = inner, after_n, kill
                self.calls = 0

            def forward(self, req):
                out = self._inner.forward(req)
                self.calls += 1
                if self.calls == self._after:
                    self._kill()
                return out

            def __getattr__(self, name):
                return getattr(self._inner, name)

        def _break_relay():
            vols["relay-v1"].stop()
            # The NAT'd server's heartbeat re-pick, compressed in time:
            # re-attach via the standby and re-advertise relay_via. The
            # in-flight client meanwhile takes the failover/replay path.
            got2 = attach_via_relay(atx, reg, nat_rec.peer_id,
                                    nat_srv.address, exclude=("relay-v1",))
            if got2 is not None:
                nat_rec.relay_via = got2["relay"]
                _events.emit("relay_attach", peer=nat_rec.peer_id,
                             relay=nat_rec.relay_via,
                             address=nat_srv.address)
                reg.register(nat_rec)

        tx2 = TcpTransport(reg, wire_dtype=wire_dtype)
        transports.append(tx2)
        cl2 = _client(tx2, _KillSwitch(ex0, kill_after, _break_relay))
        chaos = cl2.generate(list(prompt_ids), max_new_tokens,
                             sampling=sampling, session_id="rbreak-chaos")
        result["tokens_chaos"] = list(chaos.tokens)
        result["relay_after"] = nat_rec.relay_via
        result["recoveries"] = cl2.recoveries
        if list(clean.tokens) != list(chaos.tokens):
            problems.append(
                "token divergence across the relay kill: "
                f"clean={list(clean.tokens)} chaos={list(chaos.tokens)}")
        if nat_rec.relay_via != "relay-v2":
            problems.append(
                f"re-attach landed on {nat_rec.relay_via}, want relay-v2")
        if cl2.recoveries < 1:
            problems.append(
                "client reported no recoveries — the kill never landed "
                "mid-generation (raise max_new_tokens or lower kill_after)")

        # --- blame: the breaker must track the VOLUNTEER, not the peer ---
        if not cl2.breaker.allow(nat_rec.peer_id):
            problems.append(
                "circuit breaker opened for the RELAYED peer "
                f"{nat_rec.peer_id}; the dead volunteer relay-v1 should "
                "have taken the blame")

        # --- doctor: the incident must read as ONE failure chain ---
        streams = [{"meta": {"pid": os.getpid()},
                    "events": [ev.to_dict()
                               for ev in _events.get_recorder().events()]}]
        chains = _doc.failure_chains(_doc.merge_timeline(streams))
        result["chains"] = len(chains)
        ok_chain = False
        for ch in chains:
            names = {ev.get("event") for ev in ch["events"]}
            if {"relay_forward_error", "failover", "replay_done"} <= names:
                ok_chain = True
        if not ok_chain:
            problems.append(
                "doctor chains do not reconstruct the incident (want one "
                "chain with relay_forward_error + failover + replay_done)")
    finally:
        for tx in transports:
            try:
                tx.close()
            except Exception:
                pass
        for srv in servers:
            try:
                srv.stop()
            except Exception:
                pass
        for rs in registries:
            try:
                rs.stop()
            except Exception:
                pass
    result["problems"] = problems
    result["ok"] = not problems
    return result


def overload_soak(cfg, params, *, prompt_ids, max_new_tokens=8, seed=0,
                  splits=None, wire_dtype="f32", request_timeout=30.0,
                  requests_per_tenant=3, stage_params=None,
                  burst=0) -> dict:
    """Multi-tenant overload drill (--mode chaos --chaos_scenario overload).

    Boots a swarm + gateway in-process, then proves the serving tentpole's
    three contracts end-to-end over real sockets:

      * FAIRNESS — two tenants, gold:bronze weights 4:1, preload the fair
        queue while the scheduler is paused, release it, and require the
        served-TOKEN ratio over the contended window (up to gold's last
        token) to land within +/-25% of the weight ratio;
      * CORRECTNESS — every admitted request (deadline_s generous) must
        finish in budget with tokens IDENTICAL to a sequential no-gateway
        baseline on the same swarm/seed (interleaving is invisible);
      * SHEDDING — a strict gateway must refuse excess load with the typed
        Overloaded (concurrency, rate, and queue_full reasons, each with
        retry_after_s > 0), and the doctor must reconstruct the refusals
        from the flight-recorder ring.
    """
    import threading as _threading

    from .runtime.executor import StageExecutor as _SE
    from .runtime.net import (RegistryServer, RemoteRegistry, TcpStageServer,
                              TcpTransport)
    from .runtime.task_pool import StageRuntime
    from .serving import (GatewayServer, GatewaySubmitClient, Overloaded,
                          TenantConfig)
    from .telemetry import doctor as _doc
    from .telemetry import events as _events

    _events.get_recorder().enable()
    sampling = SamplingParams(temperature=0.0)  # greedy: token-identity oracle
    if stage_params is None:
        stage_params = lambda spec: slice_stage_params(cfg, params, spec)  # noqa: E731
    plan = (StagePlan.from_splits(cfg.num_layers, splits) if splits
            else StagePlan.even(cfg.num_layers, 4))
    prompt_ids = list(prompt_ids)

    def _variant(i: int) -> List[int]:
        # Distinct prompt per request (a rotation): identical results would
        # otherwise mask cross-session KV contamination.
        k = i % max(1, len(prompt_ids))
        return prompt_ids[k:] + prompt_ids[:k]

    weights = {"gold": 4.0, "bronze": 1.0}
    total = 2 * requests_per_tenant
    problems: List[str] = []
    result: dict = {"seed": seed, "weights": weights,
                    "requests_per_tenant": requests_per_tenant}
    reg_server = None
    servers: List = []
    transports: List = []
    gateways: List = []
    try:
        reg_server = RegistryServer(host="127.0.0.1", port=0)
        reg_server.start()
        reg = RemoteRegistry(reg_server.address)
        for spec in plan.stages[1:]:
            ex = _SE(cfg, spec, stage_params(spec),
                     peer_id=f"overload-s{spec.index}")
            srv = TcpStageServer(ex, host="127.0.0.1", port=0,
                                 wire_dtype=wire_dtype,
                                 runtime=StageRuntime())
            srv.start()
            rec = make_server_record(ex.peer_id, spec)
            rec.address = srv.address
            reg.register(rec)
            servers.append(srv)
        if burst > 0:
            # Burst mode: gateway sessions decode in N-tick jitted bursts
            # against a FULL-span batched server. Its record advertises
            # stage_index=0 so classic stage routing (which queries stages
            # 1..N-1) never sees it — the sequential baseline below still
            # runs the per-step path, making it the token oracle for the
            # burst-served gateway requests.
            from .models.partition import ROLE_FULL, StageSpec
            from .runtime.batching import (BatchedStageExecutor,
                                           BatchingStageAdapter)

            full = StageSpec(index=0, role=ROLE_FULL, start=0,
                             end=cfg.num_layers)
            blen = max(len(prompt_ids) + max_new_tokens + burst + 8, 64)
            bex = BatchedStageExecutor(cfg, full, stage_params(full),
                                       slots=max(2 * requests_per_tenant, 4),
                                       max_len=blen)
            bad = BatchingStageAdapter(bex, window_s=0.0,
                                       peer_id="overload-burst")
            bad.warmup(burst=burst)
            bsrv = TcpStageServer(bad, host="127.0.0.1", port=0,
                                  wire_dtype=wire_dtype)
            bsrv.start()
            brec = make_server_record(bad.peer_id, full, engine="batched")
            brec.address = bsrv.address
            reg.register(brec)
            servers.append(bsrv)
            result["burst"] = burst
        ex0 = _SE(cfg, plan.stages[0], stage_params(plan.stages[0]),
                  peer_id="overload-client")

        def _client():
            tx = TcpTransport(reg, wire_dtype=wire_dtype)
            transports.append(tx)
            return PipelineClient(cfg, plan, ex0, tx, reg,
                                  request_timeout=request_timeout,
                                  settle_seconds=0.0, seed=seed)

        # --- sequential no-gateway baseline: the token oracle ---
        base_client = _client()
        baseline: Dict[int, List[int]] = {}
        for i in range(total):
            res = base_client.generate(
                _variant(i), max_new_tokens, sampling=sampling,
                session_id=f"ov-base-{i}")
            baseline[i] = list(res.tokens)

        # --- phase A: fairness + correctness under contention ---
        tenants = {name: TenantConfig(name, weight=w, rate=1000.0,
                                      burst=1000.0, max_concurrency=64)
                   for name, w in weights.items()}
        gw = GatewayServer([_client()], tenants, port=0,
                           max_queue_depth=64, max_active=total,
                           start_paused=True, burst=burst)
        gateways.append(gw)
        gw.start()
        submits: Dict[int, dict] = {}

        def _submit(idx: int, tenant: str):
            try:
                submits[idx] = GatewaySubmitClient(gw.address).submit(
                    tenant, _variant(idx), max_new_tokens,
                    deadline_s=60.0, session_id=f"ov-{tenant}-{idx}",
                    timeout=request_timeout + 60.0)
            except Exception as exc:  # noqa: BLE001 — scored below
                submits[idx] = {"error": f"{type(exc).__name__}: {exc}"}

        tenant_order = (["gold"] * requests_per_tenant
                        + ["bronze"] * requests_per_tenant)
        threads = []
        for i, tenant in enumerate(tenant_order):
            th = _threading.Thread(target=_submit, args=(i, tenant),
                                   daemon=True)
            th.start()
            threads.append(th)
        # Preload completely before releasing the scheduler: fairness is
        # only observable when every tenant contends from step one.
        deadline = time.monotonic() + 15.0
        while gw.queue.depth() < total and time.monotonic() < deadline:
            time.sleep(0.01)
        if gw.queue.depth() < total:
            problems.append(f"preload stalled: queued {gw.queue.depth()}"
                            f"/{total} before resume")
        gw.resume()
        for th in threads:
            th.join(timeout=request_timeout + 90.0)

        for i in range(total):
            got = submits.get(i, {"error": "submit thread never reported"})
            if "error" in got:
                problems.append(f"request {i} failed: {got['error']}")
            elif got["tokens"] != baseline[i]:
                problems.append(
                    f"request {i}: gateway tokens {got['tokens']} != "
                    f"sequential baseline {baseline[i]}")
        result["queue_waits"] = sorted(
            round(s["queue_wait_s"], 4) for s in submits.values()
            if "queue_wait_s" in s)

        # Served-token fairness over the contended window: the step log up
        # to gold's LAST token (afterwards bronze runs uncontended).
        log = list(gw.step_log)
        result["step_log"] = "".join(t[0] for t in log)
        # Gold's total comes from the BASELINE (a stop heuristic — eos/
        # repeat — may end a session before max_new_tokens, identically in
        # both runs), so the window cut lands on gold's true last token.
        gold_total = sum(len(baseline[i])
                         for i, t in enumerate(tenant_order) if t == "gold")
        served = 0
        cut = len(log)
        for pos, tenant in enumerate(log):
            if tenant == "gold":
                served += 1
                if served == gold_total:
                    cut = pos + 1
                    break
        window = log[:cut]
        gold_served = sum(1 for t in window if t == "gold")
        bronze_served = len(window) - gold_served
        result["gold_served"] = gold_served
        result["bronze_served"] = bronze_served
        want_ratio = weights["gold"] / weights["bronze"]
        ratio = (gold_served / bronze_served if bronze_served
                 else float("inf"))
        result["ratio"] = ratio
        # +/-25% of the weight ratio, with one quantum of absolute slack:
        # the window necessarily cuts mid-rotation, and at tier-1 token
        # counts a single boundary step shifts the raw ratio past 25%.
        # Under burst serving the service quantum is a whole burst (one
        # pick = up to N tokens, charged to the DRR after the fact), so
        # the boundary slack is one burst, not one token.
        expected_bronze = gold_served / want_ratio
        if (gold_served < gold_total
                or abs(bronze_served - expected_bronze)
                > max(float(burst or 1), 0.25 * expected_bronze)):
            problems.append(
                f"served-token ratio {gold_served}:{bronze_served} "
                f"(= {ratio:.2f}) outside +/-25% of the 4:1 weights "
                f"(expected bronze ~{expected_bronze:.1f} in the window; "
                f"log {result['step_log']!r})")
        gw.stop()

        # --- phase B: typed shedding on a strict gateway ---
        strict = {
            "slow": TenantConfig("slow", rate=1000.0, burst=1000.0,
                                 max_concurrency=1),
            "bursty": TenantConfig("bursty", rate=1e-3, burst=1.0),
            "filler": TenantConfig("filler", rate=1000.0, burst=1000.0),
        }
        gw2 = GatewayServer([_client()], strict, port=0,
                            max_queue_depth=3, max_active=1,
                            start_paused=True)  # never resumed: pure gate
        gateways.append(gw2)
        gw2.start()
        sub2 = GatewaySubmitClient(gw2.address)

        def _bg(tenant):
            th = _threading.Thread(
                target=lambda: _submit_quietly(sub2, tenant), daemon=True)
            th.start()
            return th

        def _submit_quietly(cli, tenant):
            try:
                cli.submit(tenant, _variant(0), 2, timeout=30.0)
            except Exception:  # noqa: BLE001 — shutdown error expected
                pass

        def _expect_shed(tenant, want_reason):
            try:
                sub2.submit(tenant, _variant(0), 2, timeout=10.0)
                problems.append(
                    f"tenant {tenant}: expected Overloaded "
                    f"({want_reason}), request was served")
            except Overloaded as exc:
                result.setdefault("shed_reasons", {})[exc.reason] = round(
                    exc.retry_after_s, 4)
                if exc.reason != want_reason:
                    problems.append(
                        f"tenant {tenant}: shed reason {exc.reason!r}, "
                        f"wanted {want_reason!r}")
                if exc.retry_after_s <= 0:
                    problems.append(
                        f"tenant {tenant}: retry_after_s "
                        f"{exc.retry_after_s} must be > 0")

        def _wait_depth(n):
            deadline = time.monotonic() + 10.0
            while gw2.queue.depth() < n and time.monotonic() < deadline:
                time.sleep(0.01)

        bgs = [_bg("slow")]
        _wait_depth(1)
        _expect_shed("slow", "concurrency")     # inflight 1 >= cap 1
        bgs.append(_bg("bursty"))
        _wait_depth(2)
        _expect_shed("bursty", "rate")          # burst of 1 already spent
        bgs.append(_bg("filler"))
        _wait_depth(3)
        _expect_shed("filler", "queue_full")    # global watermark
        gw2.stop()                              # fails the queued waiters
        for th in bgs:
            th.join(timeout=10.0)

        # --- doctor: refusals must surface as failure chains ---
        chains = _doc.failure_chains(_doc.merge_timeline(
            [{"meta": {"pid": os.getpid()},
              "events": [ev.to_dict()
                         for ev in _events.get_recorder().events()]}]))
        result["chains"] = len(chains)
        shed_chains = [ch for ch in chains
                       if any(ev.get("event") == "request_shed"
                              for ev in ch["events"])]
        result["shed_chains"] = len(shed_chains)
        if not shed_chains:
            problems.append("doctor chains contain no request_shed trigger "
                            "(flight recorder missed the refusals)")
    finally:
        for gw_ in gateways:
            try:
                gw_.stop()
            except Exception:
                pass
        for tx in transports:
            try:
                tx.close()
            except Exception:
                pass
        for srv in servers:
            srv.stop()
        if reg_server is not None:
            reg_server.stop()
    result["problems"] = problems
    result["ok"] = not problems
    return result


def run_chaos(args, cfg: ModelConfig, params) -> int:
    from . import telemetry

    telemetry.enable()
    tokenizer = load_tokenizer(_remote_store(args).cache_dir
                               if _is_remote(args.checkpoint)
                               else args.checkpoint)
    prompt_ids = [i % cfg.vocab_size for i in tokenizer.encode(args.prompt)]
    splits = parse_splits(args.splits) if args.splits else None
    if args.chaos_scenario == "registry_loss":
        if args.chaos_attach:
            _emit("CHAOS SOAK FAIL: --chaos_scenario registry_loss boots "
                  "its own swarm (it must own the seeds it kills); drop "
                  "--chaos_attach")
            return 1
        res = registry_loss_soak(
            cfg, params, prompt_ids=prompt_ids,
            max_new_tokens=args.max_new_tokens, seed=args.seed,
            splits=splits, wire_dtype=args.wire_dtype,
            request_timeout=args.request_timeout,
            peers_cache=args.peers_cache)
        _emit(f"\n=== Registry-loss soak (seed={res['seed']}) ===")
        _emit(f"tokens (clean)     : {res.get('tokens_clean')}")
        _emit(f"tokens (chaos)     : {res.get('tokens_chaos')}")
        _emit(f"tokens (bootstrap) : {res.get('tokens_bootstrap')}")
        _emit(f"bootstrap records  : {res.get('bootstrap_records')}")
        _emit(f"failure chains     : {res.get('chains', 0)}")
        if res["ok"]:
            _emit("REGISTRY-LOSS SOAK PASS: identical tokens across total "
                  "seed loss; fresh client bootstrapped via gossip; doctor "
                  "reconstructed the outage")
            return 0
        for p in res["problems"]:
            _emit(f"REGISTRY-LOSS SOAK FAIL: {p}")
        return 1
    if args.chaos_scenario == "relay_break":
        if args.chaos_attach:
            _emit("RELAY-BREAK SOAK FAIL: --chaos_scenario relay_break "
                  "boots its own swarm (it must own the volunteer it "
                  "kills); drop --chaos_attach")
            return 1
        res = relay_break_soak(
            cfg, params, prompt_ids=prompt_ids,
            max_new_tokens=args.max_new_tokens, seed=args.seed,
            splits=splits, wire_dtype=args.wire_dtype,
            request_timeout=args.request_timeout)
        _emit(f"\n=== Relay-break soak (seed={res['seed']}) ===")
        _emit(f"tokens (clean, via relay) : {res.get('tokens_clean')}")
        _emit(f"tokens (chaos)            : {res.get('tokens_chaos')}")
        _emit(f"relay after failover      : {res.get('relay_after')}")
        _emit(f"client recoveries         : {res.get('recoveries')}")
        _emit(f"failure chains            : {res.get('chains', 0)}")
        if res["ok"]:
            _emit("RELAY-BREAK SOAK PASS: identical tokens across the "
                  "relay kill; breaker blamed the volunteer; doctor "
                  "reconstructed relay lost -> failover -> replay")
            return 0
        for p in res["problems"]:
            _emit(f"RELAY-BREAK SOAK FAIL: {p}")
        return 1
    if args.chaos_scenario == "overload":
        if args.chaos_attach:
            _emit("OVERLOAD SOAK FAIL: --chaos_scenario overload boots its "
                  "own swarm and gateway in-process; drop --chaos_attach")
            return 1
        res = overload_soak(
            cfg, params, prompt_ids=prompt_ids,
            max_new_tokens=args.max_new_tokens, seed=args.seed,
            splits=splits, wire_dtype=args.wire_dtype,
            request_timeout=args.request_timeout,
            burst=getattr(args, "burst", 0))
        _emit(f"\n=== Overload soak (seed={res['seed']}, weights 4:1"
              + (f", burst={res['burst']}" if res.get("burst") else "")
              + ") ===")
        _emit(f"served tokens (gold:bronze) : {res.get('gold_served')}:"
              f"{res.get('bronze_served')} "
              f"(ratio {res.get('ratio', 0.0):.2f})")
        _emit(f"queue waits (s)             : {res.get('queue_waits')}")
        _emit(f"shed refusals               : {res.get('shed_reasons')}")
        _emit(f"shed chains / total         : {res.get('shed_chains', 0)}"
              f" / {res.get('chains', 0)}")
        if res["ok"]:
            _emit("OVERLOAD SOAK PASS: weighted fairness held, admitted "
                  "requests matched the sequential baseline in budget, and "
                  "excess load was shed with typed retry hints")
            return 0
        for p in res["problems"]:
            _emit(f"OVERLOAD SOAK FAIL: {p}")
        return 1
    res = chaos_soak(
        cfg, params, prompt_ids=prompt_ids,
        max_new_tokens=args.max_new_tokens, seed=args.seed, splits=splits,
        wire_dtype=args.wire_dtype, request_timeout=args.request_timeout,
        registry_addr=(args.registry_addr if args.chaos_attach else None))
    _emit(f"\n=== Chaos soak (seed={res['seed']}, "
          f"{'attached' if res['attach'] else 'in-process'} swarm) ===")
    _emit(f"fault kinds fired : {', '.join(res.get('kinds_fired', []))}")
    _emit(f"firing counts     : {res.get('firings', {})}")
    _emit(f"tokens (clean)    : {res.get('tokens_clean')}")
    _emit(f"tokens (chaos)    : {res.get('tokens_chaos')}")
    _emit(f"deadline probe    : {res.get('deadline_probe', 'skipped')}")
    _emit(f"failure chains    : {res.get('fault_chains', 0)} with faults "
          f"/ {res.get('chains', 0)} total")
    if res["ok"]:
        _emit("CHAOS SOAK PASS: identical tokens under faults; doctor "
              "reconstructed every injection")
        return 0
    for p in res["problems"]:
        _emit(f"CHAOS SOAK FAIL: {p}")
    return 1


# ---------------------------------------------------------------------------
# Argparse (reference flag table, src/main.py:776-819)
# ---------------------------------------------------------------------------

def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.main",
        description="TPU-native distributed LLM inference (mini-Petals parity)",
    )
    p.add_argument("--mode",
                   choices=["local", "fused", "oracle",
                            "registry", "serve", "client", "status",
                            "metrics", "doctor", "top", "dcn-check",
                            "chaos", "gateway", "submit"],
                   default="local")
    p.add_argument("--telemetry", action="store_true",
                   help="enable the process-global metrics registry, "
                        "request tracer, and flight recorder (telemetry "
                        "package). Servers then answer the 'metrics' and "
                        "'dump-events' verbs; clients fold their series "
                        "into the same registry. Default off: every "
                        "instrument site is a cheap boolean check.")
    p.add_argument("--events-dump", dest="events_dump", default=None,
                   metavar="PATH",
                   help="enable the flight recorder and write its event "
                        "ring to PATH as JSONL on fatal exceptions, "
                        "SIGTERM/SIGINT, and normal exit — the file "
                        "--mode doctor ingests. Implies the recorder even "
                        "without --telemetry.")
    p.add_argument("--dumps", default=None, metavar="PATHS",
                   help="doctor mode: comma-separated event-dump files "
                        "(--events-dump output) to diagnose; omit to "
                        "scrape LIVE servers' event rings via the "
                        "registry instead")
    p.add_argument("--critical_path", action="store_true",
                   help="doctor mode: also assemble the client/server "
                        "spans embedded in the dumps into per-request "
                        "span trees and report the critical path, with "
                        "wall time attributed to network / queue / "
                        "compute / replay / client (the parts sum to each "
                        "request's wall time). Needs dumps from runs with "
                        "--telemetry.")
    p.add_argument("--once", action="store_true",
                   help="top mode: render one snapshot and exit instead "
                        "of refreshing (scripting / tests)")
    p.add_argument("--top_interval", type=float, default=2.0,
                   help="top mode: seconds between refreshes")
    p.add_argument("--log-json", dest="log_json", action="store_true",
                   help="emit every log record as one JSON object per "
                        "line (machine-ingestable) instead of the "
                        "structured text format")
    p.add_argument("--model", default="gpt2",
                   help="architecture preset (gpt2[-xl], llama-3-8b, ...)")
    p.add_argument("--num_layers", type=int, default=None, metavar="N",
                   help="random-init presets: cut the depth to the first N "
                        "layers, every width as published (how a model "
                        "that does not fit one chip is sized for it)")
    p.add_argument("--model_name", default=None,
                   help="swarm-scoping model id for the registry (the model "
                        "name embedded in every reference DHT key, "
                        "src/dht_utils.py:20-31); defaults to --model. Two "
                        "models can share one registry without cross-routing "
                        "when every server/client passes its own name.")
    p.add_argument("--checkpoint", default=None,
                   help="local HF checkpoint dir, or an http(s):// weight "
                        "store (an HF checkpoint layout behind any static "
                        "file server) — servers then fetch ONLY the shards "
                        "covering their span; omit for random init")
    p.add_argument("--weight_cache_dir", default=None,
                   help="remote --checkpoint: local shard cache directory")
    p.add_argument("--weight_cache_bytes", type=int, default=None,
                   help="remote --checkpoint: LRU-evict cached shards "
                        "beyond this many bytes")
    p.add_argument("--splits", default=None,
                   help='stage boundaries, e.g. "10,20,30" (reference format)')
    p.add_argument("--stage", type=int, default=0,
                   help="serve mode: which pipeline stage this server runs "
                        "(1..N; stage 0 lives in the client). Other modes "
                        "run all stages in-process and ignore it.")
    p.add_argument("--dtype", choices=["float32", "bfloat16", "float16"],
                   default="float32")
    p.add_argument("--lora", default=None, metavar="PATH",
                   help="serve a fine-tune: fold the adapters saved by "
                        "DistributedFineTuner.export_lora (.npz) into the "
                        "weights at load (merged before --quant; every "
                        "mode that loads weights honors it)")
    p.add_argument("--prefix_cache_mb", type=int, default=0,
                   help="enable the content-addressed prompt-prefix KV "
                        "store with this byte budget (MiB) on session "
                        "executors: repeat prefills reuse cached KV for "
                        "shared prompt prefixes at 64-token granularity "
                        "(runtime.prefix_cache). 0 = off")
    p.add_argument("--quant", choices=["none", "int8", "nf4"], default="none",
                   help="weight-only block quantization (reference V9 "
                        "surface: int8 per-channel, nf4 4-bit NormalFloat "
                        "at 4.25 bits/param) — stage servers AND the "
                        "fused/ring/oracle engines. int8 is the "
                        "throughput mode, nf4 the capacity mode "
                        "(docs/PERFORMANCE.md)")
    p.add_argument("--prompt", default="Hello, my name is")
    p.add_argument("--max_new_tokens", type=int, default=32)
    p.add_argument("--temperature", type=float, default=0.7)
    p.add_argument("--top_p", type=float, default=0.9)
    p.add_argument("--top_k", type=int, default=50)
    p.add_argument("--repetition_penalty", type=float, default=1.5)
    p.add_argument("--speculative_k", type=int, default=0,
                   help="speculative decoding: draft up to K tokens per "
                        "round trip (n-gram prompt lookup), verified by the "
                        "final stage (greedy: token-identical; temperature>0: "
                        "distribution-preserving rejection sampling)")
    p.add_argument("--request_timeout", type=float, default=60.0)
    # Host offload (reference --use_cpu_offload / --keep_layers_on_gpu,
    # src/main.py flag table): span weights in host RAM, streamed per layer.
    p.add_argument("--use_cpu_offload", action="store_true")
    p.add_argument("--keep_layers_on_gpu", type=int, default=0)
    # Load balancing (reference LB flag group)
    p.add_argument("--use_load_balancing", action="store_true")
    p.add_argument("--route_by_latency", action="store_true",
                   help="module routing minimizes estimated end-to-end step "
                        "latency (server-published next-hop RTTs + client "
                        "pings) instead of greedy max-coverage")
    p.add_argument("--num_blocks", type=int, default=None)
    p.add_argument("--total_blocks", type=int, default=None)
    p.add_argument("--num_servers", type=int, default=3)
    p.add_argument("--balance_quality", type=float, default=0.75)
    p.add_argument("--mean_balance_check_period", type=float, default=120.0)
    p.add_argument("--network_bandwidth_mbps", type=float, default=None)
    # TPU-native knobs
    p.add_argument("--num_stages", type=int, default=None,
                   help="fused mode: pipeline depth (default: #devices, <=4)")
    p.add_argument("--ring_sessions", type=int, default=0,
                   help="fused mode: serve this many CONCURRENT sessions "
                        "('||'-separated --prompt) on the multi-session "
                        "ring-decode schedule — every stage advances a "
                        "different session each tick, so steady-state "
                        "decode has no pipeline bubble (needs >= "
                        "num_stages sessions)")
    p.add_argument("--tp", type=int, default=1,
                   help="fused/serve mode: tensor parallelism per stage "
                        "(serve: the stage step is sharded over a local "
                        "('tp',) mesh of N chips)")
    # Continuous batching in the serving path (the reference's serving
    # runtime is batch-first, petals/server/server.py:557-671)
    p.add_argument("--batched", action="store_true",
                   help="serve mode: continuous slot-batched engine — "
                        "concurrent plain sessions coalesce into ONE "
                        "compiled decode step per round (speculative "
                        "draft steps coalesce too, as multi-token verify "
                        "rounds); advertised as engine=batched so clients "
                        "route plain and speculative sessions here and "
                        "beam/replay to per-session replicas")
    p.add_argument("--slots", type=int, default=8,
                   help="serve --batched: max concurrent sessions")
    p.add_argument("--max_session_len", type=int, default=2048,
                   help="serve --batched: per-slot KV capacity (tokens)")
    p.add_argument("--burst", type=int, default=0, metavar="N",
                   help="burst decode: one jitted dispatch runs N decode "
                        "ticks with on-device sampling on a FULL-span "
                        "--batched server (tokens bit-identical to per-"
                        "step decode). client mode: decode in N-token "
                        "bursts; serve --batched: pre-compile the N-tick "
                        "burst program at warmup; chaos overload: drive "
                        "the gateway at burst granularity. 0 disables")
    # Sequence-parallel long-context serving (SURVEY §5.7 exceed-the-
    # reference axis: the reference's KV must fit one machine)
    p.add_argument("--sp", type=int, default=1,
                   help="serve mode: sequence parallelism — the session's "
                        "prefix KV shards along the sequence axis of a "
                        "local ('sp',) mesh of N chips, so prompts beyond "
                        "one device's KV budget serve end-to-end; "
                        "advertised as engine=sp with --max_context")
    p.add_argument("--sp_zigzag", action="store_true",
                   help="serve --sp: zigzag sequence layout — each device "
                        "holds one early + one late half-chunk, flattening "
                        "causal-prefill work across the mesh (critical "
                        "path ~halves at sp=8); token-identical output")
    p.add_argument("--max_context", type=int, default=None,
                   help="serve --sp: advertised admission limit "
                        "(prompt+generated tokens); default 8192 per chip")
    p.add_argument("--long_context_threshold", type=int, default=None,
                   help="client mode: prompts at/above this length route "
                        "to engine=sp peers")
    # Network roles (reference --dht_port/--rpc_port/--public_ip surface,
    # src/main.py:776-819, re-homed onto the TCP registry/data plane)
    p.add_argument("--registry_addr", default="127.0.0.1:31330",
                   help="serve/client: control-plane address (the "
                        "--dht_initial_peers role). Comma-separate a "
                        "primary + standbys for registry HA: writes "
                        "broadcast to all, reads fail over, and a total "
                        "outage serves cached records under TTL grace")
    p.add_argument("--registry_port", type=int, default=31330,
                   help="registry mode: listen port (the --dht_port role)")
    p.add_argument("--peers_cache", default=None, metavar="PATH",
                   help="serve/client: persist the last-known live server "
                        "addresses to PATH (JSON) after every successful "
                        "registry read, and load them at startup as "
                        "any-peer bootstrap candidates — a fresh process "
                        "can then join the swarm through a live stage "
                        "server's gossip mirror even when EVERY "
                        "--registry_addr seed is down")
    p.add_argument("--rpc_port", type=int, default=0,
                   help="serve mode: data-plane port (0 = ephemeral)")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--public_ip", default=None,
                   help="serve mode: advertise this IP instead of --host")
    p.add_argument("--relay_capacity", type=int, default=0,
                   help="serve mode: volunteer to relay traffic for up to N "
                        "NAT'd peers that fail the dial-back reachability "
                        "vote (0 = do not volunteer). Attach requests "
                        "beyond N are shed so load spreads across "
                        "volunteers")
    p.add_argument("--peer_id", default=None)
    p.add_argument("--ttl", type=float, default=45.0,
                   help="registry mode: record TTL seconds (reference 45s); "
                        "servers learn it from heartbeat responses")
    p.add_argument("--allow_fault_injection", action="store_true",
                   help="accept the `fault` admin verb: remote clients may "
                        "install/clear/inspect a deterministic FaultPlan on "
                        "this process (registry and serve roles). NEVER set "
                        "on a production swarm — it lets any client that "
                        "can dial the port inject faults")
    p.add_argument("--chaos_scenario",
                   choices=["faults", "registry_loss", "overload",
                            "relay_break"],
                   default="faults",
                   help="chaos mode: 'faults' runs the seeded fault-"
                        "injection soak; 'registry_loss' kills the primary "
                        "AND every standby registry mid-generation and "
                        "requires identical tokens plus a gossip-served "
                        "fresh-client bootstrap (in-process swarm only); "
                        "'overload' floods a two-tenant gateway and "
                        "requires weighted-fair service, baseline-identical "
                        "tokens, and typed shedding (in-process only)")
    p.add_argument("--chaos_attach", action="store_true",
                   help="chaos mode: instead of booting an in-process "
                        "swarm, attach to the externally launched one at "
                        "--registry_addr (its roles must all run with "
                        "--allow_fault_injection --telemetry; see "
                        "scripts/chaos_swarm.py)")
    # Multi-tenant serving gateway (--mode gateway / submit, docs/SERVING.md)
    p.add_argument("--tenants", default=None, metavar="JSON_OR_PATH",
                   help="gateway mode: tenant table as inline JSON (starts "
                        "with '{') or a path to a JSON file. Per tenant: "
                        "weight (fair share), rate + burst (admission "
                        "token bucket), max_concurrency; top-level "
                        "max_queue_depth / max_active set the global "
                        "watermark and the interleaving width. Omitted: "
                        "one 'default' tenant with library defaults.")
    p.add_argument("--gateway_addr", default="127.0.0.1:31340",
                   help="submit mode: the gateway's host:port "
                        "(--mode gateway prints it at startup)")
    p.add_argument("--gateway_clients", type=int, default=1,
                   help="gateway mode: number of PipelineClients the "
                        "gateway round-robins new sessions across")
    p.add_argument("--tenant", default="default",
                   help="submit mode: tenant to submit as")
    p.add_argument("--submit_requests", type=int, default=1,
                   help="submit mode: how many requests to fire "
                        "sequentially")
    p.add_argument("--queue_high_water", type=int, default=None,
                   help="serve mode: task-pool depth that fires the "
                        "`queue_pressure level=high` flight-recorder event "
                        "(stage falling behind; default 16)")
    p.add_argument("--queue_low_water", type=int, default=None,
                   help="serve mode: task-pool depth at which pressure "
                        "relaxes back to `level=normal` (default 8; must "
                        "be <= --queue_high_water)")
    p.add_argument("--deadline_s", type=float, default=None,
                   help="end-to-end wall-clock budget for the WHOLE "
                        "generation: each hop ships the seconds remaining, "
                        "servers refuse already-expired work, and "
                        "exhaustion raises DeadlineExceeded instead of "
                        "burning retries (pipeline-client modes only)")
    p.add_argument("--wire_dtype", choices=["bf16", "f32"], default="bf16",
                   help="activation compression on the wire")
    # Multi-host DCN cluster (runtime.dcn; SURVEY.md §7.1 layer 7)
    p.add_argument("--dcn_coordinator", default="127.0.0.1:31400",
                   help="dcn-check: process 0's coordinator host:port")
    p.add_argument("--num_processes", type=int, default=1,
                   help="dcn-check: cluster size")
    p.add_argument("--process_id", type=int, default=0,
                   help="dcn-check: this process's rank")
    p.add_argument("--dcn_cpu_devices", type=int, default=None,
                   help="dcn-check: force N virtual CPU devices per process "
                        "(testing without TPU hosts)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--profile", default=None, metavar="DIR",
                   help="write a jax.profiler trace of the run to DIR "
                        "(view with TensorBoard / Perfetto)")
    p.add_argument("--profile_phases", action="store_true",
                   help="enable the host-side phase profiler: per-phase "
                        "latency histograms (server_phase_seconds) over "
                        "the serving hot path and the device "
                        "bubble-fraction gauge "
                        "(server_device_bubble_ratio). Adds a fence per "
                        "collected burst; default off so the hot path "
                        "pays only a boolean check.")
    p.add_argument("-v", "--verbose", action="store_true")
    return p


def _print_swarm_health(infos: dict, total_servers: int = 0) -> None:
    """Swarm-wide aggregation of the per-server request-log rings (the
    ``info`` verb's ``recent_requests`` tail): top error peers, slowest
    hops, cache pressure — one operator surface instead of grepping N
    server logs (exceeds the reference's announcer/log story,
    ``petals/server/handler.py:549-592``, ``server.py:721-726``)."""
    if not infos:
        return
    unreachable = max(0, total_servers - len(infos))
    _emit(f"swarm health ({len(infos)}/{total_servers or len(infos)} "
          "server rings probed):")
    if unreachable:
        # An unreachable server is the LIKELIEST one erroring — never let
        # a clean aggregate of the reachable rings read as all-clear.
        _emit(f"  WARNING: {unreachable} server(s) unreachable for info — "
              "their rings are NOT included below")
    errs = []     # (count, peer, last error record)
    slows = []    # (max_dur_ms, peer, verb)
    for peer, inf in infos.items():
        recs = inf.get("recent_requests") or []
        bad = [r for r in recs if r.get("outcome") != "ok"]
        if bad:
            errs.append((len(bad), peer, bad[-1]))
        durs = [(r.get("dur_ms"), r.get("verb")) for r in recs
                if r.get("dur_ms") is not None]
        if durs:
            d, v = max(durs)
            slows.append((d, peer, v))
    if errs:
        errs.sort(reverse=True)
        for n, peer, last in errs[:3]:
            _emit(f"  errors: {peer} x{n} (last: {last.get('verb')} "
                  f"{last.get('outcome')} {last.get('detail', '')})")
    else:
        _emit(f"  errors: none in the {len(infos)} probed ring(s)")
    if slows:
        slows.sort(reverse=True)
        _emit("  slowest hops: " + ", ".join(
            f"{peer} {d:.1f}ms ({v})" for d, peer, v in slows[:3]))
    pfx = [(peer, inf["prefix_cache"]) for peer, inf in infos.items()
           if isinstance(inf.get("prefix_cache"), dict)]
    if pfx:
        hits = sum(s.get("hits", 0) for _, s in pfx)
        misses = sum(s.get("misses", 0) for _, s in pfx)
        total = hits + misses
        rate = f"{hits / total:.0%}" if total else "n/a"
        _emit(f"  prefix cache: {len(pfx)} server(s), hit rate {rate} "
              f"({hits}/{total}), "
              f"{sum(s.get('grains_reused', 0) for _, s in pfx)} grains "
              f"reused, "
              f"{sum(s.get('bytes', 0) for _, s in pfx) >> 20} MiB resident")
    pressure = [(inf.get("cache_tokens_left"), peer)
                for peer, inf in infos.items()
                if inf.get("cache_tokens_left") is not None]
    if pressure:
        lo, lo_peer = min(pressure)
        _emit(f"  cache pressure: min {lo} tokens left ({lo_peer}); "
              f"total {sum(p for p, _ in pressure)} across "
              f"{len(pressure)} server(s)")


def _status_telemetry_line(tele) -> str:
    """One-line per-server telemetry aggregate for --mode status (empty
    when the peer runs telemetry off or has served no steps yet)."""
    if not tele or not tele.get("steps_total"):
        return ""
    parts = [f"steps={tele['steps_total']}"]
    if tele.get("steps_per_s") is not None:
        parts.append(f"{tele['steps_per_s']:.1f}/s")
    if tele.get("step_p50_ms") is not None:
        parts.append(f"p50={tele['step_p50_ms']:.1f}ms")
    if tele.get("step_p95_ms") is not None:
        parts.append(f"p95={tele['step_p95_ms']:.1f}ms")
    if tele.get("cache_hit_rate") is not None:
        parts.append(f"cache_hit={tele['cache_hit_rate'] * 100:.0f}%")
    return "\n" + " " * 26 + "telemetry: " + " ".join(parts)


def run_metrics(args) -> int:
    """Prometheus-text scrape of every live server's process registry (the
    ``metrics`` verb), concatenated with per-peer comment banners — pipe to
    a file per peer or straight into promtool. Exit 1 when no server could
    be scraped."""
    from .runtime.net import RemoteRegistry, TcpTransport
    from .scheduling.registry import PlacementRegistry as _PR

    registry = RemoteRegistry(args.registry_addr, peers_cache=args.peers_cache)
    records = registry.live_servers(model=args.model_name)
    if not records:
        _emit("no live servers")
        return 1
    snap = _PR()
    for r in records:
        snap.register(r)
    tx = TcpTransport(snap, wire_dtype=args.wire_dtype)
    scraped, failed = 0, []
    try:
        for r in sorted(records, key=lambda r: (r.start_block, r.peer_id)):
            if not r.address:
                continue
            try:
                text = tx.metrics_text(r.peer_id, timeout=3.0)
            except Exception as exc:
                _emit(f"# peer {r.peer_id}: scrape failed "
                      f"({type(exc).__name__})")
                failed.append((r.peer_id, r.address,
                               f"{type(exc).__name__}: {exc}"))
                continue
            _emit(f"# ==== peer {r.peer_id} [{r.start_block},"
                  f"{r.end_block}) ====")
            if text.strip():
                _emit(text, end="" if text.endswith("\n") else "\n")
            else:
                _emit("# (telemetry disabled on this peer — "
                      "start it with --telemetry)")
            scraped += 1
    finally:
        tx.close()
    if failed:
        # A registered-but-unreachable server is an operational problem the
        # scrape must not paper over: name each one and exit non-zero so
        # cron/CI notices even when other peers answered.
        for peer, addr, err in failed:
            _emit(f"error: server {peer} at {addr} unreachable: {err}",
                  file=sys.stderr)
        return 1
    return 0 if scraped else 1


def run_status(args) -> int:
    """Swarm inspector: live records, per-block coverage summary (the
    reference's ``get_remote_module_infos`` coverage log,
    ``src/dht_utils.py:227-240``), and a per-server `info` probe."""
    from .runtime.net import RemoteRegistry, TcpTransport
    from .scheduling.registry import PlacementRegistry as _PR

    registry = RemoteRegistry(args.registry_addr, peers_cache=args.peers_cache)
    # ONE registry snapshot: records, coverage, and info-probe addressing all
    # derive from it, so the report describes a single swarm state (and the
    # registry sees one list RPC, not N+2).
    # Status shows the WHOLE swarm by default; an explicit --model_name scopes
    # the report (and its health verdict) to that model's records.
    records = registry.live_servers(model=args.model_name)
    # Control-plane degradation banner: the report below may describe a
    # mirror- or cache-served swarm view — an operator must never mistake
    # that for "seeds healthy".
    st = registry.stale_info()
    if st["seeds_down"]:
        line = (f"registry seeds DOWN for {st['seeds_down_s']:.1f}s "
                f"(every --registry_addr address unreachable)")
        if st["stale"]:
            line += (f"; serving STALE cached records for "
                     f"{st['stale_s']:.1f}s (TTL grace)")
        else:
            line += "; records served via a stage server's gossip mirror"
        _emit(line)
    if not records:
        _emit("no live servers")
        return 1
    total = args.total_blocks or max(r.end_block for r in records)
    if not args.total_blocks:
        _emit("warning: total_blocks inferred from LIVE records — dead "
              "tail-stage servers shrink it; pass --total_blocks for a "
              "reliable health check")
    _emit(f"{len(records)} live server(s); total_blocks={total}")
    snap = _PR()
    for r in records:
        snap.register(r)
    tx = TcpTransport(snap, wire_dtype=args.wire_dtype)
    infos = {}
    unreachable = []
    for r in sorted(records, key=lambda r: (r.start_block, r.peer_id)):
        extra = ""
        if r.address:
            try:
                inf = tx.info(r.peer_id, timeout=3.0)
                infos[r.peer_id] = inf
                extra = (f" served={inf.get('requests_served')}"
                         f" rtt_probe_ok")
                extra += _status_telemetry_line(inf.get("telemetry"))
            except Exception as exc:
                extra = f" info_probe_failed({type(exc).__name__})"
                unreachable.append(
                    (r.peer_id, r.address, f"{type(exc).__name__}: {exc}"))
        rtts = ("" if not r.next_server_rtts else
                " rtts=" + ",".join(f"{p}:{v * 1e3:.1f}ms"
                                    for p, v in r.next_server_rtts.items()))
        mdl = f" model={r.model}" if r.model else ""
        # Engine capability tag (session/batched/sp): the first thing an
        # operator needs to know when a request class is being refused.
        eng = (f" eng={r.engine}" if getattr(r, "engine", None)
               and r.engine != "session" else "")
        _emit(f"  {r.peer_id:24s} [{r.start_block:3d},{r.end_block:3d}) "
              f"{r.state:8s} thr={r.throughput:8.2f} "
              f"cache_left={r.cache_tokens_left}"
              f"{' FINAL' if r.final_stage else ''}{eng}{mdl}{rtts}{extra}")
    # Coverage summary: contiguous runs of equal server-count, the exact
    # shape of the reference's log (src/dht_utils.py:227-240). The
    # CLIENT-LOCAL prefix (stage 0's span, never served remotely — the
    # lb_min_block floor, src/main.py:338-339) is taken from --splits when
    # given; it is NOT inferred from live records, because "lowest live
    # span" would silently relabel a dead low-block server as client-local.
    base = parse_splits(args.splits)[0] if args.splits else 0
    cov = [sum(1 for r in records if r.start_block <= b < r.end_block)
           for b in range(total)]
    runs, start = [], base
    for b in range(base + 1, total + 1):
        if b == total or cov[b] != cov[start]:
            runs.append((start, b, cov[start]))
            start = b
    prefix = f"[0,{base}) client-local; " if base else ""
    _emit("coverage: " + prefix + ", ".join(
        f"[{a},{b})x{n}" + ("  <-- UNCOVERED" if n == 0 else "")
        for a, b, n in runs))
    _print_swarm_health(infos, total_servers=len(records))
    tx.close()
    healthy = all(n > 0 for _, _, n in runs)
    if not any(r.final_stage for r in records):
        # Catches the dead-tail case even when total_blocks was inferred:
        # a swarm with no live final stage cannot finish any request.
        _emit("no live FINAL-stage server  <-- UNHEALTHY")
        healthy = False
    if unreachable:
        # A registered server that won't answer its own info verb is not a
        # healthy swarm, whatever the coverage map says.
        for peer, addr, err in unreachable:
            _emit(f"error: server {peer} at {addr} unreachable: {err}",
                  file=sys.stderr)
        healthy = False
    return 0 if healthy else 2


def run_doctor(args) -> int:
    """Post-mortem / live diagnosis: merge per-process flight-recorder
    streams onto one timeline and report failure chains (timeout →
    failover → replay → rebalance), per-session replay cost, and metric
    anomalies. Sources: ``--dumps f1.jsonl,f2.jsonl`` (files written by
    ``--events-dump`` / crash hooks), else a LIVE scrape of every
    registered server's event ring over the ``dump-events`` verb."""
    from .telemetry import doctor as _doc

    if args.dumps:
        paths = [p.strip() for p in args.dumps.split(",") if p.strip()]
        missing = [p for p in paths if not os.path.exists(p)]
        if missing:
            _emit("error: dump file(s) not found: " + ", ".join(missing),
                  file=sys.stderr)
            return 1
        streams = _doc.load_dumps(paths)
        _emit(_doc.diagnose_streams(streams), end="")
        if args.critical_path:
            _emit(_doc.render_critical_path(
                _doc.critical_path_reports(streams)), end="")
        return 0

    from .runtime.net import RemoteRegistry, TcpTransport
    from .scheduling.registry import PlacementRegistry as _PR

    registry = RemoteRegistry(args.registry_addr, peers_cache=args.peers_cache)
    records = registry.live_servers(model=args.model_name)
    if not records:
        _emit("no live servers and no --dumps given")
        return 1
    snap = _PR()
    for r in records:
        snap.register(r)
    tx = TcpTransport(snap, wire_dtype=args.wire_dtype)
    try:
        streams = _doc.scrape_events(
            tx, [r.peer_id for r in sorted(
                records, key=lambda r: (r.start_block, r.peer_id))
                if r.address])
    finally:
        tx.close()
    if not streams:
        _emit("no event streams scraped (are servers running with "
              "--telemetry or --events-dump?)")
        return 1
    _emit(_doc.diagnose_streams(streams), end="")
    if args.critical_path:
        _emit(_doc.render_critical_path(
            _doc.critical_path_reports(streams)), end="")
    return 0


def _render_top(rows: list, source: str, gateway: Optional[dict]) -> str:
    """One ``--mode top`` frame: a whole-swarm stats table plus (when a
    gateway answered) per-tenant SLO burn rates."""
    lines = [f"swarm top — {len(rows)} server(s) (source: {source})"]
    hdr = (f"{'PEER':<14} {'SPAN':<10} {'RELAY':<10} {'TOK/S':>8} "
           f"{'QUEUE':>6} {'BRK':>4} {'CACHE%':>7} {'BUBBLE%':>8} "
           f"{'DROP%':>6} {'HOT%':>5} {'UP(S)':>8}")
    lines.append(hdr)
    lines.append("-" * len(hdr))

    def _f(stats, key, scale=1.0, fmt="{:.1f}", dash="-"):
        v = (stats or {}).get(key)
        if v is None:
            return dash
        try:
            return fmt.format(float(v) * scale)
        except (TypeError, ValueError):
            return dash

    for row in sorted(rows, key=lambda r: (r.get("start_block", 0) or 0,
                                           str(r.get("peer_id")))):
        stats = row.get("stats")
        span = f"[{row.get('start_block', '?')},{row.get('end_block', '?')})"
        # NAT'd servers show WHO forwards for them; direct ones a dash.
        relay = str(row.get("relay_via") or "-")
        lines.append(
            f"{str(row.get('peer_id', '?')):<14} {span:<10} "
            f"{relay:<10} "
            f"{_f(stats, 'tok_s'):>8} "
            f"{_f(stats, 'queue_depth', fmt='{:.0f}'):>6} "
            f"{_f(stats, 'breaker_open', fmt='{:.0f}'):>4} "
            f"{_f(stats, 'cache_hit_ratio', 100.0):>7} "
            f"{_f(stats, 'bubble_frac', 100.0):>8} "
            f"{_f(stats, 'moe_drop_frac', 100.0):>6} "
            f"{_f(stats, 'moe_hot_share', 100.0):>5} "
            f"{_f(stats, 'uptime_s', fmt='{:.0f}'):>8}")
    if gateway is not None:
        lines.append("")
        lines.append(f"gateway: queue={gateway.get('queue_depth', '?')} "
                     f"active={gateway.get('active_sessions', '?')} "
                     f"started={gateway.get('sessions_started', '?')}")
        slo = gateway.get("slo") or {}
        for tenant in sorted(slo):
            parts = ", ".join(
                f"{obj} burn={rate:.2f}"
                for obj, rate in sorted(slo[tenant].items()))
            lines.append(f"  slo {tenant}: {parts or 'no objectives'}")
    return "\n".join(lines) + "\n"


def _collect_top(args) -> Tuple[list, str, Optional[dict]]:
    """Gather one top-frame's data: per-server record+stats rows, the
    source description, and the gateway info dict (None if unreachable).

    Stats come gossip-first: dial any live server's ``swarm-stats`` verb
    and read the piggybacked digests off its mirror — that works with
    every seed registry dead (records then come from the mirror or the
    peers cache). Rows whose gossip record carries no digest fall back to
    a direct per-peer scrape."""
    from .runtime.net import RemoteRegistry, TcpTransport
    from .scheduling.registry import PlacementRegistry as _PR

    registry = RemoteRegistry(args.registry_addr, peers_cache=args.peers_cache)
    records = registry.live_servers(model=args.model_name)
    rows: dict = {}
    for r in records:
        d = {"peer_id": r.peer_id, "address": r.address,
             "start_block": r.start_block, "end_block": r.end_block,
             "relay_via": getattr(r, "relay_via", None),
             "stats": None}
        rows[r.peer_id] = d
    snap = _PR()
    for r in records:
        snap.register(r)
    tx = TcpTransport(snap, wire_dtype=args.wire_dtype)
    source = "registry (no stats publisher reachable)"
    try:
        # Any ONE live server's mirror carries the whole swarm's digests.
        for r in records:
            if not r.address:
                continue
            try:
                view = tx.swarm_stats(r.peer_id, timeout=3.0)
            except Exception:  # noqa: BLE001 — try the next peer
                continue
            source = f"gossip via {view.get('peer_id', r.peer_id)}"
            for rec in view.get("records") or ():
                pid = rec.get("peer_id")
                if not pid:
                    continue
                row = rows.setdefault(pid, {"peer_id": pid, "stats": None})
                row.setdefault("address", rec.get("address"))
                row["start_block"] = rec.get("start_block",
                                             row.get("start_block"))
                row["end_block"] = rec.get("end_block", row.get("end_block"))
                row["relay_via"] = rec.get("relay_via",
                                           row.get("relay_via"))
                if isinstance(rec.get("stats"), dict):
                    row["stats"] = rec["stats"]
            # The answering peer's own digest is fresher than its
            # (heartbeat-cadence) gossip record.
            if r.peer_id in rows and isinstance(view.get("self"), dict):
                rows[r.peer_id]["stats"] = view["self"]
            break
        # Direct-scrape fallback for rows gossip had no digest for.
        for row in rows.values():
            if row["stats"] is None and row.get("address"):
                try:
                    row["stats"] = tx.swarm_stats(
                        row["peer_id"], timeout=3.0).get("self")
                except Exception:  # noqa: BLE001 — leave the dashes
                    pass
    finally:
        tx.close()

    gateway = None
    if args.gateway_addr:
        from .serving.gateway import GatewaySubmitClient
        try:
            gateway = GatewaySubmitClient(args.gateway_addr,
                                          connect_timeout=1.0).info(
                                              timeout=2.0)
        except Exception:  # noqa: BLE001 — no gateway running is normal
            gateway = None
    return list(rows.values()), source, gateway


def run_top(args) -> int:
    """Live whole-swarm dashboard (``--mode top``): per-server tok/s,
    queue depth, breaker state, cache hit rate, device bubble fraction —
    fed by the stats digests servers piggyback on their gossip records, so
    it keeps working with every seed registry dead. ``--once`` renders a
    single frame (tests/scripts); otherwise refreshes every
    ``--top_interval`` seconds until interrupted."""
    while True:
        rows, source, gateway = _collect_top(args)
        if not rows:
            _emit("no live servers (and no usable peers cache)")
            return 1
        _emit(_render_top(rows, source, gateway), end="", flush=True)
        if args.once:
            return 0
        try:
            time.sleep(max(0.1, args.top_interval))
        except KeyboardInterrupt:
            return 0


def run_dcn_check(args) -> int:
    """Bring up this process's slot in a multi-host cluster and run the
    cross-host collective smoke tests (runtime.dcn). Run once per host at
    deployment time — the DCN analogue of the reference's reachability
    validation (petals/server/reachability.py)."""
    from .runtime import dcn

    dcn.initialize(dcn.DcnConfig(
        coordinator_address=args.dcn_coordinator,
        num_processes=args.num_processes,
        process_id=args.process_id,
        cpu_devices_per_process=args.dcn_cpu_devices,
    ))
    import jax as _jax

    got, want = dcn.sanity_check()
    ring_ok = dcn.ring_shift()
    ok = (got == want) and ring_ok
    _emit(f"DCN_CHECK process={_jax.process_index()}/{_jax.process_count()} "
          f"devices={_jax.local_device_count()}/{_jax.device_count()} "
          f"psum={got}/{want} ring={'ok' if ring_ok else 'FAIL'} "
          f"{'OK' if ok else 'FAIL'}", flush=True)
    dcn.shutdown()
    return 0 if ok else 1


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    from .telemetry import setup_logging

    setup_logging(json_mode=args.log_json,
                  level=logging.DEBUG if args.verbose else logging.INFO)
    if args.telemetry:
        # Flip the process-global registry + tracer + flight recorder
        # BEFORE any component fetches metric handles; register_all()
        # inside makes even zero-valued families visible to the first
        # scrape.
        from . import telemetry

        telemetry.enable()
    if args.profile_phases:
        # After the telemetry flip so the phase histograms land in the
        # (now-enabled) process registry; works standalone too — the
        # profiler keeps its own per-phase stats and bubble accounting.
        from .telemetry.profiling import enable_phase_profiling

        enable_phase_profiling()
    if args.events_dump:
        # --events-dump alone still records: flip just the recorder (the
        # metrics registry stays off unless --telemetry asked for it) and
        # arm the crash hooks so a fatal exception or SIGTERM/SIGINT
        # leaves the dump behind for --mode doctor.
        import atexit

        from .telemetry import events as _events

        _events.get_recorder().enable()
        _events.emit("process_start", mode=args.mode, pid=os.getpid())
        reg = None
        if args.telemetry:
            from . import telemetry as _t

            reg = _t.get_registry()
        _events.install_crash_hooks(args.events_dump, registry=reg)
        # Normal exits dump too — doctor runs are not crash-only.
        atexit.register(
            lambda: _events.get_recorder().dump(args.events_dump,
                                                registry=reg))
    if args.mode == "registry":
        return run_registry(args, None, None)  # no model needed
    if args.mode == "dcn-check":
        return run_dcn_check(args)  # no model needed
    if args.mode == "status":
        return run_status(args)  # no model needed
    if args.mode == "metrics":
        return run_metrics(args)  # no model needed
    if args.mode == "doctor":
        return run_doctor(args)  # no model needed
    if args.mode == "top":
        return run_top(args)  # no model needed
    if args.mode == "submit":
        return run_submit(args)  # no weights: tokenizer + preset cfg only
    compile_cache_dir()
    if args.mode in ("client", "gateway"):
        # Host-side until a classic route needs stage 0 (_lazy_stage0).
        cfg, params = load_config(args), None
    else:
        _refuse_unheld_state(args, load_config(args))
        start_backend()
        cfg, params = load_model(args)
    run = {"local": run_local, "fused": run_fused, "oracle": run_oracle,
           "serve": run_serve, "client": run_client,
           "chaos": run_chaos, "gateway": run_gateway}[args.mode]
    if args.profile:
        # SURVEY.md §5.1: the reference only had wall-clock prints; we keep
        # its metric names AND produce a real device trace.
        with jax.profiler.trace(args.profile):
            return run(args, cfg, params)
    return run(args, cfg, params)


if __name__ == "__main__":
    sys.exit(main())
