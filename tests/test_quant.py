"""int8 weight-only serving (V9 parity) + quantization-aware block sizing."""

import jax
import jax.numpy as jnp
import numpy as np

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
    init_kv_cache,
    init_params,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (
    parse_splits,
    ROLE_FULL,
    slice_stage_params,
    StagePlan,
    StagePlan as SP,
    StageSpec,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.quant import (
    _quantize_leaf_nf4,
    block_bytes,
    choose_num_blocks,
    dequant_tree,
    is_quantized,
    NF4Tensor,
    params_per_block,
    quantize_layers,
    quantize_params,
    QuantizedTensor,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
    SamplingParams,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.parallel.tensor_parallel import (
    stage_param_specs,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.client import (
    make_server_record,
    PipelineClient,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.fused_decode import (
    make_fused_decode,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.messages import (
    StageRequest,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.transport import (
    LocalTransport,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.scheduling.registry import (
    PlacementRegistry,
)

from engines import (
    engine as BatchedStageExecutor,
    full_forward,
    oracle_generate,
    stage_executor as StageExecutor,
    tiny_cfg,
)

from test_tensor_parallel import tiny_cfg as tp_tiny_cfg


def test_roundtrip_error_bounded():
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    qp = quantize_params(params)
    assert is_quantized(qp["layers"]) and not is_quantized(params["layers"])
    deq = dequant_tree(qp["layers"])
    for orig, got in zip(jax.tree.leaves(params["layers"]),
                         jax.tree.leaves(deq)):
        scale = float(jnp.max(jnp.abs(orig)))
        np.testing.assert_allclose(np.asarray(got), np.asarray(orig),
                                   atol=scale / 100)


def test_quantized_pipeline_matches_dequantized_oracle():
    """Serving with int8 weights must be token-identical to serving with
    those SAME weights explicitly dequantized — the quantization error is in
    the weights, never in the execution path."""
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("2,4,6"))
    qfull = quantize_params({"layers": params["layers"]})
    deq_params = dict(params, layers=dequant_tree(qfull["layers"]))

    import random as _random

    transport = LocalTransport()
    registry = PlacementRegistry(rng=_random.Random(0))
    for spec in plan.stages[1:]:
        sp = quantize_params(slice_stage_params(cfg, params, spec))
        peer = f"q-s{spec.index}"
        transport.add_peer(peer, StageExecutor(cfg, spec, sp, peer_id=peer))
        registry.register(make_server_record(peer, spec))
    stage0 = StageExecutor(
        cfg, plan.stages[0],
        quantize_params(slice_stage_params(cfg, params, plan.stages[0])),
        peer_id="client-local")
    client = PipelineClient(cfg, plan, stage0, transport, registry,
                            settle_seconds=0.0)
    res = client.generate([5, 9, 23, 7, 81], max_new_tokens=6,
                          sampling=SamplingParams(temperature=0.0))
    ref = oracle_generate(cfg, deq_params, [5, 9, 23, 7, 81], 6,
                          SamplingParams(temperature=0.0))
    assert res.tokens == ref


def test_moe_router_stays_full_precision():
    cfg = tp_tiny_cfg("mixtral")
    params = init_params(jax.random.PRNGKey(0), cfg)
    qp = quantize_params(params)
    router = qp["layers"]["mlp"]["router"]
    assert not isinstance(router, QuantizedTensor)
    assert isinstance(qp["layers"]["mlp"]["wg"], QuantizedTensor)
    assert isinstance(qp["layers"]["attn"]["wq"], QuantizedTensor)
    # quantized mixtral forward runs end-to-end

    kc, vc = init_kv_cache(cfg, cfg.num_layers, 1, 16)
    ids = jnp.asarray([[1, 2, 3]], jnp.int32)
    logits, _, _ = full_forward(cfg, qp, ids, kc, vc, jnp.int32(0))
    assert logits.shape == (1, 3, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_quantized_offload_combo():
    """QuantizedTensor leaves survive host pinning + per-layer streaming."""
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("2,6"))
    spec = plan.stages[1]
    sp = quantize_params(slice_stage_params(cfg, params, spec))
    res = StageExecutor(cfg, spec, sp, peer_id="q")
    off = StageExecutor(cfg, spec, sp, peer_id="qo", offload=True,
                        keep_layers_resident=1)
    hid = np.random.default_rng(0).standard_normal(
        (1, 6, cfg.hidden_size)).astype(np.float32)
    a = res.forward(StageRequest(session_id="s", hidden=jnp.asarray(hid),
                                 seq_len=6, cur_len=0, is_prefill=True,
                                 max_length=16))
    b = off.forward(StageRequest(session_id="s", hidden=jnp.asarray(hid),
                                 seq_len=6, cur_len=0, is_prefill=True,
                                 max_length=16))
    np.testing.assert_allclose(np.asarray(b.hidden), np.asarray(a.hidden),
                               atol=1e-5, rtol=1e-5)


def test_block_sizing_and_auto_capacity():
    cfg = tiny_cfg()
    full = block_bytes(cfg, dtype_bytes=2)
    i8 = block_bytes(cfg, quant="int8")
    nf4 = block_bytes(cfg, quant="nf4")
    assert nf4 < i8 < full
    budget = full * 4
    assert choose_num_blocks(cfg, budget, dtype_bytes=2) <= 4
    assert choose_num_blocks(cfg, budget, quant="int8") >= \
        choose_num_blocks(cfg, budget, dtype_bytes=2)
    # clamps: never below 1, never above the model depth
    assert choose_num_blocks(cfg, 1) == 1
    assert choose_num_blocks(cfg, 1 << 40) == cfg.num_layers


def test_tp_over_quantized_params_rejected():
    """TP sharding tables are name-keyed; quantized leaves would silently
    replicate and double-count through the psum — must fail loudly."""
    import pytest
    from jax.sharding import Mesh

    cfg = tp_tiny_cfg("llama")
    params = quantize_params(init_params(jax.random.PRNGKey(0), cfg))
    with pytest.raises(NotImplementedError):
        stage_param_specs(cfg, params)


def test_block_bytes_rejects_unknown_mode():
    import pytest

    cfg = tiny_cfg()
    with pytest.raises(ValueError):
        block_bytes(cfg, quant="int4")


# ---------------------------------------------------------------------------
# NF4 (4-bit NormalFloat) execution — petals/server/block_utils.py:46 tier
# ---------------------------------------------------------------------------

def test_nf4_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((128, 96)).astype(np.float32))
    q = _quantize_leaf_nf4(w)
    assert isinstance(q, NF4Tensor)
    assert q.shape == (128, 96)
    assert q.packed.shape == (64, 96) and q.packed.dtype == jnp.uint8
    assert q.scales.shape == (2, 96) and q.scales.dtype == jnp.bfloat16
    deq = np.asarray(q.dequant())
    # Worst-case NF4 snap error is half the widest level gap (~0.14) times
    # the block absmax; for N(0,1) blocks of 64 the absmax is ~2.5-3.5.
    err = np.abs(deq - np.asarray(w))
    assert float(err.max()) < 0.5
    # Mean snap error ≈ half the mid-range level gap (~0.045) x the block
    # absmax (~3 for 64 N(0,1) draws) x E[density-weighted factor] ≈ 0.07.
    assert float(err.mean()) < 0.1


def test_nf4_padding_for_odd_input_dim():
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.standard_normal((80, 16)).astype(np.float32))  # 80 % 64 != 0
    q = _quantize_leaf_nf4(w)
    assert q.shape == (80, 16)
    deq = np.asarray(q.dequant())
    assert deq.shape == (80, 16)
    assert float(np.abs(deq - np.asarray(w)).max()) < 0.5


def test_nf4_stacked_layers_slice_and_scan():
    """NF4 leaves are pytree nodes: stacked [L, in, out] weights slice per
    layer and run under lax.scan like plain arrays."""
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(3), cfg)
    ql = quantize_layers(params["layers"], "nf4")
    assert isinstance(ql["attn"]["wq"], NF4Tensor)
    # Sub-span slicing flattens THROUGH the pytree (executor._get_subspan
    # does jax.tree.map(lambda x: x[a:b]) with no is_leaf): the packed codes
    # and scales slice on their stacked layer axis.
    sub = jax.tree.map(lambda x: x[2:4], ql)
    assert isinstance(sub["attn"]["wq"], NF4Tensor)
    assert sub["attn"]["wq"].shape[0] == 2
    deq = dequant_tree(sub)
    want = jax.tree.map(lambda x: x[2:4], params["layers"])
    for a, b in zip(jax.tree.leaves(deq), jax.tree.leaves(want)):
        assert np.asarray(a).shape == np.asarray(b).shape


def test_nf4_pipeline_matches_dequantized_oracle():
    """Serving with NF4 weights is token-identical to serving the SAME
    weights explicitly dequantized (error lives in the weights, not the
    execution path) — the int8 contract at the 4-bit tier."""
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("2,4,6"))
    qfull = quantize_params({"layers": params["layers"]}, "nf4")
    deq_params = dict(params, layers=dequant_tree(qfull["layers"]))

    import random as _random

    transport = LocalTransport()
    registry = PlacementRegistry(rng=_random.Random(0))
    for spec in plan.stages[1:]:
        sp = quantize_params(slice_stage_params(cfg, params, spec), "nf4")
        peer = f"nf4-s{spec.index}"
        transport.add_peer(peer, StageExecutor(cfg, spec, sp, peer_id=peer))
        registry.register(make_server_record(peer, spec))
    stage0 = StageExecutor(
        cfg, plan.stages[0],
        quantize_params(slice_stage_params(cfg, params, plan.stages[0]),
                        "nf4"),
        peer_id="client-local")
    client = PipelineClient(cfg, plan, stage0, transport, registry,
                            settle_seconds=0.0)
    res = client.generate([5, 9, 23, 7, 81], max_new_tokens=6,
                          sampling=SamplingParams(temperature=0.0))
    ref = oracle_generate(cfg, deq_params, [5, 9, 23, 7, 81], 6,
                          SamplingParams(temperature=0.0))
    assert res.tokens == ref


def test_nf4_sizing_matches_4_25_bits():
    cfg = tiny_cfg()
    assert block_bytes(cfg, quant="nf4") == int(params_per_block(cfg) * 4.25 / 8)
    # auto-capacity fits more nf4 blocks than int8 than bf16
    budget = block_bytes(cfg, dtype_bytes=2) * 3
    assert (choose_num_blocks(cfg, budget, quant="nf4")
            >= choose_num_blocks(cfg, budget, quant="int8")
            >= choose_num_blocks(cfg, budget, dtype_bytes=2))


def test_quantized_fused_decode_matches_dequantized_fused():
    """The fused multi-step decode engine (``--mode oracle``) must
    produce the same greedy tokens whether QuantizedTensor leaves
    dequantize inside the scan or the dequantized weights are materialized
    up front — for BOTH int8 and nf4."""
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab_size, 6).astype(np.int32)

    for mode in ("int8", "nf4"):
        qparams = quantize_params(params, mode)
        dparams = dequant_tree(qparams)   # materialized reference

        def run(p):
            fn = make_fused_decode(cfg, 8, 1, exact_head=True)
            kc, vc = init_kv_cache(cfg, cfg.num_layers, 1, 64)
            logits, kc, vc = full_forward(cfg, p, jnp.asarray(prompt[None]),
                                          kc, vc, jnp.int32(0))
            tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
            toks, _, _ = fn(p, tok, kc, vc, jnp.int32(len(prompt)),
                            jnp.int32(8))
            return [int(tok[0])] + np.asarray(toks[:, 0]).tolist()

        assert run(qparams) == run(dparams), f"{mode} fused decode diverged"


def test_quantized_batched_serving_matches_dequantized():
    """The batched serving engine (the --mode serve --batched path that a
    --quant server runs) must match its dequantized twin token-for-token."""

    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(3), cfg)
    qparams = quantize_params(params, "int8")
    dparams = dequant_tree(qparams)
    spec = StageSpec(index=0, role=ROLE_FULL, start=0, end=cfg.num_layers)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, 5).astype(np.int32)
               for _ in range(2)]

    def serve(p):
        ex = BatchedStageExecutor(cfg, spec, p, slots=2, max_len=32)
        toks = {}
        for s, prompt in enumerate(prompts):
            h = ex.prefill(f"s{s}", prompt[None, :])
            toks[f"s{s}"] = [int(jnp.argmax(ex.logits(h[:, -1:])[0, -1]))]
        for _ in range(5):
            out = ex.decode_batch({
                sid: jnp.asarray([[t[-1]]], jnp.int32)
                for sid, t in toks.items()})
            for sid in toks:
                toks[sid].append(int(jnp.argmax(out[sid][0, -1])))
        return toks

    assert serve(qparams) == serve(dparams)
