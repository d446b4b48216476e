"""Host-offload layer streaming (reference --use_cpu_offload /
--keep_layers_on_gpu, src/llama_partition.py:188-293) — offloaded execution
must be bit-identical to resident execution.
"""

import jax
import jax.numpy as jnp
import numpy as np

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
    init_params,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (
    StagePlan,
    parse_splits,
    slice_stage_params,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
    SamplingParams,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.client import (
    PipelineClient,
    make_server_record,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.executor import (
    StageExecutor,
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

from engines import oracle_generate, tiny_cfg


def _pair(cfg, params, role="mid", keep=0):
    """(resident executor, offloaded executor) for the same span."""
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("2,6"))
    spec = {"first": plan.stages[0], "mid": plan.stages[1],
            "last": plan.stages[2]}[role]
    sp = slice_stage_params(cfg, params, spec)
    res = StageExecutor(cfg, spec, sp, peer_id="res")
    off = StageExecutor(cfg, spec, sp, peer_id="off", offload=True,
                        keep_layers_resident=keep)
    return res, off


def _run(ex, hid, seq_len, cur_len, prefill, ids=False):
    return ex.forward(StageRequest(
        session_id="s", hidden=jnp.asarray(hid), seq_len=seq_len,
        cur_len=cur_len, is_prefill=prefill, max_length=64))


def test_offloaded_segment_matches_resident():
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    hid = rng.standard_normal((1, 10, cfg.hidden_size)).astype(np.float32)
    step = rng.standard_normal((1, 1, cfg.hidden_size)).astype(np.float32)

    for keep in (0, 2, 99):  # 99 -> fully resident via the offload path
        res, off = _pair(cfg, params, "mid", keep=keep)
        r1 = _run(res, hid, 10, 0, True)
        o1 = _run(off, hid, 10, 0, True)
        np.testing.assert_allclose(np.asarray(o1.hidden),
                                   np.asarray(r1.hidden),
                                   atol=1e-5, rtol=1e-5)
        r2 = _run(res, step, 1, 10, False)
        o2 = _run(off, step, 1, 10, False)
        np.testing.assert_allclose(np.asarray(o2.hidden),
                                   np.asarray(r2.hidden),
                                   atol=1e-5, rtol=1e-5)


def test_offloaded_first_and_last_roles():
    """Embedding entry (stage0) and head exit (last) work offloaded."""
    cfg = tiny_cfg("gpt2")  # learned positions: rope=None path too
    params = init_params(jax.random.PRNGKey(1), cfg)
    ids = np.asarray([[5, 9, 23, 7]], np.int32)

    res, off = _pair(cfg, params, "first", keep=1)
    r = _run(res, ids, 4, 0, True)
    o = _run(off, ids, 4, 0, True)
    np.testing.assert_allclose(np.asarray(o.hidden), np.asarray(r.hidden),
                               atol=1e-5, rtol=1e-5)

    rng = np.random.default_rng(1)
    hid = rng.standard_normal((1, 4, cfg.hidden_size)).astype(np.float32)
    res, off = _pair(cfg, params, "last")
    r = _run(res, hid, 4, 0, True)
    o = _run(off, hid, 4, 0, True)
    assert o.token_id == r.token_id


def test_offloaded_pipeline_matches_oracle():
    """Full pipeline where every server streams its layers from host."""
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("2,4,6"))
    transport = LocalTransport()
    import random as _random

    registry = PlacementRegistry(rng=_random.Random(0))
    for spec in plan.stages[1:]:
        peer = f"off-s{spec.index}"
        ex = StageExecutor(cfg, spec, slice_stage_params(cfg, params, spec),
                           peer_id=peer, offload=True, keep_layers_resident=1)
        transport.add_peer(peer, ex)
        registry.register(make_server_record(peer, spec))
    stage0 = StageExecutor(cfg, plan.stages[0],
                           slice_stage_params(cfg, params, plan.stages[0]),
                           peer_id="client-local")
    client = PipelineClient(cfg, plan, stage0, transport, registry,
                            settle_seconds=0.0)
    res = client.generate([5, 9, 23, 7, 81], max_new_tokens=6,
                          sampling=SamplingParams(temperature=0.0))
    ref = oracle_generate(cfg, params, [5, 9, 23, 7, 81], 6,
                          SamplingParams(temperature=0.0))
    assert res.tokens == ref
