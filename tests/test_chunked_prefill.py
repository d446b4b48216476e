"""Chunked prefill (petals backend.py:129-143) + session rewind
(start_from_position, petals handler.py:163-168) on the TPU-native executor.
"""

import jax.numpy as jnp
import numpy as np

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (
    StagePlan,
    parse_splits,
    slice_stage_params,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
    SamplingParams,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.executor import (
    StageExecutionError,
)
from engines import stage_executor as StageExecutor
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.messages import (
    StageRequest,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.net import (
    _header_to_request,
    _request_header,
)

import jax

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
    init_params,
)

from engines import build_cluster, oracle_generate, tiny_cfg


def _seg_executor(cfg, params, max_chunk_bytes):
    """Middle-stage executor (hidden in, hidden out)."""
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("2,6"))
    spec = plan.stages[1]  # layers [2, 6)
    return StageExecutor(cfg, spec, slice_stage_params(cfg, params, spec),
                         peer_id="seg", max_chunk_bytes=max_chunk_bytes)


def test_chunked_prefill_matches_unchunked():
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    hid = np.random.default_rng(0).standard_normal(
        (1, 50, cfg.hidden_size)).astype(np.float32)

    big = _seg_executor(cfg, params, 256 << 20)
    r_big = big.forward(StageRequest(
        session_id="s", hidden=jnp.asarray(hid), seq_len=50, cur_len=0,
        is_prefill=True, max_length=64))
    # tiny budget -> per-token estimate forces the 16-token floor: 4 chunks
    small = _seg_executor(cfg, params, 1)
    assert small._max_chunk_tokens(1) == 16
    r_small = small.forward(StageRequest(
        session_id="s", hidden=jnp.asarray(hid), seq_len=50, cur_len=0,
        is_prefill=True, max_length=64))
    np.testing.assert_allclose(np.asarray(r_small.hidden),
                               np.asarray(r_big.hidden), atol=1e-5, rtol=1e-5)
    assert small.session_len("s") == big.session_len("s") == 50

    # decode after a chunked prefill continues the same session correctly
    step = np.random.default_rng(1).standard_normal(
        (1, 1, cfg.hidden_size)).astype(np.float32)
    d_big = big.forward(StageRequest(
        session_id="s", hidden=jnp.asarray(step), seq_len=1, cur_len=50,
        is_prefill=False, max_length=64))
    d_small = small.forward(StageRequest(
        session_id="s", hidden=jnp.asarray(step), seq_len=1, cur_len=50,
        is_prefill=False, max_length=64))
    np.testing.assert_allclose(np.asarray(d_small.hidden),
                               np.asarray(d_big.hidden), atol=1e-5, rtol=1e-5)


def test_chunked_pipeline_generation_matches_oracle():
    """Whole pipeline with chunk-bounded servers produces oracle tokens."""
    cfg = tiny_cfg()
    client, transport, _, params, _ = build_cluster(cfg, splits="2,4,6")
    for p in transport.peers():
        transport.executor(p).max_chunk_bytes = 1  # force 16-token chunks
    client.stage0.max_chunk_bytes = 1
    prompt = list(range(3, 45))  # 42-token prompt -> 3 chunks per stage
    res = client.generate(prompt, max_new_tokens=6,
                          sampling=SamplingParams(temperature=0.0),
                          max_length=64)
    ref = oracle_generate(cfg, params, prompt, 6,
                          SamplingParams(temperature=0.0))
    assert res.tokens == ref


def test_rewind_replays_from_position():
    """Rewind to an earlier position must reproduce a fresh session's path."""
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    prefix = rng.standard_normal((1, 8, cfg.hidden_size)).astype(np.float32)
    step_a = rng.standard_normal((1, 1, cfg.hidden_size)).astype(np.float32)
    step_b = rng.standard_normal((1, 1, cfg.hidden_size)).astype(np.float32)

    ex = _seg_executor(cfg, params, 256 << 20)
    ex.forward(StageRequest(session_id="s", hidden=jnp.asarray(prefix),
                            seq_len=8, cur_len=0, is_prefill=True,
                            max_length=32))
    out_a1 = ex.forward(StageRequest(session_id="s", hidden=jnp.asarray(step_a),
                                     seq_len=1, cur_len=8, is_prefill=False,
                                     max_length=32))
    assert ex.session_len("s") == 9
    # rewind to 8 and send step_b instead — as if regenerating the 9th token
    out_b = ex.forward(StageRequest(session_id="s", hidden=jnp.asarray(step_b),
                                    seq_len=1, cur_len=8, is_prefill=False,
                                    max_length=32, start_from_position=8))
    assert ex.session_len("s") == 9

    # fresh session taking step_b directly must match exactly
    ex2 = _seg_executor(cfg, params, 256 << 20)
    ex2.forward(StageRequest(session_id="t", hidden=jnp.asarray(prefix),
                             seq_len=8, cur_len=0, is_prefill=True,
                             max_length=32))
    out_b_ref = ex2.forward(StageRequest(session_id="t",
                                         hidden=jnp.asarray(step_b),
                                         seq_len=1, cur_len=8,
                                         is_prefill=False, max_length=32))
    np.testing.assert_allclose(np.asarray(out_b.hidden),
                               np.asarray(out_b_ref.hidden),
                               atol=1e-6, rtol=1e-6)
    # and the rewound-path token differs from the original continuation
    assert not np.allclose(np.asarray(out_b.hidden), np.asarray(out_a1.hidden))


def test_rewind_out_of_range_rejected():
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    ex = _seg_executor(cfg, params, 256 << 20)
    hid = np.zeros((1, 4, cfg.hidden_size), np.float32)
    ex.forward(StageRequest(session_id="s", hidden=jnp.asarray(hid),
                            seq_len=4, cur_len=0, is_prefill=True,
                            max_length=16))
    step = np.zeros((1, 1, cfg.hidden_size), np.float32)
    try:
        ex.forward(StageRequest(session_id="s", hidden=jnp.asarray(step),
                                seq_len=1, cur_len=4, is_prefill=False,
                                max_length=16, start_from_position=9))
        raised = False
    except StageExecutionError:
        raised = True
    assert raised


def test_start_from_position_rides_the_wire():
    req = StageRequest(session_id="s", hidden=jnp.zeros((1, 1, 4)), seq_len=1,
                       cur_len=5, is_prefill=False, max_length=16,
                       start_from_position=3)
    hdr = _request_header(req, {"shape": [1, 1, 4], "dtype": "f32"})
    back = _header_to_request(hdr, np.zeros((1, 1, 4), np.float32).tobytes())
    assert back.start_from_position == 3
    req2 = StageRequest(session_id="s", hidden=jnp.zeros((1, 1, 4)), seq_len=1,
                        cur_len=5, is_prefill=False, max_length=16)
    hdr2 = _request_header(req2, {"shape": [1, 1, 4], "dtype": "f32"})
    back2 = _header_to_request(hdr2, np.zeros((1, 1, 4), np.float32).tobytes())
    assert back2.start_from_position is None
