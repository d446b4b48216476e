"""The two families nearest the one whose layers are of two kinds lower to
the PARENT's text: a latent family of ONE kind under the learned selection
(glm-5's: it shares `_latent_proj`, `_attend_latent`, `_decoder_layer`,
`_layer_groups` / `_scan_groups`, `_decode_span`, the prefill continuation
and the burst program with the new family) and a family whose older rows
are summaries (evabyte's: the other ring). ``GOLDEN`` holds the SHA-256 of
each program's StableHLO on the commit BEFORE the alternating stack lowered
anything (PR 57's tree; made with this file's own `programs` on a checkout
of it, under the suite's ``highest`` matmul precision), as
``tests/test_engine_lowering.py`` holds those of gpt2, qwen2 and the looped
stack. A PR that means to change a shared program makes the table again the
same way and says so."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
    config,
    quant,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (
    StagePlan,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.transformer import (
    init_params,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.batching import (
    BatchedStageExecutor,
)

GLM = dict(vocab_size=97, hidden_size=64, num_layers=3, num_heads=4,
           intermediate_size=96, max_position_embeddings=512, rope_theta=1e6,
           q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=12,
           qk_rope_head_dim=4, v_head_dim=16, index_n_heads=2,
           index_head_dim=8, index_topk=16, n_routed_experts=8,
           num_experts_per_tok=2, moe_intermediate_size=32, first_k_dense=1,
           experts_held=(0, 4))
FAMILIES = {
    "glm5": (lambda: config.glm5_config(**GLM), "bfloat16", None),
    "glm5-f32": (lambda: config.glm5_config(**GLM), "float32", None),
    "glm5-int8": (lambda: config.glm5_config(**GLM), "bfloat16", "int8"),
    "evabyte": (lambda: config.evabyte_config(
        vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=4, intermediate_size=96, max_position_embeddings=4096,
        rope_theta=100000.0, window_size=32, chunk_size=8,
        num_pred_heads=2), "bfloat16", None),
}
PROGRAMS = ("burst_tick", "decode_step", "prefill")
GOLDEN = {
    ("evabyte", "burst_tick"): "11a339bb903d36b6",
    ("evabyte", "decode_step"): "b2edb7e9da34d7a3",
    ("evabyte", "prefill"): "b000cec44aa59138",
    ("glm5", "burst_tick"): "bece19c2f4585522",
    ("glm5", "decode_step"): "cf65a39d4f0ac468",
    ("glm5", "prefill"): "ca6863e78364afb9",
    ("glm5-f32", "burst_tick"): "a6a2a503387485b9",
    ("glm5-f32", "decode_step"): "c0a13a3659507877",
    ("glm5-f32", "prefill"): "d7bd82c05211b9f3",
    ("glm5-int8", "burst_tick"): "321dfad6a27ab158",
    ("glm5-int8", "decode_step"): "339d8db6745f1bc4",
    ("glm5-int8", "prefill"): "d46e261600a72170",
}


def programs(family: str) -> dict:
    """name -> the lowered program of a tiny engine of ``family``;
    ``prefill`` is the family's own (a latent family's chunk continuation,
    a windowed family's window program)."""
    make, dtype, q = FAMILIES[family]
    cfg, dtype = make(), jnp.dtype(dtype)
    params = init_params(jax.random.PRNGKey(0), cfg, dtype)
    if q:
        params = quant.quantize_params(params, q)
    spec = StagePlan.even(cfg.num_layers, 1).stages[0]
    eng = BatchedStageExecutor(cfg, spec, params, slots=4, max_len=64,
                               dtype=dtype)
    ids = jnp.zeros((1, 8), jnp.int32)
    eng.prefill("a", np.zeros((1, 5), np.int32))
    _, args = eng._burst_prep(
        {"a": {"token": 1, "seed": 0, "budget": 4, "eos": None,
               "generated": (1,), "temperature": 0.8, "top_p": 0.95,
               "top_k": 0, "repetition_penalty": 1.0}}, 4)
    if cfg.eva_window:
        prefill = eng._build_prefill_window().lower(
            eng.params, ids, jnp.int32(1), eng.k, eng.v, jnp.int32(1))
    else:
        prefill = eng._build_prefill_suffix().lower(
            eng.params, ids, jnp.int32(1), eng.k, eng.v, jnp.int32(4),
            jnp.int32(5))
    return {
        "burst_tick": eng._get_burst_jit(4).lower(
            eng.params, *args, eng.k, eng.v),
        "decode_step": eng._build_decode(1).lower(
            eng.params, jnp.zeros((4, 1), jnp.int32),
            jnp.asarray(eng.lengths), jnp.ones((4,), bool), eng.k, eng.v),
        "prefill": prefill,
    }


def digest(lowered) -> str:
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]


@pytest.fixture(scope="module")
def lowered():
    cache = {}

    def of(family):
        if family not in cache:
            cache[family] = programs(family)
        return cache[family]

    return of


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_neighbouring_family_lowers_to_the_parent_s_text(lowered, family,
                                                           program):
    assert digest(lowered(family)[program]) == GOLDEN[family, program]


if __name__ == "__main__":      # make the table: run on the commit to pin
    jax.config.update("jax_default_matmul_precision", "highest")
    for fam in sorted(FAMILIES):
        for prog, low in programs(fam).items():
            print(f'    ("{fam}", "{prog}"): "{digest(low)}",')
