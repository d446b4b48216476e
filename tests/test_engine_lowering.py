"""The programs of the engines the benchmark already runs lower to the
PARENT's text: a family that brought a second kind of per-session state
(``tests/test_eva_attention.py``) shares `_decoder_layer`, `_residual`,
`_decode_span`, the burst program and the prefill programs with them, and
must change none of their operations. ``GOLDEN`` holds, for tiny gpt2,
qwen2 (bf16 and int8) and looped engines on the CPU, the SHA-256 of each
program's StableHLO as the commit BEFORE that family lowered it (made with
this file's own `programs` on a checkout of it, under the suite's
``highest`` matmul precision). A PR that means to change a shared program
makes the table again the same way and says so. PR 49 did, for the four
``burst_tick`` rows alone: the burst program takes its per-slot arguments
packed in two arrays (the rider in one) and returns what the host reads as
one, so its text changed by design; the ticks inside it, and the twelve
``decode_step`` / ``prefill`` / ``prefill_suffix`` rows, were PR 48's.
PR 54 did, for the ``burst_tick`` and ``decode_step`` rows of ``gpt2`` and
``looped`` (one query row a KV head, not on a TPU): their read of a cache
layer is the loop over blocks that ``qwen2``'s always was, where it was a
``switch`` over static prefixes; the eight ``prefill`` rows and the four
``qwen2`` decode rows are the parent's."""

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

QWEN = dict(vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
            num_kv_heads=2, intermediate_size=96, max_position_embeddings=256)
FAMILIES = {
    "gpt2": (lambda: config.gpt2_config(
        vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
        max_position_embeddings=64), "float32", None),
    "qwen2": (lambda: config.qwen2_config(**QWEN), "bfloat16", None),
    "qwen2-int8": (lambda: config.qwen2_config(**QWEN), "bfloat16", "int8"),
    "looped": (lambda: config.ouro_config(
        vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=4, intermediate_size=96, max_position_embeddings=256,
        head_dim=16, loop_steps=3), "bfloat16", None),
}
PROGRAMS = ("burst_tick", "decode_step", "prefill", "prefill_suffix")
GOLDEN = {
    ("gpt2", "burst_tick"): "d5059bc12d93cd1a",
    ("gpt2", "decode_step"): "739ed5718b82a7f0",
    ("gpt2", "prefill"): "82d8bf1dfeec7242",
    ("gpt2", "prefill_suffix"): "f5e5953cab9b54d3",
    ("looped", "burst_tick"): "bf751dc2e91d1f93",
    ("looped", "decode_step"): "9b3de6138859e5ee",
    ("looped", "prefill"): "464d7c5d40d72a37",
    ("looped", "prefill_suffix"): "a276ce113fbc69c5",
    ("qwen2", "burst_tick"): "b1d78407810684b8",
    ("qwen2", "decode_step"): "6598710f757744f6",
    ("qwen2", "prefill"): "60df83f486901e1d",
    ("qwen2", "prefill_suffix"): "4c8114aad5a7be82",
    ("qwen2-int8", "burst_tick"): "6ff14e1f8c9d9dd4",
    ("qwen2-int8", "decode_step"): "4d289ac3720e8763",
    ("qwen2-int8", "prefill"): "58941d2a5d9b91c7",
    ("qwen2-int8", "prefill_suffix"): "f97a3184e87b391f",
}


def programs(family: str) -> dict:
    """name -> the lowered program of a tiny engine of ``family``."""
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
    extra = [eng._rider_args(None, 4)] if eng.rider_rows else []
    return {
        "burst_tick": eng._get_burst_jit(4).lower(
            eng.params, *args, eng.k, eng.v, *extra),
        "decode_step": eng._build_decode(1).lower(
            eng.params, jnp.zeros((4, 1), jnp.int32),
            jnp.asarray(eng.lengths), jnp.ones((4,), bool), eng.k, eng.v),
        "prefill": eng._build_prefill().lower(
            eng.params, ids, jnp.int32(1), eng.k, eng.v, jnp.int32(5)),
        "prefill_suffix": eng._build_prefill_suffix().lower(
            eng.params, ids, jnp.int32(1), eng.k, eng.v, jnp.int32(4),
            jnp.int32(5)),
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
def test_a_shared_program_lowers_to_the_parent_s_text(lowered, family,
                                                      program):
    assert digest(lowered(family)[program]) == GOLDEN[family, program]


if __name__ == "__main__":      # make the table: run on the commit to pin
    jax.config.update("jax_default_matmul_precision", "highest")
    for fam in sorted(FAMILIES):
        for prog, low in programs(fam).items():
            print(f'    ("{fam}", "{prog}"): "{digest(low)}",')
