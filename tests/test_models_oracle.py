"""Golden-oracle tests: our JAX models vs transformers (torch, random weights).

The reference's only correctness check was a manual single-GPU comparison
script (``scripts/single_gpu_check.py``); here the same idea is an automated
assertion: identical weights -> logits allclose and greedy tokens identical,
including incremental decode through the KV cache.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
import torch

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
    config_from_hf,
    convert_state_dict,
    init_kv_cache,
)
from engines import full_forward

def tiny_gpt2():
    torch.manual_seed(0)
    from transformers import GPT2Config, GPT2LMHeadModel

    hf_cfg = GPT2Config(
        vocab_size=257, n_embd=64, n_layer=4, n_head=4, n_positions=128,
    )
    return GPT2LMHeadModel(hf_cfg).eval()


def tiny_llama():
    torch.manual_seed(0)
    from transformers import LlamaConfig, LlamaForCausalLM

    hf_cfg = LlamaConfig(
        vocab_size=320, hidden_size=64, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=128,
        max_position_embeddings=128, rope_theta=10000.0, tie_word_embeddings=False,
    )
    return LlamaForCausalLM(hf_cfg).eval()


def tiny_mistral():
    torch.manual_seed(0)
    from transformers import MistralConfig, MistralForCausalLM

    hf_cfg = MistralConfig(
        vocab_size=320, hidden_size=64, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=128,
        max_position_embeddings=128, sliding_window=8,
    )
    return MistralForCausalLM(hf_cfg).eval()


def tiny_mixtral():
    torch.manual_seed(0)
    from transformers import MixtralConfig, MixtralForCausalLM

    hf_cfg = MixtralConfig(
        vocab_size=320, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=96,
        max_position_embeddings=128, num_local_experts=4, num_experts_per_tok=2,
    )
    return MixtralForCausalLM(hf_cfg).eval()


def tiny_qwen2():
    torch.manual_seed(0)
    from transformers import Qwen2Config, Qwen2ForCausalLM

    hf_cfg = Qwen2Config(
        vocab_size=320, hidden_size=64, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=128,
        max_position_embeddings=128, rope_theta=10000.0,
        tie_word_embeddings=False,
    )
    return Qwen2ForCausalLM(hf_cfg).eval()


def tiny_gemma():
    torch.manual_seed(0)
    from transformers import GemmaConfig, GemmaForCausalLM

    # head_dim=32 != hidden/heads=16 exercises the decoupled-head-dim path
    # (gemma-7b ships 3072/16 heads with head_dim 256).
    hf_cfg = GemmaConfig(
        vocab_size=320, hidden_size=64, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=128,
        head_dim=32, max_position_embeddings=128, rope_theta=10000.0,
        hidden_activation="gelu_pytorch_tanh", tie_word_embeddings=True,
    )
    return GemmaForCausalLM(hf_cfg).eval()


def tiny_gemma2():
    torch.manual_seed(0)
    from transformers import Gemma2Config, Gemma2ForCausalLM

    # sliding_window=8 with a 16+-token prompt exercises the alternating
    # local/global layers; softcaps + query_pre_attn_scalar != head_dim
    # exercise the scoring path.
    hf_cfg = Gemma2Config(
        vocab_size=320, hidden_size=64, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=128,
        head_dim=32, max_position_embeddings=128, rope_theta=10000.0,
        hidden_activation="gelu_pytorch_tanh", tie_word_embeddings=True,
        sliding_window=8, query_pre_attn_scalar=16.0,
        attn_logit_softcapping=50.0, final_logit_softcapping=30.0,
        attn_implementation="eager",  # softcapping needs the eager path
    )
    return Gemma2ForCausalLM(hf_cfg).eval()


FACTORIES = {
    "gpt2": tiny_gpt2,
    "llama": tiny_llama,
    "mistral": tiny_mistral,
    "mixtral": tiny_mixtral,
    "qwen2": tiny_qwen2,
    "gemma": tiny_gemma,
    "gemma2": tiny_gemma2,
}


@pytest.mark.parametrize("family", list(FACTORIES))
def test_prefill_logits_match_hf(family):
    hf_model = FACTORIES[family]()
    cfg = config_from_hf(hf_model.config)
    params = convert_state_dict(cfg, hf_model.state_dict())

    ids = np.array([[5, 9, 23, 7, 81, 2, 14, 3]], dtype=np.int32)
    with torch.no_grad():
        ref_logits = hf_model(torch.tensor(ids, dtype=torch.long)).logits.numpy()

    kc, vc = init_kv_cache(cfg, cfg.num_layers, batch=1, max_len=32)
    logits, _, _ = full_forward(
        cfg, params, jnp.asarray(ids), kc, vc, jnp.int32(0)
    )
    if family == "mixtral":
        # Random-weight routers produce near-tied top-k gaps (observed 5e-4);
        # fp noise then flips expert choice for a token, shifting its logits
        # by ~2e-2. Accept that while still requiring argmax agreement.
        atol, rtol = 5e-2, 5e-2
    else:
        atol, rtol = 8e-3, 1e-2
    np.testing.assert_allclose(np.asarray(logits), ref_logits, atol=atol, rtol=rtol)
    assert (np.asarray(logits).argmax(-1) == ref_logits.argmax(-1)).all()


@pytest.mark.parametrize("family",
                         ["gpt2", "llama", "qwen2", "gemma", "gemma2"])
def test_incremental_decode_matches_full_recompute(family):
    """Prefill + per-token decode through the KV cache must equal one full
    forward over the whole sequence (the cache is exact, not approximate)."""
    hf_model = FACTORIES[family]()
    cfg = config_from_hf(hf_model.config)
    params = convert_state_dict(cfg, hf_model.state_dict())

    full_ids = np.array([[5, 9, 23, 7, 81, 2, 14, 3, 19, 44]], dtype=np.int32)
    prompt_len = 6

    kc, vc = init_kv_cache(cfg, cfg.num_layers, batch=1, max_len=16)
    logits, kc, vc = full_forward(
        cfg, params, jnp.asarray(full_ids[:, :prompt_len]), kc, vc, jnp.int32(0)
    )
    step_logits = [np.asarray(logits[:, -1])]
    for t in range(prompt_len, full_ids.shape[1]):
        logits, kc, vc = full_forward(
            cfg, params, jnp.asarray(full_ids[:, t : t + 1]), kc, vc, jnp.int32(t)
        )
        step_logits.append(np.asarray(logits[:, -1]))

    kc2, vc2 = init_kv_cache(cfg, cfg.num_layers, batch=1, max_len=16)
    ref_logits, _, _ = full_forward(
        cfg, params, jnp.asarray(full_ids), kc2, vc2, jnp.int32(0)
    )
    for i, sl in enumerate(step_logits):
        pos = prompt_len - 1 + i
        np.testing.assert_allclose(
            sl, np.asarray(ref_logits[:, pos]), atol=5e-3, rtol=5e-3,
            err_msg=f"mismatch at position {pos}",
        )


@pytest.mark.parametrize("family", ["gpt2", "llama", "mistral", "qwen2"])
def test_greedy_generation_token_identical(family):
    """End-to-end greedy decode vs transformers .generate — token identical."""
    hf_model = FACTORIES[family]()
    cfg = config_from_hf(hf_model.config)
    params = convert_state_dict(cfg, hf_model.state_dict())

    prompt = np.array([[5, 9, 23, 7]], dtype=np.int32)
    n_new = 12
    with torch.no_grad():
        ref = hf_model.generate(
            torch.tensor(prompt, dtype=torch.long),
            max_new_tokens=n_new, do_sample=False, use_cache=True,
            pad_token_id=0,
        ).numpy()[0, prompt.shape[1]:]

    kc, vc = init_kv_cache(cfg, cfg.num_layers, batch=1, max_len=32)
    logits, kc, vc = full_forward(
        cfg, params, jnp.asarray(prompt), kc, vc, jnp.int32(0)
    )
    out = []
    cur = int(jnp.argmax(logits[0, -1]))
    out.append(cur)
    cache_len = prompt.shape[1]
    for _ in range(n_new - 1):
        logits, kc, vc = full_forward(
            cfg, params, jnp.asarray([[cur]], dtype=jnp.int32), kc, vc,
            jnp.int32(cache_len),
        )
        cur = int(jnp.argmax(logits[0, -1]))
        out.append(cur)
        cache_len += 1

    assert out == list(ref), f"ours={out} ref={list(ref)}"


def test_llama31_rope_scaling_matches_hf():
    """Llama-3.1-style rope_scaling (type "llama3"): logits must match the
    HF implementation of the frequency remap — the reference's LB test
    model is Llama-3.1-8B (BASELINE.md)."""
    torch.manual_seed(0)
    from transformers import LlamaConfig, LlamaForCausalLM

    hf_cfg = LlamaConfig(
        vocab_size=320, hidden_size=64, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=128,
        max_position_embeddings=256, rope_theta=10000.0,
        tie_word_embeddings=False,
        rope_scaling={"rope_type": "llama3", "factor": 8.0,
                      "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                      "original_max_position_embeddings": 32},
    )
    hf_model = LlamaForCausalLM(hf_cfg).eval()
    cfg = config_from_hf(hf_model.config)
    assert cfg.rope_scaling == (8.0, 1.0, 4.0, 32)
    params = convert_state_dict(cfg, hf_model.state_dict())

    # Long enough that scaled wavelengths actually matter (> orig_max/2).
    ids = np.arange(48, dtype=np.int32)[None, :] % 320
    with torch.no_grad():
        ref = hf_model(torch.tensor(ids, dtype=torch.long)).logits.numpy()
    kc, vc = init_kv_cache(cfg, cfg.num_layers, batch=1, max_len=64)
    logits, _, _ = full_forward(cfg, params, jnp.asarray(ids), kc, vc,
                                jnp.int32(0))
    np.testing.assert_allclose(np.asarray(logits), ref, atol=8e-3, rtol=1e-2)
    assert (np.asarray(logits).argmax(-1) == ref.argmax(-1)).all()


def test_unsupported_rope_scaling_rejected():
    import pytest as _pytest

    torch.manual_seed(0)
    from transformers import LlamaConfig

    hf_cfg = LlamaConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=1,
        num_attention_heads=2, num_key_value_heads=2, intermediate_size=64,
        rope_scaling={"rope_type": "linear", "factor": 2.0},
    )
    with _pytest.raises(ValueError, match="rope_scaling"):
        config_from_hf(hf_cfg)


def test_non_llama_rope_scaling_rejected():
    import pytest as _pytest

    torch.manual_seed(0)
    from transformers import Qwen2Config

    hf_cfg = Qwen2Config(
        vocab_size=64, hidden_size=32, num_hidden_layers=1,
        num_attention_heads=2, num_key_value_heads=2, intermediate_size=64,
        rope_scaling={"rope_type": "yarn", "factor": 4.0})
    with _pytest.raises(ValueError, match="rope_scaling"):
        config_from_hf(hf_cfg)


def test_fused_qkv_layers_bitwise_matches_canonical():
    """Engine-side fused-QKV layout (models/transformer.fuse_qkv_layers):
    one [D, (H+2Hkv)*Dh] projection must be BITWISE identical to the three
    canonical matmuls — fusing along the output axis never changes a
    column's K-reduction — so every engine-vs-oracle parity test stays
    exact with engines fused and oracles canonical."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
        init_kv_cache,
        init_params,
        llama_config,
    )
    from engines import full_forward
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.transformer import (
        fuse_qkv_layers,
    )

    cfg = llama_config(vocab_size=211, hidden_size=64, num_layers=4,
                       num_heads=4, num_kv_heads=2, intermediate_size=128,
                       max_position_embeddings=64)
    params = init_params(jax.random.PRNGKey(0), cfg)
    fused = dict(params, layers=fuse_qkv_layers(params["layers"]))
    assert "wqkv" in fused["layers"]["attn"]
    assert "wq" not in fused["layers"]["attn"]
    # idempotent / guard behavior
    assert fuse_qkv_layers(fused["layers"]) is fused["layers"]

    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 9)),
        jnp.int32)
    kc, vc = init_kv_cache(cfg, cfg.num_layers, 2, 32)
    ref, kr, vr = full_forward(cfg, params, ids, kc, vc, jnp.int32(0))
    kc2, vc2 = init_kv_cache(cfg, cfg.num_layers, 2, 32)
    got, kg, vg = full_forward(cfg, fused, ids, kc2, vc2, jnp.int32(0))
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
    np.testing.assert_array_equal(np.asarray(kr), np.asarray(kg))


def test_fuse_gate_up_stacked_bitwise():
    """fuse_gate_up_layers must FIRE on vmap-stacked dense trees (wg 3-D
    [L, d, i] — the layout every engine passes; an ndim guard once made
    it a silent no-op) and produce bitwise-identical logits; MoE expert
    trees keep canonical."""
    import jax.numpy as jnp

    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
        init_kv_cache,
        init_params,
        llama_config,
        mixtral_config,
    )
    from engines import full_forward
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.transformer import (
        fuse_qkv_params,
    )

    cfg = llama_config(vocab_size=131, hidden_size=64, num_layers=3,
                       num_heads=4, num_kv_heads=2, intermediate_size=96,
                       max_position_embeddings=32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    fused = fuse_qkv_params(params)
    assert "wgu" in fused["layers"]["mlp"], "gate+up fusion did not fire"
    assert fused["layers"]["mlp"]["wgu"].shape == (3, 64, 192)
    ids = jnp.asarray([[5, 9, 23, 7]], jnp.int32)
    kc, vc = init_kv_cache(cfg, cfg.num_layers, 1, 16)
    a, _, _ = full_forward(cfg, params, ids, kc, vc, jnp.int32(0))
    kc, vc = init_kv_cache(cfg, cfg.num_layers, 1, 16)
    b, _, _ = full_forward(cfg, fused, ids, kc, vc, jnp.int32(0))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    moe = mixtral_config(vocab_size=131, hidden_size=32, num_layers=2,
                         num_heads=4, num_kv_heads=2, intermediate_size=64,
                         num_experts=2, num_experts_per_tok=1,
                         max_position_embeddings=32)
    mp = fuse_qkv_params(init_params(jax.random.PRNGKey(1), moe))
    assert "wgu" not in mp["layers"]["mlp"]      # experts stay canonical
    assert "wg" in mp["layers"]["mlp"]
