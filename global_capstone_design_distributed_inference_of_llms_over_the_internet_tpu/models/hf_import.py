"""Import HuggingFace checkpoints into the stacked-layer JAX param layout.

Capability-parity with two reference paths:
  * full-checkpoint load + prune to a stage span (``src/llama_partition.py:477-550``
    loads the whole HF model then deletes layers outside [start, end));
  * per-block weight streaming (``petals/server/from_pretrained.py:81-128``
    downloads only the shards containing one block's params).

Here both are the same operation: ``convert_state_dict(..., layer_range)``
touches only the tensors a stage needs, so a stage never materializes the full
model in host memory.

Weight-layout notes:
  * GPT-2 uses Conv1D ([in, out]) — imported as-is; its fused c_attn is split
    into wq/wk/wv.
  * LLaMA-family nn.Linear weights are [out, in] — imported transposed.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Optional, Tuple

from collections.abc import Mapping

import jax
import jax.numpy as jnp
import numpy as np

from .config import (
    ModelConfig,
    gpt2_config,
    llama_config,
    mistral_config,
    mixtral_config,
    qwen2_config,
)

Params = Dict[str, Any]


def _np(t) -> np.ndarray:
    """torch.Tensor | np.ndarray -> np.ndarray (float32 staging)."""
    if hasattr(t, "detach"):
        t = t.detach().to("cpu")
        try:
            import torch

            if t.dtype == torch.bfloat16:
                t = t.float()
        except Exception:
            pass
        t = t.numpy()
    return np.asarray(t)


def config_from_hf(hf_cfg) -> ModelConfig:
    """Build a ModelConfig from a transformers PretrainedConfig."""
    mt = hf_cfg.model_type
    if mt == "gpt2":
        return gpt2_config(
            vocab_size=hf_cfg.vocab_size,
            hidden_size=hf_cfg.n_embd,
            num_layers=hf_cfg.n_layer,
            num_heads=hf_cfg.n_head,
            max_position_embeddings=hf_cfg.n_positions,
            intermediate_size=getattr(hf_cfg, "n_inner", None) or 4 * hf_cfg.n_embd,
            norm_eps=getattr(hf_cfg, "layer_norm_epsilon", 1e-5),
        )
    common = dict(
        vocab_size=hf_cfg.vocab_size,
        hidden_size=hf_cfg.hidden_size,
        num_layers=hf_cfg.num_hidden_layers,
        num_heads=hf_cfg.num_attention_heads,
        num_kv_heads=getattr(hf_cfg, "num_key_value_heads", hf_cfg.num_attention_heads),
        intermediate_size=hf_cfg.intermediate_size,
        max_position_embeddings=hf_cfg.max_position_embeddings,
        rope_theta=getattr(hf_cfg, "rope_theta", 10000.0),
        tie_word_embeddings=getattr(hf_cfg, "tie_word_embeddings", False),
        norm_eps=getattr(hf_cfg, "rms_norm_eps", 1e-5),
    )
    rs = getattr(hf_cfg, "rope_scaling", None)
    if rs and mt != "llama":
        # Only the llama3 remap is implemented; any other family shipping
        # rope_scaling (e.g. yarn on long-context qwen2) would get silently
        # wrong positions past the base window — fail loudly instead.
        rtype = rs.get("rope_type", rs.get("type"))
        if rtype not in (None, "default"):
            raise ValueError(
                f"{mt} checkpoint carries rope_scaling type {rtype!r} — "
                "unsupported (llama3-type scaling on llama only)")
    if mt == "llama":
        cfg = llama_config(**common)
        if rs:
            rtype = rs.get("rope_type", rs.get("type"))
            if rtype == "llama3":
                # Llama-3.1/3.2 frequency remap (ops.rotary llama3 rule).
                import dataclasses

                cfg = dataclasses.replace(cfg, rope_scaling=(
                    float(rs["factor"]),
                    float(rs.get("low_freq_factor", 1.0)),
                    float(rs.get("high_freq_factor", 4.0)),
                    int(rs.get("original_max_position_embeddings", 8192)),
                ))
            elif rtype not in (None, "default"):
                # Linear/dynamic-NTK etc. would silently change positions —
                # fail loudly rather than generate subtly wrong long-context.
                raise ValueError(
                    f"unsupported rope_scaling type {rtype!r} "
                    "(supported: llama3)")
        return cfg
    if mt == "qwen2":
        common["norm_eps"] = getattr(hf_cfg, "rms_norm_eps", 1e-6)
        cfg = qwen2_config(**common)
        # Qwen2 configs carry sliding_window but only apply it when
        # use_sliding_window is set (HF Qwen2Config semantics). HF further
        # runs FULL attention for layers < max_window_layers and windowed
        # attention only above; our window is global, so only the uniform
        # cases map — a mixed checkpoint must fail LOUDLY, not silently
        # diverge past the window.
        if getattr(hf_cfg, "use_sliding_window", False):
            import dataclasses

            mwl = getattr(hf_cfg, "max_window_layers",
                          hf_cfg.num_hidden_layers)
            if mwl <= 0:  # every layer windowed
                cfg = dataclasses.replace(
                    cfg, sliding_window=getattr(hf_cfg, "sliding_window", None))
            elif mwl < hf_cfg.num_hidden_layers:  # mixed full/windowed
                raise ValueError(
                    "qwen2 checkpoint uses per-layer sliding windows "
                    f"(max_window_layers={mwl} of "
                    f"{hf_cfg.num_hidden_layers} layers) — unsupported")
            # mwl >= num layers: no layer is windowed; keep full attention.
        return cfg
    if mt == "mistral":
        return mistral_config(
            sliding_window=getattr(hf_cfg, "sliding_window", None), **common
        )
    if mt == "gemma":
        from .config import gemma_config

        # HF GemmaConfig historically defaulted hidden_act to "gelu" while
        # checkpoints run gelu_pytorch_tanh (transformers#29402); both map
        # to the tanh approximation here. norm_eps/tie_word_embeddings ride
        # in via `common` (GemmaConfig always defines both attributes).
        return gemma_config(head_dim=hf_cfg.head_dim, **common)
    if mt == "gemma2":
        from .config import gemma2_config

        return gemma2_config(
            head_dim=hf_cfg.head_dim,
            query_pre_attn_scalar=float(
                getattr(hf_cfg, "query_pre_attn_scalar", 0.0) or 0.0),
            attn_softcap=float(
                getattr(hf_cfg, "attn_logit_softcapping", 0.0) or 0.0),
            final_softcap=float(
                getattr(hf_cfg, "final_logit_softcapping", 0.0) or 0.0),
            sliding_window=int(getattr(hf_cfg, "sliding_window", 0) or 0),
            **common)
    if mt == "ouro":
        from .config import ouro_config

        if getattr(hf_cfg, "use_sliding_window", False):
            raise ValueError("ouro checkpoint uses sliding windows — "
                             "unsupported (every published layer is "
                             "full_attention)")
        return ouro_config(
            loop_steps=int(hf_cfg.total_ut_steps),
            exit_threshold=float(
                getattr(hf_cfg, "early_exit_threshold", 1.0)),
            head_dim=int(getattr(hf_cfg, "head_dim", None)
                         or hf_cfg.hidden_size // hf_cfg.num_attention_heads),
            **common)
    if mt == "evabyte":
        from .config import evabyte_config

        if getattr(hf_cfg, "attention_class", "eva") != "eva":
            raise ValueError(
                f"evabyte checkpoint with attention_class "
                f"{hf_cfg.attention_class!r} — unsupported (eva only)")
        return evabyte_config(
            window_size=int(hf_cfg.window_size),
            chunk_size=int(hf_cfg.chunk_size),
            num_pred_heads=int(getattr(hf_cfg, "num_pred_heads", 1)),
            **common)
    if mt == "glm_moe_dsa":
        from .config import glm5_config

        rope = getattr(hf_cfg, "rope_parameters", None) or {}
        common.pop("num_kv_heads")
        common["rope_theta"] = float(
            rope.get("rope_theta", common["rope_theta"]))
        return glm5_config(
            **common,
            q_lora_rank=hf_cfg.q_lora_rank,
            kv_lora_rank=hf_cfg.kv_lora_rank,
            qk_nope_head_dim=hf_cfg.qk_nope_head_dim,
            qk_rope_head_dim=hf_cfg.qk_rope_head_dim,
            v_head_dim=hf_cfg.v_head_dim,
            index_n_heads=hf_cfg.index_n_heads,
            index_head_dim=hf_cfg.index_head_dim,
            index_topk=hf_cfg.index_topk,
            n_routed_experts=hf_cfg.n_routed_experts,
            num_experts_per_tok=hf_cfg.num_experts_per_tok,
            moe_intermediate_size=hf_cfg.moe_intermediate_size,
            n_shared_experts=hf_cfg.n_shared_experts,
            routed_scaling_factor=float(hf_cfg.routed_scaling_factor),
            first_k_dense=hf_cfg.first_k_dense_replace)
    if mt == "dots3_note":
        from .config import dots3_config

        common.pop("num_kv_heads")
        return dots3_config(
            tuple(hf_cfg.layer_types), **common,
            **{k: getattr(hf_cfg, k) for k in (
                "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "index_n_heads",
                "index_head_dim", "index_topk", "sliding_window_size",
                "swa_q_lora_rank", "swa_kv_lora_rank",
                "swa_qk_nope_head_dim", "swa_qk_rope_head_dim",
                "swa_v_head_dim", "n_routed_experts", "num_experts_per_tok",
                "moe_intermediate_size", "n_shared_experts")},
            swa_num_heads=hf_cfg.swa_num_attention_heads,
            swa_rope_theta=float(hf_cfg.swa_rope_theta),
            attention_gate=hf_cfg.attention_gate_type == "headwise",
            swa_attention_gate=hf_cfg.swa_attention_gate_type == "headwise",
            lora_rescale=bool(hf_cfg.apply_mla_qkv_lora_rescale),
            routed_scaling_factor=float(hf_cfg.routed_scaling_factor),
            first_k_dense=hf_cfg.first_k_dense_replace)
    if mt == "mixtral":
        cfg = mixtral_config(
            num_experts=hf_cfg.num_local_experts,
            num_experts_per_tok=hf_cfg.num_experts_per_tok,
            **common,
        )
        sw = getattr(hf_cfg, "sliding_window", None)
        if sw is not None:
            import dataclasses

            cfg = dataclasses.replace(cfg, sliding_window=sw)
        return cfg
    # Mirrors the reference's model_type guard (src/llama_partition.py:82-83).
    raise ValueError(
        f"unsupported model_type: {mt} "
        "(expected gpt2/llama/mistral/mixtral/qwen2/gemma/ouro/evabyte/"
        "glm_moe_dsa/dots3_note)")


def _gpt2_layer(sd: Mapping[str, Any], i: int) -> Params:
    pre = f"transformer.h.{i}."
    c_attn_w = _np(sd[pre + "attn.c_attn.weight"])  # [D, 3D]
    c_attn_b = _np(sd[pre + "attn.c_attn.bias"])  # [3D]
    wq, wk, wv = np.split(c_attn_w, 3, axis=1)
    bq, bk, bv = np.split(c_attn_b, 3, axis=0)
    return {
        "ln1": {"w": _np(sd[pre + "ln_1.weight"]), "b": _np(sd[pre + "ln_1.bias"])},
        "ln2": {"w": _np(sd[pre + "ln_2.weight"]), "b": _np(sd[pre + "ln_2.bias"])},
        "attn": {
            "wq": wq, "wk": wk, "wv": wv,
            "bq": bq, "bk": bk, "bv": bv,
            "wo": _np(sd[pre + "attn.c_proj.weight"]),
            "bo": _np(sd[pre + "attn.c_proj.bias"]),
        },
        "mlp": {
            "wi": _np(sd[pre + "mlp.c_fc.weight"]),
            "bi": _np(sd[pre + "mlp.c_fc.bias"]),
            "wo": _np(sd[pre + "mlp.c_proj.weight"]),
            "bo": _np(sd[pre + "mlp.c_proj.bias"]),
        },
    }


# Checkpoint names of a llama-shaped layer's norms after ``ln1``
# (``input_layernorm`` in every family). ln2 is the norm BEFORE the MLP;
# a family with sandwich norms (``post_norms``) adds ln3 AFTER attention
# and ln4 AFTER the MLP, each before its residual add, under names of its
# own: gemma2's "post_attention_layernorm" is the POST-attention norm,
# and the looped family's "_2" is the norm after the sublayer.
_PRE_NORMS = {"ln2": "post_attention_layernorm"}
_SANDWICH_NORMS = {
    "gemma2": {"ln2": "pre_feedforward_layernorm",
               "ln3": "post_attention_layernorm",
               "ln4": "post_feedforward_layernorm"},
    "ouro": {"ln2": "post_attention_layernorm",
             "ln3": "input_layernorm_2",
             "ln4": "post_attention_layernorm_2"},
}


def _llama_layer(sd: Mapping[str, Any], i: int, cfg: ModelConfig) -> Params:
    pre = f"model.layers.{i}."
    p: Params = {
        "ln1": {"w": _np(sd[pre + "input_layernorm.weight"])},
        "attn": {
            "wq": _np(sd[pre + "self_attn.q_proj.weight"]).T,
            "wk": _np(sd[pre + "self_attn.k_proj.weight"]).T,
            "wv": _np(sd[pre + "self_attn.v_proj.weight"]).T,
            "wo": _np(sd[pre + "self_attn.o_proj.weight"]).T,
        },
    }
    names = _SANDWICH_NORMS[cfg.model_type] if cfg.post_norms else _PRE_NORMS
    for ours, theirs in names.items():
        p[ours] = {"w": _np(sd[pre + theirs + ".weight"])}
    if cfg.eva_window:
        # One vector of head_dim a head each, whatever singleton dims the
        # checkpoint keeps around them: ``[H, Dh]``.
        for ours, theirs in (("mu", "adaptive_mu_k"), ("phi", "adaptive_phi")):
            p["attn"][ours] = _np(sd[pre + "self_attn." + theirs]).reshape(
                cfg.num_heads, cfg.head_dim)
    if cfg.altern_window:
        # even layers windowed, odd global (HF Gemma2Attention layer_idx
        # rule) — the traced per-layer window leaf.
        p["window"] = np.int32(cfg.altern_window if i % 2 == 0 else 0)
    if cfg.attn_qkv_bias:  # qwen2: q/k/v biases, no o bias
        p["attn"]["bq"] = _np(sd[pre + "self_attn.q_proj.bias"])
        p["attn"]["bk"] = _np(sd[pre + "self_attn.k_proj.bias"])
        p["attn"]["bv"] = _np(sd[pre + "self_attn.v_proj.bias"])
    if cfg.is_moe:
        gate = _np(sd[pre + "block_sparse_moe.gate.weight"]).T  # [D, E]
        wg = np.stack([
            _np(sd[pre + f"block_sparse_moe.experts.{e}.w1.weight"]).T
            for e in range(cfg.num_experts)
        ])
        wu = np.stack([
            _np(sd[pre + f"block_sparse_moe.experts.{e}.w3.weight"]).T
            for e in range(cfg.num_experts)
        ])
        wd = np.stack([
            _np(sd[pre + f"block_sparse_moe.experts.{e}.w2.weight"]).T
            for e in range(cfg.num_experts)
        ])
        p["mlp"] = {"router": gate, "wg": wg, "wu": wu, "wd": wd}
    else:
        p["mlp"] = {
            "wg": _np(sd[pre + "mlp.gate_proj.weight"]).T,
            "wu": _np(sd[pre + "mlp.up_proj.weight"]).T,
            "wd": _np(sd[pre + "mlp.down_proj.weight"]).T,
        }
    return p


def _half_layout(n: int) -> np.ndarray:
    """Where each of ``n`` rotated dims sits when INTERLEAVED pairs (2i,
    2i + 1) are held as the halves (i, i + n/2) `ops.rotary` rotates: the
    permutation applied to q and k alike leaves every q . k as it is."""
    return np.concatenate([np.arange(0, n, 2), np.arange(1, n, 2)])


def _glm5_layer(sd: Mapping[str, Any], i: int, cfg: ModelConfig) -> Params:
    """One layer of a ``glm_moe_dsa`` checkpoint (HF Linear weights are
    [out, in]; ours [in, out], but for four of the attention's that keep
    the PUBLISHED orientation, the contracted axis last
    (`models.transformer._dot_t`): ``wqb_t``, ``wkvb_t`` and ``wiq_t``,
    whose outputs are grouped by head straight after the product, as
    ``[heads, rows a head, in]``, and ``wkva_t`` ``[kv_lora_rank +
    qk_rope_head_dim, in]``, whose 576 outputs are no whole number of the
    v5e's lane tiles. That is the form the chip reads a layer's slice of
    their stacks in. The dims the published model rotates as interleaved
    pairs (``rope_interleave``, ``indexer_rope_interleave``) are permuted
    to the half layout here, once: the rope part of every
    head of ``q_b_proj``, the shared key of ``kv_a_proj_with_mqa``, and
    the first ``qk_rope_head_dim`` of the indexer's queries, key and the
    key's LayerNorm. Of the routed experts only those held
    (``cfg.held_experts``) are read; the router keeps every output.

    A ``dots3_note`` checkpoint: the same names; a ``"sliding_attention"``
    layer (``cfg.layer_kinds``) has the ``swa_*`` geometry under them and no
    ``indexer.*``; both kinds add the headwise gate ``self_attn.g_proj``;
    its rotated dims are held as halves already (no ``rope_interleave``
    key: the rotate-half convention), so nothing is permuted."""
    pre = f"model.layers.{i}."
    att = pre + "self_attn."
    # the attention's sizes are its KIND's; the MLP's are the family's
    kind = cfg.sliding_kind if cfg.layer_kinds[i] == "sliding" else cfg
    h, r = kind.num_heads, kind.qk_rope_head_dim
    nope, kl = kind.qk_nope_head_dim, kind.kv_lora_rank
    half = (_half_layout(r) if kind.model_type == "glm_moe_dsa"
            else np.arange(r))
    t = lambda name: _np(sd[name])
    head = np.concatenate([np.arange(nope), nope + half])
    q_rows = (np.arange(h)[:, None] * (nope + r) + head[None]).reshape(-1)
    di = kind.index_head_dim
    ikey = np.concatenate([half, np.arange(r, di)])
    iq_rows = (np.arange(kind.index_n_heads)[:, None] * di
               + ikey[None]).reshape(-1)
    kva_rows = np.concatenate([np.arange(kl), kl + half])
    p: Params = {
        "ln1": {"w": t(pre + "input_layernorm.weight")},
        "ln2": {"w": t(pre + "post_attention_layernorm.weight")},
        "attn": {
            "wqa": t(att + "q_a_proj.weight").T,
            "q_norm": {"w": t(att + "q_a_layernorm.weight")},
            "wqb_t": t(att + "q_b_proj.weight")[q_rows].reshape(
                h, nope + r, -1),
            "wkva_t": t(att + "kv_a_proj_with_mqa.weight")[kva_rows],
            "kv_norm": {"w": t(att + "kv_a_layernorm.weight")},
            "wkvb_t": t(att + "kv_b_proj.weight").reshape(h, -1, kl),
            "wo": t(att + "o_proj.weight").T,
        },
    }
    if kind.index_topk:
        p["attn"].update({
            "wiq_t": t(att + "indexer.wq_b.weight")[iq_rows].reshape(
                kind.index_n_heads, di, -1),
            "wik": t(att + "indexer.wk.weight")[ikey].T,
            "ik_norm": {"w": t(att + "indexer.k_norm.weight")[ikey],
                        "b": t(att + "indexer.k_norm.bias")[ikey]},
            "wiw": t(att + "indexer.weights_proj.weight").T,
        })
    if kind.attention_gate:
        p["attn"]["wgate"] = t(att + "g_proj.weight").T
    mlp = pre + "mlp."

    def swiglu(owner):
        return {ours: t(mlp + owner + theirs + ".weight").T
                for ours, theirs in (("wg", "gate_proj"), ("wu", "up_proj"),
                                     ("wd", "down_proj"))}

    if i < cfg.first_k_dense:
        p["mlp"] = swiglu("")
        return p
    first, held = cfg.held_experts
    experts = [swiglu(f"experts.{e}.") for e in range(first, first + held)]
    p["mlp"] = {
        "router": t(mlp + "gate.weight").T,
        "router_bias": np.asarray(
            t(mlp + "gate.e_score_correction_bias"), np.float32),
        **{k: np.stack([e[k] for e in experts]) for k in ("wg", "wu", "wd")},
        "shared": swiglu("shared_experts."),
    }
    return p


def _stack(layer_params: Iterable[Params]) -> Params:
    layer_params = list(layer_params)
    return jax.tree.map(lambda *xs: np.stack(xs), *layer_params)


def convert_state_dict(
    cfg: ModelConfig,
    sd: Mapping[str, Any],
    dtype=np.float32,
    layer_range: Optional[Tuple[int, int]] = None,
    include_embed: bool = True,
    include_head: bool = True,
) -> Params:
    """Convert an HF state_dict to the stacked JAX layout.

    layer_range=(start, end) keeps only that span of layers; include_embed /
    include_head control whether embedding and final-norm+lm_head tensors are
    materialized (mirrors the stage-role pruning of
    ``src/llama_partition.py:506-525``).
    """
    start, end = layer_range if layer_range is not None else (0, cfg.num_layers)
    is_gpt2 = cfg.model_type == "gpt2"

    if is_gpt2:
        layers = [_gpt2_layer(sd, i) for i in range(start, end)]
    elif cfg.kv_lora_rank:
        layers = [_glm5_layer(sd, i, cfg) for i in range(start, end)]
    else:
        layers = [_llama_layer(sd, i, cfg) for i in range(start, end)]

    params: Params = {}
    if cfg.kv_lora_rank and layers:
        # Leading dense layers are another kind than the rest: two stacks.
        # The router's score bias stays float32, as published.
        k = max(0, min(cfg.first_k_dense, end) - start)
        to_device = lambda path, x: jnp.asarray(
            x, jnp.float32 if path[-1].key == "router_bias" else dtype)
        kinds = cfg.layer_kinds[start:end][k:]
        for name, group in (
                ("dense_layers", layers[:k]),
                ("layers", [p for p, kind in zip(layers[k:], kinds)
                            if kind == "full"]),
                # layers of another kind again, whose weights have other
                # shapes (``cfg.layer_types``): a third stack
                ("sliding_layers", [p for p, kind in zip(layers[k:], kinds)
                                    if kind == "sliding"])):
            if group:
                params[name] = jax.tree_util.tree_map_with_path(
                    to_device, _stack(group))
    elif layers:
        # Cast FLOAT leaves only: the gemma2 per-layer "window" leaf is
        # int32 position arithmetic — sweeping it to bf16 would mis-mask
        # keys past position ~256 (bf16 integers lose exactness there).
        params["layers"] = jax.tree.map(
            lambda x: (jnp.asarray(x, dtype)
                       if np.issubdtype(np.asarray(x).dtype, np.floating)
                       else jnp.asarray(x)),
            _stack(layers)
        )

    if include_embed:
        if is_gpt2:
            embed = {
                "wte": _np(sd["transformer.wte.weight"]),
                "wpe": _np(sd["transformer.wpe.weight"]),
            }
        else:
            embed = {"wte": _np(sd["model.embed_tokens.weight"])}
        params["embed"] = {k: jnp.asarray(v, dtype) for k, v in embed.items()}

    if include_head:
        if is_gpt2:
            params["final_norm"] = {
                "w": jnp.asarray(_np(sd["transformer.ln_f.weight"]), dtype),
                "b": jnp.asarray(_np(sd["transformer.ln_f.bias"]), dtype),
            }
        else:
            params["final_norm"] = {
                "w": jnp.asarray(_np(sd["model.norm.weight"]), dtype)
            }
        if cfg.loop_steps > 1:
            # Linear(hidden, 1): [1, D] -> our [in, out].
            params["exit_gate"] = {
                "w": jnp.asarray(
                    _np(sd["model.early_exit_gate.weight"]).T, dtype),
                "b": jnp.asarray(
                    _np(sd["model.early_exit_gate.bias"]), dtype)}
        if not cfg.tie_word_embeddings:
            head = sd.get("lm_head.weight")
            if head is not None:
                params["lm_head"] = {"w": jnp.asarray(_np(head).T, dtype)}
            else:
                # checkpoint ties embeddings even if config says otherwise
                key = "transformer.wte.weight" if is_gpt2 else "model.embed_tokens.weight"
                params["lm_head"] = {"w": jnp.asarray(_np(sd[key]).T, dtype)}
        if cfg.tie_word_embeddings and not include_embed:
            # a last-stage shard with tied embeddings still needs wte for the head
            key = "transformer.wte.weight" if is_gpt2 else "model.embed_tokens.weight"
            params["embed"] = {"wte": jnp.asarray(_np(sd[key]), dtype)}

    return params


def import_hf_model(hf_model, dtype=np.float32) -> Tuple[ModelConfig, Params]:
    """Convert an in-memory transformers model (e.g. the test oracle)."""
    cfg = config_from_hf(hf_model.config)
    return cfg, convert_state_dict(cfg, hf_model.state_dict(), dtype)


# ---------------------------------------------------------------------------
# Per-stage checkpoint streaming (petals/server/from_pretrained.py:81-128):
# a stage server reads ONLY the safetensors shards containing its span's
# tensors — the full model is never materialized on any single host.
# ---------------------------------------------------------------------------

class LazyCheckpoint(Mapping):
    """Lazy Mapping over a local HF checkpoint directory.

    Keys resolve through the safetensors index (``model.safetensors.index
    .json`` for sharded checkpoints, the single ``model.safetensors``
    otherwise); a tensor's bytes are read only when ``convert_state_dict``
    actually touches its key, and only from the shard that holds it —
    the TPU-native analogue of the reference's per-block shard filtering
    (``petals/server/from_pretrained.py:100-108``). ``.opened`` records
    which shard files were read (observable in tests: a middle stage must
    not touch the embedding/head shards)."""

    def __init__(self, path: str):
        import json
        import os

        self.path = path
        self.opened: set = set()
        self._files: Dict[str, Any] = {}  # shard -> cached safe_open handle
        self._weight_map: Dict[str, str] = {}
        index = os.path.join(path, "model.safetensors.index.json")
        single = os.path.join(path, "model.safetensors")
        if os.path.exists(index):
            with open(index) as f:
                self._weight_map = dict(json.load(f)["weight_map"])
        elif os.path.exists(single):
            from safetensors import safe_open

            with safe_open(single, framework="flax") as f:
                self._weight_map = {k: "model.safetensors" for k in f.keys()}
        else:
            raise FileNotFoundError(
                f"no model.safetensors[.index.json] under {path} "
                "(only safetensors checkpoints support per-stage streaming)"
            )
        # Official GPT-2-era checkpoints (and any save of the BASE model)
        # store keys without the LM-head wrapper prefix ('h.0...', 'wte...'
        # instead of 'transformer.h.0...'); llama equivalents drop 'model.'.
        # Alias the prefixed names convert_state_dict asks for onto them.
        self._alias: Dict[str, str] = {}
        for prefix in ("transformer.", "model."):
            if not any(k.startswith(prefix) for k in self._weight_map):
                self._alias.update(
                    {prefix + k: k for k in self._weight_map}
                )

    def _shard(self, fname: str):
        import os

        handle = self._files.get(fname)
        if handle is None:
            from safetensors import safe_open

            # framework="flax" handles every HF dtype incl. bfloat16 (the
            # "np" framework rejects bf16). Handles are cached per shard —
            # reopening per tensor would reparse the header every time.
            handle = safe_open(os.path.join(self.path, fname),
                               framework="flax")
            self._files[fname] = handle
        return handle

    def __getitem__(self, key: str) -> np.ndarray:
        key = self._alias.get(key, key)
        fname = self._weight_map[key]
        self.opened.add(fname)
        # Pin the materialization to host memory: on a TPU host the flax
        # framework would otherwise bounce every tensor through HBM.
        with jax.default_device(jax.devices("cpu")[0]):
            t = self._shard(fname).get_tensor(key)
        return np.asarray(t)

    def __iter__(self):
        return iter(self._weight_map)

    def __len__(self) -> int:
        return len(self._weight_map)


def config_from_checkpoint(path: str) -> ModelConfig:
    from transformers import AutoConfig

    return config_from_hf(AutoConfig.from_pretrained(path, local_files_only=True))


def load_stage_checkpoint(path: str, cfg: ModelConfig, spec,
                          dtype=np.float32) -> Params:
    """Load exactly one stage's parameters from a local HF checkpoint,
    reading only the shards its span touches (never the full model).
    `spec` is a ``models.partition.StageSpec``."""
    sd = LazyCheckpoint(path)
    return convert_state_dict(
        cfg, sd, dtype,
        layer_range=(spec.start, spec.end),
        include_embed=spec.is_first,
        include_head=spec.is_last,
    )
