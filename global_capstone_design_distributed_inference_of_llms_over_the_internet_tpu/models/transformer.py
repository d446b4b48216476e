"""Unified decoder-only transformer, pure JAX, stacked-layer layout.

One implementation covers every family the reference handles (GPT-2 via the
``transformer.h`` layout, LLaMA/Mistral/Mixtral via ``model.layers`` — see
reference ``src/llama_partition.py:82-93,151-156``), switched by `ModelConfig`
rather than per-family nn.Module classes.

TPU-first design decisions:
  * Per-layer parameters are STACKED along a leading layer axis and the layer
    loop is ``lax.scan`` — one trace/compile regardless of how many layers a
    stage holds, and XLA pipelines the weight loads.
  * KV caches are static-shape arrays written by ``dynamic_update_slice``
    (ops.attention) — replaces the reference's growing legacy tuples
    (``src/utils.py:51-64``).
  * Optional tensor parallelism: pass ``tp_axis`` inside ``shard_map`` — q/k/v
    and mlp-in projections consume head-/ffn-sharded weights, and the out
    projections finish with ``lax.psum`` over the axis.

Matmul convention: all weights are stored [in, out] so HF GPT-2 Conv1D weights
import directly and HF Linear weights import transposed.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.attention import cached_attention, update_kv_cache
from ..ops.norms import layer_norm, rms_norm
from ..ops.rotary import apply_rope, rope_cos_sin
from .config import ModelConfig, refuse_single_pass

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------

def _dense(rng, shape, dtype, scale=0.02):
    return (scale * jax.random.normal(rng, shape, jnp.float32)).astype(dtype)


def init_layer_params(rng: jax.Array, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    """Random init for ONE layer (no leading layer axis)."""
    d, i = cfg.hidden_size, cfg.intermediate_size
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(rng, 12)
    p: Params = {
        "attn": {
            "wq": _dense(ks[0], (d, h * dh), dtype),
            "wk": _dense(ks[1], (d, hkv * dh), dtype),
            "wv": _dense(ks[2], (d, hkv * dh), dtype),
            "wo": _dense(ks[3], (h * dh, d), dtype),
        },
    }
    if cfg.norm == "layernorm":
        p["ln1"] = {"w": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)}
        p["ln2"] = {"w": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)}
    else:
        # norm_offset (gemma): stored weight is the offset from one, so the
        # identity init is zeros, not ones.
        one = jnp.zeros((d,), dtype) if cfg.norm_offset else jnp.ones((d,), dtype)
        p["ln1"] = {"w": one}
        p["ln2"] = {"w": one}
        if cfg.post_norms:  # gemma2 sandwich norms
            p["ln3"] = {"w": one}
            p["ln4"] = {"w": one}
    if cfg.eva_window:
        # The two per-head pooling vectors of a chunk's summary, drawn as
        # the published initialiser draws them: clip(N(0, 1), -1, 1) *
        # head_dim ** -0.5.
        for name, key in (("mu", ks[8]), ("phi", ks[9])):
            p["attn"][name] = (jnp.clip(jax.random.normal(
                key, (h, dh), jnp.float32), -1.0, 1.0)
                * dh ** -0.5).astype(dtype)
    if cfg.use_bias or cfg.attn_qkv_bias:
        p["attn"]["bq"] = jnp.zeros((h * dh,), dtype)
        p["attn"]["bk"] = jnp.zeros((hkv * dh,), dtype)
        p["attn"]["bv"] = jnp.zeros((hkv * dh,), dtype)
    if cfg.use_bias:
        p["attn"]["bo"] = jnp.zeros((d,), dtype)
    if cfg.is_moe:
        e = cfg.num_experts
        p["mlp"] = {
            "router": _dense(ks[4], (d, e), dtype),
            "wg": _dense(ks[5], (e, d, i), dtype),
            "wu": _dense(ks[6], (e, d, i), dtype),
            "wd": _dense(ks[7], (e, i, d), dtype),
        }
    elif cfg.mlp == "swiglu":
        p["mlp"] = {
            "wg": _dense(ks[5], (d, i), dtype),
            "wu": _dense(ks[6], (d, i), dtype),
            "wd": _dense(ks[7], (i, d), dtype),
        }
    else:  # gelu_mlp (gpt2)
        p["mlp"] = {
            "wi": _dense(ks[5], (d, i), dtype),
            "wo": _dense(ks[6], (i, d), dtype),
        }
        if cfg.use_bias:
            p["mlp"]["bi"] = jnp.zeros((i,), dtype)
            p["mlp"]["bo"] = jnp.zeros((d,), dtype)
    return p


def init_latent_layer_params(rng: jax.Array, cfg: ModelConfig, dtype,
                             dense: bool) -> Params:
    """Random init for ONE layer of a latent-attention family
    (``cfg.kv_lora_rank``): the query and key/value bottlenecks with their
    norms, the indexer (``wiq_t`` from the query bottleneck, ``wik`` and its
    LayerNorm, the per-head weights ``wiw``; none for a sliding layer,
    ``cfg.index_topk`` 0), the headwise gate ``wgate`` where the family has
    one (``cfg.attention_gate``), and either the dense SwiGLU
    (``dense``: a leading layer) or the router with its score bias, the
    HELD routed experts and the shared expert. Four weights rest with the
    contracted axis LAST, as `models.hf_import` leaves them (`_dot_t`):
    ``wqb_t``, ``wkvb_t``, ``wiq_t`` ``[heads, out a head, in]`` and
    ``wkva_t`` ``[out, in]``; every other is ``[in, out]``."""
    d, h = cfg.hidden_size, cfg.num_heads
    ql, kl, rope = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    ih, idh = cfg.index_n_heads, cfg.index_head_dim
    ks = jax.random.split(rng, 16)
    one = lambda n: jnp.ones((n,), dtype)
    p: Params = {
        "ln1": {"w": one(d)}, "ln2": {"w": one(d)},
        "attn": {
            "wqa": _dense(ks[0], (d, ql), dtype), "q_norm": {"w": one(ql)},
            "wqb_t": _dense(ks[1], (h, cfg.head_dim, ql), dtype),
            "wkva_t": _dense(ks[2], (kl + rope, d), dtype),
            "kv_norm": {"w": one(kl)},
            "wkvb_t": _dense(ks[3], (h, cfg.qk_nope_head_dim
                                     + cfg.v_head_dim, kl), dtype),
            "wo": _dense(ks[4], (h * cfg.v_head_dim, d), dtype),
        },
    }
    if cfg.index_topk:      # a layer read under the learned selection
        p["attn"].update({
            "wiq_t": _dense(ks[5], (ih, idh, ql), dtype),
            "wik": _dense(ks[6], (d, idh), dtype),
            "ik_norm": {"w": one(idh), "b": jnp.zeros((idh,), dtype)},
            "wiw": _dense(ks[7], (d, ih), dtype),
        })
    if cfg.attention_gate:
        p["attn"]["wgate"] = _dense(jax.random.fold_in(rng, 16), (d, h),
                                    dtype)
    if dense:
        i = cfg.intermediate_size
        p["mlp"] = {"wg": _dense(ks[8], (d, i), dtype),
                    "wu": _dense(ks[9], (d, i), dtype),
                    "wd": _dense(ks[10], (i, d), dtype)}
        return p
    f, held = cfg.moe_intermediate_size, cfg.held_experts[1]
    fs = f * cfg.n_shared_experts
    p["mlp"] = {
        "router": _dense(ks[8], (d, cfg.num_experts), dtype),
        "router_bias": jnp.zeros((cfg.num_experts,), jnp.float32),
        "wg": _dense(ks[9], (held, d, f), dtype),
        "wu": _dense(ks[10], (held, d, f), dtype),
        "wd": _dense(ks[11], (held, f, d), dtype),
        "shared": {"wg": _dense(ks[12], (d, fs), dtype),
                   "wu": _dense(ks[13], (d, fs), dtype),
                   "wd": _dense(ks[14], (fs, d), dtype)},
    }
    return p


def init_params(rng: jax.Array, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    """Random init of the FULL model with stacked layers. A family with
    leading dense layers (``cfg.first_k_dense``) holds TWO stacks: those
    under ``dense_layers``, the expert layers under ``layers``. One whose
    layers are of two kinds (``cfg.layer_types``) holds the sliding ones,
    whose weights have other shapes, under ``sliding_layers``, in their
    order in the stack (``cfg.layer_period``)."""
    k_emb, k_layers, k_head = jax.random.split(rng, 3)
    layer_keys = jax.random.split(k_layers, cfg.num_layers)
    dense_layers = sliding_layers = None
    if cfg.kv_lora_rank:
        k = min(cfg.first_k_dense, cfg.num_layers)
        # a layer at a time: drawn all at once, the float32 normals of five
        # layers' held experts are 12 GB beside the 9.5 GB they are cast to
        stack = lambda keys, dense, kind=cfg: jax.lax.map(
            lambda key: init_latent_layer_params(key, kind, dtype, dense),
            keys)
        dense_layers = stack(layer_keys[:k], True) if k else None
        if "sliding" in cfg.layer_kinds:
            import numpy as np

            cfg.layer_period        # refuses an order the scans do not take
            sliding = np.asarray(
                [kind == "sliding" for kind in cfg.layer_kinds[k:]])
            sliding_layers = stack(layer_keys[k:][sliding], False,
                                   cfg.sliding_kind)
            layers = stack(layer_keys[k:][~sliding], False)
        else:
            layers = stack(layer_keys[k:], False)
    else:
        layers = jax.vmap(lambda k: init_layer_params(k, cfg, dtype))(
            layer_keys)
    if cfg.altern_window:
        # gemma2: even layer indices are windowed, odd attend globally
        # (HF Gemma2Attention's layer_idx % 2 rule); 0 disables per layer.
        layers["window"] = jnp.asarray(
            [cfg.altern_window if i % 2 == 0 else 0
             for i in range(cfg.num_layers)], jnp.int32)

    embed: Params = {"wte": _dense(k_emb, (cfg.vocab_size, cfg.hidden_size), dtype)}
    if cfg.positional == "learned":
        embed["wpe"] = _dense(
            jax.random.fold_in(k_emb, 1),
            (cfg.max_position_embeddings, cfg.hidden_size),
            dtype,
        )

    if cfg.norm == "layernorm":
        final_norm = {
            "w": jnp.ones((cfg.hidden_size,), dtype),
            "b": jnp.zeros((cfg.hidden_size,), dtype),
        }
    elif cfg.norm_offset:
        final_norm = {"w": jnp.zeros((cfg.hidden_size,), dtype)}
    else:
        final_norm = {"w": jnp.ones((cfg.hidden_size,), dtype)}

    params: Params = {"embed": embed, "layers": layers, "final_norm": final_norm}
    if dense_layers is not None:
        params["dense_layers"] = dense_layers
    if sliding_layers is not None:
        params["sliding_layers"] = sliding_layers
    if not cfg.tie_word_embeddings:
        params["lm_head"] = {"w": _dense(
            k_head, (cfg.hidden_size, cfg.pred_heads * cfg.vocab_size),
            dtype)}
    if cfg.loop_steps > 1:
        # Looped stack: the early-exit gate, Linear(hidden, 1), read on the
        # normed state that closes every pass (`close_pass`).
        params["exit_gate"] = {
            "w": _dense(jax.random.fold_in(k_head, 1), (cfg.hidden_size, 1),
                        dtype),
            "b": jnp.zeros((1,), dtype),
        }
    return params


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def embed_tokens(cfg: ModelConfig, embed: Params, input_ids: jnp.ndarray,
                 positions: jnp.ndarray) -> jnp.ndarray:
    """input_ids: [B, T] int32; positions: [B, T] int32 -> hidden [B, T, D]."""
    h = jnp.take(embed["wte"], input_ids, axis=0)
    if cfg.embed_scale:
        # Gemma normalizer: sqrt(hidden) rounded to the activation dtype
        # first (HF casts the scalar before multiplying — matching the
        # rounding keeps bf16 parity exact).
        h = h * jnp.asarray(cfg.hidden_size ** 0.5).astype(h.dtype)
    if cfg.positional == "learned":
        # Clip keeps the gather in-bounds under jit; generating past
        # max_position_embeddings must be rejected by session-level max-length
        # admission control (runtime.kv_cache), not here — same contract as
        # update_kv_cache.
        pos = jnp.clip(positions, 0, cfg.max_position_embeddings - 1)
        h = h + jnp.take(embed["wpe"], pos, axis=0)
    if cfg.fp32_residual:
        h = h.astype(jnp.float32)
    return h


def _psum_if(x: jnp.ndarray, tp_axis: Optional[str]) -> jnp.ndarray:
    return jax.lax.psum(x, tp_axis) if tp_axis is not None else x


def _dot(x: jnp.ndarray, w) -> jnp.ndarray:
    """Weight matmul with quantized dispatch: a packed NF4Tensor leaf
    (left intact by dequant_tree under NF4_KERNEL=1) runs the fused Pallas
    dequant-matmul (ops.nf4_kernel); a packed QuantizedTensor leaf (left
    intact under INT8_FOLD, the default) or a QuantizedLayerView of a
    stacked one runs the scale-folded int8 epilogue (ops.int8_kernel);
    plain arrays take the ordinary matmul. One helper so every projection
    site dispatches identically."""
    from .quant import NF4Tensor, QuantizedLayerView, QuantizedTensor

    if isinstance(w, NF4Tensor):
        from ..ops.nf4_kernel import nf4_dot

        return nf4_dot(x, w)
    if isinstance(w, (QuantizedTensor, QuantizedLayerView)):
        from ..ops.int8_kernel import int8_dot

        return int8_dot(x, w)
    return x @ w


def _plain(w):
    """A weight as an array (a quantised leaf dequantised): for the
    products that take it whole or reshaped by head."""
    return w.dequant() if hasattr(w, "dequant") else w


def _dot_t(x: jnp.ndarray, w) -> jnp.ndarray:
    """``x [..., K]`` against a weight that rests with the contracted axis
    LAST, a checkpoint's own ``[out, in]``: ``w [N, K]`` gives ``[..., N]``,
    ``w [H, Dh, K]`` (a head's rows together) ``[..., H, Dh]``, every
    element the dot product ``x @ w.reshape(-1, K).T`` holds. For the
    weights the TPU does not read as ``[K, N]`` where they rest (PERF.md
    section 6, PR 57). One whose output is split inside a head straight
    after (a latent family's ``wqb_t``, ``wiq_t``): the compiler makes the
    product head-grouped and wants a head's rows together with K minor;
    handed ``[K, H * Dh]`` it re-lays the whole stack once a burst (a
    layer's slice once a layer in every other program) and stages each
    layer's slice in VMEM before its product. One whose N is no whole
    number of lane tiles (``wkva_t``: 576): the v5e holds ``[L, K, N]``
    with K minor, to pad nothing, and the program re-lays that stack too.
    A quantised leaf (its scales one an output ROW:
    `models.quant.quantize_layers`) is dequantised here; the int8 and NF4
    kernels are `_dot`'s."""
    return jnp.tensordot(x, _plain(w), axes=(-1, -1))


def qkv_proj(cfg: ModelConfig, p: Params, x: jnp.ndarray):
    """Attention projections (+ optional q/k/v biases), reshaped to heads.
    x: [B, T, D] -> q [B, T, H, Dh], k/v [B, T, Hkv, Dh]. The ONE place the
    projection layout lives — the cached, sequence-parallel, and batched
    engines all import it.

    Two layouts: canonical wq/wk/wv (checkpoint/TP layout), or a fused
    ``wqkv`` (see `fuse_qkv_layers`) — ONE matmul instead of three, the
    measured ~17% prefill win on the flagship (three output-adjacent GEMMs
    give the MXU three short weight streams instead of one long one). The
    split is proportional (H : Hkv : Hkv), so a TP-sharded local view
    would also split correctly; outputs are BITWISE identical to the
    separate matmuls (fusing along N never changes a column's K-reduction;
    verified on the CPU test rig at f32 and bf16)."""
    b, t, _ = x.shape
    dh = cfg.head_dim
    if "wqkv" in p:
        qkv = _dot(x, p["wqkv"])
        w = qkv.shape[-1]
        hd = w * cfg.num_heads // (cfg.num_heads + 2 * cfg.num_kv_heads)
        kd = (w - hd) // 2
        q = qkv[..., :hd]
        k = qkv[..., hd:hd + kd]
        v = qkv[..., hd + kd:]
    else:
        q = _dot(x, p["wq"])
        k = _dot(x, p["wk"])
        v = _dot(x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return (q.reshape(b, t, -1, dh), k.reshape(b, t, -1, dh),
            v.reshape(b, t, -1, dh))


def _concat_out_axis(leaves):
    """Concatenate projection weights along the OUTPUT axis across all
    three leaf layouts — exact for each: plain arrays concat directly
    (fusing along N never changes a column's K-reduction); QuantizedTensor
    concats q and the per-output-channel s (every output column keeps its
    own scale); NF4Tensor concats packed codes and per-block scales
    (absmax blocks live on the input axis, untouched by an N concat).
    Returns None for mixed or unfusable leaf types — the fusions no-op
    rather than guess."""
    from .quant import NF4Tensor, QuantizedTensor

    if all(isinstance(w, jax.Array) for w in leaves):
        return jnp.concatenate(leaves, axis=-1)
    if all(isinstance(w, QuantizedTensor) for w in leaves):
        if len({w.dtype for w in leaves}) != 1:
            return None
        return QuantizedTensor(
            jnp.concatenate([w.q for w in leaves], axis=-1),
            jnp.concatenate([w.s for w in leaves], axis=-1),
            leaves[0].dtype)
    if all(isinstance(w, NF4Tensor) for w in leaves):
        if (len({w.dtype for w in leaves}) != 1
                or len({w.in_dim for w in leaves}) != 1):
            return None
        return NF4Tensor(
            jnp.concatenate([w.packed for w in leaves], axis=-1),
            jnp.concatenate([w.scales for w in leaves], axis=-1),
            leaves[0].in_dim, leaves[0].dtype)
    return None


def fuse_qkv_layers(layers: Params) -> Params:
    """Return `layers` with wq|wk|wv concatenated into one ``wqkv`` leaf
    (output axis) — an ENGINE-side layout transform applied at construction
    time, never a storage format: checkpoints, TP sharding, and the
    trainer keep the canonical split layout. Quantized trees fuse too
    (`_concat_out_axis` is exact for int8 and NF4) — for the quantized
    kernels this IS the launch aggregation: three kernel dispatches per
    layer become one covering all three projections' N tiles. No-ops
    (returns the input) when the tree is already fused, mixes leaf
    types, or has no attention weights."""
    if not isinstance(layers, dict) or "attn" not in layers:
        return layers
    attn = layers["attn"]
    if "wq" not in attn:
        return layers
    wqkv = _concat_out_axis([attn["wq"], attn["wk"], attn["wv"]])
    if wqkv is None:
        return layers
    fused = {k: v for k, v in attn.items() if k not in ("wq", "wk", "wv")}
    fused["wqkv"] = wqkv
    out = dict(layers)
    out["attn"] = fused
    return out


def fuse_gate_up_layers(layers: Params) -> Params:
    """Return `layers` with the swiglu wg|wu concatenated into one ``wgu``
    leaf (output axis) — the MLP analogue of `fuse_qkv_layers`: two
    output-adjacent GEMMs sharing the same input become ONE matmul with
    one long weight stream. Bitwise identical (concat along N never
    changes a column's K-reduction). Same engine-side-only contract and
    guards as the QKV fusion."""
    if not isinstance(layers, dict) or "mlp" not in layers:
        return layers
    mlp = layers["mlp"]
    if "wg" not in mlp or "wu" not in mlp:
        return layers
    if "router" in mlp:              # MoE expert weights keep canonical
        return layers
    wgu = _concat_out_axis([mlp["wg"], mlp["wu"]])
    if wgu is None:
        return layers
    fused = {k: v for k, v in mlp.items() if k not in ("wg", "wu")}
    fused["wgu"] = wgu
    out = dict(layers)
    out["mlp"] = fused
    return out


def fuse_qkv_params(params: Params) -> Params:
    """Engine-construction wrapper over `fuse_qkv_layers` +
    `fuse_gate_up_layers` for a whole param tree (the one place the guard
    lives — five engines apply it).

    Memory note: the fused leaves are COPIES; if the caller keeps its
    canonical tree alive (e.g. one checkpoint feeding several engines),
    both layouts stay resident — drop the caller-side reference after
    construction when projection-weight residency matters."""
    if not isinstance(params, dict) or "layers" not in params:
        return params
    fused = fuse_gate_up_layers(fuse_qkv_layers(params["layers"]))
    if fused is params["layers"]:
        return params
    return dict(params, layers=fused)


def _mlp(cfg: ModelConfig, p: Params, x: jnp.ndarray, tp_axis: Optional[str]) -> jnp.ndarray:
    if cfg.is_moe and "router" in p:     # not a leading dense layer's
        return _moe_mlp(cfg, p, x, tp_axis)
    if cfg.mlp == "swiglu":
        # Gate activation: silu (llama family) or tanh-gelu (gemma GeGLU).
        act = (partial(jax.nn.gelu, approximate=True)
               if cfg.activation == "gelu_tanh" else jax.nn.silu)
        if "wgu" in p:               # engine-fused layout (fuse_gate_up)
            gu = _dot(x, p["wgu"])
            i = gu.shape[-1] // 2
            gate = act(gu[..., :i])
            up = gu[..., i:]
        else:
            gate = act(_dot(x, p["wg"]))
            up = _dot(x, p["wu"])
        return _psum_if(_dot(gate * up, p["wd"]), tp_axis)
    y = _dot(x, p["wi"])
    if "bi" in p:
        y = y + p["bi"]
    y = jax.nn.gelu(y, approximate=True)  # gpt2 uses gelu_new (tanh approx)
    y = _psum_if(_dot(y, p["wo"]), tp_axis)
    if "bo" in p:
        y = y + p["bo"]
    return y


def _moe_mlp(cfg: ModelConfig, p: Params, x: jnp.ndarray, tp_axis: Optional[str]) -> jnp.ndarray:
    """Mixtral-style top-k routed SwiGLU experts.

    Default: the sparse sort-by-expert grouped-matmul dispatch
    (models.moe.sparse_moe_mlp) — executed MLP FLOPs proportional to
    top_k/num_experts. MOE_SPARSE=0 falls back to the dense all-expert
    formulation below, bit-for-bit the pre-dispatch behavior (tiny-model
    fallback and kill switch). Both read the switch at trace time, so a
    jitted engine picks its path when it first compiles."""
    from .moe import moe_sparse_enabled, sparse_moe_mlp

    if moe_sparse_enabled():
        return sparse_moe_mlp(cfg, p, x, tp_axis)
    return _moe_mlp_dense(cfg, p, x, tp_axis)


def _moe_mlp_dense(cfg: ModelConfig, p: Params, x: jnp.ndarray, tp_axis: Optional[str]) -> jnp.ndarray:
    """Dense MoE formulation: every expert runs on every token and the router
    weights zero out the non-selected ones. All-expert einsums keep the MXU
    busy with static shapes — MLP FLOPs scale with num_experts, so this is
    the tiny-model fallback behind MOE_SPARSE=0 (the reference has no
    runnable MoE at all — only config guards, ``src/llama_partition.py:82``).
    """
    router_logits = x.astype(jnp.float32) @ p["router"].astype(jnp.float32)  # [B,T,E]
    topv, topi = jax.lax.top_k(router_logits, cfg.num_experts_per_tok)
    weights = jax.nn.softmax(topv, axis=-1)  # normalized over selected experts
    # scatter normalized weights back to a dense [B,T,E] map
    dense_w = jnp.zeros_like(router_logits)
    b, t, _ = router_logits.shape
    dense_w = dense_w.at[
        jnp.arange(b)[:, None, None],
        jnp.arange(t)[None, :, None],
        topi,
    ].set(weights)

    # Expert parallelism: when the expert weights are sharded over tp_axis
    # (router stays replicated so the top-k is global), each device computes
    # its local experts' contribution and the closing psum combines them.
    e_local = p["wg"].shape[0]
    if tp_axis is not None and e_local != cfg.num_experts:
        offset = jax.lax.axis_index(tp_axis) * e_local
        dense_w = jax.lax.dynamic_slice_in_dim(dense_w, offset, e_local, axis=2)

    gate = jax.nn.silu(jnp.einsum("btd,edi->btei", x, p["wg"]))
    up = jnp.einsum("btd,edi->btei", x, p["wu"])
    per_expert = jnp.einsum("btei,eid->bted", gate * up, p["wd"])
    out = jnp.einsum("bted,bte->btd", per_expert, dense_w.astype(x.dtype))
    return _psum_if(out, tp_axis)


def make_rope(cfg: ModelConfig, positions: jnp.ndarray):
    """cos/sin tables for a batch of positions, or None for non-RoPE models.

    Computed ONCE per forward and threaded through every layer — inside a
    lax.scan body XLA won't hoist the transcendentals, so recomputing per
    layer would cost num_layers rebuilds (80x for llama-3-70b)."""
    if cfg.positional != "rope":
        return None
    # a latent-attention family rotates ``qk_rope_head_dim`` of a head's dims
    return rope_cos_sin(positions, cfg.qk_rope_head_dim or cfg.head_dim,
                        cfg.rope_theta, cfg.rope_scaling)


def _attention(
    cfg: ModelConfig,
    p: Params,
    x: jnp.ndarray,
    rope,
    k_cache: Optional[jnp.ndarray],
    v_cache: Optional[jnp.ndarray],
    cache_len: jnp.ndarray,
    tp_axis: Optional[str],
    window=None,
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray], Optional[jnp.ndarray]]:
    """k_cache=None selects the cache-free training path: causal attention of
    the fresh keys over themselves (same math as a cache of length T at
    position 0), nothing persisted."""
    b, t, _ = x.shape
    dh = cfg.head_dim
    q, k, v = qkv_proj(cfg, p, x)
    h_local = q.shape[2]

    if rope is not None:
        cos, sin = rope
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

    if k_cache is None:
        out = cached_attention(
            q, k, v, jnp.int32(0), sliding_window=window,
            scale=cfg.query_scale, logit_softcap=cfg.attn_softcap,
        )
    else:
        k_cache, v_cache = update_kv_cache(k_cache, v_cache, k, v, cache_len)
        out = cached_attention(
            q, k_cache, v_cache, cache_len,
            sliding_window=window,
            scale=cfg.query_scale, logit_softcap=cfg.attn_softcap,
        )
    y = _dot(out.reshape(b, t, h_local * dh), p["wo"])
    y = _psum_if(y, tp_axis)
    if "bo" in p:
        y = y + p["bo"]
    return y, k_cache, v_cache


def _norm(cfg: ModelConfig, p: Params, x: jnp.ndarray) -> jnp.ndarray:
    if cfg.norm == "layernorm":
        return layer_norm(x, p["w"], p["b"], cfg.norm_eps)
    if cfg.norm_offset:
        # Gemma convention: stored weight is the offset from one (the
        # add runs in rms_norm's f32 accumulation lane).
        return rms_norm(x, 1.0 + p["w"].astype(jnp.float32), cfg.norm_eps)
    return rms_norm(x, p["w"], cfg.norm_eps)


def layer_forward(
    cfg: ModelConfig,
    p: Params,
    x: jnp.ndarray,
    rope,
    k_cache: Optional[jnp.ndarray],
    v_cache: Optional[jnp.ndarray],
    cache_len: jnp.ndarray,
    tp_axis: Optional[str] = None,
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray], Optional[jnp.ndarray]]:
    """Pre-norm residual block. x: [B,T,D] -> ([B,T,D], new k/v cache).

    rope: (cos, sin) from `make_rope`, or None for learned-position models.
    k_cache=None selects the cache-free training path (see `_attention`).
    """
    from .quant import dequant_tree

    # int8-serving hook: materialize full-precision weights for any
    # QuantizedTensor leaves. Inside lax.scan this runs per layer, so only
    # one layer's dequantized weights exist at a time (models/quant.py).
    # keep_experts: on the sparse MoE path, 3-D expert stacks stay packed —
    # the grouped matmuls dequantize per expert (models/moe._expert_dot).
    p = dequant_tree(p, keep_experts=cfg.is_moe)
    # Per-layer window (gemma2 alternating local/global): a traced int32
    # "window" leaf on the layer tree — every engine's layer scan slices it
    # alongside the weights; <= 0 means global attention in this layer.
    window = p.get("window", cfg.sliding_window)
    attn_out, k_cache, v_cache = _attention(
        cfg, p["attn"], _norm(cfg, p["ln1"], x), rope, k_cache, v_cache,
        cache_len, tp_axis, window=window,
    )
    if cfg.post_norms:
        # Sandwich norms (gemma2): post-norm each sublayer's output before
        # the residual add.
        attn_out = _norm(cfg, p["ln3"], attn_out)
    x = x + attn_out
    mlp_out = _mlp(cfg, p["mlp"], _norm(cfg, p["ln2"], x), tp_axis)
    if cfg.post_norms:
        mlp_out = _norm(cfg, p["ln4"], mlp_out)
    x = x + mlp_out
    return x, k_cache, v_cache


def _apply_deep_prompt(
    h: jnp.ndarray, pr: jnp.ndarray, cache_len: jnp.ndarray
) -> jnp.ndarray:
    """Add a learned per-layer deep prompt to ABSOLUTE positions < pre_seq.

    h: [B, T, D] hidden states occupying absolute positions
    cache_len .. cache_len+T; pr: [pre_seq, D]. The vendored semantics add
    prompts to the first pre_seq positions of each block's input
    (``petals/server/backend.py:226-233``, ``block_functions.py:57-65``) —
    petals slices chunk-relative, which coincides with absolute positions
    because its inference prompts ride only the position-0 prefill step;
    absolute indexing generalizes the same contract to chunked prefill and
    makes decode steps past the prompt region an exact no-op.
    """
    t = h.shape[1]
    pre = pr.shape[0]
    idx = cache_len + jnp.arange(t, dtype=jnp.int32)          # [T] absolute
    rows = jnp.take(pr, jnp.clip(idx, 0, pre - 1), axis=0)    # [T, D]
    add = jnp.where((idx < pre)[:, None], rows, 0).astype(h.dtype)
    return h + add[None]


def stack_forward(
    cfg: ModelConfig,
    layers: Params,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    k_caches: jnp.ndarray,
    v_caches: jnp.ndarray,
    cache_len: jnp.ndarray,
    tp_axis: Optional[str] = None,
    prompts: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Run a span of stacked layers via lax.scan.

    layers: pytree with leading layer axis L. k_caches/v_caches: [L,B,S,Hkv,Dh].
    prompts: optional [L, pre_seq, D] inference-time deep prompts, added
    into each layer's input at absolute positions < pre_seq
    (`_apply_deep_prompt`; the petals rpc_forward/inference injection).

    Decode steps (T == 1, static under jit) carry the caches through the
    scan and update one layer's rows in place via dynamic indexing instead
    of threading them as xs/ys — the xs/ys structure makes XLA rewrite
    every layer's WHOLE cache every step, slope-measured 1.5x slower at
    long caches (see runtime/fused_decode.py and docs/PERFORMANCE.md).
    Identical math either way; prefill (T > 1, cache traffic amortized
    over T tokens) keeps the simpler xs/ys form.
    """
    rope = make_rope(cfg, positions)

    if x.shape[1] == 1:
        L = k_caches.shape[0]

        def body1(carry, xs):
            h, kc, vc = carry
            li, lp = xs[0], xs[1]
            if prompts is not None:
                h = _apply_deep_prompt(h, xs[2], cache_len)
            kci = jax.lax.dynamic_index_in_dim(kc, li, 0, keepdims=False)
            vci = jax.lax.dynamic_index_in_dim(vc, li, 0, keepdims=False)
            h, kci, vci = layer_forward(cfg, lp, h, rope, kci, vci,
                                        cache_len, tp_axis)
            kc = jax.lax.dynamic_update_index_in_dim(kc, kci, li, 0)
            vc = jax.lax.dynamic_update_index_in_dim(vc, vci, li, 0)
            return (h, kc, vc), None

        xs = (jnp.arange(L, dtype=jnp.int32), layers)
        if prompts is not None:
            xs = xs + (prompts,)
        (x, k_caches, v_caches), _ = jax.lax.scan(
            body1, (x, k_caches, v_caches), xs)
        return x, k_caches, v_caches

    def body(h, xs):
        lp, kc, vc = xs[0], xs[1], xs[2]
        if prompts is not None:
            h = _apply_deep_prompt(h, xs[3], cache_len)
        h, kc, vc = layer_forward(cfg, lp, h, rope, kc, vc, cache_len, tp_axis)
        return h, (kc, vc)

    xs = (layers, k_caches, v_caches)
    if prompts is not None:
        xs = xs + (prompts,)
    x, (k_caches, v_caches) = jax.lax.scan(body, x, xs)
    return x, k_caches, v_caches


def layer_forward_train(
    cfg: ModelConfig,
    p: Params,
    x: jnp.ndarray,
    rope,
    tp_axis: Optional[str] = None,
) -> jnp.ndarray:
    """Cache-free pre-norm block for the training path (full-sequence causal
    attention, nothing persisted). Counterpart of the vendored backward path's
    re-forward (reference ``petals/server/block_functions.py:106-124``)."""
    x, _, _ = layer_forward(cfg, p, x, rope, None, None, jnp.int32(0), tp_axis)
    return x


def stack_forward_train(
    cfg: ModelConfig,
    layers: Params,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    tp_axis: Optional[str] = None,
    remat: bool = True,
    prompts: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Training forward of a span of stacked layers (lax.scan, no KV cache).

    remat=True checkpoints each layer — reverse-mode AD recomputes the layer
    forward instead of saving every intermediate (HBM for FLOPs, the standard
    TPU training trade).

    prompts: optional [L, pre_seq, D] deep-prompt-tuning tensors, ADDED into
    the first pre_seq positions of each layer's input (the vendored semantics,
    ``petals/server/block_functions.py:57-65``)."""
    refuse_single_pass(cfg, "the training forward")
    rope = make_rope(cfg, positions)

    if prompts is None:
        def body(h, lp):
            return layer_forward_train(cfg, lp, h, rope, tp_axis), None

        if remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, layers)
        return x

    # Clamp to the sequence length (static at trace time): a batch shorter
    # than pre_seq simply uses the prompts' first T rows; the unused tail gets
    # zero gradients, so client-local and (bucket-padded) server spans agree.
    pre = min(prompts.shape[1], x.shape[1])

    def body_p(h, xs):
        lp, pr = xs
        patch = jax.lax.dynamic_slice_in_dim(h, 0, pre, axis=1) + pr[None, :pre]
        h = jax.lax.dynamic_update_slice_in_dim(h, patch.astype(h.dtype), 0, axis=1)
        return layer_forward_train(cfg, lp, h, rope, tp_axis), None

    if remat:
        body_p = jax.checkpoint(body_p)
    x, _ = jax.lax.scan(body_p, x, (layers, prompts))
    return x


def lm_head(cfg: ModelConfig, params: Params, x: jnp.ndarray,
            normed: bool = False) -> jnp.ndarray:
    """Final norm + projection to vocab. x: [B,T,D] -> [B,T,V] float32.
    ``normed``: x already passed the final norm (a looped stack's state,
    which `close_pass` normed at the end of its pass): projection only."""
    if not normed:
        x = _norm(cfg, params["final_norm"], x)
    if cfg.tie_word_embeddings:
        w = params["embed"]["wte"].T
    else:
        w = params["lm_head"]["w"]
        if cfg.pred_heads > 1:      # next-token decoding: head 0 alone
            w = w[:, :cfg.vocab_size]
    logits = x.astype(jnp.float32) @ w.astype(jnp.float32)
    if cfg.final_softcap:
        # gemma2 final-logit softcapping.
        logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
    return logits


def init_kv_cache(
    cfg: ModelConfig, num_layers: int, batch: int, max_len: int, dtype=jnp.float32
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Zeroed K and V stacks for ``num_layers`` layers of WEIGHTS: a looped
    stack keeps rows of its own for every (pass, layer), pass-major, so its
    stacks are ``cfg.loop_steps`` times as deep."""
    shape = (num_layers * cfg.loop_steps, batch, max_len, cfg.num_kv_heads,
             cfg.head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


# ---------------------------------------------------------------------------
# Looped stack (cfg.loop_steps > 1): what closes a pass, and the oracle
# ---------------------------------------------------------------------------

def exit_state(h: jnp.ndarray):
    """The exit rule's state before the first pass, for hidden ``h``
    ``[B, T, D]``: (chosen state, the passes it took or 0 while none is
    chosen, probability of still being in the loop, cumulative exit
    probability), per token."""
    b, t, _ = h.shape
    return (jnp.zeros_like(h), jnp.zeros((b, t), jnp.int32),
            jnp.ones((b, t), jnp.float32), jnp.zeros((b, t), jnp.float32))


def close_pass(cfg: ModelConfig, params: Params, x: jnp.ndarray, t, state):
    """What closes pass ``t`` (a Python int or a traced index) of a looped
    stack: ``(h, state, gate)``. ``h`` is the model's final norm of the
    pass's output ``x``, the next pass's input; ``gate`` the exit gate's
    logit on it (float32, ``[B, T]``). The exit rule: with ``lambda =
    sigmoid(gate)`` the probability of leaving at pass t is ``lambda_t *
    prod_{j<t}(1 - lambda_j)``, and a token's state for the head is that of
    the FIRST pass at which the cumulative probability reaches
    ``cfg.exit_threshold``, else the last pass's. ``state`` (`exit_state`)
    carries the choice; every pass still runs for every token."""
    with jax.named_scope("loop_norm"):
        h = _norm(cfg, params["final_norm"], x)
    with jax.named_scope("exit_gate"):
        gp = params["exit_gate"]
        gate = (h.astype(jnp.float32) @ gp["w"].astype(jnp.float32)[:, 0]
                + gp["b"].astype(jnp.float32)[0])
        lam = jax.nn.sigmoid(gate)
        chosen, steps, stay, cum = state
        cum = cum + lam * stay
        take = (steps == 0) & ((cum >= cfg.exit_threshold)
                               | (t == cfg.loop_steps - 1))
        chosen = jnp.where(take[..., None], h, chosen)
        steps = jnp.where(take, jnp.asarray(t, jnp.int32) + 1, steps)
        state = (chosen, steps, stay * (1.0 - lam), cum)
    return h, state, gate


def looped_forward(
    cfg: ModelConfig,
    params: Params,
    input_ids: jnp.ndarray,
    k_caches: jnp.ndarray,
    v_caches: jnp.ndarray,
    cache_len: jnp.ndarray,
):
    """The looped model's oracle, pass by pass in Python: ``(h, k_caches,
    v_caches, gates, steps)`` with ``h`` the chosen NORMED state per token
    (``lm_head(..., normed=True)`` projects it), ``gates`` ``[loop_steps,
    B, T]`` and ``steps`` the passes each token took. The caches hold
    ``loop_steps * num_layers`` layers, pass-major (`init_kv_cache`)."""
    n = cfg.num_layers
    if k_caches.shape[0] != cfg.loop_steps * n:
        raise ValueError(
            f"a looped stack of {n} layers x {cfg.loop_steps} passes needs "
            f"{cfg.loop_steps * n} cache layers, got {k_caches.shape[0]} "
            "(init_kv_cache sizes them)")
    t = input_ids.shape[1]
    positions = cache_len + jnp.arange(t, dtype=jnp.int32)[None, :]
    h = embed_tokens(cfg, params["embed"], input_ids, positions)
    state, gates, ks, vs = exit_state(h), [], [], []
    for p in range(cfg.loop_steps):
        x, kc, vc = stack_forward(
            cfg, params["layers"], h, positions, k_caches[p * n:(p + 1) * n],
            v_caches[p * n:(p + 1) * n], cache_len)
        h, state, gate = close_pass(cfg, params, x, p, state)
        gates.append(gate)
        ks.append(kc)
        vs.append(vc)
    return (state[0], jnp.concatenate(ks), jnp.concatenate(vs),
            jnp.stack(gates), state[1])


def full_forward(
    cfg: ModelConfig,
    params: Params,
    input_ids: jnp.ndarray,
    k_caches: jnp.ndarray,
    v_caches: jnp.ndarray,
    cache_len: jnp.ndarray,
    prompts: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Whole unpartitioned model (the single-device oracle path, mirroring
    reference ``scripts/single_gpu_check.py``). Returns (logits, new caches).
    prompts: optional [num_layers, pre_seq, D] deep prompts (the monolithic
    oracle for the distributed inference-time injection). A looped stack
    (``cfg.loop_steps > 1``) goes through `looped_forward`."""
    if cfg.eva_window:
        refuse_single_pass(cfg, "the in-program oracle")
    if cfg.loop_steps > 1:
        if prompts is not None:
            raise NotImplementedError(
                "deep prompts are per layer of ONE pass; a looped stack has "
                "no rule for them")
        h, k_caches, v_caches, _, _ = looped_forward(
            cfg, params, input_ids, k_caches, v_caches, cache_len)
        return lm_head(cfg, params, h, normed=True), k_caches, v_caches
    b, t = input_ids.shape
    positions = cache_len + jnp.arange(t, dtype=jnp.int32)[None, :]
    x = embed_tokens(cfg, params["embed"], input_ids, positions)
    x, k_caches, v_caches = stack_forward(
        cfg, params["layers"], x, positions, k_caches, v_caches, cache_len,
        prompts=prompts,
    )
    return lm_head(cfg, params, x), k_caches, v_caches
