"""Model architecture configs for the supported decoder families.

The reference supports the HF ``llama``/``mistral``/``mixtral`` model types plus
GPT-2 (guards at reference ``src/llama_partition.py:82-93``). Here each family is
described by one dataclass consumed by a single unified decoder implementation
(`models.transformer`) instead of family-specific nn.Module classes.
"""

from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture hyperparameters for one decoder-only transformer family."""

    # "gpt2" | "llama" | "mistral" | "mixtral" | "qwen2" | "gemma" | "ouro"
    # | "evabyte" | "glm_moe_dsa" | "dots3_note"
    model_type: str
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    intermediate_size: int
    max_position_embeddings: int = 2048

    # Architectural switches
    norm: str = "rmsnorm"          # "layernorm" (gpt2) | "rmsnorm" (llama family)
    positional: str = "rope"       # "learned" (gpt2) | "rope"
    activation: str = "silu"       # "gelu" (gpt2) | "silu"
    mlp: str = "swiglu"            # "gelu_mlp" (gpt2: fc->act->proj) | "swiglu"
    use_bias: bool = False         # gpt2 uses biases everywhere; llama none
    attn_qkv_bias: bool = False    # qwen2: biases on q/k/v ONLY (not o, not mlp)
    tie_word_embeddings: bool = True
    rope_theta: float = 10000.0
    # Llama-3.1-style RoPE frequency scaling (HF rope_scaling type "llama3"):
    # (factor, low_freq_factor, high_freq_factor,
    #  original_max_position_embeddings). None = unscaled RoPE. A tuple, not
    # a dict, so the frozen config stays hashable.
    rope_scaling: Optional[tuple] = None
    norm_eps: float = 1e-5
    sliding_window: Optional[int] = None  # mistral

    # MoE (mixtral)
    num_experts: int = 0
    num_experts_per_tok: int = 2

    # Gemma-family switches:
    # head_dim decoupled from hidden_size/num_heads (gemma-7b: hidden 3072,
    # 16 heads, head_dim 256 — the projections are [D, H*Dh] with
    # H*Dh != D). None = the usual hidden/heads.
    head_dim_override: Optional[int] = None
    # RMSNorm weights stored as an OFFSET from one: effective scale is
    # (1 + w), zero-init (the HF Gemma convention — keeping the stored
    # layout means convert_state_dict needs no rewrite pass).
    norm_offset: bool = False
    # Multiply token embeddings by sqrt(hidden_size) (Gemma "normalizer").
    embed_scale: bool = False

    # Gemma-2 switches:
    # Sandwich norms: each sublayer output passes a POST-norm before the
    # residual add (ln3 after attention, ln4 after the MLP).
    post_norms: bool = False
    # Logit softcapping, cap * tanh(x / cap): on attention scores pre-mask
    # (attn) and on the LM head output (final). 0 = off.
    attn_softcap: float = 0.0
    final_softcap: float = 0.0
    # Attention score scale override (query_pre_attn_scalar ** -0.5);
    # 0 = the usual head_dim ** -0.5.
    query_scale: float = 0.0
    # Alternating local/global attention: EVEN layer indices use this
    # sliding window, odd layers attend globally (HF Gemma2 layout). The
    # per-layer window rides the layer param tree as a "window" leaf so
    # every engine's layer scan sees it. 0 = off.
    altern_window: int = 0

    # Looped ("universal transformer") stack: the SAME num_layers layers run
    # loop_steps times a token, the model's final norm closing every pass
    # and feeding the next, each (pass, layer) with K/V rows of its own
    # (cache depth loop_steps * num_layers for num_layers of weights). A
    # learned gate read after every pass picks, per token, the pass whose
    # state goes to the head: the first whose cumulative exit probability
    # reaches exit_threshold, else the last. Every pass always runs and
    # writes its K/V. 1 = the stack runs once (every other family).
    loop_steps: int = 1
    exit_threshold: float = 1.0

    # Windowed attention whose older rows are summaries (EVA, as EvaByte
    # specialises it): positions fall into windows of eva_window and chunks
    # of eva_chunk; a query takes ONE softmax over the exact keys of its
    # OWN window up to itself and one summary row per chunk of every
    # EARLIER window. A complete chunk's summary is two learned poolings of
    # its rotated keys and its values, by the layer's per-head vectors
    # ``attn.mu`` (keys) and ``attn.phi`` (values), each ``[H, Dh]``. A
    # session holds, a layer, eva_window exact rows and one summary row per
    # eva_chunk earlier positions. 0 = every position keeps its own row.
    eva_window: int = 0
    eva_chunk: int = 0
    # The residual stream is carried in float32 whatever the weights'
    # type (EvaByte's fp32_skip_add); the norms hand the matmuls the
    # weights' type back.
    fp32_residual: bool = False
    # Prediction heads the untied head holds, ``[D, pred_heads * V]`` with
    # head 0 first (EvaByte's multi-byte heads). Plain next-token decoding
    # projects by head 0's V columns only; the others are held, not served.
    pred_heads: int = 1

    # Latent attention (MLA) under a learned sparse selection, as GLM-5
    # (``glm_moe_dsa``) publishes it. ``kv_lora_rank`` > 0: a position keeps,
    # a layer, ONE latent row of ``kv_lora_rank + qk_rope_head_dim`` numbers
    # (the normed compressed K/V and the one rotated key all heads share)
    # and ONE index key of ``index_head_dim``; queries come through a
    # ``q_lora_rank`` bottleneck, a head is ``qk_nope_head_dim +
    # qk_rope_head_dim`` wide against keys and ``v_head_dim`` against values;
    # an indexer of ``index_n_heads`` heads scores every earlier position
    # and the query attends to the ``index_topk`` best (all, while it has no
    # more). Only the full-span batched engine holds that state.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # Two kinds of latent layer ALTERNATING in one stack, as ``dots3_note``
    # publishes them. ``layer_types`` names every layer of the published
    # stack ``"full_attention"`` (the geometry above, under the selection)
    # or ``"sliding_attention"``: latent attention of ANOTHER geometry (the
    # ``swa_*`` keys: its own head count, ranks, head widths and RoPE base),
    # no indexer, each query reading the newest ``sliding_window_size``
    # positions, its own among them. A sliding layer's slot holds a RING of
    # that many latent rows (position p at row ``p % sliding_window_size``)
    # and never a row a position; a full layer's holds the slot's length.
    # `sliding_kind` is the configuration a sliding layer's body runs
    # under. ``attention_gate`` / ``swa_attention_gate``: head h's output
    # times ``sigmoid(a W_g)_h`` before the output projection;
    # ``lora_rescale``: the normed bottlenecks times ``(hidden_size /
    # rank) ** 0.5``.
    layer_types: Optional[tuple] = None
    sliding_window_size: int = 0
    swa_num_heads: int = 0
    swa_q_lora_rank: int = 0
    swa_kv_lora_rank: int = 0
    swa_qk_nope_head_dim: int = 0
    swa_qk_rope_head_dim: int = 0
    swa_v_head_dim: int = 0
    swa_rope_theta: float = 0.0
    attention_gate: bool = False
    swa_attention_gate: bool = False
    lora_rescale: bool = False
    # Sigmoid-routed experts beside a shared one (``moe_intermediate_size``
    # > 0; ``num_experts`` routed, ``num_experts_per_tok`` taken by score +
    # bias, their scores normalised and scaled by
    # ``routed_scaling_factor``), behind ``first_k_dense`` leading layers
    # whose MLP is the dense ``intermediate_size`` SwiGLU. ``experts_held``
    # = ``(first, count)``: the routed experts THIS deployment's chip holds;
    # the router scores all ``num_experts`` and a token's assignments to an
    # expert held elsewhere add nothing here. None = all.
    moe_intermediate_size: int = 0
    n_shared_experts: int = 0
    routed_scaling_factor: float = 1.0
    first_k_dense: int = 0
    experts_held: Optional[tuple] = None

    @property
    def held_experts(self) -> tuple:
        """``(first, count)`` of the routed experts this config holds."""
        return self.experts_held or (0, self.num_experts)

    @property
    def layer_kinds(self) -> tuple:
        """``"full"`` or ``"sliding"`` for each of the ``num_layers`` layers
        held (the first of ``layer_types``; all full without it)."""
        types = (self.layer_types or ())[:self.num_layers]
        return tuple("sliding" if t == "sliding_attention" else "full"
                     for t in types) or ("full",) * self.num_layers

    @property
    def layer_period(self) -> tuple:
        """``(leading, periods, sliding a period)`` of the layers held: the
        ``first_k_dense`` leading layers (full attention, a dense MLP), then
        ``periods`` times ONE full layer and ``sliding a period`` sliding
        ones. The form the engine's scans take the stack in
        (`runtime.batching._layer_groups`); any other order is refused."""
        kinds = self.layer_kinds
        lead = min(self.first_k_dense, len(kinds))
        rest = kinds[lead:]
        n = rest[1:].index("full") if "full" in rest[1:] else len(rest) - 1
        periods = len(rest) // (n + 1) if rest else 0
        if ("sliding" in kinds[:lead]
                or rest != (("full",) + ("sliding",) * n) * periods):
            raise NotImplementedError(
                f"layers {list(kinds)} behind {lead} leading dense "
                "layer(s): the stack is taken as whole periods of one full "
                "layer and the sliding layers behind it (cut --num_layers "
                "to a whole number of periods)")
        return lead, periods, n

    @property
    def sliding_kind(self) -> "ModelConfig":
        """The configuration a ``"sliding_attention"`` layer's body runs
        under: the ``swa_*`` geometry in the keys every latent layer reads,
        no indexer (``index_topk`` 0) and ``sliding_window`` rows visible.
        Only `runtime.batching`'s layer body is ever handed it."""
        return dataclasses.replace(
            self, layer_types=None, num_heads=self.swa_num_heads,
            num_kv_heads=self.swa_num_heads,
            head_dim_override=(self.swa_qk_nope_head_dim
                               + self.swa_qk_rope_head_dim),
            q_lora_rank=self.swa_q_lora_rank,
            kv_lora_rank=self.swa_kv_lora_rank,
            qk_nope_head_dim=self.swa_qk_nope_head_dim,
            qk_rope_head_dim=self.swa_qk_rope_head_dim,
            v_head_dim=self.swa_v_head_dim, rope_theta=self.swa_rope_theta,
            attention_gate=self.swa_attention_gate, index_n_heads=0,
            index_head_dim=0, index_topk=0,
            sliding_window=self.sliding_window_size)

    @property
    def head_dim(self) -> int:
        return (self.head_dim_override
                if self.head_dim_override is not None
                else self.hidden_size // self.num_heads)

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    def __post_init__(self):
        if self.head_dim_override is None:
            assert self.hidden_size % self.num_heads == 0
        assert self.num_heads % self.num_kv_heads == 0
        assert self.loop_steps >= 1
        if self.eva_window:
            assert self.eva_chunk > 0 and self.eva_window % self.eva_chunk == 0
            # a summary is per KV head and pooled by a vector per query head
            assert self.num_kv_heads == self.num_heads
            assert self.loop_steps == 1 and not self.sliding_window
        if self.kv_lora_rank:
            # a latent layer is read under the learned selection or, a
            # sliding one, over its window: the engine has no third read
            assert self.index_topk > 0 or (self.sliding_window or 0) > 0
            assert self.loop_steps == 1 and not self.eva_window
        if self.layer_types and "sliding_attention" in self.layer_types:
            assert self.kv_lora_rank and self.swa_kv_lora_rank
            assert self.sliding_window_size > 0 and not self.sliding_window
            assert len(self.layer_types) >= self.num_layers
        if self.moe_intermediate_size:
            first, count = self.held_experts
            assert 0 <= first and first + count <= self.num_experts


def gpt2_config(
    vocab_size: int = 50257,
    hidden_size: int = 768,
    num_layers: int = 12,
    num_heads: int = 12,
    max_position_embeddings: int = 1024,
    intermediate_size: Optional[int] = None,
    norm_eps: float = 1e-5,
) -> ModelConfig:
    return ModelConfig(
        model_type="gpt2",
        vocab_size=vocab_size,
        hidden_size=hidden_size,
        num_layers=num_layers,
        num_heads=num_heads,
        num_kv_heads=num_heads,
        intermediate_size=intermediate_size or 4 * hidden_size,
        max_position_embeddings=max_position_embeddings,
        norm="layernorm",
        positional="learned",
        activation="gelu",
        mlp="gelu_mlp",
        use_bias=True,
        tie_word_embeddings=True,
        norm_eps=norm_eps,
    )


def llama_config(
    vocab_size: int = 32000,
    hidden_size: int = 4096,
    num_layers: int = 32,
    num_heads: int = 32,
    num_kv_heads: int = 8,
    intermediate_size: int = 11008,
    max_position_embeddings: int = 4096,
    rope_theta: float = 10000.0,
    tie_word_embeddings: bool = False,
    norm_eps: float = 1e-5,
) -> ModelConfig:
    return ModelConfig(
        model_type="llama",
        vocab_size=vocab_size,
        hidden_size=hidden_size,
        num_layers=num_layers,
        num_heads=num_heads,
        num_kv_heads=num_kv_heads,
        intermediate_size=intermediate_size,
        max_position_embeddings=max_position_embeddings,
        norm="rmsnorm",
        positional="rope",
        activation="silu",
        mlp="swiglu",
        use_bias=False,
        tie_word_embeddings=tie_word_embeddings,
        rope_theta=rope_theta,
        norm_eps=norm_eps,
    )


def mistral_config(sliding_window: Optional[int] = 4096, **kw) -> ModelConfig:
    cfg = llama_config(**kw)
    return dataclasses.replace(cfg, model_type="mistral", sliding_window=sliding_window)


def qwen2_config(norm_eps: float = 1e-6, **kw) -> ModelConfig:
    """Qwen2/Qwen2.5: LLaMA architecture + biases on the q/k/v projections
    (and rms eps 1e-6). Extends the reference's model-family guard
    (``src/llama_partition.py:82-83`` accepts llama/mistral/mixtral only)."""
    cfg = llama_config(norm_eps=norm_eps, **kw)
    return dataclasses.replace(cfg, model_type="qwen2", attn_qkv_bias=True)


def gemma_config(head_dim: int = 256, norm_eps: float = 1e-6,
                 rope_theta: float = 10000.0,
                 tie_word_embeddings: bool = True, **kw) -> ModelConfig:
    """Gemma (1): LLaMA skeleton with four architectural twists — GeGLU
    (tanh-gelu gate in the gated MLP), RMSNorm as a (1 + w) offset scale,
    token embeddings multiplied by sqrt(hidden), and head_dim decoupled
    from hidden/heads. Extends the reference's model-family guard
    (``src/llama_partition.py:82-83`` accepts llama/mistral/mixtral only).
    """
    cfg = llama_config(norm_eps=norm_eps, rope_theta=rope_theta,
                       tie_word_embeddings=tie_word_embeddings, **kw)
    return dataclasses.replace(
        cfg, model_type="gemma", activation="gelu_tanh",
        head_dim_override=head_dim, norm_offset=True, embed_scale=True)


def gemma2_config(head_dim: int = 256, query_pre_attn_scalar: float = 0.0,
                  attn_softcap: float = 50.0, final_softcap: float = 30.0,
                  sliding_window: int = 4096, **kw) -> ModelConfig:
    """Gemma 2: the Gemma skeleton plus sandwich (pre+post) norms, attention
    and final-logit softcapping, alternating local/global attention (even
    layers windowed), and an optional query_pre_attn_scalar score scale."""
    cfg = gemma_config(head_dim=head_dim, **kw)
    return dataclasses.replace(
        cfg, model_type="gemma2", post_norms=True,
        attn_softcap=attn_softcap, final_softcap=final_softcap,
        query_scale=(query_pre_attn_scalar ** -0.5
                     if query_pre_attn_scalar else 0.0),
        altern_window=sliding_window)


def ouro_config(loop_steps: int = 4, exit_threshold: float = 1.0,
                head_dim: int = 128, norm_eps: float = 1e-6,
                **kw) -> ModelConfig:
    """Ouro (looped LM): the LLaMA layer with sandwich norms (ln3 after
    attention, ln4 after the MLP, plain RMSNorm scales), no biases, and the
    whole stack run ``loop_steps`` times a token over one copy of its
    weights (`ModelConfig.loop_steps`)."""
    cfg = llama_config(norm_eps=norm_eps, **kw)
    return dataclasses.replace(
        cfg, model_type="ouro", post_norms=True, head_dim_override=head_dim,
        loop_steps=loop_steps, exit_threshold=exit_threshold)


def evabyte_config(window_size: int = 2048, chunk_size: int = 16,
                   num_pred_heads: int = 8, **kw) -> ModelConfig:
    """EvaByte (byte-level LM with EVA attention): the LLaMA layer, RMSNorm
    scales stored as offsets from one, no biases, an untied head of
    ``num_pred_heads`` prediction heads, the residual stream in float32,
    and attention over ``window_size`` exact rows and one learned summary
    row per ``chunk_size`` earlier positions (`ModelConfig.eva_window`)."""
    cfg = llama_config(**kw)
    return dataclasses.replace(
        cfg, model_type="evabyte", norm_offset=True, fp32_residual=True,
        eva_window=window_size, eva_chunk=chunk_size,
        pred_heads=num_pred_heads)


def glm5_config(q_lora_rank: int = 2048, kv_lora_rank: int = 512,
                qk_nope_head_dim: int = 192, qk_rope_head_dim: int = 64,
                v_head_dim: int = 256, index_n_heads: int = 32,
                index_head_dim: int = 128, index_topk: int = 2048,
                n_routed_experts: int = 256, num_experts_per_tok: int = 8,
                moe_intermediate_size: int = 2048, n_shared_experts: int = 1,
                routed_scaling_factor: float = 2.5, first_k_dense: int = 3,
                experts_held: Optional[tuple] = None, **kw) -> ModelConfig:
    """GLM-5 (HF ``glm_moe_dsa``): RMSNorm, no biases, untied head; latent
    attention under a learned top-``index_topk`` selection
    (`ModelConfig.kv_lora_rank`); sigmoid-routed experts beside a shared
    one behind ``first_k_dense`` dense layers. The multi-token-prediction
    layer the checkpoint carries past ``num_layers`` is not held."""
    cfg = llama_config(num_kv_heads=kw["num_heads"], **kw)
    return dataclasses.replace(
        cfg, model_type="glm_moe_dsa",
        head_dim_override=qk_nope_head_dim + qk_rope_head_dim,
        q_lora_rank=q_lora_rank, kv_lora_rank=kv_lora_rank,
        qk_nope_head_dim=qk_nope_head_dim, qk_rope_head_dim=qk_rope_head_dim,
        v_head_dim=v_head_dim, index_n_heads=index_n_heads,
        index_head_dim=index_head_dim, index_topk=index_topk,
        num_experts=n_routed_experts, num_experts_per_tok=num_experts_per_tok,
        moe_intermediate_size=moe_intermediate_size,
        n_shared_experts=n_shared_experts,
        routed_scaling_factor=routed_scaling_factor,
        first_k_dense=first_k_dense, experts_held=experts_held)


def dots3_config(layer_types: tuple, q_lora_rank: int = 1024,
                 kv_lora_rank: int = 512, qk_nope_head_dim: int = 128,
                 qk_rope_head_dim: int = 64, v_head_dim: int = 128,
                 index_n_heads: int = 64, index_head_dim: int = 128,
                 index_topk: int = 2048, sliding_window_size: int = 513,
                 swa_num_heads: int = 64, swa_q_lora_rank: int = 1024,
                 swa_kv_lora_rank: int = 1024,
                 swa_qk_nope_head_dim: int = 192,
                 swa_qk_rope_head_dim: int = 64, swa_v_head_dim: int = 128,
                 swa_rope_theta: float = 50000.0,
                 attention_gate: bool = True, swa_attention_gate: bool = True,
                 lora_rescale: bool = True, n_routed_experts: int = 256,
                 moe_intermediate_size: int = 1536,
                 routed_scaling_factor: float = 1.0, **kw) -> ModelConfig:
    """dots3-note-prev (HF ``dots3_note``): GLM-5's function (`glm5_config`:
    latent attention under a learned selection, sigmoid-routed experts
    beside a shared one behind a dense layer) on the ``"full_attention"``
    layers of ``layer_types``, and on the ``"sliding_attention"`` ones
    latent attention of a second geometry over a window
    (`ModelConfig.layer_types`); a headwise gate on both kinds' output and
    the normed bottlenecks rescaled. The vision tower, the audio encoder
    and the multi-token-prediction module of the checkpoint are not held."""
    cfg = glm5_config(
        q_lora_rank=q_lora_rank, kv_lora_rank=kv_lora_rank,
        qk_nope_head_dim=qk_nope_head_dim, qk_rope_head_dim=qk_rope_head_dim,
        v_head_dim=v_head_dim, index_n_heads=index_n_heads,
        index_head_dim=index_head_dim, index_topk=index_topk,
        n_routed_experts=n_routed_experts,
        moe_intermediate_size=moe_intermediate_size,
        routed_scaling_factor=routed_scaling_factor, **kw)
    return dataclasses.replace(
        cfg, model_type="dots3_note", layer_types=tuple(layer_types),
        sliding_window_size=sliding_window_size, swa_num_heads=swa_num_heads,
        swa_q_lora_rank=swa_q_lora_rank, swa_kv_lora_rank=swa_kv_lora_rank,
        swa_qk_nope_head_dim=swa_qk_nope_head_dim,
        swa_qk_rope_head_dim=swa_qk_rope_head_dim,
        swa_v_head_dim=swa_v_head_dim, swa_rope_theta=swa_rope_theta,
        attention_gate=attention_gate, swa_attention_gate=swa_attention_gate,
        lora_rescale=lora_rescale)


def dots3_layer_types(num_layers: int, period: int = 4) -> tuple:
    """``layer_types`` as dots3-note-prev publishes them: layer 0 full, then
    from layer 1 one full layer and ``period - 1`` sliding ones, over and
    over."""
    return tuple("full_attention" if i == 0 or (i - 1) % period == 0
                 else "sliding_attention" for i in range(num_layers))


def mixtral_config(num_experts: int = 8, num_experts_per_tok: int = 2, **kw) -> ModelConfig:
    cfg = llama_config(**kw)
    return dataclasses.replace(
        cfg,
        model_type="mixtral",
        num_experts=num_experts,
        num_experts_per_tok=num_experts_per_tok,
    )


# Named presets mirroring the reference's workload envelope (BASELINE.md).
PRESETS = {
    "gpt2": lambda: gpt2_config(),
    "gpt2-medium": lambda: gpt2_config(hidden_size=1024, num_layers=24, num_heads=16),
    "gpt2-large": lambda: gpt2_config(hidden_size=1280, num_layers=36, num_heads=20),
    "gpt2-xl": lambda: gpt2_config(hidden_size=1600, num_layers=48, num_heads=25),
    "llama-2-7b": lambda: llama_config(num_kv_heads=32),
    "llama-3-8b": lambda: llama_config(
        vocab_size=128256, hidden_size=4096, num_layers=32, num_heads=32,
        num_kv_heads=8, intermediate_size=14336, max_position_embeddings=8192,
        rope_theta=500000.0,
    ),
    "llama-3-70b": lambda: llama_config(
        vocab_size=128256, hidden_size=8192, num_layers=80, num_heads=64,
        num_kv_heads=8, intermediate_size=28672, max_position_embeddings=8192,
        rope_theta=500000.0,
    ),
    # Llama-3.1: the reference's LB test model (BASELINE.md: Llama-3.1-8B,
    # total_blocks=32) — 128k context via the llama3 RoPE frequency remap.
    "llama-3.1-8b": lambda: dataclasses.replace(llama_config(
        vocab_size=128256, hidden_size=4096, num_layers=32, num_heads=32,
        num_kv_heads=8, intermediate_size=14336,
        max_position_embeddings=131072, rope_theta=500000.0,
    ), rope_scaling=(8.0, 1.0, 4.0, 8192)),
    # Llama-3.2 small models: 3.1's 128k rope remap + tied embeddings.
    "llama-3.2-1b": lambda: dataclasses.replace(llama_config(
        vocab_size=128256, hidden_size=2048, num_layers=16, num_heads=32,
        num_kv_heads=8, intermediate_size=8192,
        max_position_embeddings=131072, rope_theta=500000.0,
        tie_word_embeddings=True,
    ), rope_scaling=(32.0, 1.0, 4.0, 8192)),
    "llama-3.2-3b": lambda: dataclasses.replace(llama_config(
        vocab_size=128256, hidden_size=3072, num_layers=28, num_heads=24,
        num_kv_heads=8, intermediate_size=8192,
        max_position_embeddings=131072, rope_theta=500000.0,
        tie_word_embeddings=True,
    ), rope_scaling=(32.0, 1.0, 4.0, 8192)),
    "mixtral-8x7b": lambda: mixtral_config(
        vocab_size=32000, hidden_size=4096, num_layers=32, num_heads=32,
        num_kv_heads=8, intermediate_size=14336,
    ),
    "gemma-2b": lambda: gemma_config(
        vocab_size=256000, hidden_size=2048, num_layers=18, num_heads=8,
        num_kv_heads=1, intermediate_size=16384,
        max_position_embeddings=8192,
    ),
    "gemma-7b": lambda: gemma_config(
        vocab_size=256000, hidden_size=3072, num_layers=28, num_heads=16,
        num_kv_heads=16, intermediate_size=24576,
        max_position_embeddings=8192,
    ),
    "gemma-2-2b": lambda: gemma2_config(
        vocab_size=256000, hidden_size=2304, num_layers=26, num_heads=8,
        num_kv_heads=4, intermediate_size=9216,
        max_position_embeddings=8192, query_pre_attn_scalar=256.0,
    ),
    "gemma-2-9b": lambda: gemma2_config(
        vocab_size=256000, hidden_size=3584, num_layers=42, num_heads=16,
        num_kv_heads=8, intermediate_size=14336,
        max_position_embeddings=8192, query_pre_attn_scalar=256.0,
    ),
    "qwen2-0.5b": lambda: qwen2_config(
        vocab_size=151936, hidden_size=896, num_layers=24, num_heads=14,
        num_kv_heads=2, intermediate_size=4864, max_position_embeddings=32768,
        rope_theta=1000000.0, tie_word_embeddings=True,
    ),
    "qwen2-7b": lambda: qwen2_config(
        vocab_size=152064, hidden_size=3584, num_layers=28, num_heads=28,
        num_kv_heads=4, intermediate_size=18944, max_position_embeddings=32768,
        rope_theta=1000000.0,
    ),
    # ByteDance/Ouro-2.6B config.json: 48 layers run total_ut_steps = 4
    # times a token, early_exit_threshold 1.
    "ouro-2.6b": lambda: ouro_config(
        vocab_size=49152, hidden_size=2048, num_layers=48, num_heads=16,
        num_kv_heads=16, intermediate_size=5632,
        max_position_embeddings=65536, rope_theta=1000000.0,
    ),
    # EvaByte/EvaByte config.json: 32 layers, 32 heads of 128, vocabulary
    # 320 (bytes + specials), window_size 2048, chunk_size 16, 8
    # prediction heads, rope_theta 1e5.
    "evabyte": lambda: evabyte_config(
        vocab_size=320, hidden_size=4096, num_layers=32, num_heads=32,
        num_kv_heads=32, intermediate_size=11008,
        max_position_embeddings=32768, rope_theta=100000.0,
    ),
    # The same code path for the benchmark's CPU rehearsal, which runs an
    # eighth of every length of a cell whose prompts are 2-14 K rows: a
    # 256-row window, so that it still crosses windows, and a quarter of
    # every width (8 heads of 128), so that 7 K rows of prompts through the
    # engine and the float32 reference take seconds on a CPU, not minutes.
    "evabyte-rehearsal": lambda: evabyte_config(
        vocab_size=320, hidden_size=1024, num_layers=32, num_heads=8,
        num_kv_heads=8, intermediate_size=2752,
        max_position_embeddings=32768, rope_theta=100000.0,
        window_size=256,
    ),
    # zai-org/GLM-5 config.json as ONE chip of the stated deployment serves
    # it (perfbench/configs/glm-5.json ``deployment``): 16 chips share a
    # layer, each holding attention, the shared expert and 16 of the 256
    # routed experts (this one: 0 .. 15; the router scores all 256), the
    # vocabulary over 8 chips (154880 -> 19360 rows of embedding and head),
    # ONE leading dense layer of the published three. Every width is the
    # published one. ``--num_layers 6`` cuts the depth.
    "glm5": lambda: glm5_config(
        vocab_size=19360, hidden_size=6144, num_layers=78, num_heads=64,
        intermediate_size=12288, max_position_embeddings=202752,
        rope_theta=1000000.0, first_k_dense=1, experts_held=(0, 16),
    ),
    # The same code path for the benchmark's CPU rehearsal (an eighth of
    # every length: prompts of 255-1750 rows): ``index_topk`` 256, so that
    # they cross the selection's edge, a quarter of every width, 8 of 32
    # experts held.
    "glm5-rehearsal": lambda: glm5_config(
        vocab_size=2420, hidden_size=1536, num_layers=78, num_heads=16,
        intermediate_size=3072, max_position_embeddings=202752,
        rope_theta=1000000.0, q_lora_rank=512, kv_lora_rank=128,
        qk_nope_head_dim=48, qk_rope_head_dim=16, v_head_dim=64,
        index_n_heads=8, index_head_dim=32, index_topk=256,
        n_routed_experts=32, num_experts_per_tok=8,
        moe_intermediate_size=512, first_k_dense=1, experts_held=(0, 8),
    ),
    # dots-studio/dots3-note-prev config.json (the language model) as ONE
    # chip of the stated deployment serves it
    # (perfbench/configs/dots3-note-prev.json ``deployment``): 16 chips
    # share a layer, each holding attention, the gate, the indexer of a full
    # layer, the shared expert and 16 of the 256 routed experts (this one:
    # 0 .. 15; the router scores all 256), the vocabulary over 8 chips
    # (152064 -> 19008 rows of embedding and head). Every width, the
    # window, ``index_topk`` and the layers' order are the published ones.
    # ``--num_layers 9`` cuts the depth to the dense layer and two periods.
    "dots3": lambda: dots3_config(
        dots3_layer_types(46), vocab_size=19008, hidden_size=5120,
        num_layers=46, num_heads=128, intermediate_size=13824,
        max_position_embeddings=524288, rope_theta=80000000.0,
        first_k_dense=1, experts_held=(0, 16),
    ),
    # The same code path for the benchmark's CPU rehearsal (an eighth of
    # every length: prompts of 255-1750 rows): ``index_topk`` 256 and a
    # window of 65 rows, so that they cross the selection's edge and wrap
    # the ring, a quarter of every width, 8 of 32 experts held.
    "dots3-rehearsal": lambda: dots3_config(
        dots3_layer_types(46), vocab_size=2376, hidden_size=1280,
        num_layers=46, num_heads=32, intermediate_size=3456,
        max_position_embeddings=524288, rope_theta=80000000.0,
        q_lora_rank=256, kv_lora_rank=128, qk_nope_head_dim=32,
        qk_rope_head_dim=16, v_head_dim=32, index_n_heads=16,
        index_head_dim=32, index_topk=256, sliding_window_size=65,
        swa_num_heads=16, swa_q_lora_rank=256, swa_kv_lora_rank=256,
        swa_qk_nope_head_dim=48, swa_qk_rope_head_dim=16, swa_v_head_dim=32,
        n_routed_experts=32, num_experts_per_tok=8,
        moe_intermediate_size=384, first_k_dense=1, experts_held=(0, 8),
    ),
}

# Qwen2.5 shares the qwen2 architecture (HF model_type "qwen2") — alias
# the existing entries so a hyperparameter fix can never silently diverge.
PRESETS["qwen2.5-0.5b"] = PRESETS["qwen2-0.5b"]
PRESETS["qwen2.5-7b"] = PRESETS["qwen2-7b"]


def single_pass_unsupported(cfg: ModelConfig, what: str) -> Optional[str]:
    """Reason ``what`` (an engine or a route that keeps ONE K/V row a
    position and visits a span of layers ONCE a token) cannot run this
    config, or None: the ONE predicate on the per-session state a family
    needs. A looped stack lives in the full-span batched engine
    (runtime.batching) and the in-program oracle
    (models.transformer.full_forward) only; a family whose older rows are
    summaries (``eva_window``), and one whose rows are latent and read
    through a learned selection (``kv_lora_rank``), in the full-span
    batched engine only.
    Everything else would silently run one pass of several, or plain
    causal attention past the first window, and must refuse instead."""
    if cfg.layer_types and "sliding" in cfg.layer_kinds:
        return (f"layers of two kinds alternate ({list(cfg.layer_kinds)}): "
                f"a full layer keeps a latent row of {cfg.kv_lora_rank} + "
                f"{cfg.qk_rope_head_dim} numbers and an index key of "
                f"{cfg.index_head_dim} a position and reads the "
                f"{cfg.index_topk} rows a learned indexer selects, a "
                f"sliding layer keeps a ring of {cfg.sliding_window_size} "
                f"latent rows of {cfg.swa_kv_lora_rank} + "
                f"{cfg.swa_qk_rope_head_dim} numbers: {what} keeps one K "
                "and one V row a position in every layer and has neither a "
                "selection nor a ring; serve the model whole on the batched "
                "engine (serve --stage 0 --batched)")
    if cfg.kv_lora_rank:
        return (f"a position keeps a latent row of {cfg.kv_lora_rank} + "
                f"{cfg.qk_rope_head_dim} numbers and an index key of "
                f"{cfg.index_head_dim}, and a query reads the "
                f"{cfg.index_topk} rows a learned indexer selects: {what} "
                "keeps one K and one V row a position and has no "
                "selection; serve the model whole on the batched engine "
                "(serve --stage 0 --batched)")
    if cfg.eva_window:
        return (f"older rows are summaries ({cfg.eva_window} exact K/V rows "
                f"a layer and one learned summary row per {cfg.eva_chunk} "
                f"earlier positions, one softmax over both): {what} keeps "
                "one row a position and would attend as if no window had "
                "closed; serve the model whole on the batched engine "
                "(serve --stage 0 --batched)")
    if cfg.loop_steps > 1:
        return (f"layers run several times a token ({cfg.loop_steps} passes "
                f"over one stack of {cfg.num_layers}, a K/V cache for every "
                f"pass): {what} would run one pass; serve the model whole "
                "on the batched engine (serve --stage 0 --batched)")
    return None


def refuse_single_pass(cfg: ModelConfig, what: str) -> None:
    """Raise `single_pass_unsupported`'s reason, if it has one."""
    reason = single_pass_unsupported(cfg, what)
    if reason is not None:
        raise NotImplementedError(reason)


def custom_engine_unsupported(cfg: ModelConfig) -> Optional[str]:
    """Reason the sequence-parallel ring engine and the TP shard specs
    cannot serve this config, or None. The gemma2 semantics live in
    models.transformer.layer_forward (session/fused/oracle engines) and
    in runtime.batching's gemma2-aware layer pieces (batched engine);
    the remaining custom-math engines must refuse rather than silently
    drop them."""
    looped = single_pass_unsupported(cfg, "this engine")
    if looped is not None:
        return looped
    if (cfg.post_norms or cfg.attn_softcap or cfg.query_scale
            or cfg.altern_window):
        return ("gemma2 semantics (sandwich norms / softcap / per-layer "
                "window) are not implemented on this engine")
    return None


def get_config(name: str) -> ModelConfig:
    key = name.lower().split("/")[-1]
    if key in PRESETS:
        return PRESETS[key]()
    # Longest alias first so "meta-llama-3-8b" resolves to llama-3-8b, not the
    # "llama-3" prefix of a shorter alias. The alias must appear as a
    # delimiter-bounded token: "distilgpt2" must NOT resolve to gpt2 (different
    # architecture), while "meta-llama-3-8b" and "gpt2_finetuned" do resolve.
    import re

    for alias in sorted(PRESETS, key=len, reverse=True):
        if re.search(rf"(^|[^a-z0-9]){re.escape(alias)}([^a-z0-9]|$)", key):
            return PRESETS[alias]()
    raise KeyError(f"unknown model preset: {name}")
