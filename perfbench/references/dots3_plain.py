"""The plain reference of dots3-note-prev (HF ``dots3_note``: the language
model of the checkpoint), as ONE chip of a deployment that shares a layer's
routed experts among chips holds it; its seeded weights; and what a decode
tick of it reads at least.

Source: https://huggingface.co/dots-studio/dots3-note-prev/blob/main/config.json
This file imports nothing of the program (and nothing of the other
references: what it shares with ``glm5_plain.py`` is a copy of its own). The
program loads ``make_weights``'s checkpoint through its own importer
(``models.hf_import.convert_state_dict``) and never hands this file a
decision of its own (no cache, no ring, no selection, no routing).

Two kinds of layer, by ``layer_types`` (layer 0 ``full_attention`` and
dense, then ``[full, sliding, sliding, sliding]`` over and over). D =
hidden_size; RMSNorm ``N(x; w) = x / sqrt(mean(x^2) + eps) * w``, no
biases, untied head; ``a = N(h; input_layernorm)``.

A FULL layer (latent attention under a learned selection):
  c_q = r_q N(a W_qa; q_a_layernorm), r_q = (D / q_lora_rank)^0.5
  q = c_q W_qb -> num_attention_heads of qk_nope_head_dim +
      qk_rope_head_dim; the rope part rotated (rope_theta, pairs (i, i +
      n/2): the rotate-half convention)
  [c_kv | k_r] = a W_kva; c_kv = r_kv N(c_kv; kv_a_layernorm) of
      kv_lora_rank, r_kv = (D / kv_lora_rank)^0.5; k_r rotated likewise,
      ONE for all heads
  [k_nope | v] = c_kv W_kvb -> heads of qk_nope_head_dim + v_head_dim
  the indexer: q_I = c_q W_Iq -> index_n_heads x index_head_dim, k_I =
      LayerNorm(a W_Ik) (eps 1e-6), the first qk_rope_head_dim of each
      rotated; w = a W_Iw * index_n_heads^-0.5 * index_head_dim^-0.5;
      I(t, s) = sum_h w_h(t) ReLU(q_I,h(t) . k_I(s))
  the query at t attends to the index_topk positions s <= t of largest
      I(t, s) (all of them while t < index_topk; ties to the lower
      position): softmax over THOSE of (q_nope . k_nope + q_rope . k_r) *
      (qk_nope_head_dim + qk_rope_head_dim)^-0.5, the same weights over v
  g = sigmoid(a W_g) (one number a head); head h's output times g_h;
      h = h + o W_o

A SLIDING layer: the same latent attention at the ``swa_*`` sizes
(swa_num_attention_heads heads of swa_qk_nope_head_dim +
swa_qk_rope_head_dim against keys and swa_v_head_dim against values,
bottlenecks swa_q_lora_rank and swa_kv_lora_rank, both rescaled likewise,
rotation at swa_rope_theta), NO indexer: the query at t attends to every s
with t - sliding_window_size < s <= t; its own gate W_g.

  m = N(h; post_attention_layernorm)
  layer 0 .. first_k_dense_replace - 1:  h = h + SwiGLU_intermediate(m)
  later layers:  s = sigmoid(m W_r) over n_routed_experts; the
      num_experts_per_tok largest of s + b (e_score_correction_bias; no
      group limit); weights the chosen s normalised to sum 1, times
      routed_scaling_factor;
      h = h + sum over the chosen experts THIS CHIP HOLDS of weight x
      SwiGLU_moe(m) + the shared SwiGLU expert
  logits = N(h; norm) W_head

The expanded MLA form only, the window as a MASK over every key; the
absorbed form and the ring are the program's business.

The share (``held_experts``): the configuration file's ``deployment`` has
16 chips share a layer, so this chip holds experts 0 .. n_routed_experts /
16 - 1 of every expert layer (a dict that states ``experts_held`` holds
that many: the CPU rehearsal's). The router scores ALL experts; a token's
assignment to an expert held elsewhere adds nothing here, as on the chip.
``vocab_size`` is the slice's.

Departures and assumptions (the configuration file lists them under
``assumed``): the two rescales as the LongCat-Flash convention has them
(on the normed bottlenecks); the gate a matrix of its own from the normed
stream, ``self_attn.g_proj``; the window counted as HF's
``sliding_window`` (its own row and the 512 before it); the indexer as
GLM-5's, bfloat16 index keys with no Hadamard rotation and no fp8; the
rotate-half RoPE convention for both bases; ``init_std`` 0.02 for the
seeded matrices but the embedding, whose rows are drawn at `EMBED_STD`;
the router's bias b drawn 0.05 N(0, 1) and norm weights 1 + 0.1 N(0, 1) so
that no term is a no-op. NOT computed, as it is not served: the vision
tower, the audio encoder and the multi-token-prediction module."""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
# Query rows scored at once: 16053 rows against themselves are 2.1 GB of
# float32 attention scores a block of 256 rows at 128 heads, 1.05 GB
# a block of 128.
ATTN_ROWS = 128
# Chips that share one layer's routed experts in the stated deployment.
CHIPS_A_LAYER = 16
# The seeded embedding's standard deviation: with every matrix at 0.02 the
# stream into the first layer is smaller than what that layer's attention
# adds, and one member of the selection that float32 and bfloat16 break a
# near-tie for differently moves every later product (PERF.md section 6,
# PR 55, where the scales tried are listed).
EMBED_STD = 0.6


def _sizes(hf: dict) -> dict:
    return {"d": hf["hidden_size"], "f": hf["intermediate_size"],
            "fm": hf["moe_intermediate_size"],
            "hi": hf["index_n_heads"], "di": hf["index_head_dim"],
            "topk": hf["index_topk"], "window": hf["sliding_window_size"],
            "e": hf["n_routed_experts"], "k": hf["num_experts_per_tok"],
            "ns": hf["n_shared_experts"], "v": hf["vocab_size"],
            "dense": hf["first_k_dense_replace"]}


def geometry(hf: dict, sliding: bool) -> dict:
    """The attention sizes of a layer of one kind: heads ``h``, bottlenecks
    ``ql`` / ``kl``, a head's ``nope`` / ``rope`` / ``vd``, ``theta``."""
    pre = "swa_" if sliding else ""
    return {"h": hf[pre + "num_attention_heads"],
            "ql": hf[pre + "q_lora_rank"], "kl": hf[pre + "kv_lora_rank"],
            "nope": hf[pre + "qk_nope_head_dim"],
            "rope": hf[pre + "qk_rope_head_dim"],
            "vd": hf[pre + "v_head_dim"], "theta": hf[pre + "rope_theta"],
            "gate": hf[pre + "attention_gate_type"] == "headwise"}


def is_sliding(hf: dict, i: int) -> bool:
    return hf["layer_types"][i] == "sliding_attention"


def layer_counts(hf: dict, layers: int) -> Tuple[int, int]:
    """``(full, sliding)`` layers among the first ``layers``."""
    sliding = sum(is_sliding(hf, i) for i in range(layers))
    return layers - sliding, sliding


def held_experts(hf: dict) -> Tuple[int, int]:
    """``(first, count)`` of the routed experts this chip holds."""
    return (0, int(hf.get("experts_held",
                          hf["n_routed_experts"] // CHIPS_A_LAYER)))


def _layout(hf: dict, layers: int) -> Dict[str, tuple]:
    """name -> (shape, kind), kind in matrix|norm|bias|route_bias."""
    s = _sizes(hf)
    d = s["d"]
    first, held = held_experts(hf)
    out: Dict[str, tuple] = {"model.embed_tokens.weight": ((s["v"], d),
                                                           "matrix")}
    for i in range(layers):
        p = f"model.layers.{i}."
        a = p + "self_attn."
        g = geometry(hf, is_sliding(hf, i))
        h = g["h"]
        out[p + "input_layernorm.weight"] = ((d,), "norm")
        out[p + "post_attention_layernorm.weight"] = ((d,), "norm")
        out[a + "q_a_proj.weight"] = ((g["ql"], d), "matrix")
        out[a + "q_a_layernorm.weight"] = ((g["ql"],), "norm")
        out[a + "q_b_proj.weight"] = (
            (h * (g["nope"] + g["rope"]), g["ql"]), "matrix")
        out[a + "kv_a_proj_with_mqa.weight"] = (
            (g["kl"] + g["rope"], d), "matrix")
        out[a + "kv_a_layernorm.weight"] = ((g["kl"],), "norm")
        out[a + "kv_b_proj.weight"] = (
            (h * (g["nope"] + g["vd"]), g["kl"]), "matrix")
        out[a + "o_proj.weight"] = ((d, h * g["vd"]), "matrix")
        if g["gate"]:
            out[a + "g_proj.weight"] = ((h, d), "matrix")
        if not is_sliding(hf, i):
            out[a + "indexer.wq_b.weight"] = ((s["hi"] * s["di"], g["ql"]),
                                              "matrix")
            out[a + "indexer.wk.weight"] = ((s["di"], d), "matrix")
            out[a + "indexer.k_norm.weight"] = ((s["di"],), "norm")
            out[a + "indexer.k_norm.bias"] = ((s["di"],), "bias")
            out[a + "indexer.weights_proj.weight"] = ((s["hi"], d),
                                                      "matrix")
        m = p + "mlp."
        if i < s["dense"]:
            for name, shape in (("gate_proj", (s["f"], d)),
                                ("up_proj", (s["f"], d)),
                                ("down_proj", (d, s["f"]))):
                out[m + name + ".weight"] = (shape, "matrix")
            continue
        out[m + "gate.weight"] = ((s["e"], d), "matrix")
        out[m + "gate.e_score_correction_bias"] = ((s["e"],), "route_bias")
        fs = s["fm"] * s["ns"]
        for owner, f in [(f"experts.{e}.", s["fm"])
                         for e in range(first, first + held)] + [
                             ("shared_experts.", fs)]:
            out[m + owner + "gate_proj.weight"] = ((f, d), "matrix")
            out[m + owner + "up_proj.weight"] = ((f, d), "matrix")
            out[m + owner + "down_proj.weight"] = ((d, f), "matrix")
    out["model.norm.weight"] = ((d,), "norm")
    out["lm_head.weight"] = ((s["v"], d), "matrix")
    return out


def make_weights(hf: dict, layers: int, seed: int, dtype=jnp.bfloat16
                 ) -> Dict[str, jax.Array]:
    """The seeded checkpoint in the published tensor names, this chip's
    share of it: one jitted call, on the device, in ``dtype`` (the
    router's bias float32, as published)."""
    layout = _layout(hf, layers)
    names = sorted(layout)
    std = hf.get("initializer_range", 0.02)

    @jax.jit
    def draw(key):
        out = {}
        for i, name in enumerate(names):
            shape, kind = layout[name]
            x = jax.random.normal(jax.random.fold_in(key, i), shape, F32)
            x = {"matrix": (EMBED_STD if name.startswith("model.embed")
                            else std) * x,
                 "norm": 1.0 + 0.1 * x, "bias": std * x,
                 "route_bias": 0.05 * x}[kind]
            out[name] = x if kind == "route_bias" else x.astype(dtype)
        return out

    # any whole number of a seed: fold its high bits in, PRNGKey takes 32
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             (seed >> 31) & 0x7FFFFFFF)
    return draw(key)


def _rms_norm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _layer_norm(x, w, b, eps=1e-6):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _rope(x, theta):
    """x [T, .., n] at positions 0 .. T - 1: pairs (i, i + n/2) rotated by
    position x theta^(-2i / n) (the rotate-half convention)."""
    t, n = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, n, 2, dtype=F32) / n))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    ang = ang.reshape((t,) + (1,) * (x.ndim - 2) + (n // 2,))
    x1, x2 = x[..., :n // 2], x[..., n // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _swiglu(x, w, prefix):
    return ((jax.nn.silu(x @ w(prefix + "gate_proj.weight").T)
             * (x @ w(prefix + "up_proj.weight").T))
            @ w(prefix + "down_proj.weight").T)


def routing(hf: dict, w, prefix: str, m):
    """m [T, D] -> the dense map [T, E] of each token's routed weights
    (0 where an expert is not among its chosen)."""
    s = _sizes(hf)
    score = jax.nn.sigmoid(m @ w(prefix + "gate.weight").T)
    _, top = jax.lax.top_k(score + w(prefix + "gate.e_score_correction_bias"),
                           s["k"])
    chosen = jnp.zeros(score.shape, bool).at[
        jnp.arange(m.shape[0])[:, None], top].set(True)
    picked = jnp.where(chosen, score, 0.0)
    return (picked / (picked.sum(-1, keepdims=True) + 1e-20)
            * hf["routed_scaling_factor"])


def expert_layer(hf: dict, w, prefix: str, m, held=None, shared=True):
    """The expert layer's output for m [T, D]: the experts ``held``
    (``(first, count)``; default this chip's) of each token's chosen ones,
    weighted as routed over ALL experts, plus (``shared``) the shared
    expert."""
    first, count = held or held_experts(hf)
    weights = routing(hf, w, prefix, m)
    out = _swiglu(m, w, prefix + "shared_experts.") if shared else 0.0 * m
    for e in range(first, first + count):
        out = out + weights[:, e:e + 1] * _swiglu(m, w,
                                                  prefix + f"experts.{e}.")
    return out


def selection(scores, topk: int):
    """scores [R, S] (-inf where a query may not look) -> the mask of each
    row's ``topk`` largest entries among those it may look at; all of
    them where it has no more."""
    n = scores.shape[-1]
    if topk >= n:
        return scores > -jnp.inf
    _, top = jax.lax.top_k(scores, topk)
    mask = jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None], top].set(True)
    return mask & (scores > -jnp.inf)


def _attention(hf: dict, w, prefix: str, a, sliding: bool):
    """a [T, D] (normed) -> [T, H * v_head_dim], gated, the expanded form,
    in blocks of `ATTN_ROWS` query rows against every key: a full layer's
    rows see the positions its indexer selects, a sliding layer's those of
    its window."""
    s, g = _sizes(hf), geometry(hf, sliding)
    t, h = a.shape[0], g["h"]
    eps = hf["rms_norm_eps"]
    theta = F32(g["theta"])
    rescale = bool(hf["apply_mla_qkv_lora_rescale"])
    r_q = (s["d"] / g["ql"]) ** 0.5 if rescale else 1.0
    r_kv = (s["d"] / g["kl"]) ** 0.5 if rescale else 1.0
    c_q = r_q * _rms_norm(a @ w(prefix + "q_a_proj.weight").T,
                          w(prefix + "q_a_layernorm.weight"), eps)
    q = (c_q @ w(prefix + "q_b_proj.weight").T).reshape(
        t, h, g["nope"] + g["rope"])
    q_nope, q_rope = q[..., :g["nope"]], _rope(q[..., g["nope"]:], theta)
    kv = a @ w(prefix + "kv_a_proj_with_mqa.weight").T
    c_kv = r_kv * _rms_norm(kv[:, :g["kl"]],
                            w(prefix + "kv_a_layernorm.weight"), eps)
    k_rope = _rope(kv[:, g["kl"]:], theta)                       # [T, rope]
    kvb = (c_kv @ w(prefix + "kv_b_proj.weight").T).reshape(
        t, h, g["nope"] + g["vd"])
    k_nope, v = kvb[..., :g["nope"]], kvb[..., g["nope"]:]
    scale = (g["nope"] + g["rope"]) ** -0.5
    pad = -t % ATTN_ROWS
    pos = jnp.arange(t)[None, :]
    queries = [q_nope, q_rope]
    if not sliding:
        ix = prefix + "indexer."
        q_i = (c_q @ w(ix + "wq_b.weight").T).reshape(t, s["hi"], s["di"])
        q_i = jnp.concatenate([_rope(q_i[..., :g["rope"]], theta),
                               q_i[..., g["rope"]:]], -1)
        k_i = _layer_norm(a @ w(ix + "wk.weight").T, w(ix + "k_norm.weight"),
                          w(ix + "k_norm.bias"))
        k_i = jnp.concatenate([_rope(k_i[:, :g["rope"]], theta),
                               k_i[:, g["rope"]:]], -1)
        w_i = (a @ w(ix + "weights_proj.weight").T
               * (s["hi"] ** -0.5 * s["di"] ** -0.5))            # [T, Hi]
        queries += [q_i, w_i]
    padded = [jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
              for x in queries]

    def block(lo):
        """Query rows lo .. lo + ATTN_ROWS - 1 (rows past T are pad: they
        see every key and are cut below)."""
        rows = lo + jnp.arange(ATTN_ROWS)[:, None]
        b_nope, b_rope, *b_index = (jax.lax.dynamic_slice_in_dim(
            x, lo, ATTN_ROWS) for x in padded)
        if sliding:
            seen = (pos <= rows) & (pos > rows - s["window"])
        else:
            b_qi, b_wi = b_index
            index = (jax.nn.relu(jnp.einsum("rhd,sd->rhs", b_qi, k_i))
                     * b_wi[:, :, None]).sum(1)                  # [R, T]
            seen = selection(jnp.where(pos <= rows, index, -jnp.inf),
                             s["topk"])
        scores = (jnp.einsum("rhd,shd->hrs", b_nope, k_nope)
                  + jnp.einsum("rhd,sd->hrs", b_rope, k_rope)) * scale
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("hrs,shd->rhd", probs, v)

    out = jax.lax.map(block, jnp.arange(0, t + pad, ATTN_ROWS))
    out = out.reshape(t + pad, h, g["vd"])[:t]
    if g["gate"]:
        out = out * jax.nn.sigmoid(
            a @ w(prefix + "g_proj.weight").T)[:, :, None]
    return out.reshape(t, h * g["vd"])


def forward(hf: dict, layers: int, weights: Dict[str, jax.Array], ids):
    """ids [T] -> logits [T, vocab], float32 at highest matmul precision:
    no cache, no kernels, one pass over the whole sequence, the selection
    taken per query row from the full score row, the window a mask."""
    s = _sizes(hf)
    w = lambda name: weights[name].astype(F32)
    eps = hf["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        h = w("model.embed_tokens.weight")[ids]
        for i in range(layers):
            p = f"model.layers.{i}."
            a = _rms_norm(h, w(p + "input_layernorm.weight"), eps)
            h = h + (_attention(hf, w, p + "self_attn.", a,
                                is_sliding(hf, i))
                     @ w(p + "self_attn.o_proj.weight").T)
            m = _rms_norm(h, w(p + "post_attention_layernorm.weight"), eps)
            h = h + (_swiglu(m, w, p + "mlp.") if i < s["dense"]
                     else expert_layer(hf, w, p + "mlp.", m))
        return _rms_norm(h, w("model.norm.weight"), eps) @ w(
            "lm_head.weight").T


# -- what a tick reads ------------------------------------------------------

def attention_params(hf: dict, sliding: bool) -> int:
    """Matrix elements of one layer's attention of one kind: bottlenecks,
    expansions, output, gate, and a full layer's indexer."""
    s, g = _sizes(hf), geometry(hf, sliding)
    d, h = s["d"], g["h"]
    n = (d * g["ql"] + g["ql"] * h * (g["nope"] + g["rope"])
         + d * (g["kl"] + g["rope"]) + g["kl"] * h * (g["nope"] + g["vd"])
         + h * g["vd"] * d + (d * h if g["gate"] else 0))
    if not sliding:
        n += g["ql"] * s["hi"] * s["di"] + d * s["di"] + d * s["hi"]
    return n


def moe_params(hf: dict) -> int:
    """Matrix elements of one expert layer's MLP as this chip holds it:
    the router over all experts, the held experts, the shared expert."""
    s = _sizes(hf)
    return (s["d"] * s["e"]
            + 3 * s["d"] * s["fm"] * (held_experts(hf)[1] + s["ns"]))


def moe_tick_bytes(hf: dict, layers: int, weight_bytes: float = 2) -> float:
    """Bytes of the router, every held expert and the shared expert that
    ONE tick reads over the served depth: each once, whatever the routing."""
    dense = min(_sizes(hf)["dense"], layers)
    return (layers - dense) * moe_params(hf) * weight_bytes


def row_bytes(hf: dict, act_bytes: int = 2) -> Tuple[int, int, int]:
    """``(a full layer's latent row, its index key, a sliding layer's
    latent row)`` bytes a position a layer."""
    full, sw = geometry(hf, False), geometry(hf, True)
    return ((full["kl"] + full["rope"]) * act_bytes,
            _sizes(hf)["di"] * act_bytes, (sw["kl"] + sw["rope"]) * act_bytes)


def sparse_attn_tick_bytes(hf: dict, layers: int, scored_rows: float,
                           selected_rows: float, act_bytes: int = 2) -> float:
    """Bytes of cache ONE tick reads at least in the FULL layers among the
    first ``layers``: an index key for every row scored and a latent row
    for every row selected, a full layer."""
    latent, index, _ = row_bytes(hf, act_bytes)
    return layer_counts(hf, layers)[0] * (scored_rows * index
                                          + selected_rows * latent)


def window_tick_bytes(hf: dict, layers: int, window_rows: float,
                      act_bytes: int = 2) -> float:
    """Bytes of cache ONE tick reads at least in the SLIDING layers among
    the first ``layers``: a latent row for each of the ``window_rows`` its
    sessions' windows hold together (min(length, sliding_window_size) a
    session), a sliding layer."""
    return layer_counts(hf, layers)[1] * window_rows * row_bytes(
        hf, act_bytes)[2]


def tick_cost(hf: dict, *, layers: int, sessions: float, kv_rows: float,
              weight_bytes: float, act_bytes: int = 2, ctx=None
              ) -> Dict[str, float]:
    """Bytes and operations ONE decode tick needs AT LEAST, from what it
    READS: every held weight of the served depth once (attention of every
    layer by its kind, the dense MLP of the leading layers, router + held
    experts + shared expert of the others), the head's slice once; a full
    layer an index key for every position its sessions hold (``kv_rows`` a
    session: all are scored) and a latent row for the ``index_topk`` it
    selects; a sliding layer a latent row for each of the newest
    ``sliding_window_size`` positions."""
    s = _sizes(hf)
    dense = min(s["dense"], layers)
    n_full, n_sw = layer_counts(hf, layers)
    attn = (n_full * attention_params(hf, False)
            + n_sw * attention_params(hf, True))
    weights = (attn + dense * 3 * s["d"] * s["f"]) * weight_bytes \
        + moe_tick_bytes(hf, layers, weight_bytes)
    head = s["v"] * s["d"]
    selected = min(kv_rows, s["topk"])
    seen = min(kv_rows, s["window"])
    kv = (sparse_attn_tick_bytes(hf, layers, sessions * kv_rows,
                                 sessions * selected, act_bytes)
          + window_tick_bytes(hf, layers, sessions * seen, act_bytes))
    nbytes = weights + head * act_bytes + kv
    # per token: attention, the dense or the shared + chosen-held experts
    # (num_experts_per_tok x held / all on average), the head; index
    # scores and absorbed attention over the selected rows and the window
    first, held = held_experts(hf)
    routed = s["k"] * held / s["e"]
    per_token = (attn + dense * 3 * s["d"] * s["f"]
                 + (layers - dense) * (s["d"] * s["e"] + 3 * s["d"] * s["fm"]
                                       * (s["ns"] + routed)) + head)
    full, sw = geometry(hf, False), geometry(hf, True)
    flops = 2.0 * sessions * (
        per_token
        + n_full * (kv_rows * s["hi"] * s["di"] + selected
                    * full["h"] * (2 * full["kl"] + full["rope"]))
        + n_sw * seen * sw["h"] * (2 * sw["kl"] + sw["rope"]))
    return {"bytes": nbytes, "flops": flops, "weight_bytes": weights,
            "head_bytes": head * act_bytes, "kv_bytes": kv,
            "kv_rows_read": (n_full * selected + n_sw * seen) / layers}
