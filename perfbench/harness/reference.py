"""The plain reference of each configuration, and the seeded weights.

``make_weights`` draws a checkpoint from the seed, in the PUBLISHED tensor
names and layouts of the model's family (Hugging Face ``gpt2`` /
``qwen2`` state dicts), on the device, in one jitted call, in the type the
configuration serves. The program loads it through its own importer
(``models.hf_import.convert_state_dict``); the reference below reads the
same tensors and nothing the program has made.

``forward`` is the architecture as published, in straightforward
``jax.numpy`` float32 with every matmul at highest precision: no cache, no
batching, no kernels, one causal pass over the whole sequence.
Departures from the published models: none in the mathematics; weights are
random (normal, std 0.02; norm weights 1 + 0.1 N(0,1); biases std 0.02),
so that no term of a layer is a no-op."""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from .roofline import shape_of

F32 = jnp.float32


def _layout(cfg: dict, layers: int) -> Dict[str, tuple]:
    """name -> (shape, kind) with kind in matrix|bias|norm|zero_bias."""
    s = shape_of(cfg)
    d, v, f = s["hidden"], s["vocab"], s["ffn"]
    out: Dict[str, tuple] = {}
    if cfg["model_type"] == "gpt2":
        out["transformer.wte.weight"] = ((v, d), "matrix")
        out["transformer.wpe.weight"] = ((cfg["n_positions"], d), "matrix")
        for i in range(layers):
            p = f"transformer.h.{i}."
            out[p + "ln_1.weight"] = ((d,), "norm")
            out[p + "ln_1.bias"] = ((d,), "bias")
            out[p + "attn.c_attn.weight"] = ((d, 3 * d), "matrix")
            out[p + "attn.c_attn.bias"] = ((3 * d,), "bias")
            out[p + "attn.c_proj.weight"] = ((d, d), "matrix")
            out[p + "attn.c_proj.bias"] = ((d,), "bias")
            out[p + "ln_2.weight"] = ((d,), "norm")
            out[p + "ln_2.bias"] = ((d,), "bias")
            out[p + "mlp.c_fc.weight"] = ((d, f), "matrix")
            out[p + "mlp.c_fc.bias"] = ((f,), "bias")
            out[p + "mlp.c_proj.weight"] = ((f, d), "matrix")
            out[p + "mlp.c_proj.bias"] = ((d,), "bias")
        out["transformer.ln_f.weight"] = ((d,), "norm")
        out["transformer.ln_f.bias"] = ((d,), "bias")
        return out
    hq = s["heads"] * s["head_dim"]
    hkv = s["kv_heads"] * s["head_dim"]
    out["model.embed_tokens.weight"] = ((v, d), "matrix")
    for i in range(layers):
        p = f"model.layers.{i}."
        out[p + "input_layernorm.weight"] = ((d,), "norm")
        out[p + "self_attn.q_proj.weight"] = ((hq, d), "matrix")
        out[p + "self_attn.k_proj.weight"] = ((hkv, d), "matrix")
        out[p + "self_attn.v_proj.weight"] = ((hkv, d), "matrix")
        out[p + "self_attn.q_proj.bias"] = ((hq,), "bias")
        out[p + "self_attn.k_proj.bias"] = ((hkv,), "bias")
        out[p + "self_attn.v_proj.bias"] = ((hkv,), "bias")
        out[p + "self_attn.o_proj.weight"] = ((d, hq), "matrix")
        out[p + "post_attention_layernorm.weight"] = ((d,), "norm")
        out[p + "mlp.gate_proj.weight"] = ((f, d), "matrix")
        out[p + "mlp.up_proj.weight"] = ((f, d), "matrix")
        out[p + "mlp.down_proj.weight"] = ((d, f), "matrix")
    out["model.norm.weight"] = ((d,), "norm")
    if not s["tied"]:
        out["lm_head.weight"] = ((v, d), "matrix")
    return out


def make_weights(cfg: dict, layers: int, seed: int, dtype=jnp.bfloat16
                 ) -> Dict[str, jax.Array]:
    """The seeded checkpoint: one jitted call, on the device, in `dtype`."""
    layout = _layout(cfg, layers)
    names = sorted(layout)

    @jax.jit
    def draw(key):
        out = {}
        for i, name in enumerate(names):
            shape, kind = layout[name]
            x = jax.random.normal(jax.random.fold_in(key, i), shape, F32)
            x = {"matrix": 0.02 * x, "bias": 0.02 * x,
                 "norm": 1.0 + 0.1 * x}[kind]
            out[name] = x.astype(dtype)
        return out

    # any whole number of a seed: fold its high bits in, PRNGKey takes 32
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             (seed >> 31) & 0x7FFFFFFF)
    return draw(key)


def _layer_norm(x, w, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _rms_norm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


# Query rows a block of `_causal_attention`: the scores one block holds are
# [H, ATTN_ROWS, keys] float32, so a pass of 8192 rows of 28 heads keeps
# 0.94 GB of them and not 7.5.
ATTN_ROWS = 1024


def _causal_attention(q, k, v, block=ATTN_ROWS):
    """q [T,H,Dh], k/v [T,Hkv,Dh] -> [T,H*Dh]; plain softmax(QK^T/sqrt)V,
    in blocks of ``block`` query rows. A row's softmax runs over all the
    keys it can see at once (every key up to the block's last row; those
    after the row itself masked), so a row's arithmetic is what one
    [H, T, T] pass gave it, and a pass of at most one block IS that pass."""
    t, h, dh = q.shape
    rep = h // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    out = []
    for lo in range(0, t, block):
        hi = min(lo + block, t)
        scores = (jnp.einsum("thd,shd->hts", q[lo:hi], k[:hi])
                  / jnp.sqrt(F32(dh)))
        mask = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
        scores = jnp.where(mask[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out.append(jnp.einsum("hts,shd->thd", probs, v[:hi]))
    return jnp.concatenate(out).reshape(t, h * dh)


def _rope(x, theta):
    """HF rotate_half convention: pairs (i, i + Dh/2)."""
    t, _, dh = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def forward(cfg: dict, layers: int, weights: Dict[str, jax.Array], ids):
    """ids [T] -> logits [T, V], float32 at highest matmul precision."""
    s = shape_of(cfg)
    w = lambda name: weights[name].astype(F32)
    with jax.default_matmul_precision("highest"):
        t = ids.shape[0]
        if cfg["model_type"] == "gpt2":
            eps = cfg["layer_norm_epsilon"]
            h = (w("transformer.wte.weight")[ids]
                 + w("transformer.wpe.weight")[jnp.arange(t)])
            nh, dh = s["heads"], s["head_dim"]
            for i in range(layers):
                p = f"transformer.h.{i}."
                a = _layer_norm(h, w(p + "ln_1.weight"), w(p + "ln_1.bias"),
                                eps)
                qkv = a @ w(p + "attn.c_attn.weight") + w(
                    p + "attn.c_attn.bias")
                q, k, v = (x.reshape(t, nh, dh)
                           for x in jnp.split(qkv, 3, axis=-1))
                h = h + (_causal_attention(q, k, v)
                         @ w(p + "attn.c_proj.weight")
                         + w(p + "attn.c_proj.bias"))
                m = _layer_norm(h, w(p + "ln_2.weight"), w(p + "ln_2.bias"),
                                eps)
                m = jax.nn.gelu(m @ w(p + "mlp.c_fc.weight")
                                + w(p + "mlp.c_fc.bias"), approximate=True)
                h = h + m @ w(p + "mlp.c_proj.weight") + w(
                    p + "mlp.c_proj.bias")
            h = _layer_norm(h, w("transformer.ln_f.weight"),
                            w("transformer.ln_f.bias"), eps)
            return h @ w("transformer.wte.weight").T
        eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
        nh, nkv, dh = s["heads"], s["kv_heads"], s["head_dim"]
        h = w("model.embed_tokens.weight")[ids]
        for i in range(layers):
            p = f"model.layers.{i}."
            a = _rms_norm(h, w(p + "input_layernorm.weight"), eps)
            q = (a @ w(p + "self_attn.q_proj.weight").T
                 + w(p + "self_attn.q_proj.bias")).reshape(t, nh, dh)
            k = (a @ w(p + "self_attn.k_proj.weight").T
                 + w(p + "self_attn.k_proj.bias")).reshape(t, nkv, dh)
            v = (a @ w(p + "self_attn.v_proj.weight").T
                 + w(p + "self_attn.v_proj.bias")).reshape(t, nkv, dh)
            att = _causal_attention(_rope(q, theta), _rope(k, theta), v)
            h = h + att @ w(p + "self_attn.o_proj.weight").T
            m = _rms_norm(h, w(p + "post_attention_layernorm.weight"), eps)
            m = (jax.nn.silu(m @ w(p + "mlp.gate_proj.weight").T)
                 * (m @ w(p + "mlp.up_proj.weight").T))
            h = h + m @ w(p + "mlp.down_proj.weight").T
        h = _rms_norm(h, w("model.norm.weight"), eps)
        head_w = (w("model.embed_tokens.weight") if s["tied"]
                  else w("lm_head.weight"))
        return h @ head_w.T
