"""The plain reference of Ouro-2.6B (HF ``ouro``, a looped language model),
its seeded weights, and the least a decode tick of it needs.

Source: https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json
This file imports nothing of the program. The program loads
``make_weights``'s checkpoint through its own importer
(``models.hf_import.convert_state_dict``) and never hands this file a
decision of its own (no cache, no pass index, no exit).

The equations. ``N(x; w) = x / sqrt(mean(x^2) + eps) * w`` (RMSNorm, plain
scale), ``T = total_ut_steps`` passes over the SAME ``L`` layers:

  h_0 = E[ids]                                no embedding scale, no positions
  for t in 0..T-1:
    x = h_t
    for i in 0..L-1:                          layer i's weights, every pass
      a = N(x; input_layernorm_i)
      q, k, v = a Wq_i, a Wk_i, a Wv_i        [.., heads, head_dim], no bias
      q, k = rope(q), rope(k)                 theta 1e6, rotate-half, position
                                              = token index in every pass
      o = softmax(q k^T / sqrt(head_dim) + causal) v     over THIS pass's k, v
      x = x + N(o Wo_i; input_layernorm_2_i)             sandwich norm
      m = (silu(N(x; post_attention_layernorm_i) Wg_i)
           * (N(x; post_attention_layernorm_i) Wu_i)) Wd_i
      x = x + N(m; post_attention_layernorm_2_i)         sandwich norm
    h_{t+1} = N(x; norm)                      the final norm closes EVERY pass
    g_t = h_{t+1} . w_gate + b_gate           early-exit gate, Linear(D, 1)
  lambda_t = sigmoid(g_t)
  p_t = lambda_t * prod_{j<t}(1 - lambda_j)   (t < T-1);  p_{T-1} = the rest
  exit = first t with sum_{j<=t} p_j >= early_exit_threshold, else T-1
  logits = h_{exit+1} W_head                  no further norm

A pass attends over the keys and values of the same pass only: that is what
"a K/V cache of its own for every (pass, layer)" means without a cache. At
the published threshold 1 the exit is the last pass unless a lambda rounds
to 1; every pass runs either way.

Departures and assumptions. None in the mathematics as the issue states it.
ASSUMED, as the configuration file lists: the sandwich norms, the per-pass
final norm, the gate's form, the absence of biases and the tensor names
come from the model's published modelling code, not from a key of
``config.json``; ``initializer_range`` 0.02. Weights are random (normal,
std 0.02; norm weights 1 + 0.1 N(0,1); the gate's bias std 0.02) so that
no term of a layer is a no-op."""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _sizes(hf: dict) -> dict:
    return {"d": hf["hidden_size"], "f": hf["intermediate_size"],
            "heads": hf["num_attention_heads"],
            "kv": hf["num_key_value_heads"], "dh": hf["head_dim"],
            "v": hf["vocab_size"], "passes": hf["total_ut_steps"]}


def _layout(hf: dict, layers: int) -> Dict[str, tuple]:
    """name -> (shape, kind) with kind in matrix|bias|norm."""
    s = _sizes(hf)
    d, f = s["d"], s["f"]
    hq, hkv = s["heads"] * s["dh"], s["kv"] * s["dh"]
    out: Dict[str, tuple] = {"model.embed_tokens.weight": ((s["v"], d),
                                                           "matrix")}
    for i in range(layers):
        p = f"model.layers.{i}."
        for norm in ("input_layernorm", "input_layernorm_2",
                     "post_attention_layernorm",
                     "post_attention_layernorm_2"):
            out[p + norm + ".weight"] = ((d,), "norm")
        out[p + "self_attn.q_proj.weight"] = ((hq, d), "matrix")
        out[p + "self_attn.k_proj.weight"] = ((hkv, d), "matrix")
        out[p + "self_attn.v_proj.weight"] = ((hkv, d), "matrix")
        out[p + "self_attn.o_proj.weight"] = ((d, hq), "matrix")
        out[p + "mlp.gate_proj.weight"] = ((f, d), "matrix")
        out[p + "mlp.up_proj.weight"] = ((f, d), "matrix")
        out[p + "mlp.down_proj.weight"] = ((d, f), "matrix")
    out["model.norm.weight"] = ((d,), "norm")
    out["model.early_exit_gate.weight"] = ((1, d), "matrix")
    out["model.early_exit_gate.bias"] = ((1,), "bias")
    out["lm_head.weight"] = ((s["v"], d), "matrix")
    return out


def make_weights(hf: dict, layers: int, seed: int, dtype=jnp.bfloat16
                 ) -> Dict[str, jax.Array]:
    """The seeded checkpoint in the family's published tensor names: one
    jitted call, on the device, in ``dtype``."""
    layout = _layout(hf, layers)
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


def _rms_norm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """HF rotate_half convention: pairs (i, i + Dh/2)."""
    t, _, dh = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _causal_attention(q, k, v):
    """q [T,H,Dh], k/v [T,Hkv,Dh] -> [T,H*Dh]; plain softmax(QK^T/sqrt)V."""
    t, h, dh = q.shape
    rep = h // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(F32(dh))
    mask = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(mask[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("hts,shd->thd", probs, v).reshape(t, h * dh)


def passes(hf: dict, layers: int, weights: Dict[str, jax.Array], ids):
    """ids [T] -> (states [passes, T, D], gates [passes, T]): the normed
    state that closes each pass and the exit gate's logit on it."""
    s = _sizes(hf)
    w = lambda name: weights[name].astype(F32)
    eps, theta = hf["rms_norm_eps"], F32(hf["rope_theta"])
    t = ids.shape[0]
    states, gates = [], []
    with jax.default_matmul_precision("highest"):
        h = w("model.embed_tokens.weight")[ids]
        for _ in range(s["passes"]):
            x = h
            for i in range(layers):
                p = f"model.layers.{i}."
                a = _rms_norm(x, w(p + "input_layernorm.weight"), eps)
                q = (a @ w(p + "self_attn.q_proj.weight").T).reshape(
                    t, s["heads"], s["dh"])
                k = (a @ w(p + "self_attn.k_proj.weight").T).reshape(
                    t, s["kv"], s["dh"])
                v = (a @ w(p + "self_attn.v_proj.weight").T).reshape(
                    t, s["kv"], s["dh"])
                o = _causal_attention(_rope(q, theta), _rope(k, theta), v)
                x = x + _rms_norm(o @ w(p + "self_attn.o_proj.weight").T,
                                  w(p + "input_layernorm_2.weight"), eps)
                m = _rms_norm(x, w(p + "post_attention_layernorm.weight"),
                              eps)
                m = ((jax.nn.silu(m @ w(p + "mlp.gate_proj.weight").T)
                      * (m @ w(p + "mlp.up_proj.weight").T))
                     @ w(p + "mlp.down_proj.weight").T)
                x = x + _rms_norm(
                    m, w(p + "post_attention_layernorm_2.weight"), eps)
            h = _rms_norm(x, w("model.norm.weight"), eps)
            states.append(h)
            gates.append(h @ w("model.early_exit_gate.weight")[0]
                         + w("model.early_exit_gate.bias")[0])
    return jnp.stack(states), jnp.stack(gates)


def exit_pass(hf: dict, gates):
    """gates [passes, T] -> the pass (from 0) each token leaves at."""
    n = hf["total_ut_steps"]
    lam = jax.nn.sigmoid(gates)
    stay = jnp.ones_like(lam[0])
    cum = jnp.zeros_like(lam[0])
    out = jnp.full(lam[0].shape, n - 1, jnp.int32)
    done = jnp.zeros(lam[0].shape, bool)
    for t in range(n - 1):
        cum = cum + lam[t] * stay
        hit = (cum >= hf["early_exit_threshold"]) & ~done
        out = jnp.where(hit, t, out)
        done = done | hit
        stay = stay * (1.0 - lam[t])
    return out


def forward(hf: dict, layers: int, weights: Dict[str, jax.Array], ids):
    """ids [T] -> logits [T, V], float32 at highest matmul precision."""
    states, gates = passes(hf, layers, weights, ids)
    at = exit_pass(hf, gates)
    h = jnp.take_along_axis(states, at[None, :, None], axis=0)[0]
    with jax.default_matmul_precision("highest"):
        return h @ weights["lm_head.weight"].astype(F32).T


def layer_params(hf: dict) -> int:
    """Matrix elements of one layer: q, k, v, o and gate, up, down."""
    s = _sizes(hf)
    return (s["d"] * (s["heads"] + 2 * s["kv"]) * s["dh"]
            + s["heads"] * s["dh"] * s["d"] + 3 * s["d"] * s["f"])


def tick_cost(hf: dict, *, layers: int, sessions: float, kv_rows: float,
              weight_bytes: float, act_bytes: int = 2, ctx=None
              ) -> Dict[str, float]:
    """Bytes and operations ONE decode tick needs AT LEAST. The layer
    weights ``total_ut_steps`` times: a pass needs every layer's weights and
    the next pass needs them again, 4.9 GB at the published depth, which no
    on-chip memory holds between passes (a v5e's VMEM is 128 MiB), so each
    pass streams them from HBM. The head once. The K and V rows IN USE of
    the sessions in the tick for ``total_ut_steps * layers`` cache layers
    (every pass reads its own rows). The matmul and attention arithmetic
    ``total_ut_steps`` times, the head's once. The gate (D elements) is
    left out: a lower bound."""
    s = _sizes(hf)
    n = s["passes"]
    per_layer = layer_params(hf)
    head = s["v"] * s["d"]
    weights = n * layers * per_layer * weight_bytes
    kv_elems = sessions * kv_rows * 2 * n * layers * s["kv"] * s["dh"]
    nbytes = weights + head * act_bytes + kv_elems * act_bytes
    flops = (2.0 * sessions * (n * layers * per_layer + head)
             + 4.0 * sessions * kv_rows * s["heads"] * s["dh"] * n * layers)
    return {"bytes": nbytes, "flops": flops, "weight_bytes": weights,
            "head_bytes": head * act_bytes, "kv_bytes": kv_elems * act_bytes}
