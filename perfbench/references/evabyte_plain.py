"""The plain reference of EvaByte (HF ``evabyte``, ``attention_class: eva``:
a byte-level language model whose attention keeps exact keys for the
current window and one learned summary per chunk of earlier windows), its
seeded weights, and the least a decode tick of it needs.

Source: https://huggingface.co/EvaByte/EvaByte/blob/main/config.json
EVA is arXiv:2302.04542 (Zheng et al., "Efficient Attention via Control
Variates") as the model's published modelling code specialises it. This
file imports nothing of the program. The program loads ``make_weights``'s
checkpoint through its own importer (``models.hf_import
.convert_state_dict``) and never hands this file a decision of its own (no
cache, no window index, no summary).

The equations. ``N(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)`` (RMSNorm,
the scale stored as an offset from one: ``norm_add_unit_offset``), ``W =
window_size``, ``C = chunk_size``, ``s = head_dim ** -0.5``, per head:

  h = E[ids]
  for every layer:
    a = N(h; input_layernorm)
    q, k, v = a Wq, a Wk, a Wv              [.., heads, head_dim], no bias
    q, k = rope(q), rope(k)                 theta 1e5, rotate-half, position
                                            = byte index; everything below
                                            uses the ROTATED keys
    for every COMPLETE chunk c (positions C c .. C c + C - 1):
      k~_c = sum_i softmax_i(s k_i . mu) k_i         mu  = adaptive_mu_k
      v~_c = sum_i softmax_i(s k_i . phi) v_i        phi = adaptive_phi
    for the query at position p, ONE softmax with scale s over
      (a) the exact keys k_j, j // W == p // W and j <= p   (its own window)
      (b) the summaries k~_c, c // (W / C) < p // W         (earlier windows)
    and the same weights over the v_j and the v~_c
    h = h + o Wo
    m = N(h; post_attention_layernorm)
    h = h + (silu(m Wg) * (m Wu)) Wd
  logits = N(h; norm) W_head[:vocab]        prediction head 0 of 8

For its first W positions a sequence is plain causal attention: nothing the
architecture adds runs before row W.

Departures and assumptions (the configuration file lists them under
``assumed``, in the issue's words): the pooling form (two per-head vectors,
keys pooled by ``mu``, values by ``phi``, the scale ``s`` inside both:
``config.json`` gives none of it; a scale is absorbed by a learned vector,
so the function class is the same with or without it); summaries taken of
rotated keys; windows aligned to multiples of W from position 0; the head
as ``[8 x 320, 4096]`` with prediction head 0 first; ``init_std`` 0.01275
for the seeded matrices; ``mu`` and ``phi`` drawn ``clip(N(0, 1), -1, 1) *
head_dim ** -0.5``, held as ``[1, heads, 1, 1, head_dim]``. NOT computed
here, as it is not served: self-speculative multi-byte decoding
(prediction heads 1-7 are drawn and held, never multiplied). Norm offsets
are drawn 0.1 N(0, 1) so that no term of a layer is a no-op."""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32
# Query rows scored at once: 14133 rows against themselves would be 25.6 GB
# of float32 scores at 32 heads; a block of 1024 is 1.9 GB.
ATTN_ROWS = 1024


def _sizes(hf: dict) -> dict:
    heads = hf["num_attention_heads"]
    return {"d": hf["hidden_size"], "f": hf["intermediate_size"],
            "heads": heads, "dh": hf["hidden_size"] // heads,
            "v": hf["vocab_size"], "pred": hf["num_pred_heads"],
            "w": hf["window_size"], "c": hf["chunk_size"]}


def _layout(hf: dict, layers: int) -> Dict[str, tuple]:
    """name -> (shape, kind) with kind in matrix|norm|pool."""
    s = _sizes(hf)
    d, f, hd = s["d"], s["f"], s["heads"] * s["dh"]
    out: Dict[str, tuple] = {"model.embed_tokens.weight": ((s["v"], d),
                                                           "matrix")}
    for i in range(layers):
        p = f"model.layers.{i}."
        out[p + "input_layernorm.weight"] = ((d,), "norm")
        out[p + "post_attention_layernorm.weight"] = ((d,), "norm")
        for name in ("q_proj", "k_proj", "v_proj"):
            out[p + f"self_attn.{name}.weight"] = ((hd, d), "matrix")
        out[p + "self_attn.o_proj.weight"] = ((d, hd), "matrix")
        for name in ("adaptive_mu_k", "adaptive_phi"):
            out[p + "self_attn." + name] = (
                (1, s["heads"], 1, 1, s["dh"]), "pool")
        out[p + "mlp.gate_proj.weight"] = ((f, d), "matrix")
        out[p + "mlp.up_proj.weight"] = ((f, d), "matrix")
        out[p + "mlp.down_proj.weight"] = ((d, f), "matrix")
    out["model.norm.weight"] = ((d,), "norm")
    out["lm_head.weight"] = ((s["pred"] * s["v"], d), "matrix")
    return out


def make_weights(hf: dict, layers: int, seed: int, dtype=jnp.bfloat16
                 ) -> Dict[str, jax.Array]:
    """The seeded checkpoint in the published model's tensor names: one
    jitted call, on the device, in ``dtype``."""
    layout = _layout(hf, layers)
    names = sorted(layout)
    std, dh = hf["init_std"], _sizes(hf)["dh"]

    @jax.jit
    def draw(key):
        out = {}
        for i, name in enumerate(names):
            shape, kind = layout[name]
            x = jax.random.normal(jax.random.fold_in(key, i), shape, F32)
            x = {"matrix": std * x, "norm": 0.1 * x,
                 "pool": jnp.clip(x, -1.0, 1.0) * dh ** -0.5}[kind]
            out[name] = x.astype(dtype)
        return out

    # any whole number of a seed: fold its high bits in, PRNGKey takes 32
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             (seed >> 31) & 0x7FFFFFFF)
    return draw(key)


def _rms_norm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * (1.0 + w)


def _rope(x, theta):
    """HF rotate_half convention: pairs (i, i + Dh/2)."""
    t, _, dh = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def summaries(q_scale, k, v, mu, phi, chunk: int):
    """k, v [T, H, Dh] (k rotated), mu, phi [H, Dh] -> the summaries of
    the T // chunk complete chunks, [T // chunk, H, Dh] each."""
    n = k.shape[0] // chunk
    kc = k[:n * chunk].reshape(n, chunk, *k.shape[1:])
    vc = v[:n * chunk].reshape(n, chunk, *v.shape[1:])
    wk = jax.nn.softmax(q_scale * jnp.einsum("nchd,hd->nch", kc, mu), axis=1)
    wv = jax.nn.softmax(q_scale * jnp.einsum("nchd,hd->nch", kc, phi), axis=1)
    return (wk[..., None] * kc).sum(1), (wv[..., None] * vc).sum(1)


def _eva_attention(q, k, v, mu, phi, window: int, chunk: int):
    """q, k, v [T, H, Dh] (q, k rotated) -> [T, H * Dh]: for each block of
    at most `ATTN_ROWS` query rows, one softmax over the exact keys of the
    query's own window up to itself and the summaries of every chunk of
    earlier windows."""
    t, h, dh = q.shape
    s = dh ** -0.5
    ks, vs = summaries(s, k, v, mu, phi, chunk)
    n = ks.shape[0]
    keys = jnp.concatenate([k, ks])                     # [T + n, H, Dh]
    vals = jnp.concatenate([v, vs])
    j = jnp.arange(t)[None, :]
    c = jnp.arange(n)[None, :]
    outs = []
    for start in range(0, t, ATTN_ROWS):
        p = jnp.arange(start, min(start + ATTN_ROWS, t))[:, None]
        exact = (j // window == p // window) & (j <= p)
        summed = c // (window // chunk) < p // window
        allowed = jnp.concatenate([exact, summed], axis=1)
        scores = s * jnp.einsum("thd,shd->hts", q[start:start + ATTN_ROWS],
                                keys)
        probs = jax.nn.softmax(jnp.where(allowed[None], scores, -jnp.inf),
                               axis=-1)
        outs.append(jnp.einsum("hts,shd->thd", probs, vals))
    return jnp.concatenate(outs).reshape(t, h * dh)


def forward(hf: dict, layers: int, weights: Dict[str, jax.Array], ids):
    """ids [T] -> logits [T, vocab], float32 at highest matmul precision:
    no cache, no kernels, every chunk summarised from the full sequence."""
    s = _sizes(hf)
    w = lambda name: weights[name].astype(F32)
    eps, theta = hf["rms_norm_eps"], F32(hf["rope_theta"])
    t = ids.shape[0]
    with jax.default_matmul_precision("highest"):
        h = w("model.embed_tokens.weight")[ids]
        for i in range(layers):
            p = f"model.layers.{i}."
            a = _rms_norm(h, w(p + "input_layernorm.weight"), eps)
            q, k, v = ((a @ w(p + f"self_attn.{n}.weight").T).reshape(
                t, s["heads"], s["dh"]) for n in ("q_proj", "k_proj",
                                                  "v_proj"))
            o = _eva_attention(
                _rope(q, theta), _rope(k, theta), v,
                w(p + "self_attn.adaptive_mu_k").reshape(s["heads"], s["dh"]),
                w(p + "self_attn.adaptive_phi").reshape(s["heads"], s["dh"]),
                s["w"], s["c"])
            h = h + o @ w(p + "self_attn.o_proj.weight").T
            m = _rms_norm(h, w(p + "post_attention_layernorm.weight"), eps)
            h = h + ((jax.nn.silu(m @ w(p + "mlp.gate_proj.weight").T)
                      * (m @ w(p + "mlp.up_proj.weight").T))
                     @ w(p + "mlp.down_proj.weight").T)
        h = _rms_norm(h, w("model.norm.weight"), eps)
        return h @ w("lm_head.weight")[:s["v"]].T      # prediction head 0


def layer_params(hf: dict) -> int:
    """Matrix elements of one layer: q, k, v, o and gate, up, down."""
    s = _sizes(hf)
    return 4 * s["d"] * s["heads"] * s["dh"] + 3 * s["d"] * s["f"]


def rows_read(hf: dict, position: int) -> int:
    """Rows of ONE cache layer the query at ``position`` attends over: the
    exact rows of its window up to itself and one summary per chunk of the
    earlier windows."""
    s = _sizes(hf)
    return position % s["w"] + 1 + position // s["w"] * (s["w"] // s["c"])


def mean_rows_read(hf: dict, mean_position: float) -> float:
    """`rows_read` averaged over sessions whose positions average
    ``mean_position`` and whose phases within a window are uniform: past
    the first window (W + 1) / 2 exact rows and (p / W - 1 / 2) x (W / C)
    summaries; inside it every row."""
    s = _sizes(hf)
    w, c = s["w"], s["c"]
    if mean_position < w:
        return mean_position + 1
    return (w + 1) / 2 + mean_position / c - w / (2 * c)


def tick_cost(hf: dict, *, layers: int, sessions: float, kv_rows: float,
              weight_bytes: float, act_bytes: int = 2, ctx=None
              ) -> Dict[str, float]:
    """Bytes and operations ONE decode tick needs AT LEAST: the layer
    weights of the served depth once, head 0's rows once, and the K and V
    rows the tick READS of the sessions in it, not the positions they have
    sent: a session at position p reads `rows_read` rows a layer
    (``kv_rows`` is the harness's mean of p). Where the run's counters say
    how many rows the window's sessions held against the positions they
    had sent (``server_state_rows_held_total`` /
    ``server_positions_held_total``, both in ``ctx``), that measured share
    of ``kv_rows`` is taken; else `mean_rows_read`."""
    s = _sizes(hf)
    per_layer = layer_params(hf)
    head = s["v"] * s["d"]
    rows = mean_rows_read(hf, kv_rows)
    if ctx is not None:
        held = _delta(ctx, "server_state_rows_held_total")
        sent = _delta(ctx, "server_positions_held_total")
        if held and sent:
            rows = kv_rows * held / sent
    weights = layers * per_layer * weight_bytes
    kv_elems = sessions * rows * 2 * layers * s["heads"] * s["dh"]
    nbytes = weights + head * act_bytes + kv_elems * act_bytes
    flops = (2.0 * sessions * (layers * per_layer + head)
             + 4.0 * sessions * rows * s["heads"] * s["dh"] * layers)
    return {"bytes": nbytes, "flops": flops, "weight_bytes": weights,
            "head_bytes": head * act_bytes, "kv_bytes": kv_elems * act_bytes,
            "kv_rows_read": rows}


def _delta(ctx: dict, key: str):
    """How far a series of the servers moved in the window, or None."""
    total, seen = 0.0, False
    for peer, after in ctx.get("counters_after", {}).items():
        if key in after:
            total += after[key] - ctx.get("counters_before", {}).get(
                peer, {}).get(key, 0.0)
            seen = True
    return total if seen else None
