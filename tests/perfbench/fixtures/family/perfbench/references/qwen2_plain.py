"""A configuration's own module, as a later family would bring it: the
seeded checkpoint and the plain forward pass of the qwen2 family, written
for this fixture alone (nothing of ``harness/reference.py`` is used), plus
the optional cost count. Published tensor names and layouts (Hugging Face
``Qwen2ForCausalLM``): ``[out, in]`` projections, q/k/v biases, RoPE on
the half-split pairs, one SwiGLU, RMS norms, tied or untied head."""

import jax
import jax.numpy as jnp

PROJ = ("q_proj", "k_proj", "v_proj")


def _shapes(hf, layers):
    d, f, v = hf["hidden_size"], hf["intermediate_size"], hf["vocab_size"]
    dh = d // hf["num_attention_heads"]
    out = {"q_proj": hf["num_attention_heads"] * dh,
           "k_proj": hf["num_key_value_heads"] * dh,
           "v_proj": hf["num_key_value_heads"] * dh}
    shapes = {"model.embed_tokens.weight": (v, d), "model.norm.weight": (d,)}
    if not hf.get("tie_word_embeddings"):
        shapes["lm_head.weight"] = (v, d)
    for i in range(layers):
        p = f"model.layers.{i}."
        for name in PROJ:
            shapes[p + f"self_attn.{name}.weight"] = (out[name], d)
            shapes[p + f"self_attn.{name}.bias"] = (out[name],)
        shapes[p + "self_attn.o_proj.weight"] = (d, out["q_proj"])
        shapes[p + "mlp.gate_proj.weight"] = (f, d)
        shapes[p + "mlp.up_proj.weight"] = (f, d)
        shapes[p + "mlp.down_proj.weight"] = (d, f)
        shapes[p + "input_layernorm.weight"] = (d,)
        shapes[p + "post_attention_layernorm.weight"] = (d,)
    return shapes


def make_weights(hf, layers, seed, dtype=jnp.bfloat16):
    """One jitted call on the device. Biases are drawn wide (std 0.5), so
    that a forward pass that drops them is far from this one."""
    shapes = _shapes(hf, layers)

    @jax.jit
    def draw(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            x = jax.random.normal(jax.random.fold_in(key, i), shape)
            if name.endswith("norm.weight"):
                x = 1.0 + 0.1 * x
            else:
                x = (0.5 if name.endswith(".bias") else 0.02) * x
            out[name] = x.astype(dtype)
        return out

    return draw(jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                                   seed >> 31))


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rotate(x, theta):
    """x [T, H, Dh]: component i turns with component i + Dh/2."""
    t, _, dh = x.shape
    freq = theta ** (-jnp.arange(dh // 2, dtype=jnp.float32) * 2 / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * freq
    lo, hi = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([lo * jnp.cos(ang) - hi * jnp.sin(ang),
                            hi * jnp.cos(ang) + lo * jnp.sin(ang)], -1)


def forward(hf, layers, weights, ids):
    """ids [T] -> logits [T, V]: float32, every product at ``highest``."""
    w = {k: v.astype(jnp.float32) for k, v in weights.items()}
    nh, nkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    eps, theta, t = hf["rms_norm_eps"], hf["rope_theta"], ids.shape[0]
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    with jax.default_matmul_precision("highest"):
        x = w["model.embed_tokens.weight"][ids]
        for i in range(layers):
            p = f"model.layers.{i}."
            a = _norm(x, w[p + "input_layernorm.weight"], eps)
            q, k, v = (
                (a @ w[p + f"self_attn.{n}.weight"].T
                 + w[p + f"self_attn.{n}.bias"]).reshape(t, heads, -1)
                for n, heads in zip(PROJ, (nh, nkv, nkv)))
            q = _rotate(q, theta).reshape(t, nkv, nh // nkv, -1)
            k = _rotate(k, theta)
            score = jnp.einsum("tgrd,sgd->grts", q, k) / q.shape[-1] ** 0.5
            prob = jax.nn.softmax(jnp.where(causal, score, -jnp.inf), -1)
            att = jnp.einsum("grts,sgd->tgrd", prob, v).reshape(t, -1)
            x = x + att @ w[p + "self_attn.o_proj.weight"].T
            m = _norm(x, w[p + "post_attention_layernorm.weight"], eps)
            m = (jax.nn.silu(m @ w[p + "mlp.gate_proj.weight"].T)
                 * (m @ w[p + "mlp.up_proj.weight"].T))
            x = x + m @ w[p + "mlp.down_proj.weight"].T
        x = _norm(x, w["model.norm.weight"], eps)
        head = w["model.embed_tokens.weight" if hf.get("tie_word_embeddings")
                 else "lm_head.weight"]
        return x @ head.T


def tick_cost(hf, *, layers, sessions, kv_rows, weight_bytes, ctx):
    """Bytes and operations of one decode tick, counted for this family
    alone: every layer matrix with its q/k/v biases (which the stock count
    leaves out) and the head once, the K and V rows in use. ``ctx`` is the
    reader's context, for what only a run can count; this family takes
    nothing from it and notes that its own count ran."""
    ctx.setdefault("notes", {})["tick_cost"] = "qwen2_plain"
    d, f = hf["hidden_size"], hf["intermediate_size"]
    kv = hf["num_key_value_heads"] * (d // hf["num_attention_heads"])
    per_layer = d * (d + 2 * kv) + d * d + 3 * d * f
    head = hf["vocab_size"] * d
    weights = layers * (per_layer * weight_bytes + (d + 2 * kv) * 2)
    kv_bytes = sessions * kv_rows * 2 * layers * kv * 2
    return {"bytes": weights + head * 2 + kv_bytes,
            "flops": 2.0 * sessions * (layers * per_layer + head)
            + 4.0 * sessions * kv_rows * d * layers,
            "weight_bytes": weights, "head_bytes": head * 2,
            "kv_bytes": kv_bytes}
