"""Weight-only quantization for serving + quantization-aware block sizing.

Capability parity with the reference's quantization surface (V9,
``petals/server/block_utils.py``): the vendored server sizes and loads
transformer blocks in NONE / INT8 / NF4 precision (``resolve_block_dtype``
``:12-19``, byte accounting with NF4 = 4.25 bits ``get_block_size:22-53``)
and feeds that into how many blocks a server can hold
(``petals/server/server.py:275-326`` ``_choose_num_blocks``).

TPU-native design:
  * int8 weights with per-output-channel fp32 scales (absmax). HBM holds
    int8; dequantization happens INSIDE the jitted step right before each
    matmul — under ``lax.scan`` over stacked layers that means exactly one
    layer's weights materialize at a time, so a stage's resident weight
    memory is ~the int8 bytes.
  * `QuantizedTensor` is a registered pytree node: quantized params slice,
    stack, scan, and device_put exactly like plain arrays, so the executor,
    pipeline, offload runner, and checkpoint streaming need no changes.
  * Norms, biases, embeddings, the lm_head, and MoE routers stay in full
    precision (the reference quantizes transformer blocks only; routers are
    tiny and top-k placement is precision-sensitive).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from .config import ModelConfig

Params = Dict[str, Any]

# bits per weight for sizing (block_utils.py:46: NF4 = 4.25 incl. absmax
# block overhead). The executed NF4 layout below hits this exactly: 4-bit
# codes (two per uint8, packed on the input axis) + one bf16 absmax scale
# per 64-weight block = 4 + 16/64 = 4.25 bits/param.
QUANT_BITS = {"none": None, "int8": 8, "nf4": 4.25}

# The 16 NormalFloat4 levels (quantiles of N(0,1), endpoints at ±1 —
# the QLoRA code-book used by the reference's bitsandbytes NF4 path).
NF4_LEVELS = (
    -1.0, -0.6961928009986877, -0.5250730514526367, -0.39491748809814453,
    -0.28444138169288635, -0.18477343022823334, -0.09105003625154495, 0.0,
    0.07958029955625534, 0.16093020141124725, 0.24611230194568634,
    0.33791524171829224, 0.4407098591327667, 0.5626170039176941,
    0.7229568362236023, 1.0,
)
NF4_BLOCK = 64   # weights per absmax block (QLoRA default)


def _lut16(codes: jnp.ndarray, table) -> jnp.ndarray:
    """16-entry lookup as a 4-level SELECT TREE (15 elementwise wheres on
    the code bits) instead of a per-element gather. Measured on a v5e:
    `jnp.take` over the 16-entry table lowered to a real gather and made
    nf4 flagship decode 8x SLOWER than bf16 (32.7 ms/step vs 4.1); the
    select tree vectorizes on the VPU and fuses into the consumer. codes:
    int32 [...] in [0, 16). Returns f32 of the same shape."""
    b0 = (codes & 1).astype(bool)
    b1 = (codes & 2).astype(bool)
    b2 = (codes & 4).astype(bool)
    b3 = (codes & 8).astype(bool)
    # f32 levels — LOAD-BEARING for the fused kernel (ops.nf4_kernel runs
    # THIS function inside Mosaic, which cannot relayout int32-derived
    # bool masks into bf16-tiled selects), measured identical speed to
    # bf16 intermediates on the XLA path (op-bound, not width-bound), and
    # keeps both paths' dequant VALUES identical so they differ only by
    # matmul accumulation order.
    lvl = [jnp.float32(t) for t in table]
    l1 = [jnp.where(b0, lvl[2 * i + 1], lvl[2 * i]) for i in range(8)]
    l2 = [jnp.where(b1, l1[2 * i + 1], l1[2 * i]) for i in range(4)]
    l3 = [jnp.where(b2, l2[2 * i + 1], l2[2 * i]) for i in range(2)]
    return jnp.where(b3, l3[1], l3[0])


@jax.tree_util.register_pytree_node_class
class QuantizedTensor:
    """int8 weight + per-output-channel fp32 scale.

    Layout: q has the original weight shape [..., in, out]; s broadcasts as
    [..., 1, out] so ``q * s`` reconstructs. `dtype` records the original
    dtype for reconstruction. ``axis`` is the axis a product CONTRACTS, the
    one a scale was taken along: -2 for every ``[in, out]`` weight, -1 for
    one that rests ``[.., out, in]`` (`_MATMUL_KEYS_T`: s is then [..,
    out, 1], and the int8 kernel, which reads ``[in, out]``, is not for
    it).
    """

    def __init__(self, q: jnp.ndarray, s: jnp.ndarray, dtype: str = "float32",
                 axis: int = -2):
        self.q = q
        self.s = s
        self.dtype = dtype
        self.axis = axis

    def tree_flatten(self):
        return (self.q, self.s), (self.dtype, self.axis)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def shape(self):
        return self.q.shape

    def dequant(self) -> jnp.ndarray:
        return (self.q.astype(jnp.float32) * self.s).astype(self.dtype)

    def __repr__(self):
        return (f"QuantizedTensor(shape={tuple(self.q.shape)}, "
                f"dtype={self.dtype}, axis={self.axis})")


class QuantizedLayerView:
    """One layer of a stacked int8 weight, BY REFERENCE: the whole
    ``[L, in, out]`` `QuantizedTensor` and a layer index (a Python int or
    the traced counter of a ``lax.scan``). Reads as that layer's 2-D leaf
    (`shape`, `dtype`, `dequant`), but the slice is only taken by whoever
    needs it (`layer`): ops.int8_kernel hands the stack itself to its
    Pallas call, which starts its DMA at the layer's offset, so the
    layer's bytes are read once and never copied out first.

    Made inside a scan body (runtime.batching) and consumed there;
    deliberately NOT a pytree node, so it cannot cross a ``jit`` or
    ``scan`` boundary as an argument."""

    def __init__(self, stack: QuantizedTensor, index):
        self.stack = stack
        self.index = index

    @property
    def shape(self):
        return self.stack.q.shape[1:]

    @property
    def dtype(self):
        return self.stack.dtype

    @property
    def axis(self):
        return self.stack.axis

    def layer(self) -> QuantizedTensor:
        """The 2-D leaf a scan over the stack would have been handed."""
        q, s = (jax.lax.dynamic_index_in_dim(a, self.index, 0,
                                             keepdims=False)
                for a in (self.stack.q, self.stack.s))
        return QuantizedTensor(q, s, self.dtype, self.axis)

    def dequant(self) -> jnp.ndarray:
        return self.layer().dequant()

    def __repr__(self):
        return (f"QuantizedLayerView(shape={tuple(self.shape)}, "
                f"dtype={self.dtype})")


@jax.tree_util.register_pytree_node_class
class NF4Tensor:
    """4-bit NormalFloat weight: packed codes + per-block bf16 absmax scales.

    Layout (for an original weight [..., in, out]):
      * ``packed``: uint8 [..., in_pad/2, out] — two 4-bit codes per byte
        along the INPUT axis (high nibble = even row, low nibble = odd row);
      * ``scales``: bfloat16 [..., in_pad/64, out] — absmax per 64-weight
        input-axis block (in_pad = in rounded up to 64).

    4 + 16/64 = 4.25 bits/param resident — the exact sizing constant of
    ``petals/server/block_utils.py:46``. Registered as a pytree so NF4
    params slice/stack/scan/device_put like plain arrays; `dequant()` runs
    INSIDE the jitted step (a 16-entry gather + one multiply, fused by XLA),
    so under ``lax.scan`` only one layer materializes full-precision.
    """

    def __init__(self, packed: jnp.ndarray, scales: jnp.ndarray,
                 in_dim: int, dtype: str = "float32"):
        self.packed = packed
        self.scales = scales
        self.in_dim = in_dim
        self.dtype = dtype

    def tree_flatten(self):
        return (self.packed, self.scales), (self.in_dim, self.dtype)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux[0], aux[1])

    @property
    def shape(self):
        return (*self.packed.shape[:-2], self.in_dim, self.packed.shape[-1])

    def dequant(self) -> jnp.ndarray:
        high = (self.packed >> 4).astype(jnp.int32)
        low = (self.packed & 0xF).astype(jnp.int32)
        codes = jnp.stack([high, low], axis=-2)        # [..., P, 2, out]
        lead = self.packed.shape[:-2]
        out = self.packed.shape[-1]
        in_pad = self.packed.shape[-2] * 2
        vals = _lut16(codes.reshape(*lead, in_pad, out), NF4_LEVELS)
        nb = in_pad // NF4_BLOCK
        vals = vals.reshape(*lead, nb, NF4_BLOCK, out)
        vals = vals * self.scales[..., :, None, :].astype(jnp.float32)
        vals = vals.reshape(*lead, in_pad, out)
        return vals[..., : self.in_dim, :].astype(self.dtype)

    def __repr__(self):
        return f"NF4Tensor(shape={tuple(self.shape)}, dtype={self.dtype})"


def _quantize_leaf_nf4(w) -> NF4Tensor:
    """Host-side NF4 quantization: block the input axis by 64, scale each
    block to [-1, 1] by its (bf16-rounded) absmax, snap to the nearest of
    the 16 NF4 levels via boundary search (O(1) temp memory), pack two codes
    per byte."""
    import numpy as np

    w_np = np.asarray(jax.device_get(w), np.float32)
    *lead, in_dim, out = w_np.shape
    in_pad = -(-in_dim // NF4_BLOCK) * NF4_BLOCK
    if in_pad != in_dim:
        pad = [(0, 0)] * len(lead) + [(0, in_pad - in_dim), (0, 0)]
        w_np = np.pad(w_np, pad)
    nb = in_pad // NF4_BLOCK
    blocks = w_np.reshape(*lead, nb, NF4_BLOCK, out)
    absmax = np.max(np.abs(blocks), axis=-2, keepdims=True)
    # Quantize AGAINST the bf16-rounded scale the dequant will actually use,
    # so the round trip has no scale mismatch on top of the 4-bit error.
    scales = jnp.asarray(absmax[..., 0, :], jnp.bfloat16)
    scale32 = np.asarray(scales, np.float32)[..., None, :]
    norm = np.divide(blocks, scale32, out=np.zeros_like(blocks),
                     where=scale32 > 0)
    levels = np.asarray(NF4_LEVELS, np.float32)
    bounds = (levels[1:] + levels[:-1]) / 2.0
    codes = np.searchsorted(bounds, norm).astype(np.uint8)
    codes = codes.reshape(*lead, in_pad, out)
    packed = (codes[..., 0::2, :] << 4) | codes[..., 1::2, :]
    return NF4Tensor(jnp.asarray(packed), scales, in_dim,
                     str(jnp.asarray(w).dtype))


def _quantize_leaf(w: jnp.ndarray, axis: int = -2) -> QuantizedTensor:
    """Per-output-channel absmax int8: reduce over the input axis (``axis``:
    -2, the channel axis last, for [in, out], stacked [L, in, out] and
    expert [E, in, out] weights alike; -1 for a weight that rests [..,
    out, in], whose scales are then the SAME numbers, one an output
    row)."""
    w32 = jnp.asarray(w, jnp.float32)
    absmax = jnp.max(jnp.abs(w32), axis=axis, keepdims=True)
    s = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(w32 / s), -127, 127).astype(jnp.int8)
    return QuantizedTensor(q, s.astype(jnp.float32),
                           str(jnp.asarray(w).dtype), axis)


# The matmul weight names of models/transformer.py's layer schema. Norms,
# biases, and the MoE "router" are deliberately absent (full precision).
_MATMUL_KEYS = frozenset(
    {"wq", "wk", "wv", "wqkv", "wo", "wg", "wu", "wgu", "wd", "wi",
     # a latent-attention family's bottlenecks (models.config kv_lora_rank)
     "wqa"})
# The same family's four weights that rest with the contracted axis LAST,
# [out, in] or by head [heads, out a head, in] (models.hf_import._glm5_layer;
# read by models.transformer._dot_t and runtime.batching._attend_latent,
# dequantised there). int8 takes their scales along that axis: the numbers
# the [in, out] form had. NF4 blocks every leaf's axis -2 by 64, which for
# these is 64 output rows of one input column: 4.25 bits a weight either
# way.
_MATMUL_KEYS_T = frozenset({"wqb_t", "wkva_t", "wkvb_t", "wiq_t"})


def quantize_layers(layers: Params, quant: str = "int8") -> Params:
    """Quantize a `layers` subtree (stacked or single): matmul weights by
    NAME (norm weights and biases share the ndim of stacked matmul weights,
    so shape alone cannot distinguish them)."""
    if quant in (None, "none"):
        return layers
    if quant not in ("int8", "nf4"):
        raise NotImplementedError(
            f"quant={quant!r}: int8 and nf4 execution are implemented")
    leaf = _quantize_leaf if quant == "int8" else _quantize_leaf_nf4
    # a weight that rests [.., out, in]: int8's scales run along its LAST axis
    leaf_t = partial(_quantize_leaf, axis=-1) if quant == "int8" else leaf

    def walk(tree, key=None):
        if isinstance(tree, dict):
            return {k: walk(v, k) for k, v in tree.items()}
        if getattr(tree, "ndim", 0) < 2:
            return tree
        if key in _MATMUL_KEYS:
            return leaf(tree)
        if key in _MATMUL_KEYS_T:
            return leaf_t(tree)
        return tree

    # dict-walk instead of tree_map: the selection is name-dependent.
    return walk(layers)


def quantize_params(params: Params, quant: str = "int8") -> Params:
    """Quantize a full/stage param tree: blocks only (embed/head/norm full
    precision, matching the reference's block-scoped quantization)."""
    out = dict(params)
    for key in ("layers", "dense_layers", "sliding_layers"):
        if key in params:
            out[key] = quantize_layers(params[key], quant)
    return out


_QUANT_TYPES = (QuantizedTensor, NF4Tensor, QuantizedLayerView)


def nf4_kernel_enabled() -> bool:
    """NF4_KERNEL=1 routes per-layer NF4 matmuls through the fused Pallas
    dequant-matmul kernel (ops.nf4_kernel) instead of materializing the
    weight — the measured lever for nf4 decode throughput. Default OFF.

    Trace-time flag (utils/flags.py catalog): resolved while the engine
    traces, so flips after warmup require a retrace."""
    from ..utils.flags import bool_flag

    return bool_flag("NF4_KERNEL")


def int8_fold_enabled() -> bool:
    """INT8_FOLD=1 (default ON) keeps per-layer 2-D int8 leaves packed so
    the matmul sites stream the int8 bytes and apply the per-channel
    scale in the matmul EPILOGUE (ops.int8_kernel: ``(x @ q) * s``)
    instead of materializing a full bf16 weight per layer first (the
    int8 read, then a bf16 write and read, where decode is bound by the
    bytes it moves).
    INT8_FOLD=0 restores the dequant-materialize path (bit-for-bit the
    round-5 behavior) as the kill switch.

    Trace-time flag (utils/flags.py catalog): resolved while the engine
    traces, so flips after warmup require a retrace."""
    from ..utils.flags import bool_flag

    return bool_flag("INT8_FOLD")


def dequant_tree(tree: Params, keep_experts: bool = False) -> Params:
    """Materialize full-precision weights for any quantized leaves (int8 or
    NF4). Identity (and free) for unquantized trees; under jit+scan this
    runs per layer, so only one layer's weights exist dequantized at a
    time.

    With `nf4_kernel_enabled()`, per-layer (2-D) NF4 leaves stay packed —
    the matmul sites (`models.transformer._dot`) feed them to the fused
    kernel. With `int8_fold_enabled()` (default), per-layer (2-D) int8
    leaves stay packed the same way and run the scale-folded epilogue
    (ops.int8_kernel); a `QuantizedLayerView` IS such a leaf, passed on
    as it is or sliced and materialized like one.

    `keep_experts=True` (the PER-LAYER MoE call sites: layer_forward and
    the engine scan bodies, where any 3-D quantized leaf IS an [E, in,
    out] expert stack) keeps those stacks packed too whenever the sparse
    dispatch is on (`models.moe.moe_sparse_enabled`): the grouped matmuls
    consume them per expert (int8 scale-folded einsum / NF4 one-expert-at-
    a-time lax.map — models.moe._expert_dot), so a stage's resident expert
    bytes stay at the quantized size. Default False because callers also
    dequant whole STACKED trees, where a 3-D leaf is an [L, in, out] dense
    weight, not an expert stack."""
    keep_nf4 = nf4_kernel_enabled()
    keep_int8 = int8_fold_enabled()
    if keep_experts:
        from .moe import moe_sparse_enabled

        keep_experts = moe_sparse_enabled()

    def f(x):
        if not isinstance(x, _QUANT_TYPES):
            return x
        nd = len(x.shape)
        if keep_nf4 and isinstance(x, NF4Tensor) and nd == 2:
            return x
        if (keep_int8 and nd == 2
                and isinstance(x, (QuantizedTensor, QuantizedLayerView))):
            return x
        if keep_experts and nd == 3:
            return x
        return x.dequant()

    return jax.tree.map(
        f, tree, is_leaf=lambda x: isinstance(x, _QUANT_TYPES))


def is_quantized(tree: Params) -> bool:
    return any(isinstance(x, _QUANT_TYPES) for x in jax.tree.leaves(
        tree, is_leaf=lambda x: isinstance(x, _QUANT_TYPES)))


# ---------------------------------------------------------------------------
# Quantization-aware sizing (block_utils.get_block_size:22-53) and server
# auto-capacity (server.py _choose_num_blocks:275-326)
# ---------------------------------------------------------------------------

def params_per_block(cfg: ModelConfig) -> int:
    """Parameter count of ONE transformer block (no embed/head)."""
    d, i = cfg.hidden_size, cfg.intermediate_size
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    attn = d * h * dh + 2 * d * hkv * dh + h * dh * d
    if cfg.use_bias or cfg.attn_qkv_bias:
        attn += h * dh + 2 * hkv * dh   # q/k/v biases (gpt2 AND qwen2)
    if cfg.use_bias:
        attn += d                        # o bias (gpt2 only)
    if cfg.is_moe:
        mlp = cfg.num_experts * 3 * d * i + d * cfg.num_experts
    elif cfg.mlp == "swiglu":
        mlp = 3 * d * i
    else:
        mlp = 2 * d * i + (i + d if cfg.use_bias else 0)
    norms = (4 if cfg.norm == "layernorm" else 2) * d
    return attn + mlp + norms


def block_bytes(cfg: ModelConfig, dtype_bytes: int = 2,
                quant: str = "none") -> int:
    """Bytes one block occupies resident (quant-aware, V9 parity)."""
    if quant not in QUANT_BITS:
        raise ValueError(f"unknown quant mode {quant!r} "
                         f"(expected one of {sorted(QUANT_BITS)})")
    n = params_per_block(cfg)
    bits = QUANT_BITS[quant]
    if bits is None:  # "none": full precision
        return n * dtype_bytes
    return int(n * bits / 8)


def choose_num_blocks(
    cfg: ModelConfig,
    memory_budget_bytes: int,
    *,
    dtype_bytes: int = 2,
    quant: str = "none",
    attn_cache_bytes: int = 0,
    reserve_fraction: float = 0.05,
) -> int:
    """How many blocks fit a device budget after the KV-cache arena and a
    safety reserve — the server auto-capacity rule
    (``petals/server/server.py:275-326``, which budgets weights + attention
    cache + autograd headroom out of free GPU memory)."""
    usable = int(memory_budget_bytes * (1.0 - reserve_fraction))
    usable -= attn_cache_bytes
    per = block_bytes(cfg, dtype_bytes, quant)
    return max(1, min(cfg.num_layers, usable // max(per, 1)))
