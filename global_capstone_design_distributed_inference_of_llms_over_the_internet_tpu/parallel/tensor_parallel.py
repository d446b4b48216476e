"""Tensor / expert parallelism for one pipeline stage via shard_map.

The reference gets TP only through the external ``tensor_parallel`` package
wrapping torch blocks (``petals/server/backend.py:43``, asserts every backend
is a TensorParallel instance); MoE/EP exists only as config guards with no
runnable code (SURVEY.md §2.3). Here both are first-class mesh axes:

  * TP ("megatron"-style): q/k/v and mlp-in projections are column-sharded
    over the ``tp`` axis, out-projections row-sharded, so each matmul pair
    needs exactly ONE ``psum`` (already emitted inside
    ``models.transformer`` when ``tp_axis`` is set). The KV cache shards
    over kv heads — GQA requires ``num_kv_heads % tp == 0``.
  * EP (MoE): expert weights shard over the same axis; the router stays
    replicated so top-k routing is global, each device computes its local
    experts' weighted contribution, and the same closing psum combines.

Composability: the specs returned here are ordinary PartitionSpecs over one
named axis, so a stage can run TP inside a pipeline stage's device group
(mesh ("stage", "tp")) — the fused pipeline shard-maps over "stage" and this
module's body runs inside it over "tp".
"""

from __future__ import annotations

import re
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.config import ModelConfig
from ..models.partition import (
    StageSpec,
    match_partition_rules,
    path_name,
    stage_forward,
)

Params = Dict[str, Any]


def tp_partition_rules(cfg: ModelConfig, axis: str = "tp"):
    """Explicit (regex, PartitionSpec) rules for stacked [L, ...] layer
    leaves, consumed by `models.partition.match_partition_rules`.

    Dense blocks: column-parallel in (q/k/v and mlp-in sharded on the
    OUTPUT axis), row-parallel out (wo/wd sharded on the INPUT axis) — one
    psum per matmul pair, emitted inside models.transformer. MoE blocks:
    the expert axis (axis 1 of [L, E, ...]) shards over the SAME mesh axis
    (expert parallelism) while the router stays replicated so top-k
    routing and the sparse dispatch's capacity/drop decisions are global;
    the per-token combine rides the same closing psum. Norms, biases
    without a sharded sibling, and the per-layer `window` leaf replicate
    via the catch-all."""
    attn = (
        (r"attn/(wq|wk|wv)$", P(None, None, axis)),
        (r"attn/(bq|bk|bv)$", P(None, axis)),
        (r"attn/wo$", P(None, axis)),
    )
    if cfg.is_moe:
        mlp = (
            (r"mlp/router$", P()),
            (r"mlp/(wg|wu|wd)$", P(None, axis)),    # expert axis of [L,E,..]
        )
    else:
        mlp = (
            (r"mlp/(wg|wu|wi)$", P(None, None, axis)),
            (r"mlp/(wd|wo|bi)$", P(None, axis)),
        )
    return (*attn, *mlp, (r".*", P()))


# Replicated-leaf registry: every shardable layer leaf that DELIBERATELY
# rides the catch-all, with the reason. Replication must be a decision,
# never a fall-through — a new leaf that matches neither a sharding rule
# above nor a row here fails graftlint's spmd-catchall-leaf check, which
# parses this table (regex, reason) without importing the module.
REPLICATED_LEAVES = (
    (r"ln[0-9]/(w|b)$",
     "norm scale/shift are O(d): sharding saves nothing and would cost an "
     "all-gather before every norm"),
    (r"attn/bo$",
     "output-projection bias is applied once to the closing psum's "
     "replicated sum; a sharded copy would be counted tp times"),
    (r"mlp/bo$",
     "mlp output bias is applied after the closing psum, same layout "
     "argument as attn/bo"),
    (r"^window$",
     "per-layer attention-window vector is [L] int32 config state, not a "
     "weight — every rank needs the whole thing"),
    (r"^exit_gate/(w|b)$",
     "a looped stack's exit gate is [hidden, 1] + [1], read on the "
     "replicated normed state that closes a pass, beside final_norm: O(d), "
     "and every rank needs the same exit decision"),
)


def layer_partition_specs(cfg: ModelConfig, axis: str = "tp"):
    """Spec RESOLVER for stacked-layer leaves: returns a function
    (tree_map_with_path path) -> PartitionSpec for a [L, ...] leaf, rule-
    matched against `tp_partition_rules`. Use `stage_param_specs` for a
    ready-made spec pytree over a whole stage."""
    rules = tp_partition_rules(cfg, axis)

    def spec_for(path) -> P:
        name = path_name(path)
        for rule, spec in rules:
            if re.search(rule, name):
                return spec
        return P()

    return spec_for


def stage_param_specs(cfg: ModelConfig, params: Params, axis: str = "tp") -> Params:
    """PartitionSpec pytree for a stage's parameter shard: layer leaves get
    the `tp_partition_rules` layout; embeddings, final norm, and
    lm_head are replicated over the axis (the head's vocab matmul is
    recomputed identically on each rank — cheap next to the layer stack, and
    it keeps logits replicated for sampling). The single source of truth for
    both placement (`shard_stage_params`) and shard_map in_specs
    (`make_tp_stage_fn`)."""
    from ..models.quant import is_quantized

    if is_quantized(params):
        # QuantizedTensor's q/s leaves would miss the name-keyed TP rules
        # and silently replicate — each rank would then compute the FULL
        # projection and the closing psum would multiply results by tp.
        # Fail loudly instead of corrupting logits.
        raise NotImplementedError(
            "tensor parallelism over int8-quantized params is not "
            "supported; shard full-precision params (quantize per shard "
            "afterwards if needed)"
        )
    out = {k: jax.tree.map(lambda _: P(), v)
           for k, v in params.items() if k != "layers"}
    if "layers" in params:
        out["layers"] = match_partition_rules(
            tp_partition_rules(cfg, axis), params["layers"])
    return out


def shard_stage_params(
    cfg: ModelConfig, params: Params, mesh: Mesh, axis: str = "tp"
) -> Params:
    """Place a stage's parameter shard on the mesh with TP/EP layout."""
    return jax.tree.map(
        lambda leaf, spec: jax.device_put(leaf, NamedSharding(mesh, spec)),
        params, stage_param_specs(cfg, params, axis),
    )


def validate_tp(cfg: ModelConfig, tp: int) -> None:
    from ..models.config import custom_engine_unsupported

    reason = custom_engine_unsupported(cfg)
    if reason:
        # stage_forward would compute correctly, but the param-spec table
        # has no layout for the per-layer window leaf and the softcap has
        # no shard_map test coverage — refuse until implemented.
        raise ValueError(f"tensor parallelism: {reason}")
    if cfg.num_heads % tp:
        raise ValueError(f"num_heads {cfg.num_heads} % tp {tp} != 0")
    if cfg.num_kv_heads % tp:
        raise ValueError(
            f"num_kv_heads {cfg.num_kv_heads} % tp {tp} != 0 "
            "(GQA cache shards over kv heads)"
        )
    if cfg.is_moe and cfg.num_experts % tp:
        raise ValueError(f"num_experts {cfg.num_experts} % tp {tp} != 0")
    if not cfg.is_moe and cfg.intermediate_size % tp:
        raise ValueError(f"intermediate_size {cfg.intermediate_size} % tp != 0")


def make_tp_stage_fn(
    cfg: ModelConfig,
    spec: StageSpec,
    mesh: Mesh,
    axis: str = "tp",
    donate_cache: bool = False,
    with_prompts: bool = False,
):
    """Jitted TP stage forward. Caller passes params placed by
    `shard_stage_params` and a KV cache sharded over kv heads
    ([L, B, S, Hkv, Dh] with spec P(None, None, None, axis)).

    Returns fn(params, x, k, v, cache_len) -> (out, k, v); out replicated.
    `donate_cache=True` donates the k/v buffers (serving: the caller
    threads the returned cache and never reuses the input arrays).
    `with_prompts=True` appends a replicated deep-prompts argument
    ([span, pre, D], injected at every block entry — the ptune serving
    path): fn(params, x, k, v, cache_len, prompts).
    """
    tp = mesh.shape[axis]
    validate_tp(cfg, tp)
    kv_spec = P(None, None, None, axis)

    def build(params_example: Params):
        param_specs = stage_param_specs(cfg, params_example, axis)
        in_specs = (param_specs, P(), kv_spec, kv_spec, P())
        if with_prompts:
            in_specs = in_specs + (P(),)   # prompts replicated across tp

        def fn(params, x, k_cache, v_cache, cache_len, prompts=None):
            out, k_cache, v_cache = stage_forward(
                cfg, spec, params, x, k_cache, v_cache, cache_len,
                tp_axis=axis, prompts=prompts,
            )
            # out is replicated by the closing psums (vma: psum output is
            # axis-invariant), matching out_specs=P().
            return out, k_cache, v_cache

        fn = partial(jax.shard_map, mesh=mesh, in_specs=in_specs,
                     out_specs=(P(), kv_spec, kv_spec))(fn)
        return partial(jax.jit,
                       donate_argnums=(2, 3) if donate_cache else ())(fn)

    return build


def init_tp_kv(
    cfg: ModelConfig, spec: StageSpec, mesh: Mesh, batch: int, max_len: int,
    dtype=jnp.float32, axis: str = "tp",
):
    shape = (max(spec.num_layers, 1), batch, max_len, cfg.num_kv_heads,
             cfg.head_dim)
    sh = NamedSharding(mesh, P(None, None, None, axis))
    return (jax.device_put(jnp.zeros(shape, dtype), sh),
            jax.device_put(jnp.zeros(shape, dtype), sh))
