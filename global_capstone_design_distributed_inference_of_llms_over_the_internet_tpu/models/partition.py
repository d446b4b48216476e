"""Stage partitioning: layer spans, stage roles, per-stage forward functions.

TPU-native counterpart of the reference's model partitioner
(``src/llama_partition.py:477-550`` and the Stage0/StageSegment/StageLast
modules at ``:76-474``): a model is cut into contiguous layer spans; the first
stage also owns the embeddings, the last also owns final-norm + lm_head, and
middle stages are pure layer segments. Instead of three nn.Module classes the
stages here are three pure functions over sliced parameter pytrees, each
independently jittable and shardable.

Span semantics match the reference CLI: ``--splits "s0,s1,s2"`` produces the
four spans [0,s0) [s0,s1) [s1,s2) [s2,L) (``src/main.py:89-94,243-278``); the
generalization to N stages is spans from consecutive boundary pairs.
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig, refuse_single_pass
from .transformer import (
    embed_tokens,
    init_kv_cache,
    lm_head,
    stack_forward,
)

Params = Dict[str, Any]

ROLE_STAGE0 = "stage0"
ROLE_SEGMENT = "segment"
ROLE_LAST = "last"
ROLE_FULL = "full"  # degenerate 1-stage plan: both embeddings and head


@dataclasses.dataclass(frozen=True)
class StageSpec:
    """One stage's role and layer span [start, end)."""

    index: int
    role: str
    start: int
    end: int

    @property
    def num_layers(self) -> int:
        return self.end - self.start

    @property
    def is_first(self) -> bool:
        return self.role in (ROLE_STAGE0, ROLE_FULL)

    @property
    def is_last(self) -> bool:
        return self.role in (ROLE_LAST, ROLE_FULL)


@dataclasses.dataclass(frozen=True)
class StagePlan:
    """A full partition of a model into pipeline stages."""

    num_layers: int
    stages: Tuple[StageSpec, ...]

    @property
    def num_stages(self) -> int:
        return len(self.stages)

    def __post_init__(self):
        assert self.stages, "empty plan"
        assert self.stages[0].start == 0
        assert self.stages[-1].end == self.num_layers
        for a, b in zip(self.stages, self.stages[1:]):
            assert a.end == b.start, f"non-contiguous spans: {a} -> {b}"

    @staticmethod
    def from_splits(num_layers: int, splits: Sequence[int]) -> "StagePlan":
        """Reference-CLI style boundaries. splits=[s0,s1,s2] -> 4 stages.

        Mirrors ``src/main.py:89-94`` (stage0 = layers[0:s0]) and
        ``:243-278`` (segments; last stage gets final norm + head).
        """
        bounds = [0, *splits, num_layers]
        assert all(0 < b <= num_layers for b in splits), f"bad splits {splits}"
        assert bounds == sorted(bounds), f"splits must be increasing: {splits}"
        stages = []
        n = len(bounds) - 1
        for i in range(n):
            if n == 1:
                role = ROLE_FULL
            elif i == 0:
                role = ROLE_STAGE0
            elif i == n - 1:
                role = ROLE_LAST
            else:
                role = ROLE_SEGMENT
            stages.append(StageSpec(i, role, bounds[i], bounds[i + 1]))
        return StagePlan(num_layers, tuple(stages))

    @staticmethod
    def even(num_layers: int, num_stages: int) -> "StagePlan":
        """Near-even split into num_stages spans (larger spans first)."""
        base, rem = divmod(num_layers, num_stages)
        sizes = [base + (1 if i < rem else 0) for i in range(num_stages)]
        bounds = [0]
        for s in sizes:
            bounds.append(bounds[-1] + s)
        return StagePlan.from_splits(num_layers, bounds[1:-1])


def parse_splits(splits: str) -> List[int]:
    """"10,20,30" -> [10, 20, 30] (the reference flag format)."""
    return [int(x) for x in splits.split(",") if x.strip()]


def path_name(path) -> str:
    """tree_map_with_path key path -> "a/b/c" rule-matching name."""
    parts = []
    for p in path:
        key = getattr(p, "key", None)
        if key is None:
            key = getattr(p, "idx", p)
        parts.append(str(key))
    return "/".join(parts)


def match_partition_rules(rules, params) -> Params:
    """(regex, PartitionSpec) rules -> a PartitionSpec pytree for `params`.

    The explicit-rules idiom of the big SPMD trainers: each leaf's
    "a/b/c" key path is matched against the rules IN ORDER and the first
    ``re.search`` hit wins, so specific rules go first and a catch-all
    ``(".*", P())`` closes the list (a leaf matching no rule raises —
    silent replication of a weight that should shard corrupts psum'd
    outputs). Scalar/singleton leaves are never partitioned. This is the
    single mechanism behind `parallel.tensor_parallel`'s TP and MoE
    expert-parallel layouts."""
    from jax.sharding import PartitionSpec as P

    def spec_for(path, leaf):
        shape = tuple(getattr(leaf, "shape", ()))
        if len(shape) == 0 or math.prod(shape) == 1:
            return P()
        name = path_name(path)
        for rule, spec in rules:
            if re.search(rule, name):
                return spec
        raise ValueError(f"no partition rule matches param {name!r}")

    return jax.tree_util.tree_map_with_path(spec_for, params)


def slice_stage_params(cfg: ModelConfig, params: Params, spec: StageSpec) -> Params:
    """Prune a full stacked-parameter pytree down to one stage's shard.

    Keeps layers[start:end]; embeddings only on stage0; final-norm + lm_head
    only on the last stage — the same memory-reduction pruning as reference
    ``src/llama_partition.py:506-525``. With tied embeddings the last stage
    retains ``embed.wte`` for the head projection (cf. hf_import's shard
    loading, which does the same at checkpoint-load time).
    """
    out: Params = {}
    if "dense_layers" in params:
        # Leading layers of another kind, stacked apart (a family with
        # ``first_k_dense``; one whose layers alternate between two kinds
        # holds ``sliding_layers`` too): the full span holds every stack
        # whole, and no engine serves part of such a stack.
        if not (spec.is_first and spec.is_last):
            from .config import refuse_single_pass

            refuse_single_pass(cfg, "a stage over part of the stack")
        for key in ("dense_layers", "layers", "sliding_layers"):
            if key in params:
                out[key] = params[key]
    elif spec.num_layers > 0:
        out["layers"] = jax.tree.map(lambda x: x[spec.start : spec.end], params["layers"])
    if spec.is_first:
        out["embed"] = params["embed"]
    if spec.is_last:
        out["final_norm"] = params["final_norm"]
        if cfg.tie_word_embeddings:
            out["embed"] = {**out.get("embed", {}), "wte": params["embed"]["wte"]}
        else:
            out["lm_head"] = params["lm_head"]
        if "exit_gate" in params:    # looped stack: read with the final norm
            out["exit_gate"] = params["exit_gate"]
    return out


def init_stage_kv(
    cfg: ModelConfig, spec: StageSpec, batch: int, max_len: int, dtype=jnp.float32
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    return init_kv_cache(cfg, spec.num_layers, batch, max_len, dtype)


def stage_forward(
    cfg: ModelConfig,
    spec: StageSpec,
    params: Params,
    inputs: jnp.ndarray,
    k_caches: jnp.ndarray,
    v_caches: jnp.ndarray,
    cache_len: jnp.ndarray,
    tp_axis: Optional[str] = None,
    prompts: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Uniform stage forward, role-dispatched.

    inputs: int32 token ids [B,T] for stage0, float hidden [B,T,D] otherwise
    (the same uniform signature as the reference's three stage modules,
    ``src/llama_partition.py:99-137,222-297,391-474``). Returns
    (hidden-or-logits, new k_caches, new v_caches). Positions are derived from
    cache_len exactly like reference ``src/utils.py:40-48``.

    prompts: optional [span_layers, pre_seq, D] inference-time deep prompts
    added at each block's entry (``petals/server/block_functions.py:57-65,
    171-226`` — the ptune serving path).
    """
    refuse_single_pass(cfg, "a stage's forward")
    if spec.is_first:
        b, t = inputs.shape
        positions = cache_len + jnp.arange(t, dtype=jnp.int32)[None, :]
        x = embed_tokens(cfg, params["embed"], inputs, positions)
    else:
        b, t, _ = inputs.shape
        positions = cache_len + jnp.arange(t, dtype=jnp.int32)[None, :]
        x = inputs

    if spec.num_layers > 0:
        x, k_caches, v_caches = stack_forward(
            cfg, params["layers"], x, positions, k_caches, v_caches, cache_len,
            tp_axis=tp_axis, prompts=prompts,
        )

    if spec.is_last:
        x = lm_head(cfg, params, x)
    return x, k_caches, v_caches


def plan_forward(
    cfg: ModelConfig,
    plan: StagePlan,
    stage_params: Sequence[Params],
    input_ids: jnp.ndarray,
    stage_kvs: Sequence[Tuple[jnp.ndarray, jnp.ndarray]],
    cache_len: jnp.ndarray,
) -> Tuple[jnp.ndarray, List[Tuple[jnp.ndarray, jnp.ndarray]]]:
    """Run all stages sequentially in one process (the correctness oracle for
    every transport: pipeline-of-stage-forwards must equal full_forward)."""
    x = input_ids
    new_kvs: List[Tuple[jnp.ndarray, jnp.ndarray]] = []
    for spec, params, (kc, vc) in zip(plan.stages, stage_params, stage_kvs):
        x, kc, vc = stage_forward(cfg, spec, params, x, kc, vc, cache_len)
        new_kvs.append((kc, vc))
    return x, new_kvs
