"""Fused ICI pipeline: all stages in ONE jitted program, ppermute between them.

This is the TPU-native replacement for the reference's per-hop
serialize → libp2p → deserialize data plane (``src/rpc_transport.py:744``,
``src/rpc_handler.py:422`` — its dominant latency term, SURVEY.md §3.2): when
the pipeline stages are co-located on one TPU slice, the whole multi-stage
step compiles to a single XLA program and inter-stage activations move
HBM-to-HBM over ICI via ``jax.lax.ppermute``. The client/transport path
(`runtime.client`) remains the elastic multi-host story; this is the hot path
(SURVEY.md §7.3 hard part 1: no host round-trips between stages).

Design (GPipe-style microbatching under ``shard_map``):

  * the mesh has one axis ``"stage"`` of size S; stacked layer params
    [L, ...] are reshaped to [S, L/S, ...] and sharded on the leading axis —
    each device holds exactly its span's weights;
  * embedding and lm_head run OUTSIDE the shard_map (embedding is a cheap
    replicated gather; the head runs once on the psum-collected final hidden)
    so the shard-mapped body is uniform across stages — no role dispatch,
    no wasted head FLOPs on intermediate stages;
  * the batch is split into M microbatches; the body runs M + S - 1 ticks in
    a ``lax.fori_loop``. Each tick every stage runs its span on its current
    microbatch and ppermutes the result to its successor; stage s processes
    microbatch ``t - s`` at tick t (valid iff 0 <= t-s < M). Invalid ticks
    (pipeline bubble) compute on garbage and their KV writes are masked out;
  * KV caches are [S, L/S, M, B_mb, max_len, Hkv, Dh], sharded on stage —
    each stage's cache never leaves its device.

Capability parity note: the reference has NO intra-program pipelining at all —
every hop re-enters Python and the WAN. Matching its 4-stage topology with
M=1 microbatch already removes the per-hop overhead; M>1 additionally hides
the pipeline bubble for batched serving.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.config import ModelConfig, refuse_single_pass
from ..models.transformer import embed_tokens, lm_head, stack_forward

Params = Dict[str, Any]


def make_pipeline_mesh(num_stages: int, devices=None, tp: int = 1) -> Mesh:
    """1-D ("stage",) pipeline mesh, or 2-D ("stage", "tp") when tp > 1 —
    tensor parallelism nests INSIDE each pipeline stage's device group, so
    the per-stage psums ride the innermost (fastest) mesh axis."""
    need = num_stages * tp
    devices = devices if devices is not None else jax.devices()[:need]
    if len(devices) < need:
        raise ValueError(
            f"need {need} devices for the fused pipeline "
            f"({num_stages} stages x {tp} tp), have {len(devices)}"
        )
    import numpy as np

    arr = np.asarray(devices[:need])
    if tp == 1:
        return Mesh(arr, ("stage",))
    return Mesh(arr.reshape(num_stages, tp), ("stage", "tp"))


def stack_pipeline_params(params: Params, num_stages: int) -> Params:
    """Reshape stacked layers [L, ...] -> [S, L/S, ...] for stage sharding."""
    num_layers = jax.tree.leaves(params["layers"])[0].shape[0]
    if num_layers % num_stages:
        raise ValueError(
            f"fused pipeline needs equal spans: {num_layers} layers % "
            f"{num_stages} stages != 0 (uneven spans run on runtime.client)"
        )
    per = num_layers // num_stages
    return jax.tree.map(
        lambda x: x.reshape((num_stages, per) + x.shape[1:]), params["layers"]
    )


def _kv_spec(tp: int) -> P:
    """PartitionSpec for the pipeline KV cache laid out by `init_pipeline_kv`:
    [S, L/S, M, B, max_len, Hkv, Dh] — "stage" on axis 0, "tp" on the Hkv
    axis when TP is on. Single source of truth for build() and init_kv()."""
    return P("stage", None, None, None, None, "tp") if tp > 1 else P("stage")


def init_pipeline_kv(
    cfg: ModelConfig, num_stages: int, num_micro: int, micro_batch: int,
    max_len: int, dtype=jnp.float32,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    per = cfg.num_layers // num_stages
    shape = (num_stages, per, num_micro, micro_batch, max_len,
             cfg.num_kv_heads, cfg.head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def _pipeline_layer_specs(cfg: ModelConfig, layers_stacked: Params,
                          tp: int) -> Params:
    """PartitionSpecs for the [S, L/S, ...] stacked layer tree: axis 0 on
    "stage", plus the TP table (axes shifted +1 for the stage dim) when
    tp > 1."""
    if tp == 1:
        return jax.tree.map(lambda _: P("stage"), layers_stacked)
    from .tensor_parallel import layer_partition_specs

    spec_for = layer_partition_specs(cfg, "tp")

    def f(path, _leaf):
        sub = spec_for(path)  # spec for the [L, ...] leaf (axis 0 = layers)
        parts = ["stage"] + list(sub)
        return P(*parts)

    return jax.tree_util.tree_map_with_path(f, layers_stacked)


def _pipeline_body(cfg: ModelConfig, num_stages: int, num_micro: int,
                   tp_axis: Optional[str] = None):
    """Builds the shard-mapped tick loop. Local views per stage device:
    layers [1, L/S, ...(tp-sharded dims)]; stream [M, B, T, D] (replicated);
    kv [1, L/S, M, B, max_len, Hkv(/tp), Dh]; positions [B, T] (replicated)."""

    def body(layers, stream, k_all, v_all, positions, cache_len):
        layers = jax.tree.map(lambda x: x[0], layers)   # [L/S, ...]
        k_all, v_all = k_all[0], v_all[0]               # [L/S, M, B, ...]
        s = jax.lax.axis_index("stage")
        is_last = s == num_stages - 1
        m, b, t, d = stream.shape
        perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]

        def tick(ti, carry):
            received, k_all, v_all, outs = carry
            mb = ti - s
            valid = (mb >= 0) & (mb < num_micro)
            mbc = jnp.clip(mb, 0, num_micro - 1)
            x_in = jnp.where(
                s == 0,
                jax.lax.dynamic_index_in_dim(stream, mbc, 0, keepdims=False),
                received,
            )
            kc = jax.lax.dynamic_index_in_dim(k_all, mbc, 1, keepdims=False)
            vc = jax.lax.dynamic_index_in_dim(v_all, mbc, 1, keepdims=False)
            # kc/vc: [L/S, B, max_len, Hkv(/tp), Dh]
            out, nk, nv = stack_forward(
                cfg, layers, x_in, positions, kc, vc, cache_len,
                tp_axis=tp_axis,
            )
            # Mask bubble ticks: garbage KV writes must not land.
            nk = jnp.where(valid, nk, kc)
            nv = jnp.where(valid, nv, vc)
            k_all = jax.lax.dynamic_update_index_in_dim(k_all, nk, mbc, 1)
            v_all = jax.lax.dynamic_update_index_in_dim(v_all, nv, mbc, 1)
            outs = jnp.where(
                is_last & valid,
                jax.lax.dynamic_update_index_in_dim(outs, out, mbc, 0),
                outs,
            )
            received = jax.lax.ppermute(out, "stage", perm)
            return received, k_all, v_all, outs

        received = jax.lax.pcast(
            jnp.zeros((b, t, d), stream.dtype), ("stage",), to="varying"
        )
        outs = jax.lax.pcast(
            jnp.zeros((m, b, t, d), stream.dtype), ("stage",), to="varying"
        )
        received, k_all, v_all, outs = jax.lax.fori_loop(
            0, num_micro + num_stages - 1, tick,
            (received, k_all, v_all, outs),
        )
        # Only the last stage populated outs; psum replicates it everywhere.
        outs = jax.lax.psum(
            jnp.where(is_last, outs, jnp.zeros_like(outs)), "stage"
        )
        return outs, k_all[None], v_all[None]

    return body


@dataclasses.dataclass
class IciPipeline:
    """Compiled fused-pipeline runner. Holds the mesh + jitted step.

    Usage::

        pipe = IciPipeline.build(cfg, params, num_stages=4, num_micro=2)
        logits, kv = pipe.forward(ids, kv, cache_len)   # prefill or decode
    """

    cfg: ModelConfig
    mesh: Mesh
    num_stages: int
    num_micro: int
    tp: int
    embed: Params               # replicated
    head: Params                # replicated: final_norm (+ lm_head / tied wte)
    layers_stacked: Params      # [S, L/S, ...] sharded on stage (+ tp dims)
    _step: Any

    @staticmethod
    def build(
        cfg: ModelConfig,
        params: Params,
        num_stages: int,
        num_micro: int = 1,
        mesh: Optional[Mesh] = None,
        tp: int = 1,
    ) -> "IciPipeline":
        refuse_single_pass(cfg, "the ICI pipeline")
        if tp > 1:
            from .tensor_parallel import validate_tp

            validate_tp(cfg, tp)
        mesh = mesh or make_pipeline_mesh(num_stages, tp=tp)
        if mesh.shape.get("stage") != num_stages or mesh.shape.get("tp", 1) != tp:
            raise ValueError(
                f"mesh axes {dict(mesh.shape)} do not match num_stages="
                f"{num_stages}, tp={tp} — pass the same tp to both "
                "make_pipeline_mesh and build"
            )
        layers = stack_pipeline_params(params, num_stages)
        if tp == 1:
            # Engine-side fused QKV + gate/up layouts (bitwise-identical;
            # TP keeps the canonical splits so its per-projection shard
            # boundaries hold).
            from ..models.transformer import (
                fuse_gate_up_layers,
                fuse_qkv_layers,
            )

            layers = fuse_gate_up_layers(fuse_qkv_layers(layers))
        layer_specs = _pipeline_layer_specs(cfg, layers, tp)
        layers = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            layers, layer_specs,
        )
        repl = NamedSharding(mesh, P())
        embed = jax.device_put(params["embed"], repl)
        head = {"final_norm": params["final_norm"]}
        if cfg.tie_word_embeddings:
            head["embed"] = {"wte": params["embed"]["wte"]}
        else:
            head["lm_head"] = params["lm_head"]
        head = jax.device_put(head, repl)

        tp_axis = "tp" if tp > 1 else None
        body = _pipeline_body(cfg, num_stages, num_micro, tp_axis=tp_axis)
        spec_kv = _kv_spec(tp)

        # Donation stays UNgated here (cf. utils.platform.engine_donation):
        # the fused pipeline is a single-controller engine — one thread owns
        # the mesh and every dispatch — so the CPU async-dispatch/free race
        # the serving engines gate against has no second thread to race.
        @partial(jax.jit, donate_argnums=(3, 4))
        def step(embed_p, head_p, layers_p, k_all, v_all, ids, cache_len):
            m, b, t = ids.shape
            positions = cache_len + jnp.arange(t, dtype=jnp.int32)[None, :]
            # Replicated embedding gather for the whole stream [M, B, T, D].
            x = jax.vmap(
                lambda i: embed_tokens(cfg, embed_p, i, positions)
            )(ids)
            sharded = shard_map(
                body,
                mesh=mesh,
                in_specs=(layer_specs, P(), spec_kv, spec_kv, P(), P()),
                out_specs=(P(), spec_kv, spec_kv),
            )
            outs, k_all, v_all = sharded(
                layers_p, x, k_all, v_all,
                jnp.broadcast_to(positions, (b, t)), cache_len,
            )
            # Head once, on the collected final hidden [M, B, T, D].
            logits = jax.vmap(lambda h: lm_head(cfg, head_p, h))(outs)
            return logits, k_all, v_all

        return IciPipeline(
            cfg=cfg, mesh=mesh, num_stages=num_stages, num_micro=num_micro,
            tp=tp, embed=embed, head=head, layers_stacked=layers, _step=step,
        )

    def init_kv(self, micro_batch: int, max_len: int, dtype=jnp.float32):
        k, v = init_pipeline_kv(
            self.cfg, self.num_stages, self.num_micro, micro_batch, max_len, dtype
        )
        sh = NamedSharding(self.mesh, _kv_spec(self.tp))
        return jax.device_put(k, sh), jax.device_put(v, sh)

    def forward(
        self,
        ids: jnp.ndarray,            # [M, B, T] int32 microbatched token ids
        k_all: jnp.ndarray,
        v_all: jnp.ndarray,
        cache_len: jnp.ndarray,      # scalar int32
    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """One pipelined forward over all stages. Returns
        (logits [M, B, T, V], new k, new v)."""
        if ids.shape[0] != self.num_micro:
            raise ValueError(
                f"ids has {ids.shape[0]} microbatches, pipeline compiled for "
                f"{self.num_micro} (the clamped tick indexing would silently "
                "corrupt outputs otherwise)"
            )
        if ids.shape[1] != k_all.shape[3]:
            raise ValueError(
                f"ids micro-batch size {ids.shape[1]} != KV cache batch "
                f"{k_all.shape[3]}"
            )
        return self._step(
            self.embed, self.head, self.layers_stacked, k_all, v_all,
            ids, cache_len,
        )
