"""Pipelined distributed TRAINING step: loss + grads + AdamW in one program.

The reference's training surface is the vendored fine-tuning path — never
runnable there: ``rpc_backward`` re-forwards a span and returns input grads
(``petals/server/handler.py:434-488``, ``petals/server/block_functions.py:
84-141``). The TPU-native version doesn't shuttle gradients over RPC at all:
forward AND backward both ride ICI inside one jitted program. The GPipe-style
tick loop (same schedule as `parallel.pipeline.IciPipeline`) is written with
``lax.scan`` so reverse-mode AD differentiates straight through it —
``ppermute``'s transpose is the reversed permute, so XLA derives the backward
pipeline schedule mechanically instead of us hand-coding a second tick loop.

Trainable tree layout matches `IciPipeline`: stacked layers [S, L/S, ...]
sharded on ("stage"[, "tp"]); embed / final_norm / lm_head replicated (tied
embeddings share one leaf, so the tying gradient is exact). The optimizer is
an inline AdamW whose moment trees inherit the parameter shardings — optimizer
state never leaves the device that owns the weight shard.
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
from ..models.transformer import embed_tokens, lm_head, stack_forward_train
from .pipeline import (
    _pipeline_layer_specs,
    make_pipeline_mesh,
    stack_pipeline_params,
)

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Inline AdamW (moment trees shard like params; no opaque optimizer state)
# ---------------------------------------------------------------------------

def ml_bfloat16():
    import ml_dtypes
    import numpy as np

    return np.dtype(ml_dtypes.bfloat16)


def adamw_init(params: Params) -> Params:
    zeros = lambda t: jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), t)
    return {"mu": zeros(params), "nu": zeros(params),
            "count": jnp.zeros((), jnp.int32)}


def adamw_update(
    grads: Params, state: Params, params: Params, *,
    lr: float = 1e-4, b1: float = 0.9, b2: float = 0.999,
    eps: float = 1e-8, weight_decay: float = 0.0,
) -> Tuple[Params, Params]:
    count = state["count"] + 1
    c1 = 1.0 - jnp.power(b1, count.astype(jnp.float32))
    c2 = 1.0 - jnp.power(b2, count.astype(jnp.float32))
    g32 = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["mu"], g32)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["nu"], g32)

    def upd(p, m, v):
        step = (m / c1) / (jnp.sqrt(v / c2) + eps)
        return (p.astype(jnp.float32) - lr * (step + weight_decay *
                p.astype(jnp.float32))).astype(p.dtype)

    params = jax.tree.map(upd, params, mu, nu)
    return params, {"mu": mu, "nu": nu, "count": count}


# ---------------------------------------------------------------------------
# Pipelined training forward (tick loop, differentiable)
# ---------------------------------------------------------------------------

def _train_body(cfg: ModelConfig, num_stages: int, num_micro: int,
                tp_axis: Optional[str]):
    """shard_map body: layers [1, L/S, ...] per stage device; stream
    [M, B, T, D] replicated; positions [B, T] replicated. Returns the last
    stage's outputs [M, B, T, D], psum-replicated."""

    def body(layers, stream, positions):
        layers = jax.tree.map(lambda x: x[0], layers)
        s = jax.lax.axis_index("stage")
        is_last = s == num_stages - 1
        m, b, t, d = stream.shape
        perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]

        def tick(carry, ti):
            received, outs = carry
            mb = ti - s
            valid = (mb >= 0) & (mb < num_micro)
            mbc = jnp.clip(mb, 0, num_micro - 1)
            x_in = jnp.where(
                s == 0,
                jax.lax.dynamic_index_in_dim(stream, mbc, 0, keepdims=False),
                received,
            )
            out = stack_forward_train(cfg, layers, x_in, positions,
                                      tp_axis=tp_axis, remat=True)
            outs = jnp.where(
                is_last & valid,
                jax.lax.dynamic_update_index_in_dim(outs, out, mbc, 0),
                outs,
            )
            received = jax.lax.ppermute(out, "stage", perm)
            return (received, outs), None

        received = jax.lax.pcast(
            jnp.zeros((b, t, d), stream.dtype), ("stage",), to="varying"
        )
        outs = jax.lax.pcast(
            jnp.zeros((m, b, t, d), stream.dtype), ("stage",), to="varying"
        )
        (received, outs), _ = jax.lax.scan(
            tick, (received, outs),
            jnp.arange(num_micro + num_stages - 1, dtype=jnp.int32),
        )
        outs = jax.lax.psum(
            jnp.where(is_last, outs, jnp.zeros_like(outs)), "stage"
        )
        return outs

    return body


def _train_body_interleaved(cfg: ModelConfig, num_stages: int,
                            num_micro: int, virtual: int,
                            tp_axis: Optional[str]):
    """Interleaved virtual-stage schedule (VERDICT r3 item 7): each device
    holds V NON-CONTIGUOUS layer chunks (chunk c = v*S + s lives on device
    s), and microbatch m runs chunk v on device s at tick t = s + v*M + m.
    The next device needs only 1/V of a stage-span computed before it can
    start, so the warmup/drain bubble shrinks from (S-1)/(M+S-1) to

        (S-1) / (V*M + S-1)

    (Megatron's interleaved formula). Ticks: V*M + S - 1, each doing an
    L/(S*V)-layer chunk. The wrap edge (device S-1 -> 0, chunk transition
    v-1 -> v) arrives M-S+1 ticks early and parks in a per-microbatch
    buffer — the same write-before-read parking as ring decode's token
    buffer. M >= S is required (below that the wrap data would not be
    ready; build() enforces it).

    Differentiable by construction: one lax.scan, so reverse-mode AD
    derives the mirrored backward schedule through the ppermutes — no
    hand-coded backward pipeline. Memory note: this is interleaved GPipe
    (all-forward-then-AD-backward), which buys the bubble reduction of
    interleaving but NOT 1F1B's live-activation bound; per-layer remat
    keeps residuals to one [B,T,D] per tick.

    Local views: layers [V, 1, Lc, ...]; stream [M, B, T, D] replicated.
    Returns the final chunk's outputs [M, B, T, D], psum-replicated."""
    S, M, V = num_stages, num_micro, virtual

    def body(layers, stream, positions):
        layers = jax.tree.map(lambda x: x[:, 0], layers)   # [V, Lc, ...]
        s = jax.lax.axis_index("stage")
        is_last = s == S - 1
        m_, b, t, d = stream.shape
        perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, ti):
            received, wrap_buf, outs = carry
            # Park the wrap arrival FIRST (write-before-read): the item
            # arriving at tick ti was computed at ti-1 by device S-1 for
            # microbatch (ti - S) mod M of the previous chunk round.
            wm = jnp.mod(ti - S, M)
            parked = jax.lax.dynamic_update_index_in_dim(
                wrap_buf, received, wm, 0)
            wrap_buf = jnp.where((s == 0) & (ti >= S), parked, wrap_buf)

            rel = ti - s
            v = jnp.clip(rel // M, 0, V - 1)
            mb = jnp.mod(rel, M)
            valid = (rel >= 0) & (rel < V * M)
            src0 = jnp.where(
                v == 0,
                jax.lax.dynamic_index_in_dim(stream, mb, 0, keepdims=False),
                jax.lax.dynamic_index_in_dim(wrap_buf, mb, 0, keepdims=False))
            x_in = jnp.where(s == 0, src0, received)
            chunk = jax.tree.map(
                lambda q: jax.lax.dynamic_index_in_dim(
                    q, v, 0, keepdims=False), layers)
            out = stack_forward_train(cfg, chunk, x_in, positions,
                                      tp_axis=tp_axis, remat=True)
            outs = jnp.where(
                is_last & (v == V - 1) & valid,
                jax.lax.dynamic_update_index_in_dim(outs, out, mb, 0),
                outs,
            )
            received = jax.lax.ppermute(out, "stage", perm)
            return (received, wrap_buf, outs), None

        varying = lambda q: jax.lax.pcast(q, ("stage",), to="varying")
        received = varying(jnp.zeros((b, t, d), stream.dtype))
        wrap_buf = varying(jnp.zeros((m_, b, t, d), stream.dtype))
        outs = varying(jnp.zeros((m_, b, t, d), stream.dtype))
        (received, wrap_buf, outs), _ = jax.lax.scan(
            tick, (received, wrap_buf, outs),
            jnp.arange(V * M + S - 1, dtype=jnp.int32),
        )
        outs = jax.lax.psum(
            jnp.where(is_last, outs, jnp.zeros_like(outs)), "stage"
        )
        return outs

    return body


def stack_interleaved_params(params: Params, num_stages: int,
                             virtual: int) -> Params:
    """[L, ...] -> [V, S, L/(S*V), ...]: chunk c = v*S + s holds the
    contiguous global span [c*Lc, (c+1)*Lc) and lands on device s."""
    num_layers = jax.tree.leaves(params["layers"])[0].shape[0]
    per = num_stages * virtual
    if num_layers % per:
        raise ValueError(
            f"interleaved pipeline needs {num_layers} layers divisible by "
            f"stages*virtual = {per}")
    lc = num_layers // per
    return jax.tree.map(
        lambda x: x.reshape((virtual, num_stages, lc) + x.shape[1:]),
        params["layers"])


def _interleaved_layer_specs(cfg: ModelConfig, layers_stacked: Params,
                             tp: int) -> Params:
    """PartitionSpecs for [V, S, Lc, ...]: axis 1 on "stage" (+ tp axes
    shifted +2)."""
    if tp == 1:
        return jax.tree.map(lambda _: P(None, "stage"), layers_stacked)
    from .tensor_parallel import layer_partition_specs

    spec_for = layer_partition_specs(cfg, "tp")

    def f(path, _leaf):
        sub = spec_for(path)            # spec for the [L, ...] leaf
        return P(*([None, "stage"] + list(sub)))

    return jax.tree_util.tree_map_with_path(f, layers_stacked)


def softmax_xent(logits: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    """Mean cross-entropy over positions with target >= 0 (< 0 = ignore)."""
    mask = (targets >= 0).astype(jnp.float32)
    tgt = jnp.clip(targets, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def single_device_loss(cfg: ModelConfig, params: Params, ids: jnp.ndarray,
                       targets: jnp.ndarray) -> jnp.ndarray:
    """Unpartitioned training loss over [M, B, T] microbatches — the oracle
    the pipelined loss (and its grads) must match (same role as reference
    ``scripts/single_gpu_check.py`` for inference)."""
    m, b, t = ids.shape
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None, :], (b, t))

    def one(i):
        x = embed_tokens(cfg, params["embed"], i, positions)
        x = stack_forward_train(cfg, params["layers"], x, positions, remat=False)
        return lm_head(cfg, params, x)

    logits = jax.vmap(one)(ids)
    return softmax_xent(logits, targets)


@dataclasses.dataclass
class PipelineTrainer:
    """Compiled fused-pipeline trainer.

    Usage::

        tr = PipelineTrainer.build(cfg, params, num_stages=4, num_micro=2)
        loss = tr.step(ids, targets)     # ids/targets: [M, B, T] int32
    """

    cfg: ModelConfig
    mesh: Mesh
    num_stages: int
    num_micro: int
    tp: int
    trainables: Params          # embed/final_norm(/lm_head) repl + layers [S,L/S]
    opt_state: Params
    lr: float
    _step: Any
    virtual_stages: int = 1
    last_loss: Optional[float] = None

    @staticmethod
    def build(
        cfg: ModelConfig,
        params: Params,
        num_stages: int,
        num_micro: int = 1,
        mesh: Optional[Mesh] = None,
        tp: int = 1,
        lr: float = 1e-4,
        weight_decay: float = 0.0,
        virtual_stages: int = 1,
    ) -> "PipelineTrainer":
        refuse_single_pass(cfg, "the pipeline trainer")
        if tp > 1:
            from .tensor_parallel import validate_tp

            validate_tp(cfg, tp)
        mesh = mesh or make_pipeline_mesh(num_stages, tp=tp)
        if mesh.shape.get("stage") != num_stages or mesh.shape.get("tp", 1) != tp:
            raise ValueError(
                f"mesh axes {dict(mesh.shape)} do not match num_stages="
                f"{num_stages}, tp={tp}"
            )
        if virtual_stages > 1:
            if num_micro < num_stages:
                raise ValueError(
                    f"interleaved schedule needs num_micro >= num_stages "
                    f"({num_micro} < {num_stages}): the wrap-edge data for "
                    "a device's next chunk would not be computed yet")
            layers = stack_interleaved_params(params, num_stages,
                                              virtual_stages)
            layer_specs = _interleaved_layer_specs(cfg, layers, tp)
        else:
            layers = stack_pipeline_params(params, num_stages)
            layer_specs = _pipeline_layer_specs(cfg, layers, tp)
        repl = NamedSharding(mesh, P())
        # step() donates these buffers, so they must be OWNED copies: on the
        # CPU platform device_put's replicated shard aliases the source buffer
        # even with may_alias=False, and donating it would delete the caller's
        # params (e.g. when the same checkpoint also feeds an IciPipeline).
        # jnp.copy breaks the alias chain before resharding.
        def put(tree, sh_or_tree):
            if not isinstance(sh_or_tree, NamedSharding):
                return jax.tree.map(
                    lambda x, sp: jax.device_put(
                        jnp.copy(x), NamedSharding(mesh, sp)),
                    tree, sh_or_tree,
                )
            return jax.tree.map(
                lambda x: jax.device_put(jnp.copy(x), sh_or_tree), tree
            )
        trainables: Params = {
            "embed": put(params["embed"], repl),
            "layers_stacked": put(layers, layer_specs),
            "final_norm": put(params["final_norm"], repl),
        }
        if not cfg.tie_word_embeddings:
            trainables["lm_head"] = put(params["lm_head"], repl)
        # Moment trees inherit param shardings leaf-for-leaf.
        opt_state = jax.jit(adamw_init)(trainables)

        tp_axis = "tp" if tp > 1 else None
        if virtual_stages > 1:
            body = _train_body_interleaved(cfg, num_stages, num_micro,
                                           virtual_stages, tp_axis)
        else:
            body = _train_body(cfg, num_stages, num_micro, tp_axis)

        def loss_fn(tr: Params, ids, targets):
            m, b, t = ids.shape
            positions = jnp.broadcast_to(
                jnp.arange(t, dtype=jnp.int32)[None, :], (b, t)
            )
            x = jax.vmap(
                lambda i: embed_tokens(cfg, tr["embed"], i, positions)
            )(ids)
            sharded = shard_map(
                body,
                mesh=mesh,
                in_specs=(layer_specs, P(), P()),
                out_specs=P(),
            )
            outs = sharded(tr["layers_stacked"], x, positions)
            logits = jax.vmap(lambda h: lm_head(cfg, tr, h))(outs)
            return softmax_xent(logits, targets)

        @partial(jax.jit, donate_argnums=(0, 1))
        def step(tr, opt_state, ids, targets):
            loss, grads = jax.value_and_grad(loss_fn)(tr, ids, targets)
            tr, opt_state = adamw_update(
                grads, opt_state, tr, lr=lr, weight_decay=weight_decay
            )
            return loss, tr, opt_state

        return PipelineTrainer(
            cfg=cfg, mesh=mesh, num_stages=num_stages, num_micro=num_micro,
            tp=tp, trainables=trainables, opt_state=opt_state, lr=lr,
            _step=step, virtual_stages=virtual_stages,
        )

    def step(self, ids: jnp.ndarray, targets: jnp.ndarray) -> float:
        """One fused train step over [M, B, T] token ids / shifted targets.
        Updates trainables/opt_state in place (donated buffers)."""
        if ids.shape[0] != self.num_micro:
            raise ValueError(
                f"ids has {ids.shape[0]} microbatches, trainer compiled for "
                f"{self.num_micro}"
            )
        loss, self.trainables, self.opt_state = self._step(
            self.trainables, self.opt_state, ids, targets
        )
        self.last_loss = float(loss)
        return self.last_loss

    # ------------------------------------------------------------------
    # Checkpoint / resume (SURVEY.md §5.4): full training state — sharded
    # weights + optimizer moments + step count — to one portable .npz.
    # Restore re-places every leaf with the RUNNING trainer's shardings, so
    # a checkpoint written on one mesh resumes on another (e.g. a larger
    # pp×tp mesh) as long as the tree structure matches.
    # ------------------------------------------------------------------

    def save(self, path: str) -> None:
        import json

        import numpy as np

        state = {"trainables": self.trainables, "opt_state": self.opt_state}
        flat = jax.tree_util.tree_flatten_with_path(state)[0]
        keys, dtypes, arrays = [], [], {}
        for i, (k, v) in enumerate(flat):
            key = jax.tree_util.keystr(k)
            arr = np.asarray(jax.device_get(v))
            # Stage-stacked layer leaves ([S, L/S, ...]) are written with the
            # stage axes MERGED to [L, ...], so a checkpoint resumes on a
            # different pipeline depth (restore re-splits to the running
            # trainer's [S', L/S', ...]).
            if "layers_stacked" in key and arr.ndim >= 2:
                arr = arr.reshape(-1, *arr.shape[2:])
            dtypes.append(str(arr.dtype) if arr.dtype != ml_bfloat16()
                          else "bfloat16")
            if arr.dtype == ml_bfloat16():
                # npz has no bf16: store the raw bits; restore view-casts
                # back. Without this, np.load returns void bytes and the
                # checkpoint is unrecoverable.
                arr = arr.view(np.uint16)
            keys.append(key)
            arrays[f"a{i}"] = arr
        np.savez(path, __keys__=json.dumps({"keys": keys, "dtypes": dtypes}),
                 **arrays)

    def restore(self, path: str) -> None:
        import json

        import numpy as np

        with np.load(path, allow_pickle=False) as z:
            meta = json.loads(str(z["__keys__"]))
            keys, dtypes = meta["keys"], meta["dtypes"]
            loaded = []
            for i, dt in enumerate(dtypes):
                arr = z[f"a{i}"]
                if dt == "bfloat16":
                    arr = arr.view(ml_bfloat16())
                loaded.append(arr)
        state = {"trainables": self.trainables, "opt_state": self.opt_state}
        flat, treedef = jax.tree_util.tree_flatten_with_path(state)
        have = [jax.tree_util.keystr(k) for k, _ in flat]
        if have != keys:
            missing = set(keys) ^ set(have)
            raise ValueError(
                f"checkpoint tree does not match this trainer "
                f"(differing leaves: {sorted(missing)[:5]}...)")
        leaves = []
        for (path_k, cur), arr in zip(flat, loaded):
            key = jax.tree_util.keystr(path_k)
            if "layers_stacked" in key and cur.ndim >= 2:
                # Saved stage-merged [L, ...]; re-split for THIS trainer's
                # pipeline depth.
                if int(np.prod(arr.shape)) != int(np.prod(cur.shape)):
                    raise ValueError(
                        f"leaf {key}: checkpoint holds {arr.shape[0]} layers"
                        f", trainer expects {cur.shape[0]}x{cur.shape[1]}")
                arr = arr.reshape(cur.shape)
            elif cur.shape != arr.shape:
                raise ValueError(
                    f"leaf {key}: checkpoint shape "
                    f"{arr.shape} != trainer shape {cur.shape}")
            sh = cur.sharding
            if not isinstance(sh, NamedSharding):
                # e.g. the jit-born optimizer `count` scalar: single-device
                # and uncommitted pre-restore. device_put COMMITS, so it must
                # be placed mesh-replicated or the next step sees
                # incompatible devices.
                sh = NamedSharding(self.mesh, P())
            leaves.append(jax.device_put(jnp.asarray(arr, cur.dtype), sh))
        state = jax.tree_util.tree_unflatten(treedef, leaves)
        self.trainables = state["trainables"]
        self.opt_state = state["opt_state"]
