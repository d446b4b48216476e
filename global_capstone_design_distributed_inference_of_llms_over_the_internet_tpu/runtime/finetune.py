"""Client-driven distributed fine-tuning (deep prompt tuning) over the
elastic runtime.

This is the TPU-native realization of the training surface the reference
vendored but could never run: ``rpc_forward``/``rpc_backward`` over block
spans (``petals/server/handler.py:352-488``) plus learned per-block "deep"
prompts injected at every block (``petals/server/block_functions.py:57-65``).

Topology matches generation: the client owns the embedding + its local block
span (stage0) + the LM head; remote servers run frozen block spans. One
training step is

  1. local:   x = embed(ids); h0 = blocks[0:s0](x, prompts[0:s0])    (vjp saved)
  2. remote:  per hop, ``train_forward`` (cache-free, blocks only) with the
              hop's prompt slice; span inputs journaled for backward
  3. local:   loss = xent(lm_head(h_last), targets)                  (vjp saved)
  4. remote:  reversed hops, ``backward`` returns (grad_input, grad_prompts)
  5. local:   vjp(1) + grad chaining; AdamW on {prompts, embed?, head?}

Training is STATELESS server-side (servers recompute activations in their
backward, nothing persisted between RPCs) — so fault tolerance is simply
"re-route and retry the step", no journal replay needed.
"""

from __future__ import annotations

import logging
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.config import ModelConfig, refuse_single_pass
from ..models.transformer import embed_tokens, lm_head, stack_forward_train
from ..parallel.trainer import adamw_init, adamw_update, softmax_xent
from .client import NoRouteError, PipelineClient
from .errors import retryable_types
from .executor import StageExecutionError
from .messages import BackwardRequest, StageRequest
from .transport import PeerUnavailable

logger = logging.getLogger(__name__)

Params = Dict[str, Any]

MAX_STEP_ATTEMPTS = 3


class _HopFailed(Exception):
    """Internal: a remote hop failed; re-route and retry the whole step."""


class DistributedFineTuner:
    """Deep-prompt-tune (and LoRA-tune) a model whose blocks are served by
    remote peers.

    trainables: always ``prompts`` [num_layers, pre_seq, D]; with
    ``lora_rank > 0`` also client-owned LoRA adapters over every block
    (models.lora — shipped per-hop with each training RPC, servers stay
    frozen and stateless); optionally the embedding and/or head (tiny next
    to the frozen remote blocks — the same client-side-trainables split as
    Petals fine-tuning, extended beyond its prompts-only surface).
    """

    def __init__(
        self,
        cfg: ModelConfig,
        client: PipelineClient,
        head_params: Params,
        *,
        pre_seq: int = 8,
        lr: float = 1e-3,
        weight_decay: float = 0.0,
        tune_embed: bool = False,
        tune_head: bool = False,
        prompt_init_scale: float = 0.01,
        lora_rank: int = 0,
        lora_alpha: float = 16.0,
        lora_targets=None,
        seed: int = 0,
    ):
        refuse_single_pass(cfg, "distributed fine-tuning")
        self.cfg = cfg
        self.client = client
        self.pre_seq = pre_seq
        self.lr = lr
        self.weight_decay = weight_decay
        self.tune_embed = tune_embed
        self.tune_head = tune_head
        self.lora_rank = lora_rank
        self.lora_scale = (lora_alpha / lora_rank) if lora_rank else 0.0

        s0_params = client.stage0.params
        if "embed" not in s0_params:
            raise ValueError("client.stage0 must hold the embedding")
        self._frozen_embed = s0_params["embed"]
        self._local_layers = s0_params.get("layers")
        self._frozen_head = head_params  # {"final_norm": ..., "lm_head"?: ...}
        self.s0_end = client.plan.stages[0].end

        d = cfg.hidden_size
        prompts = prompt_init_scale * jax.random.normal(
            jax.random.PRNGKey(seed), (cfg.num_layers, pre_seq, d), jnp.float32
        )
        self.trainables: Params = {"prompts": prompts}
        if lora_rank > 0:
            # Client-owned LoRA adapters over EVERY block (models.lora):
            # per-hop slices ship with each training RPC like the prompt
            # slices; the local span merges its slice client-side.
            from ..models.lora import DEFAULT_TARGETS, init_lora

            self.trainables["lora"] = init_lora(
                jax.random.PRNGKey(seed + 1), cfg, cfg.num_layers,
                lora_rank, targets=lora_targets or DEFAULT_TARGETS)
        if tune_embed:
            self.trainables["embed"] = jax.tree.map(
                jnp.asarray, self._frozen_embed
            )
        if tune_head:
            self.trainables["head"] = jax.tree.map(jnp.asarray, head_params)
        self.opt_state = adamw_init(self.trainables)
        self.steps = 0
        self.last_loss: Optional[float] = None
        self._session_n = 0

        # Jitted local closures — one compile per batch shape. The backward
        # closures recompute their forward inside jit (remat) instead of
        # holding Python-side vjp residuals, so every step after the first is
        # pure XLA replay.
        self._local_fwd = jax.jit(self._local_forward)
        self._local_bwd = jax.jit(
            lambda tr, ids, g: jax.vjp(
                lambda t: self._local_forward(t, ids), tr
            )[1](g)[0]
        )
        self._head_vag = jax.jit(
            jax.value_and_grad(self._head_loss_fn, argnums=(0, 1))
        )

    # -- local compute ------------------------------------------------------

    def _embed_of(self, tr: Params) -> Params:
        return tr["embed"] if self.tune_embed else self._frozen_embed

    def _head_of(self, tr: Params) -> Params:
        head = tr["head"] if self.tune_head else self._frozen_head
        hp = {"final_norm": head["final_norm"]}
        if self.cfg.tie_word_embeddings:
            hp["embed"] = {"wte": self._embed_of(tr)["wte"]}
        elif "lm_head" in head:
            hp["lm_head"] = head["lm_head"]
        return hp

    def _local_forward(self, tr: Params, ids: jnp.ndarray) -> jnp.ndarray:
        b, t = ids.shape
        positions = jnp.broadcast_to(
            jnp.arange(t, dtype=jnp.int32)[None, :], (b, t)
        )
        x = embed_tokens(self.cfg, self._embed_of(tr), ids, positions)
        if self._local_layers is not None and self.s0_end > 0:
            local_prompts = jax.lax.slice_in_dim(
                tr["prompts"], 0, self.s0_end, axis=0
            )
            layers = self._local_layers
            if "lora" in tr:
                from ..models.lora import merge_lora, slice_lora

                layers = merge_lora(
                    self.cfg, layers,
                    slice_lora(tr["lora"], 0, self.s0_end),
                    self.lora_scale)
            x = stack_forward_train(
                self.cfg, layers, x, positions,
                prompts=local_prompts,
            )
        return x

    def _head_loss_fn(self, tr: Params, h: jnp.ndarray,
                      targets: jnp.ndarray) -> jnp.ndarray:
        logits = lm_head(self.cfg, self._head_of(tr), h)
        return softmax_xent(logits, targets)

    # -- remote hops --------------------------------------------------------

    def _hop_lora(self, tr: Params, hop) -> Optional[Params]:
        if "lora" not in tr:
            return None
        from ..models.lora import slice_lora

        return slice_lora(tr["lora"], hop.start_block, hop.end_block)

    def _remote_forward(self, hops, h: jnp.ndarray, seq_len: int,
                        prompts: jnp.ndarray, session_id: str,
                        tr: Params):
        """Returns (final hidden, per-hop span inputs)."""
        inputs: List[np.ndarray] = []
        for hop in hops:
            inputs.append(np.asarray(h))
            req = StageRequest(
                session_id=session_id, hidden=h, seq_len=seq_len, cur_len=0,
                is_prefill=False, max_length=0, train=True,
                prompts=prompts[hop.start_block:hop.end_block],
                lora=self._hop_lora(tr, hop), lora_scale=self.lora_scale,
                start_block=hop.start_block, end_block=hop.end_block,
            )
            try:
                resp = self.client.transport.call(
                    hop.peer_id, req, timeout=self.client.request_timeout
                )
            except retryable_types() as exc:
                self._mark_failed(hop, exc)
                raise _HopFailed from exc
            h = jnp.asarray(resp.hidden)
        return h, inputs

    def _remote_backward(self, hops, inputs, grad_out: jnp.ndarray,
                         seq_len: int, prompts: jnp.ndarray, session_id: str,
                         tr: Params):
        """Reversed hop walk; returns (grad into local output, prompt grad
        updates [(start, end, grad)], lora grad updates [(start, end, tree)])."""
        prompt_grads = []
        lora_grads = []
        for hop, h_in in zip(reversed(hops), reversed(inputs)):
            breq = BackwardRequest(
                session_id=session_id, hidden=jnp.asarray(h_in),
                grad_output=grad_out, seq_len=seq_len,
                prompts=prompts[hop.start_block:hop.end_block],
                lora=self._hop_lora(tr, hop), lora_scale=self.lora_scale,
                start_block=hop.start_block, end_block=hop.end_block,
            )
            try:
                bresp = self.client.transport.backward(
                    hop.peer_id, breq, timeout=self.client.request_timeout
                )
            except retryable_types() as exc:
                self._mark_failed(hop, exc)
                raise _HopFailed from exc
            grad_out = jnp.asarray(bresp.grad_input)
            if bresp.grad_prompts is not None:
                prompt_grads.append(
                    (hop.start_block, hop.end_block,
                     jnp.asarray(bresp.grad_prompts))
                )
            if bresp.grad_lora:
                lora_grads.append(
                    (hop.start_block, hop.end_block, bresp.grad_lora))
            elif "lora" in self.trainables:
                # We shipped adapters but got no adapter grads back: a
                # pre-LoRA peer silently dropped the trailing tensors and
                # computed the UNADAPTED span — continuing would train
                # against the wrong model with zero grads for this slice.
                # Blame the peer so retry routes around it (a newer replica
                # may serve the same span); all-old swarms fail the step
                # loudly instead of silently diverging.
                self._mark_failed(
                    hop, RuntimeError(
                        "peer returned no LoRA grads (pre-LoRA version?)"))
                raise _HopFailed
        return grad_out, prompt_grads, lora_grads

    # -- adapter checkpointing ---------------------------------------------

    def save(self, path: str) -> None:
        """Write trainables + optimizer state to one .npz (keyed by tree
        path). The frozen blocks live with the servers; this file IS the
        fine-tune — a few MB for prompts + adapters."""
        if not path.endswith(".npz"):
            # np.savez appends the suffix silently; normalize so restore
            # (np.load, which does not) finds the same file.
            path += ".npz"
        flat = {}
        for kp, leaf in jax.tree_util.tree_leaves_with_path(
                {"trainables": self.trainables, "opt": self.opt_state}):
            flat[jax.tree_util.keystr(kp)] = np.asarray(leaf)
        flat["__steps__"] = np.asarray(self.steps)
        np.savez(path, **flat)

    def restore(self, path: str) -> None:
        """Inverse of `save`; the tuner must be constructed with the same
        config (pre_seq/rank/targets) so tree structures match."""
        if not path.endswith(".npz"):
            path += ".npz"
        data = np.load(path)

        def load(tree):
            return jax.tree_util.tree_map_with_path(
                lambda kp, leaf: jnp.asarray(data[jax.tree_util.keystr(kp)]),
                tree)

        state = load({"trainables": self.trainables, "opt": self.opt_state})
        self.trainables = state["trainables"]
        self.opt_state = state["opt"]
        self.steps = int(data["__steps__"])

    def export_lora(self, path: str, allow_partial: bool = False) -> None:
        """Write the tuned adapters (+ scale) as a standalone .npz the
        serving CLI folds into the base weights with ``--lora path``.

        The file captures ONLY the adapters: a tuner that also trained
        deep prompts (pre_seq > 0) or the embed/head would serve a
        DIFFERENT model from the .npz than the one it fine-tuned, so
        export refuses unless the adapters are the sole trainables
        (construct with ``pre_seq=0, lora_rank=r`` for an exportable
        pure-LoRA tune) or the caller passes ``allow_partial=True``."""
        if "lora" not in self.trainables:
            raise ValueError("no LoRA trainables (construct with lora_rank>0)")
        if not allow_partial and (
                self.pre_seq > 0 or self.tune_embed or self.tune_head):
            raise ValueError(
                "tuner also trains deep prompts/embed/head, which --lora "
                "serving cannot apply — construct with pre_seq=0 (and no "
                "tune_embed/tune_head) for a pure-LoRA fine-tune, or pass "
                "allow_partial=True to export the adapters alone")
        from ..models.lora import save_lora

        save_lora(path, self.trainables["lora"], self.lora_scale)

    def _mark_failed(self, hop, exc) -> None:
        self.client.failed_peers.setdefault(hop.key, set()).add(hop.peer_id)
        logger.warning("finetune hop %s peer %s failed: %s",
                       hop.key, hop.peer_id, exc)

    # -- the step -----------------------------------------------------------

    def step(self, ids: jnp.ndarray, targets: jnp.ndarray) -> float:
        """One fine-tuning step over [B, T] ids / targets (< 0 = ignore).
        Stateless server-side; on hop failure re-routes and retries."""
        last_exc: Optional[Exception] = None
        for attempt in range(MAX_STEP_ATTEMPTS):
            try:
                loss = self._step_once(ids, targets,
                                       refresh_route=attempt > 0)
                self.last_loss = loss
                self.steps += 1
                return loss
            except _HopFailed as exc:
                last_exc = exc
                continue
            except NoRouteError as exc:
                last_exc = exc
                self.client.failed_peers.clear()
        raise RuntimeError(
            f"fine-tune step failed after {MAX_STEP_ATTEMPTS} attempts"
        ) from last_exc

    def _step_once(self, ids: jnp.ndarray, targets: jnp.ndarray,
                   refresh_route: bool) -> float:
        # kind="exotic": training verbs (train_forward/backward) only exist
        # on per-session executors — a batched/sp peer in the route would
        # fail every step (those engines serve plain inference only).
        hops = self.client.route(refresh=refresh_route, kind="exotic")
        self._session_n += 1
        session_id = f"ft-{id(self):x}-{self._session_n}"
        tr = self.trainables
        seq_len = int(ids.shape[1])

        # 1. local forward
        h0 = self._local_fwd(tr, ids)
        # 2. remote span forwards
        h_last, inputs = self._remote_forward(
            hops, h0, seq_len, tr["prompts"], session_id, tr
        )
        # 3. local head + loss
        loss, (g_tr_head, g_h) = self._head_vag(tr, h_last, targets)
        # 4. remote backward chain
        g_h0, prompt_grads, lora_grads = self._remote_backward(
            hops, inputs, g_h, seq_len, tr["prompts"], session_id, tr
        )
        # 5. local backward + grad assembly
        g_tr_0 = self._local_bwd(tr, ids, g_h0.astype(h0.dtype))
        grads = jax.tree.map(jnp.add, g_tr_head, g_tr_0)
        gp = grads["prompts"]
        for start, end, g in prompt_grads:
            gp = gp.at[start:end].add(g)
        grads["prompts"] = gp
        for start, end, gtree in lora_grads:
            for t, ab in gtree.items():
                for leaf in ("a", "b"):
                    grads["lora"][t][leaf] = (
                        grads["lora"][t][leaf]
                        .at[start:end].add(ab[leaf]))

        self.trainables, self.opt_state = adamw_update(
            grads, self.opt_state, tr, lr=self.lr,
            weight_decay=self.weight_decay,
        )
        return float(loss)
