"""Host-offloaded span execution: layer streaming with async prefetch.

Capability parity with the reference's CPU-offload mode (component 6:
``--use_cpu_offload`` shuttling each layer to the GPU just-in-time during
forward, ``src/llama_partition.py:188-293``, with the first N layers pinned
via ``--keep_layers_on_gpu`` ``:209-211``). A stage whose span does not fit
HBM keeps its weights in HOST memory and streams one layer at a time.

TPU-first differences from the reference's design:
  * Transfers are ONE-WAY (host → HBM). Weights are immutable, so there is
    nothing to evict — the previous layer's buffers are simply dropped and
    the allocator reuses them. The reference shuttled tensors both ways.
  * Prefetch overlaps the NEXT layer's host→HBM copy with the CURRENT
    layer's compute: ``jax.device_put`` is asynchronous, so issuing the
    copy before dispatching the jitted layer step double-buffers naturally
    (the reference moved layers synchronously inside forward, serializing
    PCIe transfer and compute).
  * One jitted layer step serves every streamed layer (same shapes/dtypes →
    one compile); the stacked KV cache is donated and updated in place at
    a traced layer index, so no per-layer cache copies.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..utils.platform import engine_donation
from ..models.config import ModelConfig, refuse_single_pass
from ..models.partition import StageSpec
from ..models.transformer import (
    _apply_deep_prompt,
    embed_tokens,
    layer_forward,
    lm_head,
    make_rope,
)

Params = Dict[str, Any]


class OffloadedSpanRunner:
    """Drop-in replacement for a subspan's jitted step function.

    Call signature matches ``StageExecutor``'s compiled step:
    ``step(params_ignored, x, k_caches, v_caches, cache_len)`` — the
    runner owns its weights (resident prefix in device HBM, the rest in
    host memory), so the params argument is ignored.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        spec: StageSpec,
        params: Params,
        *,
        keep_resident: int = 0,
        host_device: Optional[jax.Device] = None,
        compute_device: Optional[jax.Device] = None,
    ):
        refuse_single_pass(cfg, "the offloaded span runner")
        self.cfg = cfg
        self.spec = spec
        self.keep_resident = min(max(keep_resident, 0), spec.num_layers)
        self.host = host_device or jax.devices("cpu")[0]
        self.device = compute_device or jax.devices()[0]

        layers = params.get("layers")
        n = spec.num_layers
        # Resident prefix stays stacked on the compute device (the
        # keep_layers_on_gpu pinning); the tail becomes a host-memory list
        # of per-layer pytrees to stream.
        self.resident: Optional[Params] = None
        self.host_layers: List[Params] = []
        if layers is not None and n:
            if self.keep_resident:
                self.resident = jax.tree.map(
                    lambda a: jax.device_put(a[: self.keep_resident],
                                             self.device),
                    layers,
                )
            for i in range(self.keep_resident, n):
                self.host_layers.append(jax.tree.map(
                    lambda a, i=i: jax.device_put(a[i], self.host), layers
                ))
        # Embed / final-norm / head are small and always resident
        # (reference pins norm + lm_head on GPU too, llama_partition.py:350-354).
        self.aux: Params = {
            k: jax.tree.map(lambda a: jax.device_put(a, self.device), v)
            for k, v in params.items() if k != "layers"
        }

        @functools.partial(jax.jit, donate_argnums=engine_donation(3, 4))
        def _layer(lp, x, rope, k_all, v_all, idx, cache_len):
            kc = jax.lax.dynamic_index_in_dim(k_all, idx, 0, keepdims=False)
            vc = jax.lax.dynamic_index_in_dim(v_all, idx, 0, keepdims=False)
            x, kc, vc = layer_forward(cfg, lp, x, rope, kc, vc, cache_len)
            k_all = jax.lax.dynamic_update_index_in_dim(k_all, kc, idx, 0)
            v_all = jax.lax.dynamic_update_index_in_dim(v_all, vc, idx, 0)
            return x, k_all, v_all

        @functools.partial(jax.jit, static_argnums=(2,))
        def _enter(inputs, cache_len, is_first):
            t = inputs.shape[1]
            positions = cache_len + jnp.arange(t, dtype=jnp.int32)[None, :]
            if is_first:
                x = embed_tokens(cfg, self.aux["embed"], inputs, positions)
            else:
                x = inputs
            return x, make_rope(cfg, positions)

        @jax.jit
        def _head(x):
            return lm_head(cfg, self.aux, x)

        self._layer = _layer
        self._enter = _enter
        self._head = _head

    def _fetch(self, i: int) -> Params:
        """Begin the async host->HBM copy of streamed layer i."""
        return jax.tree.map(lambda a: jax.device_put(a, self.device),
                            self.host_layers[i])

    def __call__(self, _params_ignored, x, k_all, v_all, cache_len,
                 prompts=None):
        """``prompts`` ([span, pre, D]) enables inference-time deep prompt
        injection per streamed layer (eager jnp add before each layer's
        jitted step — this engine is transfer-bound, the extra dispatch is
        noise)."""
        x = jnp.asarray(x)
        cache_len = jnp.asarray(cache_len, jnp.int32)
        x, rope = self._enter(x, cache_len, self.spec.is_first)

        li = 0
        if self.resident is not None:
            for r in range(self.keep_resident):
                lp = jax.tree.map(lambda a, r=r: a[r], self.resident)
                if prompts is not None:
                    x = _apply_deep_prompt(x, prompts[li], cache_len)
                x, k_all, v_all = self._layer(lp, x, rope, k_all, v_all,
                                              jnp.int32(li), cache_len)
                li += 1

        pending = self._fetch(0) if self.host_layers else None
        for i in range(len(self.host_layers)):
            lp = pending
            if i + 1 < len(self.host_layers):
                # issue the next copy BEFORE dispatching this layer's
                # compute: async dispatch overlaps transfer with compute
                pending = self._fetch(i + 1)
            if prompts is not None:
                x = _apply_deep_prompt(x, prompts[li], cache_len)
            x, k_all, v_all = self._layer(lp, x, rope, k_all, v_all,
                                          jnp.int32(li), cache_len)
            li += 1

        if self.spec.is_last:
            x = self._head(x)
        return x, k_all, v_all
