"""The same family with ONE term wrong: the q/k/v biases are left out of
the forward pass. The checkpoint is the right one, so the program serves
the real model and this reference must fail it."""

import os

import jax.numpy as jnp

from perfbench.harness.manifest import load_module

_plain = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "qwen2_plain.py"))
make_weights = _plain.make_weights


def forward(hf, layers, weights, ids):
    return _plain.forward(hf, layers, {
        k: jnp.zeros_like(v) if k.endswith("_proj.bias") else v
        for k, v in weights.items()}, ids)
