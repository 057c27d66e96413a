"""Mistral NeMo 12B's first 16 layers as a SEIFER user brings them: a
``LayerGraph`` and an ``executor_for_version`` composed from the program's
blocks.

Layer 0 is the embedding, layers 1..16 the decoder layers.  Each layer is
``models.layers``' ``rmsnorm``, ``qkv_proj``, ``rope``, ``out_proj`` and the
SwiGLU ``mlp`` around ``kernels.flash_attention.flash_attention`` (called with
the deployment's execution knob, under ``jax.jit``), with the two residual
adds of the published layer.  ``runtime.pipeline.make_layer_executor``
turns the layers into the executor the control plane deploys.
"""

from __future__ import annotations

import functools
from types import SimpleNamespace

import jax
import jax.numpy as jnp

from repro.core.graph import Layer, LayerGraph
from repro.kernels.flash_attention import flash_attention
from repro.models.layers import mlp, out_proj, qkv_proj, rmsnorm, rope
from repro.runtime.pipeline import make_layer_executor


def build(cfg: dict, weights: dict, ref, *, seq: int, use_pallas: bool,
          interpret: bool):
    """-> (graph, executor_for_version)."""
    d, eps, theta = cfg["dim"], cfg["norm_eps"], cfg["rope_theta"]
    block = cfg["assumed"]["flash_block"]
    mcfg = SimpleNamespace(mlp_kind="swiglu")
    # called under jit: called eagerly, flash_attention(use_pallas=True)
    # traces and lowers its pallas_call anew on every call
    attention = jax.jit(functools.partial(
        flash_attention, causal=True, block=block, use_pallas=use_pallas,
        interpret=interpret))

    def embed(tokens):
        return weights["embed"][tokens]

    def make_layer(p):
        def layer(x):
            a = rmsnorm(x, p["attn_norm"], eps=eps)
            q, k, v = qkv_proj(mcfg, p, a)
            pos = jnp.arange(x.shape[1])
            q, k = rope(q, pos, theta), rope(k, pos, theta)
            o = attention(q, k, v)
            x = x + out_proj(p, o)
            return x + mlp(mcfg, p, rmsnorm(x, p["mlp_norm"], eps=eps))
        return layer

    fns = [embed] + [make_layer(p) for p in weights["layers"]]
    act = seq * d * 2
    per_layer = ref.flops_per_request(cfg, seq) / cfg["n_layers"]
    layer_bytes = sum(int(a.size) * a.dtype.itemsize for a in weights["layers"][0].values())
    layers = ([Layer("embed", cfg["vocab_size"] * d * 2, act, 0)]
              + [Layer(f"layer{i}", layer_bytes, act, int(per_layer))
                 for i in range(cfg["n_layers"])])
    graph = LayerGraph(cfg["name"], tuple(layers), in_bytes=seq * 4)
    executor = make_layer_executor(fns)
    return graph, lambda version: executor
